"""Flight telemetry (``core.telemetry``): ``capture`` reads the engine's
tallies, ``aggregate`` sums them, and phase A's launch tallies
(``blocks_sets``, ``blocks_slots``) reach the stream's summary."""
import types

import pytest

from repro.core import bitset as bs, telemetry
from repro.core.service import optimize_stream
from repro.workloads import generators as gen


def _engine(**tallies):
    return types.SimpleNamespace(algorithm="batch_mpdp_general", chunk=64,
                                 counters=[], stats={"retraces": 0},
                                 **tallies)


def test_capture_carries_phase_a_tallies():
    t = telemetry.capture(_engine(chunks_dispatched=3, blocks_sets=300,
                                  blocks_slots=768), [], nmax=16, queries=1)
    assert (t.chunks, t.blocks_sets, t.blocks_slots) == (3, 300, 768)
    assert t.to_dict()["blocks_slots"] == 768


def test_capture_of_an_engine_without_tallies_reads_zero():
    t = telemetry.capture(_engine(), [], nmax=16, queries=1)
    assert (t.blocks_sets, t.blocks_slots) == (0, 0)


def test_aggregate_sums_phase_a_tallies():
    recs = [telemetry.FlightTelemetry(nmax=16, space="s", queries=1,
                                      blocks_sets=a, blocks_slots=b)
            for a, b in ((10, 256), (300, 512))]
    agg = telemetry.aggregate(recs + [None])
    assert (agg["blocks_sets"], agg["blocks_slots"]) == (310, 768)


def _connected_sets_of_two_or_more(g):
    adj = g.adjacency()
    return sum(1 for s in range(1, 1 << g.n)
               if bin(s).count("1") >= 2 and bs.np_grow(s & -s, s, adj) == s)


@pytest.mark.parametrize("g,cyclic", [(gen.cycle(7, 1), True),
                                      (gen.musicbrainz_query(10, 1), True),
                                      (gen.chain(7, 2), False)])
def test_stream_summary_counts_every_set_phase_a_saw(g, cyclic):
    """Phase A sees each connected set of >= 2 relations once, in launches
    of at least 256 slots; a tree query takes the tree lane space and
    launches none."""
    assert (g.m >= g.n) == cyclic
    _, rep = optimize_stream([g])
    agg = rep.telemetry_summary()
    if cyclic:
        assert agg["blocks_sets"] == _connected_sets_of_two_or_more(g)
        assert agg["blocks_sets"] <= agg["blocks_slots"]
        assert agg["blocks_slots"] % 256 == 0
    else:
        assert agg["blocks_sets"] == agg["blocks_slots"] == 0
