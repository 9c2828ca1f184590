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


def test_aggregate_keeps_solo_runs_out_of_the_flight_keys():
    flight = telemetry.FlightTelemetry(nmax=16, space="s", queries=3,
                                       evaluated_lanes=50)
    solos = [telemetry.FlightTelemetry(nmax=nm, space="mpdp_general",
                                       queries=1, solo=True,
                                       evaluated_lanes=7, filter_lanes=fl,
                                       filter_sets=fs, memo_bytes=16 << nm)
             for nm, fl, fs in ((24, 65536, 900), (25, 32768, 40))]
    agg = telemetry.aggregate([flight, None] + solos)
    assert (agg["flights"], agg["queries"], agg["evaluated_lanes"]) == \
        (1, 3, 50)
    assert (agg["solo_queries"], agg["solo_filter_lanes"],
            agg["solo_filter_sets"], agg["solo_memo_bytes"]) == \
        (2, 98304, 940, 16 << 25)


def test_stream_records_each_solo_run():
    """A query past the batched memo runs on the solo engine; the stream's
    summary counts it in the solo keys, from the counters on its result."""
    g = gen.musicbrainz_query(17, 1)
    (r,), rep = optimize_stream([g])
    (t,) = rep.solo_telemetry
    assert rep.solo == 1 and not rep.flights
    assert (t.solo, t.queries, t.nmax, t.space) == (True, 1, 24, r.algorithm)
    assert (t.filter_lanes, t.filter_sets, t.memo_bytes) == \
        (r.counters.filter_lanes, r.counters.filter_sets, 16 << 24)
    agg = rep.telemetry_summary()
    assert agg["flights"] == agg["queries"] == 0
    assert agg["solo_queries"] == 1
    assert 0 < agg["solo_filter_sets"] <= agg["solo_filter_lanes"]


def test_roll_up_sums_summaries_and_keeps_the_largest_memo():
    """The daemon's STATS roll-up: each request's summary adds in, except
    the keys that keep the largest value seen."""
    total = {"queries": 0, "solo_queries": 0, "solo_memo_bytes": 0}
    for nm in (25, 24):
        rec = telemetry.FlightTelemetry(nmax=nm, space="mpdp_general",
                                        queries=1, solo=True,
                                        memo_bytes=16 << nm)
        telemetry.roll_up(total, telemetry.aggregate([rec]))
    assert total == {"queries": 0, "solo_queries": 2,
                     "solo_memo_bytes": 16 << 25}
    assert telemetry.LARGEST == {"solo_memo_bytes"}
