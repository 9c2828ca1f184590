"""The solo engine past the batched memo: NMAX buckets that follow n above
24, a memo-fit guard that refuses a query whose dense memo the device
cannot hold, and the solo run's telemetry through the service and the
daemon's STATS."""
import json
import os

import pytest

from bench import client as bclient, reference_csg
from bench.cell import ROOT, module
from repro.core import bitset as bs, dpccp, engine
from repro.daemon import DaemonClient, DaemonError, OptimizerDaemon
from repro.workloads import generators as gen

with open(os.path.join(ROOT, "bench", "configs", "musicbrainz-pg25.json")) as f:
    CFG = json.load(f)


@pytest.mark.parametrize("n", range(1, 25))
def test_buckets_up_to_24_are_unchanged(n):
    assert bs.nmax_bucket(n) == (8 if n <= 8 else 16 if n <= 16 else 24)


@pytest.mark.parametrize("n", range(25, 31))
def test_buckets_above_24_follow_n(n):
    assert bs.nmax_bucket(n) == n


def test_no_bucket_past_the_int32_ceiling():
    with pytest.raises(ValueError):
        bs.nmax_bucket(31)


def test_memo_bytes_at_25_relations():
    assert engine.memo_bytes(bs.nmax_bucket(25)) == 512 << 20
    need = engine.memo_need_bytes(25, 25)
    assert 512 << 20 < need <= 1 << 30


@pytest.mark.parametrize("free", [(16 << 30) - (1 << 28), 15 << 30])
def test_one_v5e_holds_29_relations_and_not_30(free):
    """16 GB of device memory, less what the runtime holds: n = 29 fits,
    n = 30 (a 16 GiB memo) does not."""
    assert engine.memo_need_bytes(29, 29) <= free
    assert engine.memo_need_bytes(30, 30) > free


def test_guard_refuses_before_allocating(monkeypatch):
    g = gen.musicbrainz_query(17, 1)
    need = engine.memo_need_bytes(17, bs.nmax_bucket(17))
    monkeypatch.setattr(engine, "device_free_bytes", lambda: need - 1)
    monkeypatch.setattr(engine.ExactEngine, "_init_memo",
                        lambda self: pytest.fail("memo allocated"))
    with pytest.raises(engine.MemoTooLarge) as e:
        engine.ExactEngine(g)
    assert (e.value.n, e.value.need, e.value.free) == (17, need, need - 1)
    assert isinstance(e.value, ValueError) and "17-relation" in str(e.value)


def test_guard_passes_what_fits_and_skips_without_statistics(monkeypatch):
    g = gen.musicbrainz_query(9, 2)
    monkeypatch.setattr(engine, "device_free_bytes",
                        lambda: engine.memo_need_bytes(9, 16))
    assert engine.ExactEngine(g).counters.memo_bytes == 16 << 16
    monkeypatch.setattr(engine, "device_free_bytes", lambda: None)
    assert engine.optimize(g, "dpsub").cost > 0


@pytest.fixture
def daemon(tmp_path):
    d = OptimizerDaemon(socket_path=str(tmp_path / "d.sock"),
                        checkpoint_every=10_000)
    d.start()
    yield d
    d.drain()
    assert d._stopped.wait(10)


def test_daemon_names_the_refusal_and_keeps_serving(daemon, monkeypatch):
    monkeypatch.setattr(engine, "device_free_bytes", lambda: 1 << 20)
    with DaemonClient(socket_path=daemon.address) as c:
        with pytest.raises(DaemonError, match="MemoTooLarge: .*17-relation"):
            c.optimize([gen.musicbrainz_query(17, 1)])
        assert c.ping()
        assert c.stats()["errors"] == 1
        monkeypatch.setattr(engine, "device_free_bytes", lambda: None)
        assert c.optimize([gen.chain(5, 1)])[0].cost > 0


def test_solo_query_through_the_daemon(daemon):
    """A cyclic 17-relation walk with seeded statistics: the daemon's plan
    costs what host DPccp finds, the csg-cmp reference judges it optimal,
    and STATS counts the solo run."""
    q = module("schemas", "musicbrainz").query(CFG, 17, 1, 2 ** 31 + 5)
    g = bclient.to_graph(q)
    assert g.m > g.n - 1                          # the general lane space
    with DaemonClient(socket_path=daemon.address) as c:
        before = c.stats()["telemetry"]
        (r,) = c.optimize([g])
        meta, tele = c.last_meta, c.stats()["telemetry"]
    assert meta["solo"] == 1 and meta["flights"] == 0
    assert r.cost == pytest.approx(dpccp.solve(g).cost, rel=1e-4)
    j = reference_csg.judge(q, CFG["cost_model"], bclient.shape(r.plan),
                            r.cost)
    assert j["problem"] is None
    assert j["gap"] <= CFG["check"]["limits"]["worst_gap"]
    assert j["cost_error"] <= CFG["check"]["limits"]["worst_cost_error"]
    assert before["solo_queries"] == 0
    assert tele["solo_queries"] == 1
    assert 0 < tele["solo_filter_sets"] <= tele["solo_filter_lanes"]
    assert tele["solo_filter_lanes"] % engine.FILTER_CHUNK == 0
    assert tele["solo_memo_bytes"] == engine.memo_bytes(24)
    assert tele["flights"] == before["flights"] == 0


@pytest.mark.parametrize("filter_chunk", [16, engine.FILTER_CHUNK])
def test_solo_counters_count_the_filter(monkeypatch, filter_chunk):
    """The unrank filter launches whole chunks of ``FILTER_CHUNK`` lanes,
    or of the engine's chunk where that is larger, over every level's
    C(n, i) ranks and finds each connected set of two or more relations
    once."""
    from math import comb
    monkeypatch.setattr(engine, "FILTER_CHUNK", filter_chunk)
    g = gen.cycle(7, 1)
    r = engine.optimize(g, "mpdp_general", chunk=16)
    lanes = sum(-(-comb(7, i) // filter_chunk) * filter_chunk
                for i in range(2, 8))
    connected = 7 * 5 + 1                        # arcs of 2..6, and all 7
    assert (r.counters.filter_lanes, r.counters.filter_sets) == \
        (lanes, connected)
    e = engine.optimize(g, "mpdp_general", chunk=16, enum="expand")
    assert e.counters.filter_sets == connected
    assert e.counters.filter_sets <= e.counters.filter_lanes


@pytest.mark.parametrize("n,k", [(5, 2), (9, 4), (12, 12), (25, 12), (30, 15)])
def test_unrank_matches_the_host_unrank(n, k):
    import numpy as np
    from math import comb
    import jax.numpy as jnp
    from repro.core import unrank as ur
    total = comb(n, k)
    ranks = np.unique(np.r_[np.arange(min(total, 600)),
                            np.linspace(0, total - 1, 400).astype(np.int64)])
    got = np.asarray(ur.unrank_ksubset(jnp.asarray(ranks, jnp.int32), k,
                                       jnp.asarray(ur.binom_table(n)), n))
    assert got.tolist() == [ur.np_unrank_ksubset(int(r), k, n) for r in ranks]


def test_lane_pairs_is_searchsorted():
    """Pair offsets as a chunk sees them: the first pair began in an
    earlier chunk, the padding lies far past the chunk's end."""
    import numpy as np
    import jax.numpy as jnp
    rng = np.random.default_rng(3)
    for chunk in (16, 64):
        sizes = 1 << rng.integers(1, 5, 40)
        offs = np.r_[0, np.cumsum(sizes)] - int(rng.integers(0, 4))
        p0 = int(np.searchsorted(offs, 0, side="right")) - 1
        p1 = int(np.searchsorted(offs, chunk, side="left"))
        off_local = np.full(64, 1 << 30, np.int64)
        off_local[:p1 - p0] = offs[p0:p1]
        off_local = off_local.astype(np.int32)
        t = np.arange(chunk)
        want = np.clip(np.searchsorted(off_local, t, side="right") - 1, 0,
                       p1 - p0 - 1)
        got = engine._lane_pairs(jnp.asarray(off_local), p1 - p0, chunk)
        assert np.asarray(got).tolist() == want.tolist()


@pytest.mark.parametrize("algorithm,graph", [
    ("mpdp_general", gen.cycle(9, 4)), ("mpdp_tree", gen.star(9, 5)),
    ("dpsub", gen.clique(7, 6))])
def test_fetch_waves_leave_results_alone(monkeypatch, algorithm, graph):
    """Launches fetched one at a time, in waves of three, or all at once
    give the same plan, cost and counters."""
    from repro.core import telemetry
    runs = []
    for wave in (1, 3, telemetry.FETCH_WAVE):
        monkeypatch.setattr(telemetry, "FETCH_WAVE", wave)
        r = engine.optimize(graph, algorithm, chunk=32)
        runs.append((r.cost, bclient.shape(r.plan), r.counters.evaluated,
                     r.counters.ccp, r.counters.filter_lanes,
                     r.counters.filter_sets))
    assert runs[0] == runs[1] == runs[2]


def test_memo_writes_need_ascending_distinct_indices():
    import numpy as np
    import jax.numpy as jnp
    eng = engine.ExactEngine(gen.chain(6, 1))
    buf = eng._write(jnp.zeros(eng.size, jnp.int32),
                     np.array([3, 5, 9], np.int32),
                     np.array([7, 8, 9], np.int32))
    assert np.flatnonzero(np.asarray(buf)).tolist() == [3, 5, 9]
    assert np.asarray(buf)[[3, 5, 9]].tolist() == [7, 8, 9]
    with pytest.raises(ValueError, match="ascending"):
        eng._write(buf, np.array([5, 3], np.int32), np.zeros(2, np.int32))
    with pytest.raises(ValueError, match="ascending"):
        eng._write(buf, np.array([3, 3], np.int32), np.zeros(2, np.int32))


def test_memo_writes_in_pieces(monkeypatch):
    """A write longer than a piece lands whole, whatever the piece sizes."""
    import numpy as np
    import jax.numpy as jnp
    monkeypatch.setattr(engine, "SCATTER_SMALL", 4)
    monkeypatch.setattr(engine, "SCATTER_BIG", 8)
    eng = engine.ExactEngine(gen.chain(6, 1))
    for n in (1, 4, 5, 8, 9, 30):
        idx = np.sort(np.random.default_rng(n).choice(eng.size, n,
                                                      replace=False))
        val = np.arange(1, n + 1, dtype=np.float32)
        buf = eng._write(jnp.zeros(eng.size, jnp.float32), idx, val)
        want = np.zeros(eng.size, np.float32)
        want[idx] = val
        assert np.array_equal(np.asarray(buf), want)
