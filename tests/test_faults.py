"""Robustness suite: cooperative deadlines (anytime results), fault
injection at the chunk/cache/worker/socket seams, and graceful degradation
(ISSUE 9).

The deadline tests drive ``faults.now`` with a deterministic fake clock
(each call advances one "second"), so deadline expiry lands at an *exact*
DP level — no wall-clock flakiness.  With ``deadline_s = k - 1.5`` the
first expired check is level ``k``: arming consumes t=0 and level ``i``'s
check sees ``t = i - 1``, so levels ``2..k-1`` commit and
``degraded["levels_done"] == k - 1``.
"""
import itertools
import os
import threading
import time

import pytest

from repro.core import engine, faults
from repro.core.batch import BatchEngine, optimize_many
from repro.core.config import OptimizerConfig
from repro.core.faults import FaultPlan, FaultRule, InjectedFault
from repro.core.plan import validate_plan
from repro.core.plancache import PlanCache
from repro.core.service import optimize_stream
from repro.heuristics import goo
from repro.workloads import generators as gen

G = gen.chain(6, 7)                    # acyclic: valid in all 3 lane spaces
SMALL = [gen.chain(5, 1), gen.star(6, 2), gen.musicbrainz_query(8, 3)]


@pytest.fixture(autouse=True)
def _clean_faults():
    """No test may leak an installed plan into the next."""
    faults.uninstall()
    yield
    faults.uninstall()


@pytest.fixture
def fake_clock(monkeypatch):
    """``faults.now()`` returns its call count: 0, 1, 2, ..."""
    counter = itertools.count()
    monkeypatch.setattr(faults, "now", lambda: next(counter))


def plan_shape(p):
    if p.is_leaf:
        return p.rel_set
    return (p.rel_set, plan_shape(p.left), plan_shape(p.right))


def fingerprint(results):
    return [(float(r.cost), plan_shape(r.plan)) for r in results]


# =============================================================== fault plane

class TestFaultPlan:
    def test_rule_spec_roundtrip(self):
        for r in (FaultRule("chunk", 3),
                  FaultRule("cache_write", 1, "corrupt"),
                  FaultRule("socket_send", 7, "stall", 0.25)):
            assert FaultRule.from_spec(r.spec()) == r

    def test_plan_spec_roundtrip(self):
        p = FaultPlan.seeded(5, chunk_failures=2, worker_crashes=1,
                             socket_stalls=1)
        assert FaultPlan.from_spec(p.spec()).rules == p.rules

    def test_seeded_is_deterministic(self):
        a = FaultPlan.seeded(9, chunk_failures=3, slow_chunks=2)
        b = FaultPlan.seeded(9, chunk_failures=3, slow_chunks=2)
        c = FaultPlan.seeded(10, chunk_failures=3, slow_chunks=2)
        assert a.rules == b.rules
        assert a.rules != c.rules

    def test_bad_specs_rejected(self):
        with pytest.raises(ValueError):
            FaultRule("nope", 1)
        with pytest.raises(ValueError):
            FaultRule("chunk", 0)
        with pytest.raises(ValueError):
            FaultRule.from_spec("garbage")

    def test_install_resets_counters(self):
        faults.install(FaultPlan(rules=(FaultRule("chunk", 1),)))
        with pytest.raises(InjectedFault):
            faults.fire("chunk")
        assert faults.fired() == ["chunk@1:raise"]
        faults.install(FaultPlan(rules=(FaultRule("chunk", 1),)))
        assert faults.fired() == []            # fresh counters: fires again
        with pytest.raises(InjectedFault):
            faults.fire("chunk")

    def test_uninstalled_is_inert(self):
        assert not faults.active()
        assert faults.fire("chunk") is None
        assert faults.check("cache_write") is None

    def test_install_from_env(self, monkeypatch):
        monkeypatch.setenv("REPRO_FAULTS", "worker@2:raise;chunk@1:sleep:0.01")
        assert faults.install_from_env()
        assert faults.active()
        assert faults.fire("chunk") is not None    # sleep rule returned
        faults.uninstall()
        monkeypatch.setenv("REPRO_FAULTS", "")
        assert not faults.install_from_env()


# ========================================================= anytime deadlines

def _make_engine(kind, space, pipeline, deadline_s):
    if kind == "batch":
        return BatchEngine([G], algorithm=space, pipeline=pipeline,
                           deadline_s=deadline_s)
    if kind == "shard":
        from repro.core import shard as _shard
        return _shard.ShardedBatchEngine([G], _shard.batch_mesh(4),
                                         algorithm=space, pipeline=pipeline,
                                         deadline_s=deadline_s)
    from repro.core.lattice import LatticeShardedEngine
    return LatticeShardedEngine(G, algorithm=space, pipeline=pipeline,
                                deadline_s=deadline_s)


@pytest.mark.parametrize("pipeline", [False, True], ids=["sync", "pipe"])
@pytest.mark.parametrize("space", ["dpsub", "mpdp_tree", "mpdp_general"])
class TestDeadlineEveryLevel:
    """Expiry at every DP level, in every lane space, sync and pipelined,
    on 1 device (BatchEngine), a 4-device mesh (ShardedBatchEngine) and
    the intra-query lattice — always a valid plan no worse than GOO."""

    def _run(self, kind, space, pipeline):
        base = float(goo.solve(G).cost)
        for k in range(2, G.n + 1):
            eng = _make_engine(kind, space, pipeline, deadline_s=k - 1.5)
            r = eng.run()[0]
            deg = r.info["degraded"]
            assert deg["reason"] == "deadline", (kind, k)
            assert deg["levels_done"] == k - 1, (kind, k)
            validate_plan(r.plan, G)
            assert float(r.cost) <= base + 1e-4, (kind, k)
        # a generous deadline must not degrade at all
        eng = _make_engine(kind, space, pipeline, deadline_s=1e9)
        r = eng.run()[0]
        assert "degraded" not in r.info
        validate_plan(r.plan, G)

    def test_batch(self, space, pipeline, fake_clock):
        self._run("batch", space, pipeline)

    def test_sharded(self, space, pipeline, fake_clock):
        self._run("shard", space, pipeline)

    def test_lattice(self, space, pipeline, fake_clock):
        self._run("lattice", space, pipeline)


class TestDeadlineEntryPoints:
    def test_optimize_solo_degrades(self, fake_clock):
        g = SMALL[0]
        r = engine.optimize(g, config=OptimizerConfig(algorithm="dpsub",
                                                      deadline_s=1.5))
        assert r.info["degraded"]["reason"] == "deadline"
        validate_plan(r.plan, g)
        assert float(r.cost) <= float(goo.solve(g).cost) + 1e-4

    def test_optimize_many_degrades_every_query(self, fake_clock):
        rs = optimize_many(SMALL, config=OptimizerConfig(algorithm="dpsub",
                                                         deadline_s=0.5))
        assert len(rs) == len(SMALL)
        for g, r in zip(SMALL, rs):
            assert "degraded" in r.info
            validate_plan(r.plan, g)
            assert float(r.cost) <= float(goo.solve(g).cost) + 1e-4

    def test_stream_tiny_deadline_degrades(self):
        rs, rep = optimize_stream(
            SMALL, config=OptimizerConfig(deadline_s=1e-6))
        assert len(rs) == len(SMALL)
        # a query whose full set solved before expiry is legitimately exact;
        # with a 1µs budget at least one query must have degraded, and every
        # result — exact or stitched — is valid and no worse than GOO
        assert sum(1 for r in rs if "degraded" in r.info) >= 1
        for g, r in zip(SMALL, rs):
            validate_plan(r.plan, g)
            assert float(r.cost) <= float(goo.solve(g).cost) + 1e-4

    def test_generous_deadline_bit_identical_to_no_deadline(self):
        ref = optimize_many(SMALL, algorithm="dpsub")
        rs = optimize_many(SMALL, config=OptimizerConfig(algorithm="dpsub",
                                                         deadline_s=3600.0))
        assert fingerprint(rs) == fingerprint(ref)
        assert not any("degraded" in r.info for r in rs)

    def test_degraded_results_never_cached(self, fake_clock):
        cache = PlanCache()
        rs = optimize_many(SMALL, config=OptimizerConfig(
            algorithm="dpsub", cache=cache, deadline_s=0.5))
        assert all("degraded" in r.info for r in rs)
        assert cache.stats.inserts == 0

    def test_nonpositive_deadline_rejected(self):
        with pytest.raises(ValueError):
            OptimizerConfig(deadline_s=0.0)
        with pytest.raises(ValueError):
            OptimizerConfig(deadline_s=-1.0)


# ============================================================== chunk faults

class TestChunkFaults:
    def test_device_failure_redispatches_bit_identical(self):
        ref = optimize_many(SMALL, algorithm="dpsub")
        faults.install(FaultPlan(rules=(FaultRule("chunk", 1),)))
        rs = optimize_many(SMALL, config=OptimizerConfig(algorithm="dpsub",
                                                         devices=4))
        assert faults.fired() == ["chunk@1:raise"]
        assert fingerprint(rs) == fingerprint(ref)
        assert any(r.info.get("redispatched") for r in rs)
        assert not any("degraded" in r.info for r in rs)

    def test_stream_device_failure_redispatches_bit_identical(self):
        ref = optimize_many(SMALL, algorithm="dpsub")
        faults.install(FaultPlan(rules=(FaultRule("chunk", 1),)))
        rs, _ = optimize_stream(SMALL, config=OptimizerConfig(
            algorithm="dpsub", devices=4))
        assert faults.fired() == ["chunk@1:raise"]
        assert fingerprint(rs) == fingerprint(ref)
        assert any(r.info.get("redispatched") for r in rs)

    @pytest.mark.parametrize("entry", ["optimize_many", "optimize_stream"])
    def test_programming_error_is_not_redispatched(self, entry, monkeypatch):
        """Only device-runtime and injected failures re-run on one device:
        a bug on the mesh path must surface, not become a 1-device run."""
        from repro.core import shard

        def broken(self):
            raise TypeError("shape bug on the mesh path")

        monkeypatch.setattr(shard.ShardedBatchEngine, "run_levels", broken)
        cfg = OptimizerConfig(algorithm="dpsub", devices=4)
        with pytest.raises(TypeError, match="shape bug"):
            if entry == "optimize_many":
                optimize_many(SMALL, config=cfg)
            else:
                optimize_stream(SMALL, config=cfg)

    def test_slow_chunk_changes_nothing(self):
        ref = optimize_many(SMALL, algorithm="dpsub")
        faults.install(FaultPlan(rules=(
            FaultRule("chunk", 1, "sleep", 0.01),
            FaultRule("chunk", 3, "sleep", 0.01))))
        rs = optimize_many(SMALL, algorithm="dpsub")
        assert fingerprint(rs) == fingerprint(ref)
        assert not any("degraded" in r.info or "redispatched" in r.info
                       for r in rs)


# ========================================================== checkpoint corrupt

class TestCacheCorruption:
    def test_corrupted_write_cold_loads(self, tmp_path):
        cache = PlanCache()
        g = SMALL[0]
        cache.put(g, engine.optimize(g))
        path = str(tmp_path / "plans.plancache")
        faults.install(FaultPlan(rules=(
            FaultRule("cache_write", 1, "corrupt"),)))
        cache.save(path)                       # torn write lands on disk
        faults.uninstall()
        loaded = PlanCache.load(path)
        assert loaded.stale_load and len(loaded) == 0
        cache.save(path)                       # clean save heals the file
        healed = PlanCache.load(path)
        assert not healed.stale_load and len(healed) == 1


# ============================================================== daemon faults

class TestDaemonFaults:
    def test_worker_crash_then_retry_identical_plan(self, tmp_path):
        from repro.daemon import DaemonClient, DaemonError, OptimizerDaemon
        ref = engine.optimize_many(SMALL)
        faults.install(FaultPlan(rules=(FaultRule("worker", 1),)))
        d = OptimizerDaemon(socket_path=str(tmp_path / "wc.sock"))
        d.start()
        try:
            with DaemonClient(socket_path=d.address) as c:
                with pytest.raises(DaemonError, match="worker crashed") as ei:
                    c.optimize(SMALL)
                assert ei.value.retryable
                rs = c.optimize(SMALL, retries=2)   # resend: re-spawned
                assert fingerprint(rs) == fingerprint(ref)  # worker serves it
                assert c.stats()["worker_restarts"] == 1
        finally:
            faults.uninstall()
            d.drain()
            assert d._stopped.wait(10)

    def test_request_deadline_timeout_is_structured(self, tmp_path):
        from repro.daemon import DaemonClient, DaemonError, OptimizerDaemon
        gate = threading.Event()               # park the worker: the per-
        d = OptimizerDaemon(socket_path=str(tmp_path / "to.sock"),
                            worker_gate=gate)  # request wait must expire
        d.start()
        try:
            with DaemonClient(socket_path=d.address) as c:
                t0 = time.monotonic()
                with pytest.raises(DaemonError, match="deadline") as ei:
                    c.optimize(SMALL[:1],
                               config=OptimizerConfig(deadline_s=0.05))
                assert ei.value.retryable
                assert time.monotonic() - t0 < 10.0    # bounded, not hung
        finally:
            gate.set()
            d.drain()
            assert d._stopped.wait(10)

    def test_stalled_socket_raises_frame_timeout(self, tmp_path):
        from repro.daemon import DaemonClient, FrameTimeout, OptimizerDaemon
        d = OptimizerDaemon(socket_path=str(tmp_path / "st.sock"))
        d.start()
        try:
            c = DaemonClient(socket_path=d.address)
            # nth=2: call 1 is the client's own request send; call 2 is the
            # daemon's reply send — that's the stall a recv deadline catches
            faults.install(FaultPlan(rules=(
                FaultRule("socket_send", 2, "stall", 1.0),)))
            with pytest.raises(FrameTimeout):
                c._call({"op": "ping"}, timeout=0.25)
            faults.uninstall()
            c.close()
        finally:
            faults.uninstall()
            d.drain()
            assert d._stopped.wait(10)

    def test_daemon_reports_degraded_results(self, tmp_path):
        from repro.daemon import DaemonClient, OptimizerDaemon
        d = OptimizerDaemon(socket_path=str(tmp_path / "dg.sock"))
        d.start()
        try:
            with DaemonClient(socket_path=d.address) as c:
                rs = c.optimize(SMALL,
                                config=OptimizerConfig(deadline_s=1e-4))
                assert c.last_meta["degraded"] >= 1
                assert sum(1 for r in rs if "degraded" in r.info) == \
                    c.last_meta["degraded"]
                for g, r in zip(SMALL, rs):
                    validate_plan(r.plan, g)
                    assert float(r.cost) <= float(goo.solve(g).cost) + 1e-4
        finally:
            d.drain()
            assert d._stopped.wait(10)

    def test_connect_failure_is_daemon_error_with_cause(self, tmp_path):
        from repro.daemon import DaemonClient, DaemonError
        with pytest.raises(DaemonError, match="could not connect") as ei:
            DaemonClient(socket_path=str(tmp_path / "missing.sock"),
                         connect_timeout=0.2)
        assert isinstance(ei.value.__cause__, OSError)
