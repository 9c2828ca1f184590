"""Daemon benchmark: correctness gates + open-loop load generator.

Spawns a real ``python -m repro.daemon`` subprocess and drives it through
six phases; the resulting JSON report feeds ``check_regression.py``.

Deterministic phases (gated):

  1. **cold** — client 1 optimizes the canonical ``mixed_stream`` (first
     request pays JIT warmup + fills the daemon's ``PlanCache``);
  2. **warm** — client 1 resends the identical stream: every query must be
     a plan-cache hit and the executable-cache compile delta must be zero;
  3. **proc2** — a *separate client process* (``python -m
     repro.daemon.client``) sends the same stream under another tenant:
     zero compiles, and every query is a **cross-client** plan-cache hit;
  4. **fresh** — client 1 sends a same-size-multiset stream with shifted
     seeds: engines actually run, but every bucket shape was compiled in
     phase 1, so the compile delta stays at the committed baseline (0 —
     the zero-retrace-after-warmup contract under *new* queries);
  5. **load** — open-loop Poisson arrivals from several tenant threads,
     each arrival an independent connection requesting a warmed subset;
     arrivals are scheduled by the clock, not by completions, so when the
     daemon's bounded queue / per-tenant caps saturate, requests SHED.
     Latency percentiles (client-side and the daemon's own request-wall
     STATS) and shed counts are **reported, never gated** — they measure
     the runner, not the code;
  6. **drain** — SIGTERM; the daemon must drain in-flight work, write a
     final atomic cache checkpoint (which must load back non-stale), and
     exit 0.

Every optimize phase is replayed in-process (``engine.optimize_many``
against one shared ``PlanCache``, same request order) and costs must match
**bit-identically** — the daemon may never change results, only reuse
warm state.

One process per device: while the daemon is alive it is the only process
that starts a JAX backend.  The client subprocess runs with
``JAX_PLATFORMS=cpu`` (it needs sockets, graph builders and the host
plan re-coster only), this process starts no backend until the daemon has
exited (gated as ``parent_backend_free``), and the in-process reference
replay runs after the drain.

    PYTHONPATH=src python benchmarks/bench_daemon.py --json BENCH_daemon.json
    PYTHONPATH=src python benchmarks/bench_daemon.py --smoke   # CI-sized
    python benchmarks/check_regression.py BENCH_daemon.json \
        benchmarks/BENCH_baseline.json
"""
from __future__ import annotations

import argparse
import json
import os
import random
import signal
import subprocess
import sys
import tempfile
import threading
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _percentiles(xs, ps=(50, 95, 99)) -> dict:
    import numpy as np
    if not xs:
        return {f"p{p}": 0.0 for p in ps}
    arr = np.asarray(xs, float)
    return {f"p{p}": float(np.percentile(arr, p)) for p in ps}


def _costs(results) -> list[float]:
    return [float(r.cost) for r in results]


def _backend_started() -> bool:
    """Whether this process has started a JAX backend (importing jax does
    not start one; building device arrays or listing devices does)."""
    if "jax" not in sys.modules:
        return False
    from jax._src import xla_bridge
    return xla_bridge.backends_are_initialized()


def _spawn_daemon(sockp: str, ckpt: str, queue_depth: int,
                  tenant_inflight: int, devices: int | None):
    cmd = [sys.executable, "-m", "repro.daemon", "--socket", sockp,
           "--cache-file", ckpt, "--checkpoint-every", "1000",
           "--queue-depth", str(queue_depth),
           "--tenant-inflight", str(tenant_inflight)]
    if devices:
        cmd += ["--devices", str(devices)]
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src") \
        + os.pathsep + env.get("PYTHONPATH", "")
    return subprocess.Popen(cmd, env=env)


def _load_phase(sockp: str, graphs, tenants: int, rate_hz: float,
                arrivals: int, seed: int) -> dict:
    """Open-loop Poisson load: ``arrivals`` total requests across
    ``tenants`` tenant threads, inter-arrival gaps ~ Exp(rate per tenant),
    one connection per arrival (so saturation hits admission control, not
    a client-side serialization point)."""
    from repro.daemon import DaemonClient, DaemonShed
    lock = threading.Lock()
    lat, shed, errors = [], [0], [0]
    per_tenant = max(1, arrivals // tenants)

    def one_request(tenant: str):
        t0 = time.perf_counter()
        try:
            with DaemonClient(socket_path=sockp, tenant=tenant,
                              connect_timeout=30.0) as c:
                c.optimize(graphs)
            with lock:
                lat.append(time.perf_counter() - t0)
        except DaemonShed:
            with lock:
                shed[0] += 1
        except Exception:
            with lock:
                errors[0] += 1

    def tenant_thread(i: int):
        rng = random.Random(seed * 1000 + i)
        tenant, pending = f"load-{i}", []
        for _ in range(per_tenant):
            time.sleep(rng.expovariate(rate_hz))   # open loop: clock-driven
            t = threading.Thread(target=one_request, args=(tenant,),
                                 daemon=True)
            t.start()
            pending.append(t)
        for t in pending:
            t.join(timeout=120)

    threads = [threading.Thread(target=tenant_thread, args=(i,), daemon=True)
               for i in range(tenants)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    return {"arrivals": per_tenant * tenants, "tenants": tenants,
            "offered_rate_hz": rate_hz * tenants,
            "completed": len(lat), "shed": shed[0], "errors": errors[0],
            "wall_s": time.perf_counter() - t0,
            "latency_s": _percentiles(lat)}


def bench(nq: int = 32, seed: int = 0, devices: int | None = None,
          queue_depth: int = 4, tenant_inflight: int = 2,
          load_tenants: int = 3, load_rate_hz: float = 20.0,
          load_arrivals: int = 60, smoke: bool = False) -> dict:
    if smoke:
        nq, load_tenants, load_arrivals = 8, 2, 12
    from repro.core.plancache import PlanCache
    from repro.daemon import DaemonClient
    from repro.workloads.generators import mixed_stream

    graphs = mixed_stream(nq, seed)
    fresh_graphs = mixed_stream(nq, seed + nq)   # same size multiset,
    sockp = tempfile.mktemp(suffix=".sock")      # disjoint seeds
    ckpt = tempfile.mktemp(suffix=".plancache")
    proc = _spawn_daemon(sockp, ckpt, queue_depth, tenant_inflight, devices)
    rep: dict = {"queries": nq, "seed": seed, "queue_depth": queue_depth,
                 "tenant_inflight": tenant_inflight}
    try:
        c = DaemonClient(socket_path=sockp, tenant="bench",
                         connect_timeout=120.0)
        # ---- phase 1: cold ------------------------------------------------
        t0 = time.perf_counter()
        cold = c.optimize(graphs)
        rep["cold_wall_s"] = time.perf_counter() - t0
        warmup_compiles = c.stats()["exec"]["compiles"]
        rep["warmup_compiles"] = warmup_compiles
        # ---- phase 2: warm (identical stream) -----------------------------
        t0 = time.perf_counter()
        warm = c.optimize(graphs)
        rep["warm_wall_s"] = time.perf_counter() - t0
        rep["warm_cache_hits"] = c.last_meta["cache_hits"]
        rep["warm_compile_delta"] = \
            c.stats()["exec"]["compiles"] - warmup_compiles
        # ---- phase 3: second client process -------------------------------
        env = dict(os.environ)
        env["PYTHONPATH"] = os.path.join(REPO, "src") \
            + os.pathsep + env.get("PYTHONPATH", "")
        env["JAX_PLATFORMS"] = "cpu"           # the daemon owns the device
        out = subprocess.run(
            [sys.executable, "-m", "repro.daemon.client", "--socket", sockp,
             "--queries", str(nq), "--seed", str(seed), "--tenant", "proc2",
             "--stats"],
            env=env, capture_output=True, text=True, timeout=300)
        if out.returncode != 0:
            raise RuntimeError(f"client subprocess failed: {out.stderr}")
        p2 = json.loads(out.stdout)
        p2_round = p2["rounds"][0]
        rep["proc2_cache_hits"] = p2_round["cache_hits"]
        rep["proc2_compile_delta"] = \
            p2["stats"]["exec"]["compiles"] - warmup_compiles \
            - rep["warm_compile_delta"]
        # ---- phase 4: fresh stream, warmed executables --------------------
        exec_before = c.stats()["exec"]
        t0 = time.perf_counter()
        fresh = c.optimize(fresh_graphs)
        rep["fresh_wall_s"] = time.perf_counter() - t0
        rep["fresh_cache_hits"] = c.last_meta["cache_hits"]
        exec_after = c.stats()["exec"]
        # a fresh stream may introduce a genuinely new bucket shape (a new
        # key = first compile); what it must never do is RE-trace a warmed
        # one — the two deltas are gated separately
        rep["fresh_compile_delta"] = \
            exec_after["compiles"] - exec_before["compiles"]
        rep["fresh_retrace_delta"] = \
            exec_after["retraces"] - exec_before["retraces"]
        # ---- phase 5: open-loop Poisson load (reported, never gated) ------
        rep["load"] = _load_phase(sockp, graphs[:2], load_tenants,
                                  load_rate_hz, load_arrivals, seed)
        st = c.stats()
        rep["load"]["daemon_request_wall_s"] = st["request_wall_s"]
        rep["load"]["daemon_shed_total"] = st["shed"]
        rep["daemon_stats"] = {k: st[k] for k in
                               ("requests", "queries", "shed", "errors",
                                "flights", "exec", "plancache")}
        c.close()
        rep["parent_backend_free"] = not _backend_started()
        # ---- phase 6: SIGTERM drain ---------------------------------------
        proc.send_signal(signal.SIGTERM)
        rc = proc.wait(timeout=60)
        rep["drain_exit_code"] = rc
        loaded = PlanCache.load(ckpt)
        rep["checkpoint_entries"] = len(loaded)
        rep["drain_clean"] = (rc == 0 and len(loaded) >= 2 * nq
                              and not os.path.exists(sockp))
    finally:
        if proc.poll() is None:
            proc.kill()
        for p in (ckpt, sockp):
            if os.path.exists(p):
                os.unlink(p)
    # ---- in-process reference, now that the daemon released the device:
    # same request order, one shared cache
    from repro.core.engine import optimize_many
    ref_cache = PlanCache()
    kw = {"devices": devices} if devices else {}
    ref_cold = optimize_many(graphs, cache=ref_cache, **kw)
    ref_warm = optimize_many(graphs, cache=ref_cache, **kw)
    ref_p2 = optimize_many(graphs, cache=ref_cache, **kw)
    ref_fresh = optimize_many(fresh_graphs, cache=ref_cache, **kw)
    rep["costs_equal_cold"] = _costs(cold) == _costs(ref_cold)
    rep["costs_equal_warm"] = _costs(warm) == _costs(ref_warm)
    rep["costs_equal_proc2"] = p2_round["costs"] == _costs(ref_p2)
    rep["costs_equal_fresh"] = _costs(fresh) == _costs(ref_fresh)
    return {"queries": nq, "seed": seed, "daemon": rep}


# Explicit, replayable chaos schedule (see docs/robustness.md).  Nth-call
# indices are chosen so the injected worker crashes land on load-phase job
# pickups (pickup 1 is the warmup request), the straggler chunks land
# during warmup dispatch, and the socket stall hits a mid-run reply.  NO
# cache_write corruption: the final drain checkpoint must load non-stale.
CHAOS_FAULTS = ("worker@2:raise;worker@4:raise;"
                "chunk@3:sleep:0.02;chunk@9:sleep:0.02;chunk@15:sleep:0.02;"
                "socket_send@5:stall:0.2")


def bench_chaos(nq: int = 4, seed: int = 0, requests: int = 6,
                rate_hz: float = 4.0, drain_timeout: float = 20.0,
                smoke: bool = False) -> dict:
    """Chaos phase: the daemon runs under a fixed ``REPRO_FAULTS`` schedule
    (worker crashes, straggler chunks, a mid-frame socket stall) and
    ``--drain-timeout``; clients drive Poisson-ish load with per-request
    timeouts + retries, plus one deadline-carrying request over fresh
    queries.  Deterministic gates (``check_regression.py check_chaos``):
    zero hung requests, degraded plans valid and no worse than GOO, the
    worker supervisor restarted at least once, and a clean bounded drain
    with a loadable checkpoint."""
    del smoke                        # chaos phase is already CI-sized
    from repro.core.config import OptimizerConfig
    from repro.core.plan import validate_plan
    from repro.core.plancache import PlanCache
    from repro.daemon import DaemonClient, DaemonShed
    from repro.heuristics import goo
    from repro.workloads.generators import mixed_stream

    graphs = mixed_stream(nq, seed)
    deadline_graphs = mixed_stream(nq, seed + 101)   # must miss the plan
    sockp = tempfile.mktemp(suffix=".sock")          # cache: fresh seeds
    ckpt = tempfile.mktemp(suffix=".plancache")
    cmd = [sys.executable, "-m", "repro.daemon", "--socket", sockp,
           "--cache-file", ckpt, "--checkpoint-every", "1000",
           "--queue-depth", "8", "--tenant-inflight", "2",
           "--drain-timeout", str(drain_timeout)]
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src") \
        + os.pathsep + env.get("PYTHONPATH", "")
    env["REPRO_FAULTS"] = CHAOS_FAULTS
    proc = subprocess.Popen(cmd, env=env)
    ch: dict = {"fault_plan": CHAOS_FAULTS, "requests": 0, "completed": 0,
                "shed": 0, "retried": 0, "failed": 0, "hung": 0}
    lock = threading.Lock()

    def robust_optimize(c, **kw):
        """First try without retries (so injected failures are observed),
        then retry with backoff — the documented client contract."""
        try:
            return c.optimize(graphs if "config" not in kw
                              else deadline_graphs,
                              timeout=kw.pop("timeout", 120.0),
                              retries=0, **kw)
        except Exception as e:
            retryable = (isinstance(e, (DaemonShed, ConnectionResetError,
                                        BrokenPipeError))
                         or getattr(e, "retryable", False))
            if not retryable:
                raise
            with lock:
                ch["retried"] += 1
            return c.optimize(graphs if "config" not in kw
                              else deadline_graphs,
                              timeout=120.0, retries=6, backoff_s=0.1, **kw)

    try:
        c = DaemonClient(socket_path=sockp, tenant="chaos",
                         connect_timeout=180.0)
        # warmup (worker pickup 1: no fault scheduled; pays JIT compile)
        robust_optimize(c, timeout=None)
        ch["requests"] += 1
        ch["completed"] += 1

        # Poisson-ish load: each arrival its own connection + thread; the
        # injected worker crashes land on these pickups and the retry
        # contract must absorb them — the gate is zero hung requests
        def one_request(i: int):
            try:
                with DaemonClient(socket_path=sockp,
                                  tenant=f"chaos-{i % 2}",
                                  connect_timeout=60.0) as cc:
                    robust_optimize(cc)
                with lock:
                    ch["completed"] += 1
            except DaemonShed:
                with lock:
                    ch["shed"] += 1
            except Exception:
                with lock:
                    ch["failed"] += 1

        rng = random.Random(seed)
        pending = []
        for i in range(requests):
            time.sleep(rng.expovariate(rate_hz))
            t = threading.Thread(target=one_request, args=(i,), daemon=True)
            t.start()
            pending.append(t)
        for t in pending:
            t.join(timeout=300)
            if t.is_alive():
                with lock:
                    ch["hung"] += 1
        ch["requests"] += requests

        # deadline-carrying request over fresh queries: must answer fast
        # with degraded (anytime) plans, never hang
        res = robust_optimize(c, config=OptimizerConfig(deadline_s=1e-4))
        ch["requests"] += 1
        ch["completed"] += 1
        ch["degraded"] = sum(1 for r in res if "degraded" in r.info)
        ok = True
        for g, r in zip(deadline_graphs, res):
            validate_plan(r.plan, g)
            if float(r.cost) > float(goo.solve(g).cost) * (1 + 1e-6):
                ok = False
        ch["degraded_valid"] = ok

        st = c.stats()
        ch["worker_restarts"] = st["worker_restarts"]
        ch["daemon_shed_total"] = st["shed"]
        c.close()

        # bounded drain: one SIGTERM; --drain-timeout caps the flush
        proc.send_signal(signal.SIGTERM)
        rc = proc.wait(timeout=120)
        ch["drain_exit_code"] = rc
        loaded = PlanCache.load(ckpt)
        ch["checkpoint_entries"] = len(loaded)
        ch["drain_clean"] = (rc == 0 and not loaded.stale_load
                             and len(loaded) >= 1
                             and not os.path.exists(sockp))
    finally:
        if proc.poll() is None:
            proc.kill()
        for p in (ckpt, sockp):
            if os.path.exists(p):
                os.unlink(p)
    return {"queries": nq, "seed": seed, "chaos": ch}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--queries", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--devices", type=int, default=None)
    ap.add_argument("--queue-depth", type=int, default=4)
    ap.add_argument("--tenant-inflight", type=int, default=2)
    ap.add_argument("--load-tenants", type=int, default=3)
    ap.add_argument("--load-rate", type=float, default=20.0,
                    help="per-tenant Poisson arrival rate (Hz)")
    ap.add_argument("--load-arrivals", type=int, default=60)
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run (8 queries, small load phase)")
    ap.add_argument("--chaos", action="store_true",
                    help="run the fault-injection chaos phase instead of "
                         "the standard six phases (seeded REPRO_FAULTS "
                         "daemon, retrying clients, deadline request, "
                         "bounded drain)")
    ap.add_argument("--json", type=str, default=None,
                    help="write the report here ('-' for stdout)")
    args = ap.parse_args()
    from repro.hostdev import ensure_compile_cache
    ensure_compile_cache()     # the daemon child and the reference replay
    if args.chaos:
        rep = bench_chaos(seed=args.seed, smoke=args.smoke)
        ch = rep["chaos"]
        print(f"[chaos] {ch['completed']}/{ch['requests']} completed, "
              f"{ch['shed']} shed, {ch['retried']} retried, "
              f"{ch['failed']} failed, {ch['hung']} hung")
        print(f"[chaos] degraded {ch.get('degraded')} valid "
              f"{ch.get('degraded_valid')}; worker restarts "
              f"{ch.get('worker_restarts')}")
        print(f"[chaos] drain: exit {ch.get('drain_exit_code')} checkpoint "
              f"{ch.get('checkpoint_entries')} entries clean "
              f"{ch.get('drain_clean')}")
        if args.json:
            payload = json.dumps(rep, indent=2, sort_keys=True)
            if args.json == "-":
                print(payload)
            else:
                with open(args.json, "w") as f:
                    f.write(payload + "\n")
        ok = (ch["hung"] == 0 and ch["failed"] == 0
              and ch.get("degraded", 0) >= 1 and ch.get("degraded_valid")
              and ch.get("worker_restarts", 0) >= 1
              and ch.get("drain_clean"))
        return 0 if ok else 1
    rep = bench(nq=args.queries, seed=args.seed, devices=args.devices,
                queue_depth=args.queue_depth,
                tenant_inflight=args.tenant_inflight,
                load_tenants=args.load_tenants, load_rate_hz=args.load_rate,
                load_arrivals=args.load_arrivals, smoke=args.smoke)
    d = rep["daemon"]
    print(f"[daemon] cold {d['cold_wall_s']:.2f}s warm "
          f"{d['warm_wall_s']*1e3:.1f}ms fresh {d['fresh_wall_s']:.2f}s "
          f"(warmup compiles {d['warmup_compiles']})")
    print(f"[daemon] compile deltas: warm {d['warm_compile_delta']} "
          f"proc2 {d['proc2_compile_delta']} fresh {d['fresh_compile_delta']}")
    print(f"[daemon] costs equal: cold {d['costs_equal_cold']} warm "
          f"{d['costs_equal_warm']} proc2 {d['costs_equal_proc2']} "
          f"fresh {d['costs_equal_fresh']}")
    print(f"[daemon] proc2 cross-client cache hits {d['proc2_cache_hits']}")
    ld = d["load"]
    print(f"[daemon] load: {ld['completed']}/{ld['arrivals']} completed, "
          f"{ld['shed']} shed @ {ld['offered_rate_hz']:.0f} Hz offered; "
          f"p99 {ld['latency_s']['p99']*1e3:.1f}ms")
    print(f"[daemon] drain: exit {d['drain_exit_code']} checkpoint "
          f"{d['checkpoint_entries']} entries clean {d['drain_clean']}")
    print(f"[daemon] parent started no JAX backend while the daemon ran: "
          f"{d['parent_backend_free']}")
    if args.json:
        payload = json.dumps(rep, indent=2, sort_keys=True)
        if args.json == "-":
            print(payload)
        else:
            with open(args.json, "w") as f:
                f.write(payload + "\n")
    ok = (d["costs_equal_cold"] and d["costs_equal_warm"]
          and d["costs_equal_proc2"] and d["costs_equal_fresh"]
          and d["warm_compile_delta"] == 0 and d["proc2_compile_delta"] == 0
          and d["fresh_retrace_delta"] == 0
          and d["proc2_cache_hits"] >= 1 and d["drain_clean"]
          and d["parent_backend_free"])
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
