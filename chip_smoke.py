#!/usr/bin/env python3
"""Drive the served optimizer once on a TPU and check what comes out.

    python chip_smoke.py             # one chip (the default)
    python chip_smoke.py --chips 4   # the multi-chip paths only

One chip, in one process (the in-process daemon is the only user of the
chip; nothing here starts a child process):

  serve-cold    an ``OptimizerDaemon`` on a unix socket, driven by
                ``DaemonClient``, answers a 32-query MusicBrainz
                ``mixed_stream`` (sizes 10/12/14/16: the nmax=16 batched
                bucket, one 16-query flight per MPDP lane space);
  serve-warm    the same stream again: every query a plan-cache hit, no
                compile, no retrace;
  serve-solo    two exact queries past the batched bucket (n=20, n=24: the
                solo engine's 2^24 memo);
  reference     in-process ``optimize_many`` over the same request order
                (costs must be equal, bit for bit) and host DPCCP for every
                n <= 16 query (relative 1e-4, the suites' tolerance);
  heuristic     ``uniondp.solve`` on the full 56-table MusicBrainz schema
                and an 80-relation snowflake, each no worse than GOO;
  pallas        one flight with the Pallas evaluate kernels compiled (not
                interpreted) against the vector path's costs.

``--chips 4`` runs only what exists across chips: the daemon with
``devices=4`` on the same stream plus two lattice-sharded queries
(n=18, n=20), each compared bit for bit with one-chip results computed in
this process, with one level-commit collective per committed level.

Every phase prints one line with the device kind, its first-run wall time
(set-up including compilation, not a measurement), the XLA compile count,
the device's peak bytes in use and the compile-cache directory, then one
line per check.  The last line of stdout is one JSON object
``{"ok": ..., "device": {"platform", "kind", "count"}}``; the exit code is
0 only when every check passed on a TPU.  Anywhere else the script stops
before its phases and exits 1.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import tempfile
import time
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))

STREAM_QUERIES, STREAM_SIZES, STREAM_SEED = 32, (10, 12, 14, 16), 0
SOLO = ((20, 420), (24, 424))                  # (n, musicbrainz seed)
LATTICE = ((18, 418), (20, 420))
HEURISTIC = (("musicbrainz", 56, 256), ("snowflake", 80, 80))  # k=10
PALLAS_DPSUB = 8          # first stream queries re-run in the DPSUB space
DPCCP_RTOL = 1e-4         # device vs host DPCCP (tests/oracle tolerance)
GOO_EPS = 2e-3            # UnionDP <= GOO margin (test_uniondp_quality)


class PhaseFailed(RuntimeError):
    """A phase raised: later phases would only repeat the failure."""


class Smoke:
    """Per-phase bookkeeping: checks, compile counts, device memory."""

    def __init__(self, cache_dir: str):
        import jax
        self.cache_dir = cache_dir
        self.dev = jax.devices()[0]
        self.failed: list[str] = []
        self.xla_compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, _secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.xla_compiles += 1

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def peak_bytes(self):
        stats = self.dev.memory_stats() or {}
        return stats.get("peak_bytes_in_use", "not reported")

    @contextlib.contextmanager
    def phase(self, name: str):
        """Run one phase; yields ``check(cond, what)``.  A failed check
        fails the phase; an exception fails it and stops the run."""
        from repro.core.exec_cache import EXEC
        checks: list[tuple[bool, str]] = []

        def check(cond, what: str) -> bool:
            checks.append((bool(cond), what))
            return bool(cond)

        t0, c0, h0, e0 = (time.perf_counter(), self.xla_compiles,
                          self.cache_hits, EXEC.total())
        err = None
        try:
            yield check
        except Exception as e:                   # reported, then re-raised
            err = e
            traceback.print_exc()
        wall = time.perf_counter() - t0
        ok = err is None and all(c for c, _ in checks)
        print(f"[{name}] {'PASS' if ok else 'FAIL'} | device "
              f"{self.dev.device_kind} | first-run set-up {wall:.3f} s "
              f"(compiles included, not a measurement) | XLA compile requests "
              f"{self.xla_compiles - c0} (persistent-cache hits "
              f"{self.cache_hits - h0}) | executable-cache compiles "
              f"{EXEC.total() - e0} | peak device bytes {self.peak_bytes()} "
              f"| compile cache {self.cache_dir}", flush=True)
        for c, what in checks:
            print(f"    {'ok  ' if c else 'FAIL'} {what}", flush=True)
        if err is not None:
            print(f"    FAIL {type(err).__name__}: {err}", flush=True)
        if not ok:
            self.failed.append(name)
        if err is not None:
            raise PhaseFailed(name) from err


# ----------------------------------------------------------------- checks --

def _shape(p):
    return p.rel_set if p.is_leaf else (_shape(p.left), _shape(p.right))


def fingerprint(results) -> list:
    return [(float(r.cost), _shape(r.plan)) for r in results]


def check_results(check, graphs, results, what: str) -> None:
    """Valid plans, no degraded or re-dispatched result."""
    from repro.core.plan import validate_plan
    bad = []
    for g, r in zip(graphs, results):
        try:
            validate_plan(r.plan, g)
        except AssertionError as e:              # collect, report once
            bad.append(f"{g.n}: {e}")
    check(len(results) == len(graphs) and not bad,
          f"{what}: {len(results)} plans pass validate_plan {bad or ''}")
    flagged = [k for r in results for k in ("degraded", "redispatched")
               if r.info.get(k)]
    check(not flagged, f"{what}: no degraded or redispatched result "
                       f"{flagged or ''}")


@contextlib.contextmanager
def _daemon(sock: str, **kw):
    from repro.daemon import DaemonClient, OptimizerDaemon
    d = OptimizerDaemon(socket_path=sock, **kw)
    d.start()
    try:
        with DaemonClient(socket_path=sock, tenant="smoke") as c:
            yield c
    finally:
        d.drain(timeout=60)


# ----------------------------------------------------------------- phases --

def one_chip(sm: Smoke, tmp: str, stream, solos, heuristic, pallas_dpsub):
    """The one-chip phases over the given graphs (see the module doc)."""
    from repro.core import dpccp, faults
    from repro.core.engine import optimize_many
    from repro.core.plancache import PlanCache
    from repro.heuristics import goo, uniondp
    from repro.kernels import ops

    with _daemon(os.path.join(tmp, "one.sock")) as c:
        with sm.phase("serve-cold") as check:
            check(not faults.active(), "no fault plan installed")
            start = c.stats()        # the executable cache is process-wide
            cold = c.optimize(stream)
            meta = dict(c.last_meta)
            check_results(check, stream, cold, f"{len(stream)}-query stream")
            check(meta["cache_hits"] == 0 and meta["flights"] >= 1,
                  f"computed in {meta['flights']} flights, "
                  f"{meta['cache_hits']} cache hits")
        with sm.phase("serve-warm") as check:
            st0 = c.stats()
            warm = c.optimize(stream)
            meta, st1 = dict(c.last_meta), c.stats()
            check(meta["cache_hits"] == len(stream),
                  f"plan-cache hits {meta['cache_hits']}/{len(stream)}")
            dc = st1["exec"]["compiles"] - st0["exec"]["compiles"]
            dr = st1["exec"]["retraces"] - st0["exec"]["retraces"]
            check(dc == 0 and dr == 0,
                  f"executable compiles {dc}, retraces {dr} on the warm pass")
        with sm.phase("serve-solo") as check:
            solo = c.optimize(solos) if solos else []
            check_results(check, solos, solo,
                          f"solo exact n={[g.n for g in solos]}")
            check(not solos or c.last_meta["solo"] == len(solos),
                  f"{len(solos)} queries ran on the solo engine")
            st = c.stats()
            dr = st["exec"]["retraces"] - start["exec"]["retraces"]
            check(st["errors"] == 0 and dr == 0,
                  f"daemon STATS: errors {st['errors']}, retraces {dr}, "
                  f"shed {st['shed']}")

    with sm.phase("reference") as check:
        cache = PlanCache()
        ref_cold = optimize_many(stream, cache=cache)
        ref_warm = optimize_many(stream, cache=cache)
        ref_solo = optimize_many(solos, cache=cache) if solos else []
        for what, got, ref in (("cold", cold, ref_cold),
                               ("warm", warm, ref_warm),
                               ("solo", solo, ref_solo)):
            check(fingerprint(got) == fingerprint(ref),
                  f"daemon {what} costs and plans equal in-process "
                  "optimize_many exactly")
        worst, off = 0.0, []
        for g, r in zip(stream, cold):
            if g.n > 16:
                continue
            want = float(dpccp.solve(g).cost)
            rel = abs(float(r.cost) - want) / want
            worst = max(worst, rel)
            if rel > DPCCP_RTOL:
                off.append((g.n, float(r.cost), want))
        check(not off, f"host DPCCP agrees on every n<=16 query: worst "
                       f"relative gap {worst!r} (limit {DPCCP_RTOL}) "
                       f"{off or ''}")

    with sm.phase("heuristic") as check:
        for g in heuristic:
            r = uniondp.solve(g, k=10)
            check_results(check, [g], [r], f"uniondp n={g.n}")
            bound = float(goo.solve(g).cost)
            check(float(r.cost) <= bound * (1 + GOO_EPS),
                  f"uniondp n={g.n}: cost {float(r.cost)!r} <= GOO "
                  f"{bound!r} (x{1 + GOO_EPS})")

    with sm.phase("pallas") as check:
        interp = ops.interpret_mode()
        check(interp == (sm.dev.platform == "cpu"),
              f"Pallas kernels {'interpreted' if interp else 'compiled'} "
              f"on {sm.dev.platform}")
        sub = stream[:pallas_dpsub]
        vec_dpsub = optimize_many(sub, algorithm="dpsub")
        from repro.core.exec_cache import EXEC
        before = set(EXEC.snapshot())
        os.environ["REPRO_PALLAS"] = "1"
        try:
            pal = optimize_many(stream)
            pal_dpsub = optimize_many(sub, algorithm="dpsub")
        finally:
            del os.environ["REPRO_PALLAS"]
        keys = [k for k in set(EXEC.snapshot()) - before
                if ("pallas", True) in k]
        check(keys, f"{len(keys)} Pallas executables compiled")
        check_results(check, stream, pal, "Pallas flight")
        check(fingerprint(pal) == fingerprint(ref_cold),
              "Pallas costs and plans equal the vector path's (MPDP spaces)")
        check(fingerprint(pal_dpsub) == fingerprint(vec_dpsub),
              "Pallas costs and plans equal the vector path's (DPSUB)")


def four_chips(sm: Smoke, tmp: str, stream, lattice, devices: int):
    """Batch-sharded and lattice-sharded daemon flights against one-chip
    results from this process."""
    from repro.core import engine
    from repro.distributed import collectives as coll

    with sm.phase("one-chip-reference") as check:
        one = engine.optimize_many(stream)
        one_lat = [engine.optimize(g) for g in lattice]
        check_results(check, stream + lattice, one + one_lat,
                      "one-chip stream + lattice queries")
    with _daemon(os.path.join(tmp, "four.sock"), devices=devices) as c:
        with sm.phase(f"sharded-{devices}") as check:
            got = c.optimize(stream)
            check_results(check, stream, got, f"devices={devices} stream")
            check(fingerprint(got) == fingerprint(one),
                  f"devices={devices} costs and plans equal one chip's")
        with sm.phase(f"lattice-{devices}") as check:
            commits0 = coll.STATS.snapshot()
            got = c.optimize(lattice)
            commits = coll.STATS.snapshot() - commits0
            levels = sum(g.n - 1 for g in lattice)
            check(c.last_meta["lattice"] == len(lattice),
                  f"{c.last_meta['lattice']}/{len(lattice)} lattice flights")
            check_results(check, lattice, got,
                          f"lattice n={[g.n for g in lattice]}")
            check(fingerprint(got) == fingerprint(one_lat),
                  "lattice costs and plans equal the one-chip solo engine's")
            check(commits == levels,
                  f"{commits} level-commit collectives for {levels} "
                  "committed levels")


# ------------------------------------------------------------------- main --

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded-batch and lattice paths "
                         "against one-chip results")
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(HERE, "src"))
    from repro.hostdev import ensure_compile_cache   # before importing jax
    cache_dir = ensure_compile_cache()
    import jax

    devs = jax.devices()
    dev = devs[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs)}
    if dev.platform != "tpu" or len(devs) < args.chips:
        print(f"chip_smoke: needs {args.chips} TPU chip(s); JAX found "
              f"{len(devs)} {dev.platform} device(s)", file=sys.stderr)
        print(json.dumps({"ok": False, "device": device}))
        return 1

    from repro.workloads import generators as gen
    sm = Smoke(cache_dir)
    stream = gen.mixed_stream(STREAM_QUERIES, STREAM_SEED, sizes=STREAM_SIZES)
    try:
        with tempfile.TemporaryDirectory() as tmp:
            if args.chips == 1:
                one_chip(sm, tmp, stream,
                         [gen.musicbrainz_query(n, seed=s) for n, s in SOLO],
                         [gen.musicbrainz_query(n, seed=s)
                          if kind == "musicbrainz" else gen.snowflake(n, seed=s)
                          for kind, n, s in HEURISTIC],
                         PALLAS_DPSUB)
            else:
                four_chips(sm, tmp, stream,
                           [gen.musicbrainz_query(n, seed=s)
                            for n, s in LATTICE], args.chips)
    except PhaseFailed:
        pass
    entries = sum(len(fs) for _, _, fs in os.walk(cache_dir)) \
        if os.path.isdir(cache_dir) else 0
    print(f"compile cache {cache_dir}: {entries} files")
    ok = not sm.failed
    if not ok:
        print(f"chip_smoke: failed phases {sm.failed}", file=sys.stderr)
    print(json.dumps({"ok": ok, "device": device}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
