#!/usr/bin/env python3
"""Run one benchmark cell once and print its result.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Loads the cell's configuration and traffic mix (bench/cell.py), builds the
run's queries from the seed, sets up and warms up the system under test,
measures for ``--seconds``, then checks every answer of the window against
the plain reference (bench/reference.py, via the configuration's check in
bench/checks/) and prints the metrics.  ``--trace 0`` reports the cell's
end-to-end metrics, ``--trace 1`` traces the window with the JAX profiler
and reports its per-layer metrics instead.

Earlier lines of stdout give set-up phases, compiles inside the window
(there should be none), how late the load generator ran, shed and failed
requests and the device's peak memory.  The last lines of stderr give each
number the check compared with its limit, and the last line of stdout is
one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``,
``device`` (with ``busy_s`` and ``window_s`` when traced), ``breakdown``
when traced, and last ``checks``.  Without a TPU, or with fewer chips than
the cell needs, it prints no result and exits 2.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell needs."""


class Context:
    """What a driver needs, and the window it marks."""

    def __init__(self, cell, seed, seconds, trace, tmpdir, plan, t_start):
        self.cell, self.seed, self.seconds = cell, seed, seconds
        self.trace, self.tmpdir, self.plan = trace, tmpdir, plan
        self.t_start = t_start
        self.compiles = {"requests": 0, "cache_hits": 0}
        self._ann = None

    def count_compiles(self):
        import jax

        def on_duration(event, _secs, **_kw):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compiles["requests"] += 1

        def on_event(event, **_kw):
            if event == "/jax/compilation_cache/cache_hits":
                self.compiles["cache_hits"] += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def window_begin(self):
        import jax
        from repro.core.exec_cache import EXEC
        self.setup_s = time.perf_counter() - self.t_start
        self.before = (dict(self.compiles), EXEC.total())
        if self.trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            self.trace_dir = os.path.join(self.tmpdir, "trace")
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
            self._ann = jax.profiler.TraceAnnotation("bench.window")
            self._ann.__enter__()
        self.t0 = time.perf_counter()

    def window_end(self):
        import jax
        from repro.core.exec_cache import EXEC
        self.t1 = time.perf_counter()
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            jax.profiler.stop_trace()
        c0, e0 = self.before
        self.in_window = {k: self.compiles[k] - c0[k] for k in c0}
        self.in_window["traces"] = EXEC.total() - e0


def _pool(workers: int):
    """Processes for the reference.  They import numpy only (this module's
    top level, which spawn runs again, imports no JAX), so none of them
    touches the chip."""
    import multiprocessing
    from concurrent.futures import ProcessPoolExecutor
    return ProcessPoolExecutor(
        max_workers=workers, mp_context=multiprocessing.get_context("spawn"))


def _device(require_tpu: bool, chips: int) -> dict:
    import jax
    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < chips):
        raise NoChip(f"the cell needs {chips} TPU chip(s); JAX found "
                     f"{len(devs)} {devs[0].platform} device(s)")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def _peak_bytes(chips: int):
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()[:chips]]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def execute(cell: dict, seed: int, seconds: float, trace: bool, *,
            require_tpu: bool = True, workers: int | None = None,
            t_start: float | None = None, log=print) -> dict:
    """Run the cell once; returns the result object (see the module doc)
    and logs the earlier lines through ``log``."""
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from repro.hostdev import ensure_compile_cache
    cache_dir = ensure_compile_cache()
    from bench import cell as _cell, traffic, tracing
    t_start = T_START if t_start is None else t_start
    device = _device(require_tpu, cell["chips"])
    t_backend = time.perf_counter() - t_start
    tmpdir = tempfile.mkdtemp(prefix="bench-")
    try:
        plan = traffic.build(cell, seed, seconds)
        ctx = Context(cell, seed, seconds, trace, tmpdir, plan, t_start)
        ctx.count_compiles()
        driver = _cell.module("drivers", cell["traffic"]["driver"])
        out = driver.run(ctx)
        peak = _peak_bytes(cell["chips"])
        run = {"cell": cell, "seed": seed, "records": out["records"],
               "requests": plan["requests"], "stats": out.get("stats"),
               "setup_s": ctx.setup_s, "seconds": seconds, "device": device}
        if trace:
            pb = [os.path.join(dp, f) for dp, _, fs in os.walk(ctx.trace_dir)
                  for f in fs if f.endswith(".xplane.pb")]
            run["trace"] = tracing.reduce(tracing.load(pb[0]))
            shutil.rmtree(ctx.trace_dir, ignore_errors=True)
        pool = _pool(workers or min(12, os.cpu_count() or 1))
        try:
            verdict = _cell.module("checks", cell["config"]["check"]["kind"]
                                   ).check(run, pool)
            metrics = {}
            for m in cell["per_layer" if trace else "end_to_end"]:
                v = _cell.module("metrics", m["name"]).read(run)
                if v is not None:
                    metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        finally:
            pool.shutdown()
    finally:
        shutil.rmtree(tmpdir, ignore_errors=True)
    recs = run["records"]
    late = sorted(r["send"] - r["due"] for r in recs if r["due"] is not None)
    failed = sum(r["queries"] for r in recs if r["status"] != "ok")
    log(f"device {device['kind']} x{device['count']} | set-up "
        f"{ctx.setup_s!r} s (backend {t_backend!r} s) | compile cache "
        f"{cache_dir}")
    log(f"window {ctx.t1 - ctx.t0!r} s | requests {len(recs)} | shed "
        f"{sum(r['status'] == 'shed' for r in recs)} | errors "
        f"{sum(r['status'] == 'error' for r in recs)} | compiles in window "
        f"{ctx.in_window['requests']} (persistent-cache hits "
        f"{ctx.in_window['cache_hits']}, executable traces "
        f"{ctx.in_window['traces']})")
    if late:
        log(f"generator lateness: median {late[len(late) // 2] * 1e3!r} ms, "
            f"max {late[-1] * 1e3!r} ms")
    log(f"peak device bytes {peak} | checked {verdict['checked']} answers "
        f"{verdict['notes'] or ''}")
    if trace:
        device.update(busy_s=run["trace"]["busy_s"],
                      window_s=run["trace"]["window_s"])
    device["memory_peak_bytes"] = peak
    result = {"correct": bool(verdict["ok"]),
              "attempted": sum(r["queries"] for r in recs),
              "failed": failed, "metrics": metrics, "device": device}
    if trace:
        result["breakdown"] = {"device_ops": run["trace"]["device_ops"],
                               "idle_gaps": run["trace"]["idle_gaps"]}
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in verdict["numbers"].items()}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    from bench.cell import load_cell
    cell = load_cell(args.workload)
    try:
        result = execute(cell, args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
