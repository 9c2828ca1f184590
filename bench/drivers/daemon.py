"""Serve a cell through the optimizer daemon, driven from a child process.

This process holds the chip and runs an ``OptimizerDaemon`` on a unix
socket; the load generator (bench/client.py) runs as a child on the CPU
backend, so it never touches the chip and never takes the daemon's
interpreter lock.  Warm-up requests go through the same path before the
window; the daemon then gets a fresh plan cache, so no window query can be
served from a plan that the warm-up computed.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import threading

CLIENT = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "client.py")


def _read_line(proc, timeout: float) -> str:
    """Next stdout line of ``proc``, or raise after ``timeout`` seconds."""
    box: list[str] = []
    t = threading.Thread(target=lambda: box.append(proc.stdout.readline()),
                         daemon=True)
    t.start()
    t.join(timeout)
    if not box:
        raise TimeoutError(f"client said nothing within {timeout} s")
    return box[0]


def run(ctx) -> dict:
    from repro.core.plancache import PlanCache
    from repro.daemon import DaemonClient, OptimizerDaemon
    cfg = ctx.cell["config"]
    sock = os.path.join(ctx.tmpdir, "daemon.sock")
    cell_file = os.path.join(ctx.tmpdir, "cell.json")
    with open(cell_file, "w") as f:
        json.dump(ctx.cell, f)
    d = OptimizerDaemon(socket_path=sock,
                        queue_depth=int(cfg["daemon"]["queue_depth"]),
                        tenant_inflight=int(cfg["daemon"]["tenant_inflight"]),
                        devices=ctx.cell["chips"] if ctx.cell["chips"] > 1
                        else None)
    d.start()
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.Popen(
        [sys.executable, CLIENT, "--cell", cell_file, "--seed", str(ctx.seed),
         "--seconds", str(ctx.seconds), "--socket", sock],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, env=env, text=True)
    try:
        line = _read_line(proc, 900)
        if line.strip() != "READY":
            raise RuntimeError(f"client did not get ready: {line!r}")
        d.cache = PlanCache()
        with DaemonClient(socket_path=sock, tenant="bench-stats") as sc:
            st0 = sc.stats()
            ctx.window_begin()
            proc.stdin.write("GO\n")
            proc.stdin.flush()
            line = _read_line(proc, ctx.seconds + 240)
            ctx.window_end()
            st1 = sc.stats()
        proc.wait(timeout=60)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
        d.drain(timeout=60)
    if proc.returncode != 0:
        raise RuntimeError(f"client exited with {proc.returncode}")
    return {"records": json.loads(line)["records"],
            "stats": {"before": st0, "after": st1}}
