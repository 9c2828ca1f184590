"""Program spans on the profiler's host timeline (``telemetry.span``).

One trace, recorded here on the CPU with ``jax.profiler``, holds a daemon
request, a pipelined ``StreamOptimizer`` stream and a UnionDP solve of 20
relations, each run once untraced first so nothing compiles inside it.
Every documented span name must appear, the spans of each host line must
nest as documented, and each line's self times must fit the window.
"""
import glob
import threading
import time

import pytest

import jax

from bench import spans as bspans
from repro.core.plancache import PlanCache
from repro.core.service import StreamOptimizer
from repro.daemon import DaemonClient, OptimizerDaemon
from repro.heuristics import uniondp
from repro.workloads import generators as gen

NAMES = {"daemon.idle", "daemon.job", "daemon.decode", "daemon.encode",
         "service.admit", "service.finalize", "engine.setup",
         "engine.levels", "engine.collect", "level.filter", "level.register",
         "level.pairs", "level.eval", "level.fetch", "uniondp.solve",
         "uniondp.partition", "uniondp.reopt"}

# span -> the spans it may sit directly inside (None: a line's top level)
PARENTS = {
    "level.fetch": {"level.filter", "level.pairs", "level.eval",
                    "engine.collect"},
    "level.filter": {"engine.levels"}, "level.register": {"engine.levels"},
    "level.pairs": {"engine.levels"}, "level.eval": {"engine.levels"},
    "engine.setup": {"daemon.job", "uniondp.solve", "uniondp.partition",
                     "uniondp.reopt", None},
    "engine.levels": {"daemon.job", "uniondp.solve", "uniondp.partition",
                      "uniondp.reopt", None},
    "engine.collect": {"service.finalize", "uniondp.solve",
                       "uniondp.partition", "uniondp.reopt"},
    "service.admit": {"daemon.job", "uniondp.solve", "uniondp.partition",
                      "uniondp.reopt", None},
    "service.finalize": {"daemon.job", "uniondp.solve", "uniondp.partition",
                         "uniondp.reopt", None},
    "daemon.decode": {"daemon.job"}, "daemon.encode": {"daemon.job"},
    "daemon.job": {None}, "daemon.idle": {None},
    "uniondp.partition": {"uniondp.solve"}, "uniondp.reopt": {"uniondp.solve"},
    "uniondp.solve": {None},
}

GRAPHS = [gen.chain(6, 1), gen.cycle(7, 2), gen.musicbrainz_query(9, 3)]
BIG = gen.snowflake(20, 4)


def workload(sock: str) -> None:
    with DaemonClient(socket_path=sock, tenant="t") as c:
        c.optimize(GRAPHS)
        c.optimize(GRAPHS[:1])    # the worker's idle span between the two
                                  # requests starts and ends in the trace
    StreamOptimizer(pipeline=True).optimize_stream(GRAPHS)
    uniondp.solve(BIG, k=8, reopt_rounds=2)


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("spans")
    d = OptimizerDaemon(socket_path=str(tmp / "d.sock"),
                        checkpoint_every=10_000)
    d.start()
    try:
        workload(d.address)                    # compiles every shape
        d.cache = PlanCache()
        jax.profiler.start_trace(str(tmp / "trace"))
        t0 = time.perf_counter_ns()
        workload(d.address)
        time.sleep(0.05)                       # the worker is idle again
        jax.profiler.stop_trace()
        wall = time.perf_counter_ns() - t0
    finally:
        d.drain()
    (pb,) = glob.glob(str(tmp / "trace" / "**" / "*.xplane.pb"),
                      recursive=True)
    return bspans.load(pb), wall


def parents(spans):
    """(name, parent name) of every span, per host line, by a stack."""
    out = []
    by_line: dict = {}
    for name, line, s, e in spans:
        by_line.setdefault(line, []).append((s, -e, name))
    for items in by_line.values():
        stack: list = []
        for s, neg_e, name in sorted(items):
            while stack and stack[-1][0] <= s:
                stack.pop()
            assert not stack or -neg_e <= stack[-1][0], \
                f"{name} overlaps {stack[-1][1]} without nesting in it"
            out.append((name, stack[-1][1] if stack else None))
            stack.append((-neg_e, name))
    return out


def test_every_documented_span_appears(traced):
    spans, _ = traced
    assert {n for n, *_ in spans} == NAMES


def test_spans_nest_as_documented(traced):
    spans, _ = traced
    pairs = parents(spans)
    for name, parent in pairs:
        assert parent in PARENTS[name], (name, parent)
    inside = {(n, p) for n, p in pairs}
    assert ("level.fetch", "level.eval") in inside
    assert ("level.fetch", "level.pairs") in inside
    assert ("engine.levels", "daemon.job") in inside
    assert ("engine.levels", "uniondp.partition") in inside
    assert ("engine.levels", "uniondp.reopt") in inside


def test_daemon_spans_share_the_worker_line(traced):
    spans, _ = traced
    job_lines = {ln for n, ln, *_ in spans if n == "daemon.job"}
    solve_lines = {ln for n, ln, *_ in spans if n == "uniondp.solve"}
    assert len(job_lines) == 1 and job_lines.isdisjoint(solve_lines)
    assert {n for n, ln, *_ in spans if ln in job_lines} >= {
        "daemon.idle", "daemon.decode", "daemon.encode", "service.admit",
        "engine.levels", "level.fetch"}


def test_self_times_fit_the_window(traced):
    spans, wall = traced
    lo = min(s for *_, s, _ in spans)
    hi = max(e for *_, e in spans)
    assert hi - lo <= wall
    for line in {ln for _, ln, *_ in spans}:
        r = bspans.reduce([sp for sp in spans if sp[1] == line], lo, hi)
        assert sum(v["self_s"] for v in r.values()) <= (hi - lo) / 1e9
        for v in r.values():
            assert 0 <= v["self_s"] <= v["total_s"] + 1e-12


def test_trace_op_profiles_a_live_daemon(tmp_path):
    """An operator's ``trace`` request: the daemon profiles itself for the
    given seconds and the spans of requests it served meanwhile are in
    the written trace."""
    d = OptimizerDaemon(socket_path=str(tmp_path / "d.sock"),
                        checkpoint_every=10_000)
    d.start()
    try:
        with DaemonClient(socket_path=d.address) as c:
            c.optimize(GRAPHS[:1])             # compiled before the trace
        d.cache = PlanCache()
        out: dict = {}

        def trace():
            with DaemonClient(socket_path=d.address) as c:
                out["dir"] = c.trace(str(tmp_path / "live"), seconds=3.0)

        t = threading.Thread(target=trace)
        t.start()
        time.sleep(0.5)
        with DaemonClient(socket_path=d.address) as c:
            c.optimize(GRAPHS[:1])
        t.join(timeout=60)
        assert out["dir"] == str(tmp_path / "live")
    finally:
        d.drain()
    (pb,) = glob.glob(str(tmp_path / "live" / "**" / "*.xplane.pb"),
                      recursive=True)
    names = {n for n, *_ in bspans.load(pb)}
    assert {"daemon.job", "service.admit", "level.eval"} <= names
