"""Program spans of a trace (bench/spans.py): per-name count, total and self
time on handmade spans, idle-gap labels that name program spans, and the
loader on a trace recorded here with ``jax.profiler``."""
import glob
import threading

import pytest

from bench import spans, tracing

# window [0, 100] ns; line 0 nests job > eval > fetch, and a second eval
# runs past the window's end; line 1 idles from before the window's start
HAND = [["daemon.job", 0, 10, 90], ["level.eval", 0, 20, 60],
        ["level.fetch", 0, 30, 50], ["level.eval", 0, 70, 85],
        ["level.fetch", 0, 72, 80], ["engine.collect", 0, 95, 130],
        ["level.fetch", 0, 100, 110], ["daemon.idle", 1, -40, 40],
        ["daemon.idle", 1, 150, 160]]


def test_counts_totals_and_self_times():
    r = spans.reduce(HAND, 0, 100)
    assert r["daemon.job"] == {"count": 1, "total_s": pytest.approx(80e-9),
                               "self_s": pytest.approx((80 - 40 - 15) * 1e-9)}
    assert r["level.eval"] == {"count": 2, "total_s": pytest.approx(55e-9),
                               "self_s": pytest.approx((40 - 20 + 15 - 8)
                                                       * 1e-9)}
    assert r["level.fetch"] == {"count": 2, "total_s": pytest.approx(28e-9),
                                "self_s": pytest.approx(28e-9)}
    # clipped at the window's end; its child starts at the end: not counted
    assert r["engine.collect"] == {"count": 1, "total_s": pytest.approx(5e-9),
                                   "self_s": pytest.approx(5e-9)}
    # clipped at the window's start; the span after the window is left out
    assert r["daemon.idle"] == {"count": 1, "total_s": pytest.approx(40e-9),
                                "self_s": pytest.approx(40e-9)}
    assert set(r) == {"daemon.job", "level.eval", "level.fetch",
                      "engine.collect", "daemon.idle"}


def test_self_time_is_per_line():
    """A span on another line that overlaps in time is no child."""
    r = spans.reduce([["daemon.job", 0, 0, 50], ["level.eval", 1, 10, 20]],
                     0, 100)
    assert r["daemon.job"]["self_s"] == pytest.approx(50e-9)
    assert r["level.eval"]["self_s"] == pytest.approx(10e-9)


def test_self_times_sum_to_covered_time():
    r = spans.reduce(HAND, 0, 100)
    covered = (90 - 10) + (100 - 95) + 40          # line 0 + line 1
    assert sum(v["self_s"] for v in r.values()) == pytest.approx(covered
                                                                 * 1e-9)


TRACE = {"modules": {"/device:TPU:0": [["jit_btree", 10, 30],
                                       ["jit_bfilter", 60, 70]]},
         "host": [["daemon.job", 6, 95], ["level.fetch", 32, 58],
                  ["np.asarray(jax.Array)", 40, 50]],
         "marks": [["bench.window", 0, 100]]}


def test_gap_labels_name_program_spans(monkeypatch):
    """Program spans on a Python line join the gap labels; the innermost
    span at a gap's middle names it."""
    monkeypatch.setattr(tracing, "GRID_NS", 1)
    gaps = dict(tracing.reduce(TRACE)["idle_gaps"])
    assert gaps["np.asarray(jax.Array)"] == pytest.approx(30e-9)   # 30-60
    assert gaps["daemon.job"] == pytest.approx(30e-9)              # 70-100
    assert gaps["bench.window"] == pytest.approx(10e-9)            # 0-10
    assert spans.longest_gap(TRACE) == (pytest.approx(30e-9),
                                        "np.asarray(jax.Array)")


def test_longest_gap_names_the_span_at_its_middle(monkeypatch):
    monkeypatch.setattr(tracing, "GRID_NS", 1)
    t = dict(TRACE, modules={"/device:TPU:0": [["jit_btree", 0, 20]]})
    assert spans.longest_gap(t) == (pytest.approx(80e-9), "daemon.job")


def test_load_keeps_program_spans_of_every_line(tmp_path):
    """Spans recorded by the profiler on two threads: metadata cut from
    the names, each thread on a line of its own, other events left out."""
    import jax
    from repro.core.telemetry import span

    def worker():
        with span("daemon.job", seq=7, tenant="t"):
            with span("level.fetch"):
                pass

    jax.profiler.start_trace(str(tmp_path))
    with span("uniondp.solve", n=3):
        with span("bench.solve"):
            t = threading.Thread(target=worker)
            t.start()
            t.join()
    jax.profiler.stop_trace()
    (pb,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    got = spans.load(pb)
    assert sorted(n for n, *_ in got) == ["daemon.job", "level.fetch",
                                          "uniondp.solve"]
    line = {n: ln for n, ln, _, _ in got}
    assert line["daemon.job"] == line["level.fetch"] != line["uniondp.solve"]
    r = spans.reduce(got, min(s for *_, s, _ in got),
                     max(e for *_, e in got))
    assert r["daemon.job"]["self_s"] < r["daemon.job"]["total_s"]
