"""The trace reduction, on a handmade trace and on a small trace recorded on
a TPU v5e (a batched MPDP pass, bench/testdata/tpu_trace_small.json.gz)."""
import os

import pytest

from bench import tracing

RECORDED = os.path.join(os.path.dirname(tracing.__file__), "testdata",
                        "tpu_trace_small.json.gz")


def brute_busy(intervals, lo, hi):
    """Covered length by a sweep over sorted endpoints."""
    events = sorted([(max(s, lo), 1) for s, e in intervals if e > lo and s < hi]
                    + [(min(e, hi), -1) for s, e in intervals
                       if e > lo and s < hi])
    busy, depth, last = 0.0, 0, None
    for t, d in events:
        if depth > 0:
            busy += t - last
        depth += d
        last = t
    return busy


HAND = {"modules": {"/device:TPU:0": [["jit_btree", 10, 30], ["jit_bfilter", 20, 40],
                                      ["jit_btree", 60, 70], ["jit_bgeneral", 95, 130]]},
        "host": [["np.asarray(jax.Array)", 40, 60], ["DevicePut", 70, 90]],
        "marks": [["bench.window", 0, 100]]}


def test_union_merges_and_clips():
    u = tracing.union([[5, 8], [1, 3], [2, 4], [7, 9], [20, 30]], 0, 25)
    assert u.tolist() == [[1, 4], [5, 9], [20, 25]]


def test_handmade_trace(monkeypatch):
    monkeypatch.setattr(tracing, "GRID_NS", 1)
    r = tracing.reduce(HAND)
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["busy_s"] == pytest.approx((30 + 10 + 5) * 1e-9)
    assert r["module_s"]["jit_btree"] == pytest.approx(30e-9)
    assert r["module_s"]["jit_bgeneral"] == pytest.approx(5e-9)
    assert r["device_ops"][0][0] == "jit_btree"
    gaps = dict(r["idle_gaps"])
    assert gaps["np.asarray(jax.Array)"] == pytest.approx(20e-9)
    assert gaps["DevicePut"] == pytest.approx(25e-9)       # 70-95: mid in it
    assert gaps["bench.window"] == pytest.approx(10e-9)    # 0-10: no span
    assert sum(gaps.values()) == pytest.approx(r["window_s"] - r["busy_s"])


def test_recorded_tpu_trace():
    t = tracing.read(RECORDED)
    assert os.path.getsize(RECORDED) < 256 * 1024
    (dev,) = t["modules"]
    assert dev.startswith("/device:TPU:")
    lo, hi = tracing.window(t)
    r = tracing.reduce(t)
    mods = t["modules"][dev]
    assert r["window_s"] == pytest.approx((hi - lo) / 1e9)
    assert r["busy_s"] == pytest.approx(
        brute_busy([(s, e) for _, s, e in mods], lo, hi) / 1e9)
    assert 0 < r["busy_s"] < r["window_s"]
    per = {}
    for name, s, e in mods:
        per[name] = per.get(name, 0) + max(0, min(e, hi) - max(s, lo))
    assert r["module_s"] == pytest.approx({k: v / 1e9 for k, v in per.items()
                                           if v > 0})
    assert len(r["device_ops"]) <= 10 and len(r["idle_gaps"]) <= 10
    assert [v for _, v in r["device_ops"]] == \
        sorted((v for _, v in r["device_ops"]), reverse=True)
    assert any(name.startswith("jit_b") for name, _ in r["device_ops"])
    assert sum(v for _, v in r["idle_gaps"]) <= r["window_s"] - r["busy_s"] \
        + 1e-12
