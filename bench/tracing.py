"""Reduce a profiler trace to what the benchmark reports.

A trace here is a plain dict (so a small recorded one can be committed for
the tests):

    {"modules": {device: [[name, start_ns, end_ns], ...]}, # XLA Modules
     "host":    [[name, start_ns, end_ns], ...],           # Python threads
     "marks":   [[name, start_ns, end_ns], ...]}           # bench.* spans

``load`` reads the profiler's ``.xplane.pb`` into that form.  ``reduce``
takes the window from the ``bench.window`` mark and gives device busy time
(the union of the intervals in which an executable ran on the device,
averaged over the devices; the host dispatches whole executables, so the
device idles only between them), the device time of every executable
(name without its hash),
the executables that took the most time, and the device's idle gaps by
what the host was doing in them (the innermost host span at the gap's
middle, on a 10 us grid).  Times are seconds, averaged over devices.
"""
from __future__ import annotations

import gzip
import json
import re
from collections import defaultdict

import numpy as np

WINDOW_MARK = "bench.window"
GRID_NS = 10_000
_HASH = re.compile(r"\(\d+\)$")


def load(path: str) -> dict:
    """Read an ``.xplane.pb`` file written by ``jax.profiler``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out = {"modules": {}, "host": [], "marks": []}
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == "XLA Modules":
                    out["modules"][plane.name] = [
                        [_HASH.sub("", e.name), e.start_ns, e.end_ns]
                        for e in line.events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    item = [e.name, e.start_ns, e.end_ns]
                    if e.name.startswith("bench."):
                        out["marks"].append(item)
                    elif line.name.startswith(("python", "main")):
                        out["host"].append(item)
    return out


def save(trace: dict, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(trace, f)


def read(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


def window(trace: dict) -> tuple[float, float]:
    marks = [m for m in trace["marks"] if m[0] == WINDOW_MARK]
    if marks:
        return float(marks[0][1]), float(marks[0][2])
    ivs = [m for mods in trace["modules"].values() for m in mods]
    return float(min(m[1] for m in ivs)), float(max(m[2] for m in ivs))


def union(intervals, lo: float, hi: float) -> np.ndarray:
    """Merged intervals clipped to [lo, hi], as an (k, 2) float array."""
    iv = np.asarray(intervals, np.float64).reshape(-1, 2)
    iv = np.clip(iv, lo, hi)
    iv = iv[iv[:, 1] > iv[:, 0]]
    if not len(iv):
        return iv
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    reach = np.maximum.accumulate(iv[:, 1])
    new = np.ones(len(iv), bool)
    new[1:] = iv[1:, 0] > reach[:-1]
    starts = iv[new, 0]
    ends = reach[np.r_[np.nonzero(new)[0][1:] - 1, len(iv) - 1]]
    return np.stack([starts, ends], axis=1)


def _labels(host, lo: float, hi: float):
    """Innermost host span on a grid over [lo, hi]: (names, slot ids)."""
    slots = np.full(int((hi - lo) // GRID_NS) + 1, -1, np.int32)
    names: dict[str, int] = {}
    for name, s, e in sorted(host, key=lambda h: h[1] - h[2]):   # longest 1st
        a, b = int((max(s, lo) - lo) // GRID_NS), int((min(e, hi) - lo)
                                                       // GRID_NS) + 1
        if b > a:
            slots[a:b] = names.setdefault(name, len(names))
    return list(names), slots


def reduce(trace: dict, top: int = 10) -> dict:
    """Busy time, executable times and breakdown over the window."""
    lo, hi = window(trace)
    devices = sorted(trace["modules"])
    nd = max(1, len(devices))
    names, slots = _labels(trace["host"] + trace["marks"], lo, hi)
    busy, idle = 0.0, defaultdict(float)
    for d in devices:
        u = union([[s, e] for _, s, e in trace["modules"][d]], lo, hi)
        busy += float((u[:, 1] - u[:, 0]).sum())
        edges = np.concatenate([[lo], u.ravel(), [hi]])
        gs, ge = edges[0::2], edges[1::2]
        keep = ge > gs
        gs, ge = gs[keep], ge[keep]
        lab = slots[((gs + ge) / 2 - lo).astype(np.int64) // GRID_NS]
        sums = np.bincount(lab + 1, weights=ge - gs)
        for k, w in enumerate(sums):
            if w > 0:
                idle["(no host span)" if k == 0 else names[k - 1]] += w / nd
    by_name: dict[str, float] = defaultdict(float)
    for d in devices:
        for name, s, e in trace["modules"][d]:
            s, e = max(s, lo), min(e, hi)
            if e > s:
                by_name[name] += (e - s) / nd
    def rank(d):
        return sorted(([k, v / 1e9] for k, v in d.items()),
                      key=lambda kv: -kv[1])[:top]

    return {"window_s": (hi - lo) / 1e9, "busy_s": busy / nd / 1e9,
            "module_s": {k: v / 1e9 for k, v in by_name.items()},
            "device_ops": rank(by_name),
            "idle_gaps": rank(idle)}
