"""Shared arithmetic of the metric readers in bench/metrics/.

A run (as bench/run.py hands it to a reader) holds the window's request
records, with times in seconds from the window's start: ``due`` (open
loop), ``send`` and ``reply``.
"""
from __future__ import annotations

import math


def ok(run: dict) -> list:
    return [r for r in run["records"] if r["status"] == "ok"]


def latencies(run: dict) -> list[float]:
    """Latency of every answered request: from its due time in an open
    loop (the wait a stall imposes on later requests counts), from its
    send in a closed loop."""
    return sorted(r["reply"] - (r["due"] if r["due"] is not None
                                else r["send"]) for r in ok(run))


def percentile(xs: list[float], p: float) -> float:
    """Linear interpolation between closest ranks (numpy's default)."""
    k = (len(xs) - 1) * p / 100
    f = math.floor(k)
    c = min(f + 1, len(xs) - 1)
    return xs[f] + (xs[c] - xs[f]) * (k - f)


def plans(run: dict) -> int:
    return sum(r["queries"] for r in ok(run))


def window_s(run: dict) -> float:
    """From the window's start to the last reply: all the work and all the
    time the window's requests took."""
    return max([run["seconds"]] + [r["reply"] for r in run["records"]])


def telemetry_delta(run: dict, key: str) -> int:
    st = run.get("stats")
    return st["after"]["telemetry"][key] - st["before"]["telemetry"][key]


def module_seconds(run: dict, pattern: str) -> float | None:
    """Device seconds of the executables whose name matches ``pattern``."""
    import re
    if "trace" not in run:
        return None
    rx = re.compile(pattern)
    return sum(v for k, v in run["trace"]["module_s"].items() if rx.search(k))
