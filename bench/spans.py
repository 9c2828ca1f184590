"""Program spans of a profiler trace, reduced per name.

The optimizer marks its layers with host spans named ``<layer>.<step>``
(``repro.core.telemetry.span``; the list is in ``docs/telemetry.md``).
``load`` reads them from an ``.xplane.pb`` written by ``jax.profiler``:
every host line's events whose name starts with a program prefix, as
``[name, line, start_ns, end_ns]`` with the line's index in the host plane
as its id and any ``#...#`` metadata suffix cut from the name.  ``reduce``
gives, over a window, each name's span count, total time and self time:
the time its spans cover less what their child program spans on the same
line cover, every span clipped to the window.  ``longest_gap`` gives the
device's longest idle gap in a ``bench/tracing.py`` trace and the host
span at its middle, as ``tracing.reduce`` labels gaps.
"""
from __future__ import annotations

from collections import defaultdict

import numpy as np

from bench import tracing

PREFIXES = ("daemon.", "service.", "engine.", "level.", "uniondp.")


def load(path: str) -> list[list]:
    """Program spans of every host line of the ``.xplane.pb`` at ``path``."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line_id, line in enumerate(plane.lines):
            for e in line.events:
                name = e.name.split("#", 1)[0]
                if name.startswith(PREFIXES):
                    out.append([name, line_id, e.start_ns, e.end_ns])
    return out


def reduce(spans, lo: float, hi: float) -> dict:
    """``{name: {"count", "total_s", "self_s"}}`` over ``[lo, hi]`` (ns)."""
    out: dict = defaultdict(lambda: {"count": 0, "total_s": 0.0,
                                     "self_s": 0.0})
    by_line = defaultdict(list)
    for name, line, s, e in spans:
        if e > lo and s < hi:
            by_line[line].append((s, -e, name, max(s, lo), min(e, hi)))
    for items in by_line.values():
        stack: list[list] = []       # open spans: [end, name, clipped, self]
        for s, neg_e, name, cs, ce in sorted(items):
            while stack and stack[-1][0] <= s:
                _close(out, stack.pop())
            if stack:
                stack[-1][3] -= ce - cs     # a child's time is not self time
            stack.append([-neg_e, name, ce - cs, ce - cs])
        while stack:
            _close(out, stack.pop())
    return {k: dict(v) for k, v in out.items()}


def _close(out: dict, frame: list) -> None:
    _, name, clipped, own = frame
    r = out[name]
    r["count"] += 1
    r["total_s"] += clipped / 1e9
    r["self_s"] += own / 1e9


def longest_gap(trace: dict) -> tuple[float, str]:
    """The device's longest idle gap in the window (seconds, first device)
    and the innermost host span at its middle."""
    lo, hi = tracing.window(trace)
    devices = sorted(trace["modules"])
    mods = trace["modules"][devices[0]] if devices else []
    u = tracing.union([[s, e] for _, s, e in mods], lo, hi)
    edges = np.concatenate([[lo], u.ravel(), [hi]])
    gs, ge = edges[0::2], edges[1::2]
    k = int(np.argmax(ge - gs))
    names, slots = tracing._labels(trace["host"] + trace["marks"], lo, hi)
    lab = slots[int((gs[k] + ge[k]) / 2 - lo) // tracing.GRID_NS]
    return float(ge[k] - gs[k]) / 1e9, ("(no host span)" if lab < 0
                                         else names[lab])
