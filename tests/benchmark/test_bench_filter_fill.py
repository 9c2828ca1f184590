"""The ``filter_fill.solo`` reader on synthetic runs: the STATS counters'
window deltas, sets over lane slots, and nothing from a daemon without
them."""
import pytest

from bench import cell as bcell

READ = bcell.module("metrics", "filter_fill.solo").read


def stats(before, after):
    """``before``/``after``: (solo_filter_sets, solo_filter_lanes) at either
    end."""
    return {side: {"requests": 1,
                   "telemetry": {"solo_filter_sets": sets,
                                 "solo_filter_lanes": lanes}}
            for side, (sets, lanes) in (("before", before), ("after", after))}


def test_window_delta_sets_over_lanes():
    run = {"stats": stats((6969, 524288),
                          (6969 + 467_896, 524288 + 1040 * 32768))}
    assert READ(run) == pytest.approx(467_896 / (1040 * 32768))


@pytest.mark.parametrize("run", [
    {"stats": None},                                   # the heuristic cell
    {"stats": stats((10, 32768), (10, 32768))},        # no solo query ran
    {"stats": {"before": {"requests": 1, "telemetry": {"flights": 1}},
               "after": {"requests": 2, "telemetry": {"flights": 2}}}},
])
def test_nothing_to_read(run):
    assert READ(run) is None
