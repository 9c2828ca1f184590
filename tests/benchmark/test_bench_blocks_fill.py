"""The ``blocks_fill.open`` reader on synthetic runs: the STATS counters'
window deltas, sets over slots, and nothing from a daemon without them."""
import pytest

from bench import cell as bcell

READ = bcell.module("metrics", "blocks_fill.open").read


def stats(before, after):
    """``before``/``after``: (blocks_sets, blocks_slots) at either end."""
    return {side: {"requests": 1,
                   "telemetry": {"blocks_sets": sets, "blocks_slots": slots}}
            for side, (sets, slots) in (("before", before), ("after", after))}


def test_window_delta_sets_over_slots():
    run = {"stats": stats((500, 4096), (500 + 76_896, 4096 + 211_968))}
    assert READ(run) == pytest.approx(76_896 / 211_968)


@pytest.mark.parametrize("run", [
    {"stats": None},                                   # the heuristic cell
    {"stats": stats((10, 256), (10, 256))},            # nothing launched
    {"stats": {"before": {"requests": 1, "telemetry": {"flights": 1}},
               "after": {"requests": 2, "telemetry": {"flights": 2}}}},
])
def test_nothing_to_read(run):
    assert READ(run) is None
