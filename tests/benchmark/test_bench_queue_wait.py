"""The ``queue_wait_ms.open`` reader on synthetic runs: the STATS counter
over the window per request, and nothing from a daemon without it."""
import pytest

from bench import cell as bcell

READ = bcell.module("metrics", "queue_wait_ms.open").read


def stats(before_wait, after_wait, before_req, after_req):
    return {"before": {"requests": before_req,
                       "telemetry": {"queue_wait_s": before_wait}},
            "after": {"requests": after_req,
                      "telemetry": {"queue_wait_s": after_wait}}}


def test_window_delta_per_request():
    run = {"stats": stats(3.0, 3.0 + 49.0, 98, 98 + 98)}
    assert READ(run) == pytest.approx(500.0)


@pytest.mark.parametrize("run", [
    {"stats": None},                                   # the heuristic cell
    {"stats": stats(1.0, 2.0, 5, 5)},                  # no request answered
    {"stats": {"before": {"requests": 1, "telemetry": {"flights": 1}},
               "after": {"requests": 2, "telemetry": {"flights": 2}}}},
])
def test_nothing_to_read(run):
    assert READ(run) is None
