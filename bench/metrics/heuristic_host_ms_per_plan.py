"""Host time of the heuristic tier per plan: the solves' wall time minus
the device's busy time in the traced window, over the plans."""
from bench import measure


def read(run):
    if "trace" not in run:
        return None
    done = measure.ok(run)
    if not done:
        return None
    wall = sum(r["reply"] - r["send"] for r in done)
    return (wall - run["trace"]["busy_s"]) / len(done) * 1e3
