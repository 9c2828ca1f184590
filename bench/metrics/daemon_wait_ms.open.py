"""Mean time a request spends outside the daemon's optimizer worker: the
client's round trip minus the worker's own wall time (reply ``wall_s``):
socket, codecs and the wait in the admission queue."""
from bench import measure


def read(run):
    xs = [r["reply"] - r["send"] - r["wall_s"] for r in measure.ok(run)]
    return sum(xs) / len(xs) * 1e3 if xs else None
