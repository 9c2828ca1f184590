"""Mean time a request of the window waited in the daemon's admission
queue, admission to worker pickup: the STATS telemetry counter
``queue_wait_s`` over the window, per request answered in it.  A daemon
without that counter gives nothing."""
from bench import measure


def read(run):
    st = run.get("stats")
    if not st or "queue_wait_s" not in st["before"]["telemetry"]:
        return None
    n = st["after"]["requests"] - st["before"]["requests"]
    return measure.telemetry_delta(run, "queue_wait_s") / n * 1e3 if n else None
