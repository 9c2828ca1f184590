"""Queries per flight over the window (daemon STATS telemetry delta)."""
from bench import measure


def read(run):
    if not run.get("stats"):
        return None
    flights = measure.telemetry_delta(run, "flights")
    return measure.telemetry_delta(run, "queries") / flights if flights else None
