"""Median client-side latency of the window's answered requests."""
from bench import measure


def read(run):
    lat = measure.latencies(run)
    return measure.percentile(lat, 50) * 1e3 if lat else None
