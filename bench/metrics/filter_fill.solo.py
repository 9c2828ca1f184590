"""Fill of the solo engine's connected-set filter over the window: the
connected sets of two or more relations it found over the lane slots its
launches held, from the STATS telemetry counters ``solo_filter_sets`` and
``solo_filter_lanes``.  A daemon without those counters, or one whose
window ran no solo query, gives nothing."""
from bench import measure


def read(run):
    st = run.get("stats")
    if not st or "solo_filter_lanes" not in st["before"]["telemetry"]:
        return None
    lanes = measure.telemetry_delta(run, "solo_filter_lanes")
    sets = measure.telemetry_delta(run, "solo_filter_sets")
    return sets / lanes if lanes else None
