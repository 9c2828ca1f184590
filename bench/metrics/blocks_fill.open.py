"""Fill of MPDP-general phase A's block-finding launches over the window:
the sets the launches were given over the set slots they held, from the
STATS telemetry counters ``blocks_sets`` and ``blocks_slots``.  A daemon
without those counters, or one whose window launched nothing, gives
nothing."""
from bench import measure


def read(run):
    st = run.get("stats")
    if not st or "blocks_slots" not in st["before"]["telemetry"]:
        return None
    slots = measure.telemetry_delta(run, "blocks_slots")
    return measure.telemetry_delta(run, "blocks_sets") / slots if slots else None
