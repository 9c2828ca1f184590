"""Plans answered in the window, over the window: from its start to the
last reply."""
from bench import measure


def read(run):
    return measure.plans(run) / measure.window_s(run)
