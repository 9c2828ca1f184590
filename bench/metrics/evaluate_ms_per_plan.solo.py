"""Device time of the solo engine's evaluate chunk executables per plan,
from the trace: the csg-cmp pair evaluators ``_eval_*_chunk`` of
``ExactEngine``, as the profiler names their modules.  A window in which
none of them ran gives nothing."""
from bench import measure

EVALUATE = r"^jit__eval_\w+_chunk$"


def read(run):
    t = measure.module_seconds(run, EVALUATE)
    n = measure.plans(run)
    return t / n * 1e3 if t and n else None
