"""Device time of the evaluate chunk executables per plan, from the trace.

The evaluate executables are the csg-cmp pair evaluators of the batched
lane spaces (``btree``, ``bgeneral``, ``bdpsub``) and of the solo engine
(``_eval_*_chunk``), as the profiler names their modules.
"""
from bench import measure

EVALUATE = r"^jit_(btree|bgeneral|bdpsub|_eval_\w+_chunk)$"


def read(run):
    t = measure.module_seconds(run, EVALUATE)
    n = measure.plans(run)
    return t / n * 1e3 if t and n else None
