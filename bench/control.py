#!/usr/bin/env python3
"""The control of a cell's check: the reference, put in the program's place
and computed in bfloat16 (the precision below the float32 the
configurations state), judged by the same check as the program.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 [--queries N]

For each seed it builds the run's queries as a window would
(``traffic.build``), answers the first ``N`` of them with the control
(exact cells: the bfloat16 exact DP; heuristic cells: bfloat16 GOO with
every subtree of at most k relations re-optimized by the bfloat16 exact
DP, ``reference.reoptimized``) and
prints the check's number for them: a control that comes out correct would
show the check cannot tell float32 from bfloat16.  The benchmark's own
runs never run it.  It needs no chip.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))


def judge(cfg: dict, q: dict) -> dict:
    """The check's numbers for one query answered by the control: its plan
    and the cost it reports for that plan, both computed in bfloat16."""
    from bench import reference as ref
    consts = cfg["cost_model"]
    if cfg["check"]["kind"] == "exact":
        _, shape, _ = ref.exact(q, consts, rnd=ref.bf16)
    else:
        shape = ref.reoptimized(q, consts, int(cfg["uniondp"]["k"]), ref.bf16)
    reported = ref.CostModel(q, consts, ref.bf16).plan_cost(shape)
    if cfg["check"]["kind"] == "exact":
        j = ref.judge_exact(q, consts, shape, reported)
        return {"worst_gap": j["gap"], "worst_cost_error": j["cost_error"]}
    j = ref.judge_heuristic(q, consts, shape, reported,
                            int(cfg["uniondp"]["k"]))
    return {"worst_local_gap": j["local_gap"],
            "worst_excess_over_goo": j["ratio"] - 1,
            "worst_cost_error": j["cost_error"]}


def reading(cell: dict, seed: int, queries: int, seconds: float,
            pool=None) -> dict:
    """Worst check numbers of the control over a run's first queries."""
    from bench import traffic
    plan = traffic.build(cell, seed, seconds)
    qs, seen = [], set()
    for r in plan["requests"]:
        for q in r["queries"]:
            if id(q) not in seen:
                seen.add(id(q))
                qs.append(q)
    qs = qs[:queries]
    mapper = pool.map if pool is not None else map
    judged = list(mapper(judge, [cell["config"]] * len(qs), qs))
    return {k: max(j[k] for j in judged) for k in judged[0]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--queries", type=int, default=10 ** 9)
    ap.add_argument("--seconds", type=float, default=None)
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.dirname(HERE))
    import multiprocessing
    from concurrent.futures import ProcessPoolExecutor
    from bench.cell import load_cell
    cell = load_cell(args.workload)
    seconds = args.seconds or cell["run_seconds"]
    out = {"workload": args.workload, "readings": {}}
    with ProcessPoolExecutor(max_workers=min(12, os.cpu_count() or 1),
                             mp_context=multiprocessing.get_context("spawn")
                             ) as pool:
        for s in args.seeds.split(","):
            out["readings"][s] = reading(cell, int(s), args.queries, seconds,
                                         pool)
            print(json.dumps({s: out["readings"][s]}), flush=True)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
