#!/usr/bin/env python3
"""Every plan the window served must be optimal under the cost model,
judged by the csg-cmp reference (bench/reference_csg.py), which reaches
queries of 25 relations within a run where bench/reference.py cannot.

The numbers are those of bench/checks/exact.py: a plan must be a valid
bushy join tree without cross products; its cost, by the reference's
float64 arithmetic, may lie above the reference's optimum by at most the
configuration's ``worst_gap``; the cost the daemon reported for it may
differ from the reference's cost of the same plan by at most
``worst_cost_error`` (relative); and a request that got an error instead
of plans is an answer that never came.

Its control (the reference in the program's place, computed in bfloat16,
the precision below the float32 the configuration states) needs no chip:

    python3 bench/checks/exact_csg.py --workload <cell> --seeds 1,2,3 \\
        [--queries N]

prints, per seed, the check's numbers for the control's answers to the
run's first ``N`` queries; a control that comes out correct would show
the check cannot tell float32 from bfloat16.
"""
from __future__ import annotations

import argparse
import json
import os
import sys


def check(run: dict, pool) -> dict:
    from bench import reference_csg
    cfg = run["cell"]["config"]
    consts, limits = cfg["cost_model"], cfg["check"]["limits"]
    jobs = []
    for rec in run["records"]:
        if rec["status"] == "ok":
            for q, shape, cost in zip(run["requests"][rec["id"]]["queries"],
                                      rec["plans"], rec["costs"]):
                jobs.append((q, consts, shape, cost))
    judged = (list(pool.map(reference_csg.judge, *zip(*jobs)))
              if jobs else [])
    return verdict(judged, limits,
                   sum(r["status"] == "error" for r in run["records"]))


def verdict(judged: list, limits: dict, errors: int) -> dict:
    """The check's numbers over judged answers (``reference_csg.judge``)."""
    sound = [j for j in judged if not j["problem"]]
    numbers = {
        "worst_gap": [max((j["gap"] for j in sound), default=0.0),
                      limits["worst_gap"]],
        "worst_cost_error": [max((j["cost_error"] for j in sound),
                                 default=0.0), limits["worst_cost_error"]],
        "invalid_plans": [len(judged) - len(sound), 0],
        "errors": [errors, 0]}
    return {"ok": bool(judged) and all(v <= lim for v, lim in numbers.values()),
            "numbers": numbers, "checked": len(judged),
            "notes": [j["problem"] for j in judged if j["problem"]][:3]}


def control_reading(cell: dict, seed: int, queries: int, seconds: float,
                    pool=None) -> dict:
    """The check's verdict on the control's answers to a run's first
    ``queries`` distinct queries."""
    from bench import reference_csg, traffic
    plan = traffic.build(cell, seed, seconds)
    qs, seen = [], set()
    for r in plan["requests"]:
        for q in r["queries"]:
            if id(q) not in seen:
                seen.add(id(q))
                qs.append(q)
    qs = qs[:queries]
    mapper = pool.map if pool is not None else map
    judged = list(mapper(reference_csg.judge_control, qs,
                         [cell["config"]["cost_model"]] * len(qs)))
    return verdict(judged, cell["config"]["check"]["limits"], 0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="bfloat16 control of the "
                                 "exact_csg check")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--queries", type=int, default=10 ** 9)
    ap.add_argument("--seconds", type=float, default=None)
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))
    import multiprocessing
    from concurrent.futures import ProcessPoolExecutor
    from bench.cell import load_cell
    cell = load_cell(args.workload)
    seconds = args.seconds or cell["run_seconds"]
    with ProcessPoolExecutor(max_workers=min(12, os.cpu_count() or 1),
                             mp_context=multiprocessing.get_context("spawn")
                             ) as pool:
        for s in args.seeds.split(","):
            v = control_reading(cell, int(s), args.queries, seconds, pool)
            print(json.dumps({s: {k: n for k, (n, _) in
                                  v["numbers"].items()},
                              "correct": v["ok"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
