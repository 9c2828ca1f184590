"""Every heuristic plan the window served must be locally optimal and no
worse than GOO.

Each answered query is judged by the plain reference (bench/reference.py):
its plan must be a valid bushy join tree without cross products; every
subtree of at most ``k`` relations (the configuration's partition size)
must cost no more than the exact optimum of its relation set, by the
reference's float64 arithmetic, beyond ``worst_local_gap`` (relative): a
tier that solves its pieces exactly leaves none, a greedy plan does; its
cost may exceed the cost of the reference's GOO plan by at most the share
the configuration guarantees (``worst_excess_over_goo``); and the cost the
program reported for it may differ from the reference's cost of the same
plan by at most ``worst_cost_error`` (relative).  A request that got an
error instead of a plan is an answer that never came.
"""
from __future__ import annotations


def check(run: dict, pool) -> dict:
    from bench import reference
    cfg = run["cell"]["config"]
    consts, limits = cfg["cost_model"], cfg["check"]["limits"]
    k = int(cfg["uniondp"]["k"])
    jobs, seen = [], set()
    for rec in run["records"]:
        if rec["status"] == "ok":
            q = run["requests"][rec["id"]]["queries"][0]
            key = (id(q), repr(rec["plans"][0]), rec["costs"][0])  # replays
            if key not in seen:
                seen.add(key)
                jobs.append((q, consts, rec["plans"][0], rec["costs"][0], k))
    judged = (list(pool.map(reference.judge_heuristic, *zip(*jobs)))
              if jobs else [])
    sound = [j for j in judged if not j["problem"]]

    def worst(key):
        return max((j[key] for j in sound), default=0.0)

    numbers = {
        "worst_local_gap": [worst("local_gap"), limits["worst_local_gap"]],
        "worst_excess_over_goo": [worst("ratio") - 1 if sound else 0.0,
                                  limits["worst_excess_over_goo"]],
        "worst_cost_error": [worst("cost_error"), limits["worst_cost_error"]],
        "invalid_plans": [len(judged) - len(sound), 0],
        "errors": [sum(r["status"] == "error" for r in run["records"]), 0]}
    return {"ok": bool(judged) and all(v <= lim for v, lim in numbers.values()),
            "numbers": numbers, "checked": len(judged),
            "notes": [j["problem"] for j in judged if j["problem"]][:3]}
