"""Every plan the window served must be optimal under the cost model.

Each answered query is judged by the plain reference (bench/reference.py):
its plan must be a valid bushy join tree without cross products; its cost,
by the reference's float64 arithmetic, may lie above the reference's
optimum by at most the configuration's ``worst_gap``; and the cost the
daemon reported for it may differ from the reference's cost of the same
plan by at most ``worst_cost_error`` (relative).  A request that got an
error instead of plans is an answer that never came.
"""
from __future__ import annotations


def check(run: dict, pool) -> dict:
    from bench import reference
    cfg = run["cell"]["config"]
    consts, limits = cfg["cost_model"], cfg["check"]["limits"]
    jobs = []
    for rec in run["records"]:
        if rec["status"] == "ok":
            for q, shape, cost in zip(run["requests"][rec["id"]]["queries"],
                                      rec["plans"], rec["costs"]):
                jobs.append((q, consts, shape, cost))
    judged = list(pool.map(reference.judge_exact, *zip(*jobs))) if jobs else []
    sound = [j for j in judged if not j["problem"]]
    numbers = {
        "worst_gap": [max((j["gap"] for j in sound), default=0.0),
                      limits["worst_gap"]],
        "worst_cost_error": [max((j["cost_error"] for j in sound),
                                 default=0.0), limits["worst_cost_error"]],
        "invalid_plans": [len(judged) - len(sound), 0],
        "errors": [sum(r["status"] == "error" for r in run["records"]), 0]}
    return {"ok": bool(judged) and all(v <= lim for v, lim in numbers.values()),
            "numbers": numbers, "checked": len(judged),
            "notes": [j["problem"] for j in judged if j["problem"]][:3]}
