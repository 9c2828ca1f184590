"""Snowflake join queries: a fact relation at the centre, dimension chains.

Each relation gets up to ``branch`` children, chains reach ``depth``
levels, and a dimension at level l has cardinality U(dim_card) *
level_decay**l (at least ``min_card``); once every chain is at full depth
the frontier restarts from all relations.  Predicates are PK-FK:
selectivity U(sel) / card(child).  Sizes and ranges come from the
configuration file (the paper's synthetic workload for large queries).
"""
from __future__ import annotations

import random


def query(cfg: dict, n: int, seed: int, stats_seed: int | None = None) -> dict:
    """The shape depends on ``n`` alone; the statistics come from
    ``stats_seed`` when one is given, else from ``seed``."""
    branch, depth = int(cfg["branch"]), int(cfg["depth"])
    d_lo, d_hi = cfg["dim_card"]
    s_lo, s_hi = cfg["pk_fk_sel"]
    r = random.Random(seed if stats_seed is None else stats_seed)
    cards = [r.uniform(*cfg["fact_card"])]
    edges, sels = [], []
    levels = {0: 0}
    frontier = [0]
    while len(cards) < n:
        nxt = []
        for p in frontier:
            for _ in range(branch):
                if len(cards) >= n:
                    break
                if levels[p] >= depth:
                    continue
                i = len(cards)
                c = max(r.uniform(d_lo, d_hi) * (cfg["level_decay"] ** levels[p]),
                        float(cfg["min_card"]))
                cards.append(c)
                edges.append((p, i))
                sels.append(min(1.0, r.uniform(s_lo, s_hi) / c))
                levels[i] = levels[p] + 1
                nxt.append(i)
        if not nxt:
            levels = {k: 0 for k in levels}
            nxt = list(levels.keys())
        frontier = nxt
    return {"n": n, "edges": edges, "cards": cards, "sels": sels,
            "names": [f"R{i}" for i in range(n)]}
