"""MusicBrainz-like join queries: random walks over a PK-FK schema.

The schema (tables with cardinalities, foreign keys) comes from the
configuration file.  A query of ``n`` relations is a random walk over the
foreign-key graph that restarts from a picked table with unpicked
neighbours when it stalls; every foreign key between two picked tables
becomes a predicate with selectivity ~ 1/card(referenced table), and every
table's cardinality is scaled by a filter factor.  The walk can revisit
hubs, so queries can hold cycles (paper section 7.2.2).
"""
from __future__ import annotations

import random


def query(cfg: dict, n: int, seed: int, stats_seed: int | None = None) -> dict:
    """The walk from ``seed``; the statistics from ``stats_seed`` when one
    is given, else from the same draws (the program's generator)."""
    names = [t for t, _ in cfg["tables"]]
    cards = {t: float(c) for t, c in cfg["tables"]}
    idx = {t: i for i, t in enumerate(names)}
    fks = [(idx[a], idx[b]) for a, b in cfg["foreign_keys"]]
    sel_lo, sel_hi = cfg["pk_fk_sel"]
    f_lo, f_hi = cfg["filter_scale"]
    r = random.Random(seed)
    nbr: dict[int, list[int]] = {}
    for a, b in fks:
        nbr.setdefault(a, []).append(b)
        nbr.setdefault(b, []).append(a)
    cur = r.choice(list(nbr.keys()))
    picked, pset, stall = [cur], {cur}, 0
    while len(picked) < n:
        nxt = r.choice(nbr[cur])
        if nxt not in pset:
            picked.append(nxt)
            pset.add(nxt)
        cur = nxt
        stall += 1
        if stall >= 400:
            frontier = [v for v in picked
                        if any(w not in pset for w in nbr[v])]
            if not frontier:
                raise ValueError(f"schema exhausted at {len(picked)} < {n}")
            cur = r.choice(frontier)
            stall = 0
    if stats_seed is not None:
        r = random.Random(stats_seed)
    local = {g: i for i, g in enumerate(picked)}
    edges, sels = [], []
    for a, b in fks:
        if a in pset and b in pset:
            sels.append(min(1.0, r.uniform(sel_lo, sel_hi) / cards[names[b]]))
            edges.append((local[a], local[b]))
    return {"n": n, "edges": edges,
            "cards": [cards[names[p]] * r.uniform(f_lo, f_hi) for p in picked],
            "sels": sels, "names": [names[p] for p in picked]}
