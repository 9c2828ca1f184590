"""Plain reference for exact cells whose queries are too large for
``reference.exact``: dynamic programming over the csg-cmp pairs that DPccp
(Moerkotte & Neumann, VLDB 2006) enumerates.

``reference.exact`` splits every connected subset of the query, about 3^n
work, and cannot judge 25 relations within a run.  This one enumerates
only the csg-cmp pairs: a connected set S1 and a connected set S2, disjoint
and joined by a predicate, each unordered pair once.  The enumeration runs
in Python; the costing runs level by level (by the size of S1 | S2) with
numpy, every connected set keyed by its position in their sorted array.
The cost model, the plan checks and the precisions are
``bench/reference.py``'s, imported: this module imports nothing of the
program either.

A query is the benchmark's spec (``n``, ``edges``, ``cards``, ``sels``); a
plan the recorded nested shape (a leaf an int bitmap, an inner node a pair).
"""
from __future__ import annotations

import math
from array import array

import numpy as np

from bench.reference import CostModel, bf16, ident, plan_problem


def _neighbourhood(n: int, adj):
    """``nb(s)``: the OR of ``adj[v]`` over the members v of bitmap s, by
    lookup tables over its bytes."""
    tabs = []
    for base in range(0, n, 8):
        t = [0] * 256
        for x in range(1, 256):
            lb = x & -x
            v = base + lb.bit_length() - 1
            t[x] = t[x ^ lb] | (adj[v] if v < n else 0)
        tabs.append(t)
    if len(tabs) <= 4:
        t0, t1, t2, t3 = tabs + [[0] * 256] * (4 - len(tabs))

        def nb(s: int) -> int:
            return (t0[s & 255] | t1[(s >> 8) & 255] | t2[(s >> 16) & 255]
                    | t3[(s >> 24) & 255])
        return nb

    def nb_wide(s: int) -> int:
        out = 0
        for t in tabs:
            out |= t[s & 255]
            s >>= 8
        return out
    return nb_wide


def enumerate_pairs(n: int, adj):
    """DPccp's enumeration: ``(csgs, left, right)``, int64 arrays of every
    connected set and of every csg-cmp pair, each unordered pair once
    (EnumerateCsg, and EnumerateCmp for every set it emits)."""
    nb = _neighbourhood(n, adj)
    csgs, right, counts = array("q"), array("q"), array("q")
    add_csg, add_r = csgs.append, right.append

    def cmp_rec(s: int, x: int, nb_s: int) -> None:
        """EnumerateCsgRec over the complement side: every connected
        S2 = s | sub with sub in s's neighbourhood outside x."""
        nn = nb_s & ~x
        if not nn:
            return
        subs, sub = [], 0
        while True:
            sub = (sub - nn) & nn
            if not sub:
                break
            add_r(s | sub)
            subs.append(sub)
        x |= nn
        for sub in subs:
            cmp_rec(s | sub, x, nb_s | nb(sub))

    def emit(s1: int, nb_s1: int) -> None:
        """A connected set and all its complements (EnumerateCmp): start
        from each neighbour v outside B_min(S1) | S1, highest first, and
        grow without the neighbours below v."""
        add_csg(s1)
        start = len(right)
        lo = s1 & -s1
        x = (lo - 1) | s1
        nn = nb_s1 & ~x
        rest = nn
        while rest:
            v = 1 << (rest.bit_length() - 1)
            rest ^= v
            add_r(v)
            cmp_rec(v, x | (nn & ((v << 1) - 1)), nb(v))
        counts.append(len(right) - start)

    def csg_rec(s: int, x: int, nb_s: int) -> None:
        """EnumerateCsgRec: every connected s | sub, sub in s's
        neighbourhood outside x."""
        nn = nb_s & ~x
        if not nn:
            return
        grown, sub = [], 0
        while True:
            sub = (sub - nn) & nn
            if not sub:
                break
            g = (s | sub, nb_s | nb(sub))
            emit(*g)
            grown.append(g)
        x |= nn
        for t, nb_t in grown:
            csg_rec(t, x, nb_t)

    for i in range(n - 1, -1, -1):
        v = 1 << i
        emit(v, nb(v))
        csg_rec(v, (v << 1) - 1, nb(v))
    csgs = np.frombuffer(csgs, np.int64)
    return (csgs, np.repeat(csgs, np.frombuffer(counts, np.int64)),
            np.frombuffer(right, np.int64))


def _dp(cm: CostModel):
    """The optimum of every connected set over its csg-cmp pairs: the sorted
    sets, their costs, the left side of each best split (the first pair in
    enumeration order among the cheapest), and the pair count."""
    rnd, n = cm.rnd, cm.n
    csgs, a, b = enumerate_pairs(n, cm.adj)
    sets = np.sort(csgs)
    pos = np.full(1 << n, -1, np.int32)         # set -> its index, 4 << n B
    pos[sets] = np.arange(len(sets), dtype=np.int32)
    rows = cm.rows(sets)
    best = np.full(len(sets), np.inf)
    left = np.zeros(len(sets), np.int64)
    leaves = pos[np.int64(1) << np.arange(n, dtype=np.int64)]
    best[leaves] = cm.scan(rows[leaves])
    u = a | b
    size = np.bitwise_count(u)
    order = np.argsort(size, kind="stable")
    bounds = np.searchsorted(size[order], np.arange(2, n + 2))
    for k in range(2, n + 1):
        sel = order[bounds[k - 2]: bounds[k - 1]]
        if not len(sel):
            continue
        ia, ib, iu = pos[a[sel]], pos[b[sel]], pos[u[sel]]
        cand = rnd(rnd(best[ia] + best[ib])
                   + cm.join(rows[ia], rows[ib], rows[iu]))
        np.minimum.at(best, iu, cand)
        win = np.flatnonzero(cand == best[iu])
        win = win[np.unique(iu[win], return_index=True)[1]]
        left[iu[win]] = a[sel][win]
    return sets, best, left, len(a)


def exact(q: dict, consts: dict, rnd=ident, plan: bool = True):
    """Optimal plan of a query by dynamic programming over its csg-cmp
    pairs.  Returns ``(cost, shape or None, pairs)`` as ``reference.exact``
    does, ``pairs`` counting unordered csg-cmp pairs."""
    n = int(q["n"])
    sets, best, left, pairs = _dp(CostModel(q, consts, rnd))
    top = (1 << n) - 1
    i = int(np.searchsorted(sets, top))
    if i == len(sets) or sets[i] != top:
        raise ValueError("query graph is disconnected")

    def shape(s: int):
        if s & (s - 1) == 0:
            return s
        a = int(left[np.searchsorted(sets, s)])
        return [shape(a), shape(s ^ a)]
    return float(best[i]), shape(top) if plan else None, pairs


def judge(q: dict, consts: dict, shape, reported: float) -> dict:
    """A served plan against the optimum: validity, the relative gap of its
    cost to the optimum, and the relative error of the cost the program
    reported for it, all by the reference's float64 arithmetic."""
    opt, _, _ = exact(q, consts, plan=False)
    out = {"n": int(q["n"]), "gap": math.inf, "cost_error": math.inf,
           "problem": plan_problem(shape, q)}
    if out["problem"] is None:
        cost = CostModel(q, consts).plan_cost(shape)
        out.update(gap=cost / opt - 1, cost_error=abs(reported / cost - 1))
    return out


def control(q: dict, consts: dict, rnd=bf16) -> tuple:
    """The control's answer: the plan this reference finds when computed in
    the precision ``rnd`` gives (bfloat16, the precision below the float32
    the configurations state), and the cost it reports for that plan in the
    same precision."""
    _, shape, _ = exact(q, consts, rnd=rnd)
    return shape, CostModel(q, consts, rnd).plan_cost(shape)


def judge_control(q: dict, consts: dict) -> dict:
    """The control's answer to one query, judged as a served plan."""
    return judge(q, consts, *control(q, consts))
