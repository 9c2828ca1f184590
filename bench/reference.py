"""Plain reference for the join-order optimizer: cost model, exact DP, GOO,
and the local optimality of a heuristic plan.

Written from the semantics the configurations state (PostgreSQL-flavoured
cost model of the paper's section 7.1, carried in log2 rows), with numpy
only: it imports nothing of the program and takes nothing the program made.
A query is the benchmark's own spec, a dict with ``n``, ``edges`` (pairs of
relation ids), ``cards`` and ``sels`` (linear statistics).

    rows(S)    = max(sum log2 card + sum log2 sel of edges inside S, 0)
    scan(R)    = c_seq * rows
    join(l, r) = min(hash, merge, nested loop) + c_tup * out   (see join)
    cost(plan) = scans of the leaves + joins of the inner nodes

Everything is float64 by default.  ``rnd`` rounds after every arithmetic
step; passing ``bf16`` gives the control (the same reference computed in
bfloat16, the precision below the float32 the configuration states).

A plan is the nested shape the benchmark records: a leaf is an int bitmap
with one bit, an inner node a pair ``[left, right]``.
"""
from __future__ import annotations

import math

import numpy as np

def ident(x):
    return x


def bf16(x):
    """Round to bfloat16 (8 significant bits), returned as float64."""
    import ml_dtypes
    return np.asarray(x, np.float64).astype(ml_dtypes.bfloat16).astype(
        np.float64)


# ------------------------------------------------------------- statistics --

def log2_stats(q: dict):
    """Per-relation log2 cards and per-edge log2 sels of a query spec.  Two
    predicates on one pair keep the more selective one (the deployment's
    rule for duplicate predicates)."""
    cards = np.log2(np.maximum(np.asarray(q["cards"], np.float64), 1.0))
    pairs: dict[tuple[int, int], float] = {}
    for (u, v), s in zip(q["edges"], q["sels"]):
        e = (min(u, v), max(u, v))
        sl = math.log2(min(max(float(s), 1e-30), 1.0))
        pairs[e] = min(pairs.get(e, 0.0), sl)
    edges = sorted(pairs)
    return cards, edges, np.array([pairs[e] for e in edges], np.float64)


class CostModel:
    """The configuration's cost model over one query's statistics."""

    def __init__(self, q: dict, consts: dict, rnd=ident):
        self.n = int(q["n"])
        self.c = {k: float(v) for k, v in consts.items()}
        self.rnd = rnd
        self.card_l2, self.edges, self.sel_l2 = log2_stats(q)
        self.card_l2 = rnd(self.card_l2)
        self.sel_l2 = rnd(self.sel_l2)
        self.adj = [0] * self.n
        for u, v in self.edges:
            self.adj[u] |= 1 << v
            self.adj[v] |= 1 << u

    def rows(self, sets) -> np.ndarray:
        """log2 rows of relation sets, vectorized over int64 bitmaps."""
        r = self.rnd
        sets = np.asarray(sets, np.int64)
        out = np.zeros(sets.shape, np.float64)
        for v in range(self.n):
            out = r(out + np.where((sets >> v) & 1, self.card_l2[v], 0.0))
        for (u, v), s in zip(self.edges, self.sel_l2):
            inside = ((sets >> u) & 1) & ((sets >> v) & 1)
            out = r(out + np.where(inside, s, 0.0))
        return np.maximum(out, 0.0)

    def _exp2(self, x):
        return self.rnd(np.exp2(np.minimum(x, self.c["log2_cap"])))

    def scan(self, rl2):
        return self.rnd(self.c["c_seq"] * self._exp2(rl2))

    def join(self, rl2_l, rl2_r, rl2_out):
        c, r = self.c, self.rnd
        rl, rr, ro = self._exp2(rl2_l), self._exp2(rl2_r), self._exp2(rl2_out)
        tup = r(c["c_tup"] * ro)
        hj = r(r(r(c["c_hash_build"] * np.minimum(rl, rr))
                 + r(c["c_hash_probe"] * np.maximum(rl, rr))) + tup)
        lg_l, lg_r = np.maximum(rl2_l, 1.0), np.maximum(rl2_r, 1.0)
        mj = r(r(r(c["c_sort"] * r(r(rl * lg_l) + r(rr * lg_r)))
                 + r(c["c_merge"] * r(rl + rr))) + tup)
        nl = r(r(c["c_nl"] * self._exp2(r(rl2_l + rl2_r))) + tup)
        return np.minimum(hj, np.minimum(mj, nl))

    def set_rows(self, s: int) -> float:
        """log2 rows of one relation set, in the model's precision."""
        r, out = self.rnd, 0.0
        for v in range(self.n):
            if (s >> v) & 1:
                out = float(r(out + self.card_l2[v]))
        for (u, v), sl in zip(self.edges, self.sel_l2):
            if (s >> u) & 1 and (s >> v) & 1:
                out = float(r(out + sl))
        return max(out, 0.0)

    # ---------------------------------------------------------- plans ----
    def plan_cost(self, shape) -> float:
        """Cost of a recorded plan shape (the reference's own arithmetic)."""
        def rec(x):
            if isinstance(x, int):
                rl2 = self.set_rows(x)
                return float(self.scan(rl2)), x, rl2
            cl, sl, rl = rec(x[0])
            cr, sr, rr = rec(x[1])
            s = sl | sr
            ro = self.set_rows(s)
            return float(self.rnd(self.rnd(cl + cr)
                                  + self.join(rl, rr, ro))), s, ro
        return rec(shape)[0]


def connected(s: int, adj) -> bool:
    if s == 0:
        return False
    reach = s & -s
    while True:
        nb = reach
        x = reach
        while x:
            v = (x & -x).bit_length() - 1
            nb |= adj[v]
            x &= x - 1
        nb &= s
        if nb == reach:
            return reach == s
        reach = nb


def plan_problem(shape, q: dict) -> str | None:
    """Why a recorded plan is not a valid bushy join tree of the query
    without cross products, or None when it is."""
    n = int(q["n"])
    adj = [0] * n
    for u, v in q["edges"]:
        adj[u] |= 1 << v
        adj[v] |= 1 << u

    def rec(x):
        if isinstance(x, int):
            if x <= 0 or x & (x - 1):
                raise ValueError(f"leaf {x:#x} is not one relation")
            return x
        if not isinstance(x, (list, tuple)) or len(x) != 2:
            raise ValueError(f"node {x!r} is not a pair")
        a, b = rec(x[0]), rec(x[1])
        if a & b:
            raise ValueError(f"join sides {a:#x} and {b:#x} overlap")
        if not (connected(a, adj) and connected(b, adj)):
            raise ValueError(f"join side of {a:#x}/{b:#x} is disconnected")
        if not any(adj[v] & b for v in range(n) if (a >> v) & 1):
            raise ValueError(f"no predicate between {a:#x} and {b:#x}")
        return a | b

    try:
        full = rec(shape)
    except (ValueError, RecursionError) as e:
        return str(e)
    if full != (1 << n) - 1:
        return f"plan covers {full:#x}, not all {n} relations"
    return None


# --------------------------------------------------------------- exact DP --

def _masks_info(n: int, adj):
    """Connectivity and popcount of every subset of n relations."""
    masks = np.arange(1 << n, dtype=np.int64)
    reach = masks & -masks
    adj_a = np.asarray(adj, np.int64)
    while True:
        nb = reach.copy()
        for v in range(n):
            nb |= np.where((reach >> v) & 1, adj_a[v], 0)
        nb &= masks
        if np.array_equal(nb, reach):
            break
        reach = nb
    pop = np.zeros(1 << n, np.int64)
    for v in range(n):
        pop += (masks >> v) & 1
    return (reach == masks) & (masks > 0), pop


def _splits(S: np.ndarray, k: int, rows_per_chunk: int):
    """Yield (row slice, S1 matrix): every proper subset S1 of each set in S
    (all of size k) that holds its lowest relation."""
    low = S & -S
    rest = S ^ low
    pos = np.zeros((len(S), k - 1), np.int64)
    x = rest.copy()
    for t in range(k - 1):
        lb = x & -x
        pos[:, t] = np.log2(np.maximum(lb, 1)).astype(np.int64)
        x ^= lb
    J = np.arange((1 << (k - 1)) - 1, dtype=np.int64)     # excludes S1 = S
    bits = [((J >> t) & 1) for t in range(k - 1)]
    for r0 in range(0, len(S), rows_per_chunk):
        sl = slice(r0, r0 + rows_per_chunk)
        sub = np.broadcast_to(low[sl, None], (len(low[sl]), len(J))).copy()
        for t in range(k - 1):
            sub |= bits[t][None, :] << pos[sl, t][:, None]
        yield sl, sub


def _dp(cm: CostModel):
    """Dynamic programming over the connected subsets of the model's query
    (csg-cmp pairs only): the optimal cost of every subset (``inf`` where
    it is disconnected), the left side of its best split, and the count of
    unordered csg-cmp pairs."""
    rnd, n = cm.rnd, cm.n
    conn, pop = _masks_info(n, cm.adj)
    masks = np.arange(1 << n, dtype=np.int64)
    rows = cm.rows(masks)
    best = np.full(1 << n, np.inf)
    left = np.zeros(1 << n, np.int64)
    for v in range(n):
        best[1 << v] = float(cm.scan(rows[1 << v]))
    pairs = 0
    for k in range(2, n + 1):
        S = masks[conn & (pop == k)]
        if not len(S):
            continue
        per = max(1, (1 << 20) >> (k - 1))
        for sl, sub in _splits(S, k, per):
            other = S[sl, None] ^ sub
            ok = conn[sub] & conn[other]
            r_i, c_i = np.nonzero(ok)
            pairs += len(r_i)
            a, b = sub[r_i, c_i], other[r_i, c_i]
            s = S[sl][r_i]
            cand = rnd(rnd(best[a] + best[b]) + cm.join(rows[a], rows[b],
                                                        rows[s]))
            full = np.full(ok.shape, np.inf)
            full[r_i, c_i] = cand
            j = np.argmin(full, axis=1)
            rr = np.arange(len(j))
            best[S[sl]] = full[rr, j]
            left[S[sl]] = sub[rr, j]
    return best, left, pairs


def _shape(left, s: int):
    """The best plan of set ``s`` from the DP's split table."""
    if s & (s - 1) == 0:
        return int(s)
    a = int(left[s])
    return [_shape(left, a), _shape(left, s ^ a)]


def exact(q: dict, consts: dict, rnd=ident, plan: bool = True):
    """Optimal plan of a query by dynamic programming over connected
    subsets (csg-cmp pairs only).  Returns ``(cost, shape or None, pairs)``
    where ``pairs`` counts unordered csg-cmp pairs."""
    best, left, pairs = _dp(CostModel(q, consts, rnd))
    top = (1 << int(q["n"])) - 1
    if not np.isfinite(best[top]):
        raise ValueError("query graph is disconnected")
    return float(best[top]), _shape(left, top) if plan else None, pairs


# ---------------------------------------- local optimality of heuristics --

def _leaves(x) -> int:
    return x if isinstance(x, int) else _leaves(x[0]) | _leaves(x[1])


def _maximal_subtrees(shape, k: int):
    """The plan's subtrees of at most k relations whose parent has more (the
    whole plan when it has k or fewer)."""
    if bin(_leaves(shape)).count("1") <= k:
        yield shape
        return
    for side in shape:
        yield from _maximal_subtrees(side, k)


def sub_query(q: dict, s: int):
    """The query restricted to relation set ``s``, relabelled from 0, and
    the map from old relation ids to new ones.  A set's rows and a plan's
    cost depend on the relations and predicates inside it alone."""
    ids = [v for v in range(int(q["n"])) if (s >> v) & 1]
    pos = {v: i for i, v in enumerate(ids)}
    inside = [((u, v), sl) for (u, v), sl in zip(q["edges"], q["sels"])
              if u in pos and v in pos]
    return {"n": len(ids), "edges": [(pos[u], pos[v]) for (u, v), _ in inside],
            "cards": [q["cards"][v] for v in ids],
            "sels": [sl for _, sl in inside]}, pos


def _relabel(x, pos):
    """A plan shape with every leaf's relation id mapped through ``pos``."""
    if isinstance(x, int):
        return 1 << pos[x.bit_length() - 1]
    return [_relabel(x[0], pos), _relabel(x[1], pos)]


def local_gap(q: dict, consts: dict, shape, k: int) -> float:
    """The largest relative excess, over every subtree of a valid plan with
    at most ``k`` relations, of its cost over the exact optimum of its
    relation set.  A heuristic that solves its pieces of up to k relations
    exactly leaves 0 up to rounding; a greedy plan does not."""
    worst = 0.0
    for top in _maximal_subtrees(shape, k):
        sq, pos = sub_query(q, _leaves(top))
        cm = CostModel(sq, consts)
        best, _, _ = _dp(cm)

        def walk(x):
            """(cost, set, log2 rows) of a subtree; records its gap."""
            nonlocal worst
            if isinstance(x, int):
                rl2 = cm.set_rows(x)
                return float(cm.scan(rl2)), x, rl2
            cl, sl, rl = walk(x[0])
            cr, sr, rr = walk(x[1])
            s = sl | sr
            ro = cm.set_rows(s)
            c = float(cl + cr + cm.join(rl, rr, ro))
            worst = max(worst, float(c / best[s] - 1))
            return c, s, ro

        walk(_relabel(top, pos))
    return worst


def reoptimized(q: dict, consts: dict, k: int, rnd=ident):
    """GOO's plan with every maximal subtree of at most ``k`` relations
    replaced by the exact optimum of its relation set, all in the precision
    ``rnd`` gives: the reference's own answer for a heuristic cell."""
    _, shape = goo(q, consts, rnd)
    out = []
    for top in _maximal_subtrees(shape, k):
        sq, pos = sub_query(q, _leaves(top))
        _, sub, _ = exact(sq, consts, rnd)
        out.append((top, _relabel(sub, {i: v for v, i in pos.items()})))

    def rec(x):
        for top, sub in out:
            if x is top:
                return sub
        return [rec(x[0]), rec(x[1])]
    return rec(shape)


# -------------------------------------------------------------------- GOO --

def goo(q: dict, consts: dict, rnd=ident):
    """Greedy operator ordering: join the connected pair of components with
    the fewest result rows until one is left.  Returns ``(cost, shape)``,
    the cost always in float64."""
    cm = CostModel(q, consts, rnd)
    shape = {v: v for v in range(cm.n)}          # component id -> shape
    raw = {v: float(cm.card_l2[v]) for v in range(cm.n)}
    cross: dict[tuple[int, int], float] = {}     # summed sels between two
    for (u, v), sl in zip(cm.edges, cm.sel_l2):
        cross[(u, v)] = float(sl)
    nxt = cm.n
    while len(shape) > 1:
        if not cross:
            raise ValueError("query graph is disconnected")
        best = None
        for (a, b), sl in sorted(cross.items()):
            r = max(float(rnd(rnd(raw[a] + raw[b]) + sl)), 0.0)
            if best is None or r < best[0]:
                best = (r, a, b, float(rnd(rnd(raw[a] + raw[b]) + sl)))
        _, a, b, rab = best
        c, nxt = nxt, nxt + 1
        shape[c] = [_bits(shape.pop(a)), _bits(shape.pop(b))]
        raw[c] = rab
        merged: dict[tuple[int, int], float] = {}
        for (x, y), sl in cross.items():
            x2 = c if x in (a, b) else x
            y2 = c if y in (a, b) else y
            if x2 == y2:
                continue
            k = (min(x2, y2), max(x2, y2))
            merged[k] = float(rnd(merged[k] + sl)) if k in merged else sl
        cross = merged
    top = _bits(next(iter(shape.values())))
    return CostModel(q, consts).plan_cost(top), top


def _bits(x):
    """Component shapes hold relation ids at the leaves until joined."""
    return 1 << x if isinstance(x, int) else x


# ------------------------------------------------------- judging answers --

def judge_exact(q: dict, consts: dict, shape, reported: float) -> dict:
    """A served plan against the exact optimum: validity, the relative gap
    of its cost to the optimum, and the relative error of the cost the
    program reported for it, all by the reference's arithmetic."""
    opt, _, _ = exact(q, consts, plan=False)
    out = {"n": int(q["n"]), "gap": math.inf,
           "cost_error": math.inf, "problem": plan_problem(shape, q)}
    if out["problem"] is None:
        cost = CostModel(q, consts).plan_cost(shape)
        out.update(gap=cost / opt - 1, cost_error=abs(reported / cost - 1))
    return out


def judge_heuristic(q: dict, consts: dict, shape, reported: float,
                    k: int) -> dict:
    """A served heuristic plan: validity, its cost over GOO's, its largest
    local gap over subtrees of at most ``k`` relations (``local_gap``), and
    the relative error of the cost the program reported for it."""
    base, _ = goo(q, consts)
    out = {"n": int(q["n"]), "ratio": math.inf, "local_gap": math.inf,
           "cost_error": math.inf, "problem": plan_problem(shape, q)}
    if out["problem"] is None:
        cost = CostModel(q, consts).plan_cost(shape)
        out.update(ratio=cost / base, cost_error=abs(reported / cost - 1),
                   local_gap=local_gap(q, consts, shape, k))
    return out
