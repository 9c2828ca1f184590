"""The benchmark's plain reference: pair counts, exact optimum, GOO, judges."""
import json
import math
import os
import random

import pytest

from bench import reference as ref

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
with open(os.path.join(ROOT, "bench", "configs", "musicbrainz-pg.json")) as f:
    MB = json.load(f)
CONSTS = MB["cost_model"]


def rand_query(n: int, seed: int, extra: int = 2) -> dict:
    """A random connected query: a spanning tree plus a few cycle edges."""
    r = random.Random(seed)
    edges = [(r.randrange(i), i) for i in range(1, n)]
    for _ in range(extra):
        u, v = r.sample(range(n), 2)
        if (min(u, v), max(u, v)) not in {(min(a, b), max(a, b))
                                          for a, b in edges}:
            edges.append((u, v))
    return {"n": n, "edges": edges,
            "cards": [10 ** r.uniform(1, 7) for _ in range(n)],
            "sels": [10 ** r.uniform(-6, -1) for _ in edges],
            "names": [f"R{i}" for i in range(n)]}


def brute_pairs(q: dict) -> int:
    n = q["n"]
    adj = [0] * n
    for u, v in q["edges"]:
        adj[u] |= 1 << v
        adj[v] |= 1 << u
    count = 0
    for a in range(1, 1 << n):
        for b in range(a + 1, 1 << n):
            if a & b or not (ref.connected(a, adj) and ref.connected(b, adj)):
                continue
            if any(adj[v] & b for v in range(n) if (a >> v) & 1):
                count += 1
    return count


def all_plans(s: int, adj):
    """Every bushy plan without cross products over relation set s."""
    if s & (s - 1) == 0:
        yield s
        return
    low = s & -s
    sub = (s - 1) & s
    while sub:
        if sub & low and ref.connected(sub, adj) and \
                ref.connected(s ^ sub, adj):
            for a in all_plans(sub, adj):
                for b in all_plans(s ^ sub, adj):
                    yield [a, b]
        sub = (sub - 1) & s


@pytest.mark.parametrize("n,seed", [(n, s) for n in range(2, 8)
                                    for s in range(3)])
def test_ccp_pairs_match_brute_force(n, seed):
    q = rand_query(n, seed)
    assert ref.exact(q, CONSTS)[2] == brute_pairs(q)


@pytest.mark.parametrize("n,seed", [(n, s) for n in range(2, 7)
                                    for s in range(2)])
def test_exact_is_the_cheapest_plan(n, seed):
    q = rand_query(n, 100 + seed)
    cm = ref.CostModel(q, CONSTS)
    best = min(cm.plan_cost(p) for p in all_plans((1 << n) - 1, cm.adj))
    cost, shape, _ = ref.exact(q, CONSTS)
    assert ref.plan_problem(shape, q) is None
    assert cost == pytest.approx(best, rel=1e-12)
    assert cm.plan_cost(shape) == pytest.approx(best, rel=1e-12)


def test_goo_joins_the_smallest_result_first():
    q = {"n": 3, "edges": [(0, 1), (1, 2)], "cards": [1e6, 1e3, 1e4],
         "sels": [1e-3, 1e-4], "names": ["a", "b", "c"]}
    cost, shape = ref.goo(q, CONSTS)
    assert shape == [1, [2, 4]]                  # b-c (1e3 rows) before a
    assert cost == ref.CostModel(q, CONSTS).plan_cost(shape)


def test_judges_flag_bad_answers():
    q = rand_query(7, 7)
    cost, shape, _ = ref.exact(q, CONSTS)
    good = ref.judge_exact(q, CONSTS, shape, cost)
    assert good["problem"] is None and good["gap"] == 0.0
    assert good["cost_error"] < 1e-12
    worse = max(all_plans((1 << 7) - 1, ref.CostModel(q, CONSTS).adj),
                key=ref.CostModel(q, CONSTS).plan_cost)
    assert ref.judge_exact(q, CONSTS, worse, cost)["gap"] > 1e-3
    assert ref.judge_exact(q, CONSTS, shape, cost * 1.01)["cost_error"] \
        == pytest.approx(0.01)
    bad = ref.judge_exact(q, CONSTS, [shape, 1], cost)    # relation twice
    assert bad["problem"] and math.isinf(bad["gap"])
    chain = {"n": 3, "edges": [(0, 1), (1, 2)], "cards": [10, 10, 10],
             "sels": [0.1, 0.1], "names": ["a", "b", "c"]}
    assert "no predicate" in ref.plan_problem([[1, 4], 2], chain)
    assert "covers" in ref.plan_problem([1, 2], chain)
    g = ref.judge_heuristic(q, CONSTS, shape, cost, 7)
    assert g["problem"] is None and g["ratio"] <= 1.0 + 1e-12
    assert g["local_gap"] == 0.0
    assert ref.judge_heuristic(q, CONSTS, worse, cost, 7)["local_gap"] > 1e-3


def brute_local_gap(q, shape, k):
    """Every subtree of at most k relations against the cheapest plan of
    its relation set, found by enumerating all plans."""
    cm = ref.CostModel(q, CONSTS)
    worst = 0.0

    def rec(x):
        nonlocal worst
        if isinstance(x, int):
            return x
        s = rec(x[0]) | rec(x[1])
        if bin(s).count("1") <= k:
            best = min(cm.plan_cost(p) for p in all_plans(s, cm.adj))
            worst = max(worst, cm.plan_cost(x) / best - 1)
        return s
    rec(shape)
    return worst


@pytest.mark.parametrize("n,k,seed", [(6, 3, 0), (7, 4, 1), (7, 7, 2),
                                      (8, 5, 3)])
def test_local_gap_matches_brute_force(n, k, seed):
    q = rand_query(n, 200 + seed)
    cm = ref.CostModel(q, CONSTS)
    for shape in (ref.goo(q, CONSTS)[1],
                  max(all_plans((1 << n) - 1, cm.adj), key=cm.plan_cost)):
        assert ref.local_gap(q, CONSTS, shape, k) == \
            pytest.approx(brute_local_gap(q, shape, k), rel=1e-9, abs=1e-12)
    fixed = ref.reoptimized(q, CONSTS, k)
    assert ref.plan_problem(fixed, q) is None
    assert ref.local_gap(q, CONSTS, fixed, k) == 0.0
    assert cm.plan_cost(fixed) <= ref.goo(q, CONSTS)[0] * (1 + 1e-12)


def test_bf16_rounds_to_eight_bits():
    x = ref.bf16([1.0 + 2 ** -9, 3.0, 1e30])
    assert x[0] == 1.0 and x[1] == 3.0
    assert abs(x[2] / 1e30 - 1) < 2 ** -8
