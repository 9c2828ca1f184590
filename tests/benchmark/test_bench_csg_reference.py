"""The csg-cmp reference (bench/reference_csg.py) and its check
(bench/checks/exact_csg.py): the same optimum and pair count as the 3^n
reference, valid plans, a check that catches a plan or a reported cost 1 %
off, a bfloat16 control that fails it, and no import of the program."""
import ast
import json
import os
import subprocess
import sys

import pytest

from bench import reference as ref, reference_csg as rc, traffic
from bench.cell import ROOT, module

with open(os.path.join(ROOT, "bench", "configs", "musicbrainz-pg25.json")) as f:
    CFG = json.load(f)
CONSTS, LIMITS = CFG["cost_model"], CFG["check"]["limits"]
SCHEMA = module("schemas", "musicbrainz")
CHECK = module("checks", "exact_csg")


def walk(n: int, j: int, stats_seed: int) -> dict:
    """The j-th catalogue walk of n relations, statistics from the seed."""
    return SCHEMA.query(CFG, n, traffic.derive("catalogue", f"query0/{n}", j),
                        stats_seed)


def shaped(n: int, edges) -> dict:
    return {"n": n, "edges": edges,
            "cards": [10.0 ** (2 + (5 * i) % 7) for i in range(n)],
            "sels": [10.0 ** -(1 + (3 * k) % 5) for k in range(len(edges))]}


SHAPES = {
    "chain": shaped(9, [(i, i + 1) for i in range(8)]),
    "star": shaped(9, [(0, i) for i in range(1, 9)]),
    "cycle": shaped(9, [(i, (i + 1) % 9) for i in range(9)]),
    "clique": shaped(8, [(u, v) for u in range(8) for v in range(u + 1, 8)]),
}


def agree(q: dict) -> None:
    cost, shape, pairs = rc.exact(q, CONSTS)
    cost3, _, pairs3 = ref.exact(q, CONSTS, plan=False)
    assert pairs == pairs3
    assert cost == pytest.approx(cost3, rel=1e-12)
    assert ref.plan_problem(shape, q) is None
    assert ref.CostModel(q, CONSTS).plan_cost(shape) == \
        pytest.approx(cost, rel=1e-12)


@pytest.mark.parametrize("n,j", [(n, j) for n in range(6, 15)
                                 for j in range(3)])
def test_agrees_with_the_3n_reference_on_walks(n, j):
    agree(walk(n, j, 1000 * n + j))


@pytest.mark.parametrize("name", sorted(SHAPES))
def test_agrees_with_the_3n_reference_on_shapes(name):
    agree(SHAPES[name])


def test_pair_counts_of_known_shapes():
    """Chain (n^3 - n) / 6, cycle n (n - 1)^2 / 2, star (n - 1) 2^(n - 2),
    clique (3^n - 2^(n + 1) + 1) / 2 (Moerkotte & Neumann, VLDB 2006)."""
    def count(name):
        return rc.exact(SHAPES[name], CONSTS, plan=False)[2]
    assert count("chain") == (9 ** 3 - 9) // 6
    assert count("cycle") == 9 * 8 ** 2 // 2
    assert count("star") == 8 * 2 ** 7
    assert count("clique") == (3 ** 8 - 2 ** 9 + 1) // 2


def run_of(q: dict, shape, reported: float, status: str = "ok") -> dict:
    rec = {"id": 0, "status": status, "queries": 1}
    if status == "ok":
        rec.update(plans=[shape], costs=[reported])
    return {"cell": {"config": CFG}, "records": [rec],
            "requests": [{"queries": [q]}]}


class _Inline:
    """A pool that maps in this process."""
    map = staticmethod(map)


def suboptimal_plan(q: dict, opt: float):
    """A left-deep plan that costs at least 1 % above the optimum."""
    cm = ref.CostModel(q, CONSTS)
    n = q["n"]
    for start in range(n):
        plan, have = 1 << start, 1 << start
        while have != (1 << n) - 1:
            v = next(v for v in range(n) if not (have >> v) & 1
                     and cm.adj[v] & have)
            plan, have = [plan, 1 << v], have | 1 << v
        cost = cm.plan_cost(plan)
        if cost >= 1.01 * opt:
            return plan, cost
    raise AssertionError("every left-deep plan is within 1 % of the optimum")


def test_check_passes_the_optimum_and_fails_plans_1pct_off():
    q = walk(12, 0, 77)
    opt, shape, _ = rc.exact(q, CONSTS)
    good = CHECK.check(run_of(q, shape, opt), _Inline)
    assert good["ok"] and good["checked"] == 1
    dear = CHECK.check(run_of(q, shape, opt * 1.01), _Inline)
    assert not dear["ok"]
    assert dear["numbers"]["worst_cost_error"][0] == pytest.approx(0.01)
    plan, cost = suboptimal_plan(q, opt)
    worse = CHECK.check(run_of(q, plan, cost), _Inline)
    assert not worse["ok"] and worse["numbers"]["worst_gap"][0] >= 0.01
    assert worse["numbers"]["worst_cost_error"][0] <= 1e-12


def test_check_counts_invalid_plans_and_errors():
    q = walk(8, 1, 5)
    bad = CHECK.check(run_of(q, [1, 2], 1.0), _Inline)
    assert bad["numbers"]["invalid_plans"][0] == 1 and not bad["ok"]
    err = CHECK.check(run_of(q, None, 0.0, status="error"), _Inline)
    assert err["numbers"]["errors"][0] == 1 and not err["ok"]


@pytest.mark.parametrize("seed", (3, 2 ** 31 + 5, 987654321))
def test_bf16_control_fails_the_check(seed):
    judged = [rc.judge_control(walk(n, j, traffic.derive(seed, "query", n)),
                               CONSTS)
              for n in (10, 12, 14) for j in range(2)]
    v = CHECK.verdict(judged, LIMITS, 0)
    assert v["checked"] == 6 and not v["ok"], v["numbers"]


def test_imports_nothing_of_the_program():
    for name in ("reference_csg.py", os.path.join("checks", "exact_csg.py")):
        with open(os.path.join(ROOT, "bench", name)) as f:
            tree = ast.parse(f.read())
        mods = {a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
                for a in n.names}
        mods |= {n.module for n in ast.walk(tree)
                 if isinstance(n, ast.ImportFrom) and n.module}
        assert not {m for m in mods if m.split(".")[0] in ("repro", "jax")}
    code = ("import sys; import bench.reference_csg; "
            "print(sorted(m for m in sys.modules "
            "if m.split('.')[0] in ('repro', 'jax')))")
    out = subprocess.run([sys.executable, "-c", code], cwd=ROOT, check=True,
                         capture_output=True, text=True,
                         env=dict(os.environ, PYTHONPATH=ROOT)).stdout
    assert out.strip() == "[]"
