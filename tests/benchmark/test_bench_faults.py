"""A whole run of each cell, on the CPU at a small size, with the timed path
sound and then broken underneath: the check has to say correct, then not
correct.  (Only the harness's look for a chip is skipped.)"""
import time

import pytest

from bench import cell as bcell
from bench import run as brun

SMALL = {"mb-mid-open": {"sizes": [6, 7, 8], "rate_per_s": 3.0},
         "snow-uniondp-closed": {"sizes": [24], "pool": 2,
                                 "max_requests": 4}}


def small_cell(name):
    c = bcell.load_cell(name)
    c["traffic"].update(SMALL[name])
    sizes = c["traffic"]["sizes"]
    c["config"]["relations"] = [min(sizes), max(sizes)]
    if c["config"]["schema"] == "snowflake":
        c["config"]["uniondp"]["k"] = 8
    return c


def run_small(name, seconds=1.5):
    return brun.execute(small_cell(name), 2 ** 31 + 99, seconds, False,
                        require_tpu=False, workers=2,
                        t_start=time.perf_counter(), log=lambda *_: None)


def left_deep(g):
    """A valid plan a DP would not choose: relations joined one at a time
    in breadth-first order from relation 0."""
    from repro.core.plan import cost_plan, join_plans, leaf_plan
    adj = g.adjacency()
    order, seen = [0], {0}
    for v in order:
        for w in range(g.n):
            if (adj[v] >> w) & 1 and w not in seen:
                seen.add(w)
                order.append(w)
    p = leaf_plan(order[0], g)
    for v in order[1:]:
        p = join_plans(p, leaf_plan(v, g), g)
    return cost_plan(p, g)


@pytest.mark.parametrize("name", sorted(SMALL))
def test_sound_run_is_correct(name):
    res = run_small(name)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {m["name"] for m in
                                   small_cell(name)["end_to_end"]}
    assert list(res)[-1] == "checks"


def _served_plans_altered(monkeypatch):
    from repro.core import service

    orig = service.StreamOptimizer.optimize_stream

    def altered(self, graphs):
        results, report = orig(self, graphs)
        for g, r in zip(graphs, results):
            r.plan = left_deep(g)
        return results, report

    monkeypatch.setattr(service.StreamOptimizer, "optimize_stream", altered)


def _heuristic_plan_altered(monkeypatch):
    from repro.core.plan import Counters, OptimizeResult
    from repro.heuristics import uniondp

    def altered(g, **_kw):
        p = left_deep(g)
        return OptimizeResult(plan=p, cost=p.cost, counters=Counters(),
                              algorithm="altered")

    monkeypatch.setattr(uniondp, "solve", altered)


def _heuristic_returns_goo(monkeypatch):
    """The tier's exact subsolves skipped: GOO's plan served instead."""
    from repro.heuristics import goo, uniondp

    monkeypatch.setattr(uniondp, "solve", lambda g, **_kw: goo.solve(g))


def _half_the_subproblems_left_out(monkeypatch):
    """Each batched MPDP pass solves the first half of its subproblems; the
    rest get GOO's plan instead of the exact one."""
    from repro.core import engine
    from repro.heuristics import goo

    orig = engine.optimize_many

    def half(graphs, **kw):
        keep = max(1, len(graphs) // 2)
        return orig(graphs[:keep], **kw) + [goo.solve(g)
                                            for g in graphs[keep:]]

    monkeypatch.setattr(engine, "optimize_many", half)


@pytest.mark.parametrize("name,fault", [
    ("mb-mid-open", _served_plans_altered),
    ("snow-uniondp-closed", _heuristic_plan_altered),
    ("snow-uniondp-closed", _heuristic_returns_goo),
    ("snow-uniondp-closed", _half_the_subproblems_left_out),
])
def test_broken_timed_path_is_not_correct(name, fault, monkeypatch):
    fault(monkeypatch)
    res = run_small(name)
    assert not res["correct"]
    assert any(c["value"] > c["limit"] for c in res["checks"].values())
