"""Serve a cell through the heuristic tier in this process, closed loop.

The daemon refuses queries past the exact tier, and the query service
sends those to ``repro.heuristics.uniondp.solve``, so this driver calls it
directly: one client, each request one query, the next sent when the last
plan is back, until the window's time is up.  Each solve is marked as a
``bench.solve`` span in the trace.
"""
from __future__ import annotations

import time


def run(ctx) -> dict:
    import jax
    from bench.client import shape
    from repro.core.joingraph import JoinGraph
    from repro.heuristics import uniondp
    kw = ctx.cell["config"]["uniondp"]

    def graph(q):
        return JoinGraph.make(q["n"], [tuple(e) for e in q["edges"]],
                              q["cards"], q["sels"], names=q["names"])

    for req in ctx.plan["warmup"]:
        uniondp.solve(graph(req["queries"][0]), **kw)
    reqs = [(r, graph(r["queries"][0])) for r in ctx.plan["requests"]]
    records = []
    ctx.window_begin()
    t0 = time.perf_counter()
    for req, g in reqs:
        if time.perf_counter() - t0 >= ctx.seconds:
            break
        rec = {"id": req["id"], "client": 0, "tenant": req["tenant"],
               "due": None, "queries": 1, "send": time.perf_counter() - t0}
        try:
            with jax.profiler.TraceAnnotation("bench.solve"):
                r = uniondp.solve(g, **kw)
            rec.update(status="ok", plans=[shape(r.plan)],
                       costs=[float(r.cost)])
        except Exception as e:                   # recorded, judged later
            rec.update(status="error", error=f"{type(e).__name__}: {e}")
        rec["reply"] = time.perf_counter() - t0
        records.append(rec)
    ctx.window_end()
    return {"records": records}
