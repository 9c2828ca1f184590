"""The one traffic generator: a mix file's parameters and a seed in, the
requests of a run out.

Every seed gets the same amount of work: the same count of requests, the
same inter-arrival gaps, the same tenant shares and the same join graphs
(drawn from a fixed catalogue per size), by default in an order the seed
draws.  Near capacity the queue's waits follow that order (a cluster of
short gaps and large queries builds a queue), so a mix with ``"order":
"catalogue"`` takes the order of arrivals, sizes and tenants from the
catalogue as well.  The exact tier's work depends on the graph alone, so
by default the seed draws every query's statistics and each run plans
new queries; the heuristic tier partitions by cost, so its work follows
the statistics, and a mix with ``"statistics": "catalogue"`` takes them
from the catalogue too: every seed plans the same queries.

Mix keys (see bench/traffic/*.json):

    driver               the bench/drivers/ module that serves the mix
    loop                 "open" (arrivals on a clock) or "closed"
    rate_per_s           open loop: offered requests per second
    tenants              open loop: [[tenant, share], ...]
    clients              closed loop: concurrent clients, one tenant each
    queries_per_request  queries in one request
    sizes                relation counts, cycled over a request's queries;
                         each within the configuration's ``relations``
    order                "seed" (default) or "catalogue" (see above)
    statistics           "seed" (default) or "catalogue" (see above)
    pool                 closed loop: replay this many distinct requests
                         per client instead of fresh ones, each pass
                         over them in an order of its own
    max_requests         closed loop: requests made ready per client

The warm-up, sent before the window, is every distinct window request
again with its statistics redrawn: the same join graphs, so the same
compiled shapes, and different queries.
"""
from __future__ import annotations

import hashlib
import json
import math
import random
from itertools import accumulate

from . import cell as _cell


def derive(seed: int, stream: str, i: int) -> int:
    """A 64-bit seed for item ``i`` of ``stream`` under the run's seed."""
    h = hashlib.sha256(f"{seed}/{stream}/{i}".encode()).digest()
    return int.from_bytes(h[:8], "little")


class _Queries:
    """Query specs of one stream.  The j-th query of a given size takes the
    j-th join graph of a fixed catalogue, the same for every seed; its
    statistics come from the run's seed, or with ``"statistics":
    "catalogue"`` from the catalogue as well."""

    def __init__(self, cell, seed, stream):
        self.cfg = cell["config"]
        self.schema = _cell.module("schemas", self.cfg["schema"])
        self.seed, self.stream, self.k = seed, stream, 0
        self.fixed = cell["traffic"].get("statistics", "seed") == "catalogue"
        self.taken: dict = {}

    def draw(self, n: int) -> dict:
        lo, hi = self.cfg["relations"]
        if not lo <= n <= hi:
            raise ValueError(f"size {n} is outside the configuration's "
                             f"relations {lo}-{hi}")
        j = self.taken.get(n, 0)
        self.taken[n] = j + 1
        q = self.schema.query(self.cfg, n,
                              derive("catalogue", f"{self.stream}/{n}", j),
                              None if self.fixed else
                              derive(self.seed, self.stream, self.k))
        self.k += 1
        return q


def _request(src: _Queries, tr: dict, rng: random.Random) -> list:
    sizes = list(tr["sizes"])
    qs = [src.draw(sizes[j % len(sizes)])
          for j in range(int(tr["queries_per_request"]))]
    rng.shuffle(qs)
    return qs


def redraw(q: dict, seed: int) -> dict:
    """The same join graph with every cardinality and selectivity scaled by
    a factor in [0.9, 1.1]: statistics that drifted a little, so the
    heuristic tier partitions it as it will the window's query."""
    r = random.Random(seed)
    return dict(q, cards=[max(1.0, c * r.uniform(0.9, 1.1)) for c in q["cards"]],
                sels=[min(1.0, s * r.uniform(0.9, 1.1)) for s in q["sels"]])


def _warm_copies(reqs, seed, fixed):
    """Each distinct request once, drifted; with catalogue statistics the
    drift comes from the catalogue as well, so every seed warms up alike."""
    out, seen = [], set()
    for r in reqs:
        if id(r["queries"]) in seen:
            continue
        seen.add(id(r["queries"]))
        k = len(out)
        out.append(dict(r, id=k, tenant="warmup", due=None, queries=[
            redraw(q, derive("catalogue", "redraw", json.dumps(q["cards"]))
                   if fixed else derive(seed, "redraw", k * 1000 + j))
            for j, q in enumerate(r["queries"])]))
    if fixed:
        out.sort(key=lambda r: json.dumps(r["queries"][0]["cards"]))
        for k, r in enumerate(out):
            r["id"] = k
    return out


def build(cell: dict, seed: int, seconds: float) -> dict:
    """``{"warmup": [request], "requests": [request]}``; a request is
    ``{"id", "client", "tenant", "due", "queries"}`` (``due`` in seconds
    from the window's start for the open loop, else None)."""
    tr = cell["traffic"]
    oseed = "catalogue" if tr.get("order", "seed") == "catalogue" else seed
    rng = random.Random(derive(oseed, "order", 0))
    if tr["loop"] == "open":
        rate = float(tr["rate_per_s"])
        n = max(1, round(rate * seconds))
        # fixed exponential quantiles: the same gaps for every seed
        gaps = [-math.log(1.0 - (i + 0.5) / n) / rate for i in range(n)]
        rng.shuffle(gaps)
        due = [0.0] + list(accumulate(gaps))[:-1]
        tenants = [t for t, share in tr["tenants"]
                   for _ in range(round(share * n))]
        tenants = (tenants + [tr["tenants"][0][0]] * n)[:n]
        rng.shuffle(tenants)
        q = int(tr["queries_per_request"])
        sizes = [tr["sizes"][i % len(tr["sizes"])] for i in range(n * q)]
        rng.shuffle(sizes)
        src = _Queries(cell, seed, "query")
        reqs = [{"id": i, "client": 0, "tenant": tenants[i], "due": due[i],
                 "queries": [src.draw(sizes[i * q + j]) for j in range(q)]}
                for i in range(n)]
    else:
        reqs = []
        for c in range(int(tr["clients"])):
            crng = random.Random(derive(oseed, "client", c))
            src = _Queries(cell, seed, f"query{c}")
            own = [_request(src, tr, crng)
                   for _ in range(int(tr.get("pool") or tr["max_requests"]))]
            order: list = []
            while len(order) < int(tr["max_requests"]):
                order += crng.sample(range(len(own)), len(own))
            reqs += [{"id": len(reqs) + i, "client": c,
                      "tenant": f"client-{c}", "due": None,
                      "queries": own[order[i]]}
                     for i in range(int(tr["max_requests"]))]
    fixed = tr.get("statistics", "seed") == "catalogue"
    return {"warmup": _warm_copies(reqs, seed, fixed), "requests": reqs}
