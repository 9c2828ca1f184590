"""Load generator for the daemon cells: a child process off the chip.

    JAX_PLATFORMS=cpu python3 bench/client.py --cell CELL.json --seed S \
        --seconds T --socket PATH

``CELL.json`` is the cell as ``cell.load_cell`` gives it (the harness
writes it).  Builds the run's requests from the seed (``traffic.build``), sends the
warm-up requests, prints ``READY`` and waits for ``GO`` on stdin.  Then it
runs the window and prints one JSON line of records, one per request:

    due, send, reply   seconds from the window's start (due: open loop)
    status             "ok", "shed" or "error"
    wall_s, flights, cache_hits, solo   the daemon's reply metadata
    plans, costs       plan shapes (leaf bitmaps, pairs) and the costs the
                       daemon reported for them, one per query

Open loop: each request is handed to a worker thread when it is due, on
its own connection per tenant and thread, whatever is still outstanding;
its latency counts from the due time, and ``send - due`` is how late the
generator ran.  Closed loop: each client sends its next request when the
previous reply is in, until the window's time is up.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE), os.path.join(os.path.dirname(HERE),
                                                    "src")]

# open loop: threads that send due requests, enough that none waits for one
OPEN_LOOP_THREADS = 64


def shape(p):
    """Plan tree -> nested pairs of leaf bitmaps."""
    return p.rel_set if p.left is None else [shape(p.left), shape(p.right)]


def to_graph(q: dict):
    from repro.core.joingraph import JoinGraph
    return JoinGraph.make(q["n"], [tuple(e) for e in q["edges"]], q["cards"],
                          q["sels"], names=q["names"])


class Sender:
    """One connection per (thread, tenant); records one request each."""

    def __init__(self, socket_path: str):
        self.socket_path = socket_path
        self.local = threading.local()

    def client(self, tenant: str):
        from repro.daemon import DaemonClient
        conns = self.local.__dict__.setdefault("conns", {})
        if tenant not in conns:
            conns[tenant] = DaemonClient(socket_path=self.socket_path,
                                         tenant=tenant, connect_timeout=30.0)
        return conns[tenant]

    def send(self, req: dict, graphs: list, t0: float) -> dict:
        from repro.daemon import DaemonShed
        rec = {"id": req["id"], "client": req["client"],
               "tenant": req["tenant"], "due": req["due"],
               "queries": len(graphs)}
        c = self.client(req["tenant"])
        rec["send"] = time.perf_counter() - t0
        try:
            results = c.optimize(graphs)
            rec["status"] = "ok"
            rec.update({k: c.last_meta.get(k) for k in
                        ("wall_s", "flights", "cache_hits", "solo")})
            rec["plans"] = [shape(r.plan) for r in results]
            rec["costs"] = [float(r.cost) for r in results]
        except DaemonShed as e:
            rec["status"], rec["error"] = "shed", e.reason
        except Exception as e:                   # recorded, judged later
            rec["status"], rec["error"] = "error", f"{type(e).__name__}: {e}"
        rec["reply"] = time.perf_counter() - t0
        return rec


def run_open(sender: Sender, reqs, graphs, t0: float,
             workers: int = OPEN_LOOP_THREADS):
    futs = []
    with ThreadPoolExecutor(max_workers=workers) as pool:
        for req, gs in sorted(zip(reqs, graphs), key=lambda x: x[0]["due"]):
            wait = t0 + req["due"] - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            futs.append(pool.submit(sender.send, req, gs, t0))
        return [f.result() for f in futs]


def run_closed(sender: Sender, reqs, graphs, t0: float, seconds: float):
    out, lock = [], threading.Lock()

    def client_loop(c: int):
        for req, gs in zip(reqs, graphs):
            if req["client"] != c:
                continue
            if time.perf_counter() - t0 >= seconds:
                return
            rec = sender.send(req, gs, t0)
            with lock:
                out.append(rec)

    threads = [threading.Thread(target=client_loop, args=(c,))
               for c in sorted({r["client"] for r in reqs})]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--cell", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--socket", required=True)
    args = ap.parse_args(argv)
    from bench import traffic
    with open(args.cell) as f:
        cell = json.load(f)
    plan = traffic.build(cell, args.seed, args.seconds)
    tr = cell["traffic"]
    sender = Sender(args.socket)
    for req in plan["warmup"]:          # answers judged in the window only
        sender.send(req, [to_graph(q) for q in req["queries"]],
                    time.perf_counter())
    graphs = [[to_graph(q) for q in r["queries"]] for r in plan["requests"]]
    print("READY", flush=True)
    if sys.stdin.readline().strip() != "GO":
        return 1
    t0 = time.perf_counter()
    if tr["loop"] == "open":
        recs = run_open(sender, plan["requests"], graphs, t0)
    else:
        recs = run_closed(sender, plan["requests"], graphs, t0, args.seconds)
    print(json.dumps({"records": recs}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
