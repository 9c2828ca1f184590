"""End-to-end driver of the paper's kind: a *streaming* optimize-and-execute
query service over the MusicBrainz-like schema.

A stream of generated analytic queries (10-56 relations — the random walk
restarts on stall, so the full 56-table schema is reachable) flows through
the PostgreSQL-style policy the paper enables:

    n <= exact limit   -> exact MPDP through the admission-controlled
                          streaming service (``repro.core.service``): queries
                          are grouped into (NMAX bucket, lane space) flights
                          behind a canonical-signature plan cache, flight i's
                          host finalize overlaps flight i+1's device work,
                          and per-query latency percentiles are reported
    n >  exact limit   -> UnionDP(MPDP, k)      (paper §4.2; its per-round
                          partitions batch internally too)

The exact limit is ``EXACT_LIMIT`` (14) on a single device; with
``--devices N`` it rises to ``EXACT_LIMIT_LATTICE`` (18), because the
service admits oversized queries as intra-query *lattice* flights
(``repro.core.lattice``: one query's DP lane space sharded over the mesh,
replicated per-device memo, one collective per committed level) instead of
bouncing them to the heuristic tier.

``--devices N`` shards every batched pass (the exact tier AND UnionDP's
per-round partitions) over an N-device ``batch`` mesh — on CPU the devices
are emulated, so the flag must be parsed before jax initializes.
``--pipeline`` additionally runs every engine's level loop pipelined (host
compaction of level i+1 under device evaluate of level i; bit-identical
plans).  ``--cache-file PATH`` persists the plan cache across service runs
(the file self-invalidates when the stats-quantization version changes).
``--explain`` prints, for the first UnionDP-tier query, the partition
boundaries each recursion round chose (table names per partition) and the
re-optimization loop's per-pass total costs — the worked example in
``docs/heuristics.md`` is this output.

Each optimized plan is executed on synthetic data by the numpy hash-join
engine; results are cross-checked against a GOO plan for semantic equality.

    PYTHONPATH=src python examples/query_service.py [--queries 8]
        [--devices 4] [--pipeline] [--cache-file plans.plancache]
"""
import argparse
import os
import time

EXACT_LIMIT = 14           # CPU-container budget; 25 on the paper's GPU
EXACT_LIMIT_LATTICE = 18   # with a mesh: lattice flights shard one query's
                           # lane space, so exact DP reaches further


def optimize_stream(graphs, cache, devices=None, pipeline=None, policy=None,
                    budget_s=None):
    """Optimize the whole stream: exact-tier queries through the streaming
    service (admission-controlled flights), large queries through UnionDP;
    ``devices`` shards both batched tiers, ``pipeline`` overlaps host and
    device work inside every engine.  With a ``policy.PolicyTable`` the
    static exact limit is replaced by the learned one
    (``policy.exact_limit``: the largest observed NMAX bucket whose
    wall-per-query EMA fits ``budget_s``) and both tiers learn their
    dispatch from flight telemetry.  Returns (results, StreamReport)."""
    from repro.core import service
    from repro.core.config import OptimizerConfig
    from repro.heuristics import uniondp
    results = [None] * len(graphs)
    limit = EXACT_LIMIT_LATTICE if devices else EXACT_LIMIT
    if policy is not None and budget_s is not None:
        limit = policy.exact_limit(limit, budget_s)
    exact_idx = [i for i, g in enumerate(graphs) if g.n <= limit]
    report = None
    if exact_idx:
        cfg = OptimizerConfig(cache=cache, devices=devices,
                              pipeline=pipeline, policy=policy)
        rs, report = service.optimize_stream(
            [graphs[i] for i in exact_idx], config=cfg)
        for i, r in zip(exact_idx, rs):
            results[i] = r
    for i, g in enumerate(graphs):
        if results[i] is None:
            results[i] = uniondp.solve(g, k=10, devices=devices,
                                       pipeline=pipeline, policy=policy)
    return results, report


def load_cache(path):
    from repro.core.plancache import PlanCache
    if path and os.path.exists(path):
        cache = PlanCache.load(path)
        state = "stale, invalidated" if cache.stale_load else \
            f"{len(cache)} entries"
        print(f"plan cache: loaded {path} ({state})")
        return cache
    return PlanCache()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--queries", type=int, default=6)
    ap.add_argument("--devices", type=int, default=None,
                    help="shard batched passes over N devices (CPU devices "
                         "are emulated when needed)")
    ap.add_argument("--pipeline", action="store_true",
                    help="pipelined engines: overlap host compaction with "
                         "device evaluation (bit-identical plans)")
    ap.add_argument("--cache-file", type=str, default=None,
                    help="persist the plan cache here across service runs")
    ap.add_argument("--explain", action="store_true",
                    help="print the chosen partition boundaries and "
                         "per-round re-optimization costs for the first "
                         "UnionDP-tier query")
    args = ap.parse_args()
    # before the first jax import: backends read XLA_FLAGS exactly once
    from repro.hostdev import ensure_compile_cache, ensure_host_devices
    ensure_host_devices(args.devices)
    ensure_compile_cache()

    from repro.core.plan import validate_plan
    from repro.execution import executor as ex
    from repro.heuristics import goo
    from repro.workloads import generators as gen

    sizes = [10, 12, 16, 24, 40, 56][: args.queries] + \
            [12] * max(0, args.queries - 6)
    # the stall-restarting walk reaches every size up to the full schema;
    # disjoint seed windows keep stream entries distinct (no fake cache hits)
    graphs = [gen.musicbrainz_query(n, seed=100 + 50 * qi)
              for qi, n in enumerate(sizes)]
    cache = load_cache(args.cache_file)

    t0 = time.perf_counter()
    stream, report = optimize_stream(graphs, cache, devices=args.devices,
                                     pipeline=args.pipeline or None)
    total_opt = time.perf_counter() - t0

    total_exec = 0.0
    for qi, (g, res) in enumerate(zip(graphs, stream)):
        validate_plan(res.plan, g)

        data = ex.generate_data(g, max_rows=300, seed=qi)
        out, exec_s = ex.execute_timed(res.plan, g, data)
        # semantic cross-check vs an independently derived plan
        ref = ex.execute(goo.solve(g).plan, g, data)
        assert out.canonical().shape == ref.canonical().shape
        assert (out.canonical() == ref.canonical()).all()

        total_exec += exec_s
        print(f"Q{qi}: n={g.n:3d} algo={res.algorithm:14s} "
              f"cost={res.cost:10.4g} exec={1e3*exec_s:6.1f}ms rows={out.count}")
    if args.explain:
        for qi, (g, res) in enumerate(zip(graphs, stream)):
            if "partitions" not in res.info:
                continue               # exact-tier query: no partitioning
            print(f"\nexplain Q{qi} (n={g.n}, {res.algorithm}):")
            for rnd, groups in enumerate(res.info["partitions"]):
                names = ["{" + ",".join(g.names[v] for v in gr) + "}"
                         for gr in sorted(groups, key=len, reverse=True)]
                print(f"  round {rnd}: {len(groups)} partitions  "
                      + " ".join(names))
            rc = res.info["round_costs"]
            print("  re-optimization: " + " -> ".join(f"{c:.6g}" for c in rc)
                  + (f"  ({len(rc) - 1} accepted pass"
                     + ("es" if len(rc) != 2 else "") + ")"))
            break                      # one worked example is the contract
    if report is not None and report.flights:
        # the engines honor REPRO_PIPELINE when --pipeline is absent; label
        # the mode that actually ran, not just the flag
        pipelined = args.pipeline or os.environ.get("REPRO_PIPELINE") == "1"
        print(f"\nflights ({'pipelined' if pipelined else 'synchronous'} "
              "engines, finalize overlapped):")
        for f in report.flights:
            tag = " lattice" if f.lattice else ""
            print(f"  (nmax={f.nmax:2d}, {f.space:12s}) x{len(f.queries)} "
                  f"wall={1e3*f.wall_s:7.1f}ms "
                  f"finalize={1e3*f.finalize_s:6.1f}ms{tag}")
        pct = report.latency_percentiles()
        print("exact-tier latency: " +
              " ".join(f"p{p}={1e3*v:.1f}ms" for p, v in pct.items()))
    print(f"\nservice done: {len(sizes)} queries, "
          f"opt {total_opt:.2f}s (streamed flights), exec {total_exec:.2f}s, "
          f"plan cache {cache.stats.hits} hits / {cache.stats.misses} misses")
    if args.cache_file:
        cache.save(args.cache_file)
        print(f"plan cache: saved {len(cache)} entries -> {args.cache_file}")


if __name__ == "__main__":
    main()
