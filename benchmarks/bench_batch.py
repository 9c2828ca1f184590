"""Multi-query throughput + lane-space accounting: batched vs sequential.

Streams of mixed 8-14-relation MusicBrainz-like queries (the query_service
regime; PK-FK random walks, so the stream is tree-heavy/sparse) are
optimized three ways after a warm-up pass that amortizes XLA compilation:

  * query-by-query through ``engine.optimize`` (sequential baseline);
  * batched through the DPSUB lane space (``sets x 2^i``);
  * batched through the MPDP lane spaces (``auto``: per-bucket topology
    dispatch into MPDP:Tree ``sets x m`` / MPDP-general block prefix-sum).

Costs are asserted bit-identical across all three; throughput is reported
as queries/sec and enumeration effort as evaluated-lane counts (the paper's
EvaluatedCounter) — on sparse streams the MPDP spaces must evaluate strictly
fewer lanes than batched DPSUB.

``--devices N`` additionally times every batched algorithm sharded over an
N-device ``batch`` mesh *and* over the degenerate 1-device mesh, reporting
aggregate and per-device queries/sec plus the N-vs-1 scaling ratio.  On CPU
the devices are emulated (the flag is parsed before jax initializes, so
``--xla_force_host_platform_device_count`` can be injected); per-query lane
counts are asserted identical to the unsharded batched run — sharding must
change *where* lanes run, never how many.

``--pipeline`` additionally times the pipelined engines (host compaction of
level i+1 overlapped under device evaluate of level i) against the
synchronous path on the same stream: costs must stay bit-identical and the
timed repeats must trigger zero kernel retraces (both gated by
``check_regression.py``); the speedup ratio is reported but never gated —
it measures how host-bound the runner is.

    PYTHONPATH=src python -m benchmarks.bench_batch [--queries 32]
        [--repeat 3] [--smoke] [--devices 4] [--pipeline]
        [--json BENCH_batch.json]

``--uniondp`` additionally runs the **plan-quality** benchmark: skewed
PK-FK streams (MusicBrainz random walks, deep snowflakes; 30-80 relations)
and a uniform-selectivity control stream are optimized with plain GOO,
IDP2, the legacy size-greedy UnionDP (no re-optimization) and the current
cost-aware UnionDP (raw — no GOO floor).  Per-query cost ratios vs GOO and
the geometric-mean improvement of the new partitioner over the legacy one
are recorded; ``check_regression.py`` gates both deterministically
(<= GOO on every query, >= 1.2x geomean improvement on the skewed streams)
plus the sync-vs-pipelined cost equality of the re-optimization loop.

``--lattice`` (requires ``--devices N``) additionally runs the
**intra-query lattice** benchmark: one query's DP lane space sharded over
the mesh (``repro.core.lattice``) on all three spaces — DPSUB on a chain,
MPDP-general on a cycle, and MPDP:Tree on a 17-relation snowflake that the
single-device batched path cannot even admit (``nmax`` cap 16).  Every gate
is deterministic and enforced by ``check_regression.py``: costs bit-identical
to the solo oracle *and* to the degenerate 1-device lattice run, exactly one
collective per committed DP level, zero retraces across the timed repeats.
The frontier speedup vs the solo oracle is reported, never gated.

``--policy`` additionally runs the **learned-policy** benchmark: a
``repro.core.policy.PolicyTable`` learns its (NMAX bucket, lane space)
dispatch from flight telemetry over ``POLICY_WARMUP`` full-stream passes,
is frozen, and the frozen table's dispatch is timed against the static
defaults on the same stream.  ``check_regression.py`` gates the safety
half deterministically — learned costs bit-identical to static, the
policy-off run's lane counts equal to the plain batched run's (the policy
machinery must be a no-op when absent), zero retraces in the timed
repeats — and the throughput half against a conservative noise floor
(the learned dispatch must not *lose* to the static defaults it was
trained against).

``--mixed-joins`` additionally runs the **typed-join** smoke: a
``mixed_joins_stream`` (left/semi/anti/full bridges + explicit m:n
fan-outs) shares one ``optimize_many`` flight with a plain inner-only
stream.  Every gate is deterministic and enforced by
``check_regression.py``: each plan passes the brute-force oracle's
conflict rules (``tests/oracle.py``) and each typed query small enough to
enumerate exhaustively costs within 2 ulp of the true optimum; batched
costs are bit-identical to the solo engine per resolved lane space; the
inner-only queries' per-query evaluated-lane counts in the mixed flight
equal the same queries optimized alone (typed graphs bucket separately —
the inner kernels must be byte-for-byte undisturbed); the flight's total
lane count must not grow over the baseline and the timed repeats must
trigger zero retraces.  Throughput is reported, never gated.

``--json`` writes the machine-readable report consumed by
``benchmarks/check_regression.py`` (the CI bench-regression gate; the
``devices-4`` CI job adds the sharded section to the gated report);
``--smoke`` is the trimmed per-PR CI mode.
"""
from __future__ import annotations

import argparse
import json
import time

BATCH_ALGOS = ("dpsub", "mpdp")


def make_stream(nq: int, seed: int = 0):
    from repro.workloads.generators import mixed_stream
    return mixed_stream(nq, seed)


def _lanes(results):
    return (sum(r.counters.evaluated for r in results),
            sum(r.counters.ccp for r in results))


def bench(nq: int = 32, repeat: int = 3, seed: int = 0,
          devices: int | None = None, pipeline: bool = False,
          uniondp: bool = False, lattice: bool = False,
          policy: bool = False, mixed_joins: bool = False,
          smoke: bool = False) -> dict:
    from repro.core import engine
    graphs = make_stream(nq, seed)

    # warm-up: compile every path on the FULL stream.  Batched compile keys
    # include the bucket's bcap and the sequential general path's keys
    # include per-query statics (pcap, cyc_cap), so warming on a shard would
    # leave some timed runs paying XLA compilation — the warm-up must be
    # symmetric or the speedup (the regression-gate metric) is biased
    for g in graphs:
        engine.optimize(g, "auto")
    for algo in BATCH_ALGOS:
        engine.optimize_many(graphs, algorithm=algo)

    t_seq = []
    seq_costs = None
    for _ in range(repeat):
        t0 = time.perf_counter()
        seq = [engine.optimize(g, "auto") for g in graphs]
        t_seq.append(time.perf_counter() - t0)
        seq_costs = [r.cost for r in seq]
    best_seq = min(t_seq)

    out = {
        "queries": nq,
        "repeat": repeat,
        "seed": seed,
        "seq_s": best_seq,
        "seq_qps": nq / best_seq,
        "algorithms": {},
    }
    for algo in BATCH_ALGOS:
        t_bat = []
        bat = None
        for _ in range(repeat):
            t0 = time.perf_counter()
            bat = engine.optimize_many(graphs, algorithm=algo)
            t_bat.append(time.perf_counter() - t0)
        assert seq_costs == [r.cost for r in bat], \
            f"batched {algo} costs diverged from sequential"
        best = min(t_bat)
        ev, ccp = _lanes(bat)
        out["algorithms"][algo] = {
            "batch_s": best,
            "qps": nq / best,
            "speedup": best_seq / best,
            "evaluated_lanes": ev,
            "ccp_lanes": ccp,
            "spaces": sorted({r.algorithm for r in bat}),
        }
    # the paper's point, as an invariant: MPDP lane spaces prune the
    # enumeration on sparse (tree-heavy) streams
    assert (out["algorithms"]["mpdp"]["evaluated_lanes"]
            < out["algorithms"]["dpsub"]["evaluated_lanes"]), \
        "MPDP lane spaces did not prune vs batched DPSUB"

    if devices and devices > 1:
        out["sharded"] = bench_sharded(graphs, seq_costs, best_seq, repeat,
                                       devices, out["algorithms"])
    if pipeline:
        out["pipeline"] = bench_pipeline(graphs, repeat)
    if policy:
        out["policy"] = bench_policy(graphs, repeat)
    if uniondp:
        out["uniondp_quality"] = bench_uniondp_quality(smoke)
    if lattice:
        out["lattice"] = bench_lattice(devices, repeat)
    if mixed_joins:
        out["mixed_joins"] = bench_mixed_joins(repeat, smoke)
    return out


# exhaustive-oracle ceiling: tests/oracle.py enumerates every ordered CCP of
# every connected subset, so the spot-check stays cheap only up to here
_MIXED_ORACLE_NMAX = 7


def bench_mixed_joins(repeat: int, smoke: bool) -> dict:
    """Typed-join (non-inner + m:n) smoke on the batched engines.

    A ``mixed_joins_stream`` and a plain inner-only ``mixed_stream`` share
    one ``optimize_many`` flight.  Everything gated here is deterministic
    (``check_regression.py``):

      * ``oracle_valid`` — every plan in the flight satisfies the
        brute-force oracle's conflict rules (``tests/oracle.py``, the
        independent TES restatement) plus ``validate_plan``, and each typed
        query with n <= ``_MIXED_ORACLE_NMAX`` costs within 2 ulp of the
        exhaustively enumerated optimum (``oracle_checked`` counts those);
      * ``costs_equal_solo`` — batched costs bit-identical to the solo
        engine on each query's resolved lane space;
      * ``inner_lanes_unchanged`` — the inner queries' *per-query*
        evaluated-lane counts in the mixed flight equal the same queries
        optimized alone: typed graphs bucket separately, so inner flights
        must be byte-for-byte undisturbed by the typed extension;
      * ``evaluated_lanes`` (whole flight) must not grow over the baseline
        and the timed repeats must trigger zero ``retraces``.
    """
    from repro.core import engine
    from repro.core.exec_cache import EXEC
    from repro.core.plan import validate_plan
    from repro.workloads.generators import mixed_joins_stream, mixed_stream
    try:
        from tests import oracle as _oracle     # repo-root checkouts (CI)
    except ImportError:
        _oracle = None

    algo = "mpdp"
    nt, ni = (8, 6) if smoke else (16, 12)
    typed = mixed_joins_stream(nt, seed=0, sizes=(5, 6, 7, 8))
    inner = mixed_stream(ni, seed=1, sizes=(8, 9, 10))
    flight = inner + typed

    # warm every path the section times or compares against
    alone = engine.optimize_many(inner, algorithm=algo)
    engine.optimize_many(flight, algorithm=algo)
    rs = engine.optimize_many(flight, algorithm=algo)
    solo = [engine.optimize(g, r.algorithm.replace("batch_", ""))
            for g, r in zip(flight, rs)]

    compiles0 = EXEC.total()
    t_bat = []
    for _ in range(repeat):
        t0 = time.perf_counter()
        rs = engine.optimize_many(flight, algorithm=algo)
        t_bat.append(time.perf_counter() - t0)
    retraces = EXEC.total() - compiles0

    # recorded, not asserted (same convention as bench_pipeline): failures
    # must land in the JSON report so check_regression can gate them
    costs_equal = all(s.cost == r.cost for s, r in zip(solo, rs))
    if not costs_equal:
        print("# WARNING: mixed-joins batched costs diverged from solo")
    lanes_alone = [r.counters.evaluated for r in alone]
    lanes_mixed = [r.counters.evaluated for r in rs[:len(inner)]]
    inner_unchanged = lanes_alone == lanes_mixed
    if not inner_unchanged:
        print("# WARNING: inner-only lane counts perturbed by typed flight")
    valid, checked = True, 0
    for g, r in zip(flight, rs):
        try:
            validate_plan(r.plan, g)
        except AssertionError:
            valid = False
        if _oracle is not None:
            valid = valid and _oracle.plan_valid(g, r.plan)
            if g.typed and g.n <= _MIXED_ORACLE_NMAX:
                oc, _ = _oracle.solve(g)
                valid = valid and _oracle.ulp_diff(r.cost, oc) <= 2
                checked += 1
    if not valid:
        print("# WARNING: a mixed-joins plan failed the oracle spot-check")
    ev, ccp = _lanes(rs)
    best = min(t_bat)
    return {
        "algorithm": algo,
        "typed_queries": nt,
        "inner_queries": ni,
        "batch_s": best,
        "qps": len(flight) / best,
        "evaluated_lanes": ev,
        "ccp_lanes": ccp,
        "spaces": sorted({r.algorithm for r in rs}),
        "costs_equal_solo": costs_equal,
        "inner_lanes_unchanged": inner_unchanged,
        "oracle_valid": valid,
        "oracle_checked": checked,
        "retraces": retraces,
    }


# (space, generator kind, n) — one case per lane space; the snowflake is the
# frontier case: nmax_bucket(17) = 18 > the batched cap of 16, so only the
# lattice path can solve it exactly
_LATTICE_CASES = [("dpsub", "chain", 7),
                  ("mpdp_general", "cycle", 7),
                  ("mpdp_tree", "snow", 17)]


def _lattice_graph(kind: str, n: int):
    from repro.workloads import generators as gen
    if kind == "chain":
        return gen.chain(n, seed=1)
    if kind == "cycle":
        return gen.cycle(n, seed=2)
    return gen.snowflake(n, seed=3)


def bench_lattice(devices: int, repeat: int) -> dict:
    """Intra-query lattice sharding over a D-device mesh, one case per lane
    space (``_LATTICE_CASES``).

    Everything gated here is deterministic (``check_regression.py``):

      * ``costs_equal_solo`` / ``costs_equal_1dev`` — the D-device lattice
        cost must equal both the solo single-device oracle and the
        degenerate 1-device lattice run, bit-for-bit (the lane partition
        must relocate work, never change results);
      * ``collectives_ok`` — each run dispatches exactly one
        ``min_left_commit`` exchange per committed DP level (``n - 1``),
        cross-checked against the host-side ``collectives.STATS`` counter
        (a hot-path collective would have to go through that module);
      * ``retraces`` — the timed repeats must hit the executable cache
        (zero compiles after warm-up).

    The frontier case's speedup vs the solo oracle is reported, never
    gated; on the 17-relation snowflake the solo comparison is only
    possible at all because the unbatched oracle replans level-by-level —
    the *batched* path rejects n > 16 outright.
    """
    from repro.core import engine
    from repro.core.exec_cache import EXEC
    from repro.core.lattice import LatticeShardedEngine, lattice_bucket
    from repro.distributed import collectives as coll

    out: dict = {"devices": devices, "cases": [],
                 "costs_equal_solo": True, "costs_equal_1dev": True,
                 "collectives_ok": True, "retraces": 0}
    # warm + oracle phase: every solo/1-device/D-device compile lands here
    # so the timed repeats below can be gated on zero retraces
    oracle = {}
    for space, kind, n in _LATTICE_CASES:
        g = _lattice_graph(kind, n)
        engine.optimize(g, "auto")                     # solo compile
        t0 = time.perf_counter()
        solo = engine.optimize(g, "auto")
        solo_s = time.perf_counter() - t0
        r1 = LatticeShardedEngine(g, 1, algorithm=space).run()[0]
        LatticeShardedEngine(g, devices, algorithm=space).run()
        oracle[(space, kind, n)] = (g, solo.cost, solo_s, r1.cost)
    compiles0 = EXEC.total()
    for space, kind, n in _LATTICE_CASES:
        g, solo_cost, solo_s, cost_1dev = oracle[(space, kind, n)]
        commits0 = coll.STATS.snapshot()
        best, eng, rd = float("inf"), None, None
        for _ in range(repeat):
            t0 = time.perf_counter()
            eng = LatticeShardedEngine(g, devices, algorithm=space)
            rd = eng.run()[0]
            best = min(best, time.perf_counter() - t0)
        commits = coll.STATS.snapshot() - commits0
        levels = g.n - 1
        ok = eng.collectives == levels and commits == repeat * levels
        out["costs_equal_solo"] = bool(out["costs_equal_solo"]
                                       and rd.cost == solo_cost)
        out["costs_equal_1dev"] = bool(out["costs_equal_1dev"]
                                       and rd.cost == cost_1dev)
        out["collectives_ok"] = bool(out["collectives_ok"] and ok)
        out["cases"].append({
            "space": space, "kind": kind, "n": n,
            "nmax": lattice_bucket(n),
            "cost": rd.cost,
            "wall_s": best,
            "solo_s": solo_s,
            "speedup_vs_solo": solo_s / best,
            "collectives": eng.collectives,
            "levels": levels,
            "evaluated_lanes": rd.counters.evaluated,
        })
    out["retraces"] = EXEC.total() - compiles0
    if not (out["costs_equal_solo"] and out["costs_equal_1dev"]):
        print("# WARNING: lattice costs diverged (solo/1-device mismatch)")
    return out


def bench_pipeline(graphs, repeat) -> dict:
    """Pipelined vs synchronous batched engines on the standard stream.

    Two deterministic invariants are recorded for the regression gate
    (``check_regression.py``): the pipelined costs must equal the
    synchronous ones bit-for-bit, and the timed repeats must trigger **zero**
    kernel retraces (every bucket shape was compiled by the warm-up; the
    executable cache must serve every later engine).  The speedup ratio is
    reported but never gated — it measures how host-bound the runner is
    (a 2-core CI container shows ~1x; wide hosts with the device saturated
    by eval chunks show the real overlap win).
    """
    from repro.core import engine
    from repro.core.exec_cache import EXEC
    algo = "mpdp"
    # warm both modes: the pipelined driver dispatches the same kernels on
    # the same chunk grids, so this is where every compile must land
    engine.optimize_many(graphs, algorithm=algo, pipeline=False)
    engine.optimize_many(graphs, algorithm=algo, pipeline=True)
    compiles0 = EXEC.total()
    t_sync, sync_costs = [], None
    for _ in range(repeat):
        t0 = time.perf_counter()
        rs = engine.optimize_many(graphs, algorithm=algo, pipeline=False)
        t_sync.append(time.perf_counter() - t0)
        sync_costs = [r.cost for r in rs]
    t_pipe, pipe_costs = [], None
    for _ in range(repeat):
        t0 = time.perf_counter()
        rs = engine.optimize_many(graphs, algorithm=algo, pipeline=True)
        t_pipe.append(time.perf_counter() - t0)
        pipe_costs = [r.cost for r in rs]
    # recorded, not asserted: a divergence must still land in the JSON
    # report so check_regression can fail with the gate message instead of
    # this script dying before writing the artifact
    costs_equal = sync_costs == pipe_costs
    if not costs_equal:
        print("# WARNING: pipelined costs diverged from synchronous")
    retraces = EXEC.total() - compiles0
    nq = len(graphs)
    return {
        "algorithm": algo,
        "sync_s": min(t_sync),
        "pipe_s": min(t_pipe),
        "qps": nq / min(t_pipe),
        "qps_sync": nq / min(t_sync),
        "speedup_vs_sync": min(t_sync) / min(t_pipe),
        "costs_equal": costs_equal,
        "retraces": retraces,
    }


# full-stream learning passes before the table is frozen: every (nmax,
# space) bucket must clear its explore phase (up to 3 candidate arms x
# EXPLORE_FLIGHTS flights on tree buckets) and settle its wall-per-query
# EMAs, so the frozen table exploits a converged estimate, not a coin flip
POLICY_WARMUP = 8


def bench_policy(graphs, repeat) -> dict:
    """Learned-policy dispatch vs the static defaults on the same stream.

    A fresh ``PolicyTable`` learns over ``POLICY_WARMUP`` full-stream
    passes (exploring every candidate lane space per bucket, folding
    flight telemetry into its EMAs), is frozen, and one uncounted frozen
    pass compiles whatever (space, chunk, pend-window) configuration the
    table now chooses.  The timed repeats then interleave nothing new:

      * ``costs_equal`` — learned dispatch and static dispatch must return
        bit-identical costs (a policy can only move lanes between spaces
        that enumerate the same CCP minima, never change plans);
      * ``off_evaluated_lanes`` — the policy-off run timed here must match
        the plain batched run's lane count exactly (``check_regression``
        compares it to the report's ``algorithms.mpdp`` figure: passing
        ``policy=None`` must be byte-for-byte the static path);
      * ``retraces`` — the timed repeats must hit the executable cache
        (the frozen table replays one fixed dispatch; zero compiles);
      * ``speedup_vs_static`` — gated against a conservative noise floor:
        the learned dispatch must not lose to the defaults it was trained
        against.  On CPU containers the win comes from buckets where
        batched DPSUB out-runs the MPDP spaces wall-clock despite
        evaluating more lanes; the learned lane counts are reported, never
        gated (trading lanes for wall time is the point).
    """
    from repro.core import engine
    from repro.core.exec_cache import EXEC
    from repro.core.policy import PolicyTable
    algo = "mpdp"
    # static warm: the defaults' compiles land here (bench() already warmed
    # this path, but keep the section self-contained)
    engine.optimize_many(graphs, algorithm=algo)

    pol = PolicyTable()
    learn_costs_equal = True
    ref_costs = None
    for _ in range(POLICY_WARMUP):
        rs = engine.optimize_many(graphs, algorithm=algo, policy=pol)
        costs = [r.cost for r in rs]
        if ref_costs is None:
            ref_costs = costs
        learn_costs_equal = learn_costs_equal and costs == ref_costs
    pol.freeze()
    # uncounted frozen pass: compiles the chosen configuration so the timed
    # repeats below can be gated on zero retraces
    engine.optimize_many(graphs, algorithm=algo, policy=pol)

    compiles0 = EXEC.total()
    t_off, off = [], None
    for _ in range(repeat):
        t0 = time.perf_counter()
        off = engine.optimize_many(graphs, algorithm=algo)
        t_off.append(time.perf_counter() - t0)
    t_on, on = [], None
    for _ in range(repeat):
        t0 = time.perf_counter()
        on = engine.optimize_many(graphs, algorithm=algo, policy=pol)
        t_on.append(time.perf_counter() - t0)
    retraces = EXEC.total() - compiles0
    off_costs = [r.cost for r in off]
    on_costs = [r.cost for r in on]
    # recorded, not asserted: a divergence must still land in the JSON
    # report so check_regression fails with the gate message instead of
    # this script dying before writing the artifact
    costs_equal = (off_costs == on_costs == ref_costs
                   and learn_costs_equal)
    if not costs_equal:
        print("# WARNING: learned-policy costs diverged from static")
    off_ev, off_ccp = _lanes(off)
    on_ev, on_ccp = _lanes(on)
    nq = len(graphs)
    return {
        "algorithm": algo,
        "warmup_passes": POLICY_WARMUP,
        "costs_equal": costs_equal,
        "off_s": min(t_off),
        "on_s": min(t_on),
        "qps": nq / min(t_on),
        "qps_static": nq / min(t_off),
        "speedup_vs_static": min(t_off) / min(t_on),
        "off_evaluated_lanes": off_ev,
        "off_ccp_lanes": off_ccp,
        "on_evaluated_lanes": on_ev,
        "on_ccp_lanes": on_ccp,
        "spaces_static": sorted({r.algorithm for r in off}),
        "spaces_learned": sorted({r.algorithm for r in on}),
        "retraces": retraces,
        "table": pol.summary(),
    }


UNIONDP_K = 10
# deterministic quality gates, written into every report so a baseline
# refresh (commit the fresh report verbatim) preserves them: <= GOO per
# query up to the f32 temp-table-vs-canonical epsilon, and the geomean
# improvement floor over the legacy size-greedy partitioner
UNIONDP_GOO_GATE = 1.002
UNIONDP_IMPROVEMENT_GATE = 1.2

# (tag, generator kind, n) — deterministic streams; "mb" is the skewed
# PK-FK MusicBrainz random walk (schema caps at 56 tables), "snow" the deep
# skewed snowflake (reaches 80), "mbu" the uniform-selectivity control
# (same walks, sel drawn log-uniform instead of 1/card(PK))
_UNIONDP_SKEWED = [("mb", 30), ("mb", 40), ("mb", 56),
                   ("snow", 30), ("snow", 60), ("snow", 80)]
_UNIONDP_SKEWED_SMOKE = [("mb", 30), ("mb", 56), ("snow", 60)]
_UNIONDP_UNIFORM = [("mbu", 30), ("mbu", 40)]
_UNIONDP_UNIFORM_SMOKE = [("mbu", 30)]


def _uniondp_graph(kind: str, n: int):
    from repro.workloads import generators as gen
    if kind == "mb":
        return gen.musicbrainz_query(n, seed=200 + n)
    if kind == "mbu":
        return gen.musicbrainz_query(n, seed=300 + n, pk_fk=False)
    return gen.snowflake(n, seed=n)


def bench_uniondp_quality(smoke: bool) -> dict:
    """Plan-quality section: raw UnionDP (cost-aware partitions +
    re-optimization, no GOO floor) vs plain GOO, IDP2 and the legacy
    size-greedy partitioner on skewed + uniform large-query streams.

    Everything here is *deterministic* (fixed generator seeds, no timing),
    so ``check_regression.py`` gates the ratios exactly: every query's
    ``new/goo`` must stay under the baseline's ``goo_gate`` and the
    geometric-mean ``old/new`` improvement on the skewed streams over
    ``improvement_gate``.  The sync-vs-pipelined equality of the first
    skewed query is recorded as ``pipeline_costs_equal`` (same gate idea as
    the throughput section's: the re-optimization loop must not perturb
    results when the engines overlap host and device work).
    """
    import math
    from repro.heuristics import goo, idp, uniondp

    skewed = _UNIONDP_SKEWED_SMOKE if smoke else _UNIONDP_SKEWED
    uniform = _UNIONDP_UNIFORM_SMOKE if smoke else _UNIONDP_UNIFORM
    out: dict = {"k": UNIONDP_K, "queries": [], "pipeline_costs_equal": True,
                 "goo_gate": UNIONDP_GOO_GATE,
                 "improvement_gate": UNIONDP_IMPROVEMENT_GATE}
    imp_logs = []
    for stream, cases in (("skewed", skewed), ("uniform", uniform)):
        for kind, n in cases:
            g = _uniondp_graph(kind, n)
            goo_c = goo.solve(g).cost
            idp_c = idp.solve(g, k=UNIONDP_K).cost
            old_c = uniondp.solve(g, k=UNIONDP_K, partition="size",
                                  reopt_rounds=0).cost
            new = uniondp.solve(g, k=UNIONDP_K)
            out["queries"].append({
                "stream": stream, "kind": kind, "n": n,
                "goo": goo_c, "idp2": idp_c, "old": old_c, "new": new.cost,
                "ratio_vs_goo": new.cost / goo_c,
                "ratio_vs_idp2": new.cost / idp_c,
                "improvement_vs_size": old_c / new.cost,
                # accepted re-optimization passes (round_costs also holds
                # the seed cost, hence the -1)
                "reopt_passes": len(new.info["round_costs"]) - 1,
            })
            if stream == "skewed":
                imp_logs.append(math.log(old_c / new.cost))
    # sync-vs-pipelined equality through partition rounds + reopt passes
    g = _uniondp_graph(*skewed[0])
    sync = uniondp.solve(g, k=UNIONDP_K)
    pipe = uniondp.solve(g, k=UNIONDP_K, pipeline=True)
    out["pipeline_costs_equal"] = (
        sync.cost == pipe.cost
        and sync.info["round_costs"] == pipe.info["round_costs"])
    out["worst_ratio_vs_goo"] = max(q["ratio_vs_goo"] for q in out["queries"])
    out["geomean_improvement_skewed"] = math.exp(sum(imp_logs) / len(imp_logs))
    return out


def bench_sharded(graphs, seq_costs, best_seq, repeat, devices,
                  unsharded) -> dict:
    """Time each batched algorithm over a D-device mesh and the degenerate
    1-device mesh (same shard_map machinery, so the N-vs-1 ratio isolates
    actual device parallelism from wrapper overhead)."""
    from repro.core import engine
    nq = len(graphs)
    sh: dict = {"devices": devices, "algorithms": {}}
    for algo in BATCH_ALGOS:
        per_mesh, lanes_at = {}, {}
        for d in (1, devices):
            engine.optimize_many(graphs, algorithm=algo, devices=d)  # warm
            t_bat, bat = [], None
            for _ in range(repeat):
                t0 = time.perf_counter()
                bat = engine.optimize_many(graphs, algorithm=algo, devices=d)
                t_bat.append(time.perf_counter() - t0)
            assert seq_costs == [r.cost for r in bat], \
                f"sharded {algo} (devices={d}) costs diverged from sequential"
            lanes_at[d], _ = _lanes(bat)
            assert lanes_at[d] == unsharded[algo]["evaluated_lanes"], \
                (f"sharded {algo} (devices={d}) lane count changed: "
                 f"{lanes_at[d]} != {unsharded[algo]['evaluated_lanes']}")
            per_mesh[d] = min(t_bat)
        best = per_mesh[devices]
        sh["algorithms"][algo] = {
            "batch_s": best,
            "batch_s_1dev": per_mesh[1],
            "qps": nq / best,
            "qps_per_device": nq / best / devices,
            "speedup": best_seq / best,
            "scaling_vs_1dev": per_mesh[1] / best,
            # the *measured* sharded count, NOT a copy of the unsharded
            # figure: check_regression's lane-equality gate compares the two
            # report fields, so copying would make that gate vacuous
            "evaluated_lanes": lanes_at[devices],
        }
    return sh


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--queries", type=int, default=32)
    ap.add_argument("--repeat", type=int, default=3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--devices", type=int, default=None,
                    help="also bench optimize_many sharded over N devices "
                         "(emulated on CPU when fewer exist)")
    ap.add_argument("--pipeline", action="store_true",
                    help="also bench pipelined vs synchronous engines "
                         "(result-equality + zero-retrace gate; speedup "
                         "reported, never gated)")
    ap.add_argument("--uniondp", action="store_true",
                    help="also bench UnionDP plan quality on skewed + "
                         "uniform 30-80-relation streams (all gates "
                         "deterministic: <= GOO per query, geomean "
                         "improvement vs the size-greedy partitioner)")
    ap.add_argument("--lattice", action="store_true",
                    help="also bench intra-query lattice sharding (one "
                         "query's lane space over the mesh; all gates "
                         "deterministic: costs equal solo + 1-device, one "
                         "collective per level, zero retraces); needs "
                         "--devices >= 2")
    ap.add_argument("--policy", action="store_true",
                    help="also bench the learned PolicyTable dispatch vs "
                         "the static defaults (costs bit-identical + "
                         "policy-off lane identity + zero-retrace gates; "
                         "throughput gated against a noise floor)")
    ap.add_argument("--mixed-joins", action="store_true",
                    help="also bench the typed-join (non-inner + m:n) "
                         "stream sharing a flight with inner queries (all "
                         "gates deterministic: oracle-valid plans, costs "
                         "equal solo, inner lane counts unchanged, zero "
                         "retraces)")
    ap.add_argument("--smoke", action="store_true",
                    help="trimmed CI mode (16 queries, min-of-2 repeats)")
    ap.add_argument("--json", type=str, default=None,
                    help="write the machine-readable report here")
    args = ap.parse_args()
    if args.lattice and (args.devices or 0) < 2:
        ap.error("--lattice shards one query's lane space over a mesh; "
                 "pass --devices N with N >= 2")
    # must land before the first jax import: backends read XLA_FLAGS once
    from repro.hostdev import ensure_compile_cache, ensure_host_devices
    ensure_host_devices(args.devices)
    ensure_compile_cache()
    nq, repeat = args.queries, args.repeat
    if args.smoke:
        # min-of-2: a single repeat makes the regression gate hostage to
        # one noisy-neighbor blip on a shared CI runner
        nq, repeat = min(nq, 16), 2
    r = bench(nq, repeat, args.seed, devices=args.devices,
              pipeline=args.pipeline, uniondp=args.uniondp,
              lattice=args.lattice, policy=args.policy,
              mixed_joins=args.mixed_joins, smoke=args.smoke)
    print("mode,queries,wall_s,queries_per_s,evaluated_lanes")
    print(f"sequential,{r['queries']},{r['seq_s']:.3f},{r['seq_qps']:.2f},-")
    for algo, a in r["algorithms"].items():
        print(f"batched[{algo}],{r['queries']},{a['batch_s']:.3f},"
              f"{a['qps']:.2f},{a['evaluated_lanes']}")
    if "sharded" in r:
        d = r["sharded"]["devices"]
        for algo, a in r["sharded"]["algorithms"].items():
            print(f"sharded[{algo}]@{d}dev,{r['queries']},{a['batch_s']:.3f},"
                  f"{a['qps']:.2f},{a['evaluated_lanes']}")
    m = r["algorithms"]["mpdp"]
    dp = r["algorithms"]["dpsub"]
    print(f"# mpdp speedup {m['speedup']:.2f}x (costs bit-identical); "
          f"lanes {m['evaluated_lanes']} vs dpsub {dp['evaluated_lanes']} "
          f"({dp['evaluated_lanes'] / max(m['evaluated_lanes'], 1):.1f}x fewer)")
    if "sharded" in r:
        d = r["sharded"]["devices"]
        for algo, a in r["sharded"]["algorithms"].items():
            print(f"# sharded[{algo}] {d} devices: {a['qps']:.2f} q/s "
                  f"aggregate ({a['qps_per_device']:.2f} q/s/device), "
                  f"{a['scaling_vs_1dev']:.2f}x vs 1-device mesh "
                  f"(costs bit-identical, lane counts unchanged)")
    if "pipeline" in r:
        p = r["pipeline"]
        print(f"pipelined[{p['algorithm']}],{r['queries']},{p['pipe_s']:.3f},"
              f"{p['qps']:.2f},-")
        print(f"# pipelined[{p['algorithm']}] {p['speedup_vs_sync']:.2f}x vs "
              f"synchronous ({p['qps']:.2f} vs {p['qps_sync']:.2f} q/s), "
              f"costs bit-identical, {p['retraces']} retraces in timed runs")
    if "policy" in r:
        p = r["policy"]
        print(f"policy[{p['algorithm']}],{r['queries']},{p['on_s']:.3f},"
              f"{p['qps']:.2f},{p['on_evaluated_lanes']}")
        print(f"# policy[{p['algorithm']}] {p['speedup_vs_static']:.2f}x vs "
              f"static defaults ({p['qps']:.2f} vs {p['qps_static']:.2f} "
              f"q/s) after {p['warmup_passes']} learning passes; costs "
              f"bit-identical: {p['costs_equal']}, lanes "
              f"{p['on_evaluated_lanes']} (static {p['off_evaluated_lanes']}),"
              f" {p['retraces']} retraces in timed runs; table "
              f"{p['table']['entries']} entries / "
              f"{p['table']['space_overrides']} space overrides")
    if "lattice" in r:
        lat = r["lattice"]
        d = lat["devices"]
        for c in lat["cases"]:
            print(f"lattice[{c['space']}]@{d}dev,n={c['n']},"
                  f"{c['wall_s']:.3f},{c['speedup_vs_solo']:.2f}x vs solo,"
                  f"{c['evaluated_lanes']}")
        front = max(lat["cases"], key=lambda c: c["n"])
        print(f"# lattice {d} devices: costs equal solo "
              f"{lat['costs_equal_solo']}, equal 1-dev "
              f"{lat['costs_equal_1dev']}, one collective per level "
              f"{lat['collectives_ok']}, {lat['retraces']} retraces; "
              f"frontier n={front['n']} (nmax {front['nmax']} > batched cap) "
              f"solved in {front['wall_s']:.2f}s, "
              f"{front['speedup_vs_solo']:.2f}x vs solo oracle")
    if "mixed_joins" in r:
        mj = r["mixed_joins"]
        print(f"mixed-joins[{mj['algorithm']}],"
              f"{mj['inner_queries']}+{mj['typed_queries']}t,"
              f"{mj['batch_s']:.3f},{mj['qps']:.2f},{mj['evaluated_lanes']}")
        print(f"# mixed-joins oracle valid {mj['oracle_valid']} "
              f"(exhaustive on {mj['oracle_checked']} queries), costs equal "
              f"solo {mj['costs_equal_solo']}, inner lanes unchanged "
              f"{mj['inner_lanes_unchanged']}, {mj['retraces']} retraces; "
              f"spaces {','.join(mj['spaces'])}")
    if "uniondp_quality" in r:
        u = r["uniondp_quality"]
        print("stream,kind,n,new/goo,new/idp2,old/new,reopt_passes")
        for q in u["queries"]:
            print(f"{q['stream']},{q['kind']},{q['n']},"
                  f"{q['ratio_vs_goo']:.4f},{q['ratio_vs_idp2']:.4f},"
                  f"{q['improvement_vs_size']:.2f},{q['reopt_passes']}")
        print(f"# uniondp quality (k={u['k']}): worst vs goo "
              f"{u['worst_ratio_vs_goo']:.4f}x, geomean improvement vs "
              f"size-greedy {u['geomean_improvement_skewed']:.2f}x (skewed "
              f"streams), pipelined costs equal: {u['pipeline_costs_equal']}")
    if args.json:
        with open(args.json, "w") as f:
            json.dump(r, f, indent=2, sort_keys=True)
        print(f"# wrote {args.json}")


if __name__ == "__main__":
    main()
