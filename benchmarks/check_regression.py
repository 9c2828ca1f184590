"""CI gate: compare a fresh ``bench_batch --json`` report to the committed
baseline and fail on throughput or lane-space regressions.

Two checks per batched algorithm:

  * **lane counts** (deterministic): ``evaluated_lanes`` must not grow over
    the baseline — a growth means an enumeration-space regression (e.g. a
    bucket silently falling back from the MPDP spaces to DPSUB).
  * **throughput** (noisy): the batched *speedup over the same run's
    sequential baseline* must not regress more than ``--tolerance`` (default
    25%).  Speedup is self-normalizing — absolute queries/sec depends on the
    CI machine, the within-run ratio does not — so the 25% gate tracks real
    pipeline regressions instead of runner lottery.  Because the ratio still
    shifts with core count (the general lanes' phase A is host-serialized),
    a baseline entry may carry an explicit ``speedup_floor`` that replaces
    the computed ``speedup * (1 - tolerance)`` floor with a conservative
    hand-picked cross-machine bound.

Also re-asserts the structural invariant that the MPDP lane spaces evaluate
fewer lanes than batched DPSUB on the (tree-heavy) benchmark stream.

When the baseline carries a ``sharded`` section (from ``bench_batch
--devices N``) and the current report was produced with ``--devices``, the
device path is gated too: per-query lane counts must **equal** the
unsharded run's (sharding moves lanes across devices, it never changes how
many there are — any drift means the shard decode broke) and the sharded
speedup over the same run's sequential baseline must clear its floor.  The
``scaling_vs_1dev`` ratio is reported but never gated — it measures the
runner's core count, not the code.  A current report without a ``sharded``
section skips these checks with a note (the single-device CI jobs bench
without ``--devices``; the ``devices-4`` job provides the gating run).

When the baseline carries a ``mixed_joins`` section (from ``bench_batch
--mixed-joins``), the typed-join path is gated on its deterministic
invariants: every plan in the mixed (inner + non-inner/m:n) flight passes
the brute-force oracle's conflict rules, the exhaustive cost spot-check
covers at least as many small typed queries as the baseline, batched costs
equal the solo engine bit-for-bit, the inner-only queries' per-query lane
counts are untouched by typed graphs sharing the flight, the flight's
total lane count does not grow, and the timed repeats trigger zero
retraces.  Throughput is reported, never gated.

When the baseline carries a ``pipeline`` section (from ``bench_batch
--pipeline``), the pipelined path is gated on its two deterministic
invariants: pipelined costs **equal** the synchronous run's bit-for-bit, and
the timed repeats trigger **zero** kernel retraces (the executable cache
must serve every repeated bucket shape).  The pipelined-vs-sync speedup is
reported, never gated — on a 2-core CI container the overlap has nothing to
hide behind.

When the baseline carries a ``policy`` section (from ``bench_batch
--policy``), the learned-dispatch path is gated on three deterministic
invariants plus one conservative throughput floor: learned costs must equal
the static defaults' bit-for-bit (a policy may move lanes between spaces,
never change plans), the policy-off run's lane count must equal the plain
batched run's (``policy=None`` must be byte-for-byte the static path), the
timed repeats must trigger zero retraces (a frozen table replays one fixed
dispatch), and the learned-vs-static speedup must clear the baseline's
``speedup_floor`` (default 0.95 — the learned dispatch must not lose to the
defaults it was trained against; its upside is reported, never gated).

When the baseline carries a ``lattice`` section (from ``bench_batch
--lattice --devices N``), the intra-query lattice path is gated on its
deterministic invariants only: the D-device lattice cost must equal both the
solo oracle's and the 1-device lattice run's bit-for-bit, every run must
dispatch exactly one level-commit collective per committed DP level, and the
timed repeats must trigger zero retraces.  The frontier speedup vs the solo
oracle is reported, never gated.

When the baseline carries a ``uniondp_quality`` section (from ``bench_batch
--uniondp``), the plan-quality gates fire — all fully deterministic (fixed
generator seeds, cost ratios, no timing):

  * every benchmarked query's ``new/goo`` cost ratio must stay at or under
    the baseline's ``goo_gate`` (1 + a small f32 temp-table-vs-canonical
    costing epsilon): raw UnionDP — no GOO floor — must not lose to plain
    GOO on either the skewed or the uniform streams;
  * the geometric-mean improvement of the cost-aware partitioner +
    re-optimization over the legacy size-greedy partitioner on the *skewed*
    streams must clear the baseline's ``improvement_gate`` (the paper-claim
    half: partitions chosen by estimated cost, not size, are what make the
    divide-and-conquer competitive);
  * ``pipeline_costs_equal`` must be true (the re-optimization loop is
    bit-identical under the pipelined engines).

When the baseline carries a ``daemon`` section (from
``benchmarks/bench_daemon.py``), the cross-process daemon is gated on its
deterministic invariants: every phase's costs bit-identical to the
in-process ``optimize_many`` replay, compile deltas on the warm /
second-process / fresh phases at or under the committed baseline (zero),
at least one cross-client plan-cache hit, and a clean SIGTERM drain.
Open-loop load latency percentiles and shed counts are reported, never
gated.  A report may carry *only* a ``daemon`` section (bench_daemon
output) — all other checks then skip cleanly.

    python benchmarks/check_regression.py BENCH_batch.json \
        benchmarks/BENCH_baseline.json [--tolerance 0.25]

Exit code 0 = no regression; 1 = regression (message on stdout).
"""
from __future__ import annotations

import argparse
import json
import sys


def check(current: dict, baseline: dict, tolerance: float = 0.25) -> list[str]:
    errors: list[str] = []
    # a report may carry only one section (e.g. bench_daemon produces just
    # "daemon"); every per-section check skips cleanly when its section is
    # absent from either side
    for algo, base in (baseline.get("algorithms") or {}).items():
        cur = (current.get("algorithms") or {}).get(algo)
        if cur is None:
            if "algorithms" not in current:
                break                  # daemon-only (or similar) report
            errors.append(f"[{algo}] missing from current report")
            continue
        if cur["evaluated_lanes"] > base["evaluated_lanes"]:
            errors.append(
                f"[{algo}] evaluated lanes grew: {cur['evaluated_lanes']} > "
                f"baseline {base['evaluated_lanes']}")
        floor = base.get("speedup_floor", base["speedup"] * (1.0 - tolerance))
        if cur["speedup"] < floor:
            errors.append(
                f"[{algo}] queries/sec regressed >{tolerance:.0%}: speedup "
                f"{cur['speedup']:.2f}x < {floor:.2f}x "
                f"(baseline {base['speedup']:.2f}x)")
    algos = current.get("algorithms") or {}
    if ("mpdp" in algos and "dpsub" in algos
            and algos["mpdp"]["evaluated_lanes"] >= algos["dpsub"]["evaluated_lanes"]):
        errors.append(
            "mpdp lane spaces no longer prune vs dpsub: "
            f"{algos['mpdp']['evaluated_lanes']} >= "
            f"{algos['dpsub']['evaluated_lanes']}")
    errors += check_sharded(current, baseline, tolerance)
    errors += check_mixed_joins(current, baseline)
    errors += check_pipeline(current, baseline)
    errors += check_policy(current, baseline)
    errors += check_lattice(current, baseline)
    errors += check_uniondp(current, baseline)
    errors += check_daemon(current, baseline)
    errors += check_chaos(current, baseline)
    return errors


def check_chaos(current: dict, baseline: dict) -> list[str]:
    """Deterministic chaos gates (from ``bench_daemon.py --chaos``): under
    the seeded fault plan no request may hang or fail terminally (shed +
    retry must absorb injected worker crashes and stalls), the deadline
    request must return valid degraded plans no worse than GOO, the worker
    supervisor must actually have restarted, and the bounded drain must
    exit clean with a loadable checkpoint."""
    base_c = baseline.get("chaos")
    cur_c = current.get("chaos")
    if base_c is None:
        if cur_c is not None:
            print("note: current report has a chaos section but the "
                  "baseline does not — chaos gates are vacuous until the "
                  "baseline is refreshed with bench_daemon --chaos --json")
        return []
    if cur_c is None:
        print("note: baseline has a chaos section but the current report "
              "was not produced by bench_daemon --chaos; chaos checks "
              "skipped (the chaos-smoke CI job runs the gating "
              "configuration)")
        return []
    errors: list[str] = []
    if cur_c.get("hung", 1) != base_c.get("hung", 0):
        errors.append(
            f"[chaos] hung requests: {cur_c.get('hung')} (every request "
            "must resolve — ok, shed, retried or failed — within its "
            "bound)")
    if cur_c.get("failed", 1) != 0:
        errors.append(
            f"[chaos] {cur_c.get('failed')} request(s) failed terminally "
            "(the retry contract must absorb the injected faults)")
    if cur_c.get("completed", 0) < base_c.get("min_completed", 1):
        errors.append(
            f"[chaos] only {cur_c.get('completed')} request(s) completed "
            f"(< {base_c.get('min_completed', 1)})")
    if cur_c.get("degraded", 0) < base_c.get("min_degraded", 1):
        errors.append(
            f"[chaos] deadline request produced {cur_c.get('degraded')} "
            f"degraded plans (< {base_c.get('min_degraded', 1)}; the "
            "anytime path did not engage)")
    if not cur_c.get("degraded_valid", False):
        errors.append(
            "[chaos] a degraded plan failed validation or cost more than "
            "plain GOO (the degradation ladder must floor at GOO)")
    if cur_c.get("worker_restarts", 0) < base_c.get("min_worker_restarts", 1):
        errors.append(
            f"[chaos] worker restarts {cur_c.get('worker_restarts')} < "
            f"{base_c.get('min_worker_restarts', 1)} (the injected crashes "
            "never exercised the supervisor)")
    if not cur_c.get("drain_clean", False):
        errors.append(
            f"[chaos] unclean bounded drain: exit "
            f"{cur_c.get('drain_exit_code')} / checkpoint "
            f"{cur_c.get('checkpoint_entries')} entries (SIGTERM under "
            "--drain-timeout must checkpoint and exit 0)")
    return errors


def check_daemon(current: dict, baseline: dict) -> list[str]:
    """Deterministic daemon gates (from ``bench_daemon.py``): every phase's
    costs bit-identical to the in-process replay, zero executable compiles
    on the warm / second-process / fresh phases beyond the committed
    baseline deltas, at least one cross-client plan-cache hit from the
    second client process, a clean SIGTERM drain (exit 0 + loadable
    checkpoint), and no JAX backend in the benchmark process while the
    daemon holds the device.  Latency percentiles and shed counts under the open-loop
    Poisson load are reported, never gated."""
    base_d = baseline.get("daemon")
    cur_d = current.get("daemon")
    if base_d is None:
        if cur_d is not None:
            print("note: current report has a daemon section but the "
                  "baseline does not — daemon gates are vacuous until the "
                  "baseline is refreshed with bench_daemon --json")
        return []
    if cur_d is None:
        print("note: baseline has a daemon section but the current report "
              "was not produced by bench_daemon; daemon checks skipped "
              "(the daemon-smoke CI job runs the gating configuration)")
        return []
    errors: list[str] = []
    for phase in ("cold", "warm", "proc2", "fresh"):
        if not cur_d.get(f"costs_equal_{phase}", False):
            errors.append(
                f"[daemon:{phase}] costs diverged from the in-process "
                "optimize_many replay (the daemon may reuse warm state, "
                "never change results)")
    for phase in ("warm", "proc2"):
        allowed = base_d.get(f"{phase}_compile_delta", 0)
        got = cur_d.get(f"{phase}_compile_delta", -1)
        if got > allowed:
            errors.append(
                f"[daemon:{phase}] executable compiles after warmup: "
                f"{got} > baseline {allowed} (warmed bucket shapes must hit "
                "the shared executable cache with zero retraces)")
    if cur_d.get("fresh_retrace_delta", -1) > \
            base_d.get("fresh_retrace_delta", 0):
        errors.append(
            f"[daemon:fresh] warmed bucket shapes re-traced on a fresh "
            f"stream: retrace delta {cur_d.get('fresh_retrace_delta')} > "
            f"baseline {base_d.get('fresh_retrace_delta', 0)}")
    # new-KEY compiles on a fresh stream are legitimate (first compile of a
    # genuinely new bucket shape) but their count is deterministic per
    # stream shape — gate it only when the shapes match
    if cur_d.get("queries") == base_d.get("queries") and \
            cur_d.get("fresh_compile_delta", 0) > \
            base_d.get("fresh_compile_delta", 0):
        errors.append(
            f"[daemon:fresh] new-key compile count grew: "
            f"{cur_d['fresh_compile_delta']} > baseline "
            f"{base_d['fresh_compile_delta']} (bucket-shape quantization "
            "regressed — more shapes now miss the warmed executables)")
    min_hits = base_d.get("min_proc2_cache_hits", 1)
    if cur_d.get("proc2_cache_hits", 0) < min_hits:
        errors.append(
            f"[daemon:proc2] cross-client plan-cache hits "
            f"{cur_d.get('proc2_cache_hits', 0)} < {min_hits} (a second "
            "client on a warm daemon must see the first client's plans)")
    if not cur_d.get("drain_clean", False):
        errors.append(
            f"[daemon:drain] unclean shutdown: exit code "
            f"{cur_d.get('drain_exit_code')} / checkpoint "
            f"{cur_d.get('checkpoint_entries')} entries (SIGTERM must "
            "drain, checkpoint atomically, and exit 0)")
    if not cur_d.get("parent_backend_free", False):
        errors.append(
            "[daemon:device] the benchmark process started a JAX backend "
            "while the daemon was alive (one process per device: only the "
            "daemon may hold it)")
    return errors


def check_lattice(current: dict, baseline: dict) -> list[str]:
    """Deterministic intra-query lattice gates: D-device costs equal the
    solo oracle and the 1-device lattice bit-for-bit, exactly one collective
    per committed DP level, zero retraces in the timed repeats.  Timings are
    reported only."""
    base_l = baseline.get("lattice")
    cur_l = current.get("lattice")
    if base_l is None:
        if cur_l is not None:
            print("note: current report has a lattice section but the "
                  "baseline does not — lattice gates are vacuous until the "
                  "baseline is refreshed with bench_batch --lattice")
        return []
    if cur_l is None:
        print("note: baseline has a lattice section but the current report "
              "was benched without --lattice; lattice checks skipped "
              "(the devices-4 CI job runs the gating configuration)")
        return []
    errors: list[str] = []
    if not cur_l.get("costs_equal_solo", False):
        errors.append("[lattice] sharded cost diverged from the solo "
                      "single-device oracle (must be bit-identical)")
    if not cur_l.get("costs_equal_1dev", False):
        errors.append("[lattice] D-device cost diverged from the 1-device "
                      "lattice run (the lane partition must relocate work, "
                      "never change results)")
    if not cur_l.get("collectives_ok", False):
        errors.append("[lattice] collective count != committed DP levels "
                      "(memo exchange must happen exactly once per level "
                      "commit — no hot-path collectives)")
    if cur_l.get("retraces", 0) > base_l.get("retraces", 0):
        errors.append(
            f"[lattice] timed repeats retraced kernels: "
            f"{cur_l['retraces']} > baseline {base_l['retraces']} "
            "(repeated lattice engines must hit the executable cache)")
    return errors


def check_uniondp(current: dict, baseline: dict) -> list[str]:
    """Deterministic UnionDP plan-quality gates (see module docstring)."""
    base_u = baseline.get("uniondp_quality")
    cur_u = current.get("uniondp_quality")
    if base_u is None:
        if cur_u is not None:
            print("note: current report has a uniondp_quality section but "
                  "the baseline does not — quality gates are vacuous until "
                  "the baseline is refreshed with bench_batch --uniondp")
        return []
    if cur_u is None:
        print("note: baseline has a uniondp_quality section but the current "
              "report was benched without --uniondp; quality checks skipped "
              "(the bench-regression CI job runs the gating configuration)")
        return []
    errors: list[str] = []
    goo_gate = base_u.get("goo_gate", 1.002)
    for q in cur_u["queries"]:
        if q["ratio_vs_goo"] > goo_gate:
            errors.append(
                f"[uniondp:{q['kind']}{q['n']}] raw plan lost to GOO: "
                f"cost ratio {q['ratio_vs_goo']:.4f} > gate {goo_gate} "
                "(cost-aware partitioning + re-optimization must beat the "
                "greedy baseline without the retired goo_floor)")
    imp_gate = base_u.get("improvement_gate", 1.2)
    if cur_u["geomean_improvement_skewed"] < imp_gate:
        errors.append(
            f"[uniondp] geomean improvement over the size-greedy "
            f"partitioner fell to {cur_u['geomean_improvement_skewed']:.2f}x "
            f"< gate {imp_gate}x on the skewed streams")
    if not cur_u.get("pipeline_costs_equal", False):
        errors.append(
            "[uniondp] pipelined re-optimization costs diverged from the "
            "synchronous path (must be bit-identical)")
    return errors


def check_policy(current: dict, baseline: dict) -> list[str]:
    """Learned-policy gates: safety is deterministic (costs bit-identical
    to static, policy-off lane identity, zero retraces from the frozen
    table), throughput is a conservative floor (the learned dispatch must
    not lose to the static defaults; its upside is reported only)."""
    base_p = baseline.get("policy")
    cur_p = current.get("policy")
    if base_p is None:
        if cur_p is not None:
            print("note: current report has a policy section but the "
                  "baseline does not — policy gates are vacuous until the "
                  "baseline is refreshed with bench_batch --policy")
        return []
    if cur_p is None:
        print("note: baseline has a policy section but the current report "
              "was benched without --policy; policy checks skipped "
              "(the bench-regression CI job runs the gating configuration)")
        return []
    errors: list[str] = []
    if not cur_p.get("costs_equal", False):
        errors.append("[policy] learned-dispatch costs diverged from the "
                      "static defaults (a policy may move lanes between "
                      "spaces, never change plans)")
    uns = (current.get("algorithms") or {}).get(cur_p.get("algorithm"))
    if uns is not None and \
            cur_p.get("off_evaluated_lanes") != uns["evaluated_lanes"]:
        errors.append(
            f"[policy] policy-off lane count diverged from the plain "
            f"batched run: {cur_p.get('off_evaluated_lanes')} != "
            f"{uns['evaluated_lanes']} (passing policy=None must be "
            "byte-for-byte the static path)")
    if cur_p.get("retraces", 0) > base_p.get("retraces", 0):
        errors.append(
            f"[policy] timed repeats retraced kernels: "
            f"{cur_p['retraces']} > baseline {base_p['retraces']} "
            "(a frozen table replays one fixed dispatch — the uncounted "
            "post-freeze pass must have compiled everything)")
    floor = base_p.get("speedup_floor", 0.95)
    if cur_p.get("speedup_vs_static", 0.0) < floor:
        errors.append(
            f"[policy] learned dispatch lost to the static defaults: "
            f"{cur_p.get('speedup_vs_static', 0.0):.2f}x < floor {floor} "
            "(after warmup the table must at least replay the static "
            "choice; losing means the wall-clock EMAs steer wrong)")
    return errors


def check_mixed_joins(current: dict, baseline: dict) -> list[str]:
    """Deterministic typed-join gates (from ``bench_batch --mixed-joins``):
    every plan in the mixed flight passes the brute-force oracle's conflict
    rules with the exhaustive cost spot-check covering at least as many
    queries as the baseline, batched costs equal the solo engine
    bit-for-bit, the inner-only queries' per-query lane counts are
    untouched by typed graphs sharing the flight, the flight's total lane
    count does not grow, and the timed repeats trigger zero retraces.
    Throughput is reported, never gated."""
    base_m = baseline.get("mixed_joins")
    cur_m = current.get("mixed_joins")
    if base_m is None:
        if cur_m is not None:
            print("note: current report has a mixed_joins section but the "
                  "baseline does not — typed-join gates are vacuous until "
                  "the baseline is refreshed with bench_batch --mixed-joins")
        return []
    if cur_m is None:
        print("note: baseline has a mixed_joins section but the current "
              "report was benched without --mixed-joins; typed-join checks "
              "skipped (the bench-regression CI job runs the gating "
              "configuration)")
        return []
    errors: list[str] = []
    if not cur_m.get("oracle_valid", False):
        errors.append(
            "[mixed-joins] a plan failed the brute-force oracle spot-check "
            "(conflict-rule validity on every query, exhaustive cost "
            "optimality on the small typed ones)")
    if cur_m.get("oracle_checked", 0) < base_m.get("oracle_checked", 0):
        errors.append(
            f"[mixed-joins] exhaustive oracle coverage shrank: "
            f"{cur_m.get('oracle_checked', 0)} queries < baseline "
            f"{base_m.get('oracle_checked', 0)}")
    if not cur_m.get("costs_equal_solo", False):
        errors.append(
            "[mixed-joins] batched costs diverged from the solo engine "
            "(same lane space must be bit-identical batched vs solo)")
    if not cur_m.get("inner_lanes_unchanged", False):
        errors.append(
            "[mixed-joins] inner-only per-query lane counts were perturbed "
            "by typed graphs sharing the flight (typed queries must bucket "
            "separately — inner flights stay byte-for-byte unchanged)")
    if cur_m.get("evaluated_lanes", 0) > base_m.get("evaluated_lanes", 0):
        errors.append(
            f"[mixed-joins] evaluated lanes grew: "
            f"{cur_m.get('evaluated_lanes')} > baseline "
            f"{base_m.get('evaluated_lanes')} (the conflict mask prunes "
            "lanes — growth means typed bucketing or masking regressed)")
    if cur_m.get("retraces", 0) > base_m.get("retraces", 0):
        errors.append(
            f"[mixed-joins] timed repeats retraced kernels: "
            f"{cur_m['retraces']} > baseline {base_m['retraces']} "
            "(repeated typed bucket shapes must hit the executable cache)")
    return errors


def check_pipeline(current: dict, baseline: dict) -> list[str]:
    """Deterministic pipeline gates: pipelined costs equal the synchronous
    path bit-for-bit, and the timed repeats compile nothing (the executable
    cache must serve every repeated bucket shape).  The speedup ratio is
    reported only — it tracks the runner's core count, not the code."""
    base_p = baseline.get("pipeline")
    cur_p = current.get("pipeline")
    if base_p is None:
        if cur_p is not None:
            print("note: current report has a pipeline section but the "
                  "baseline does not — pipeline gates are vacuous until the "
                  "baseline is refreshed with bench_batch --pipeline")
        return []
    if cur_p is None:
        print("note: baseline has a pipeline section but the current report "
              "was benched without --pipeline; pipeline checks skipped")
        return []
    errors: list[str] = []
    if not cur_p.get("costs_equal", False):
        errors.append("[pipeline] pipelined costs diverged from the "
                      "synchronous path (must be bit-identical)")
    if cur_p.get("retraces", 0) > base_p.get("retraces", 0):
        errors.append(
            f"[pipeline] timed repeats retraced kernels: "
            f"{cur_p['retraces']} > baseline {base_p['retraces']} "
            "(repeated same-shape buckets must hit the executable cache)")
    return errors


def check_sharded(current: dict, baseline: dict, tolerance: float) -> list[str]:
    base_sh = baseline.get("sharded")
    cur_sh = current.get("sharded")
    if base_sh is None:
        if cur_sh is not None:
            print("note: current report has a sharded section but the "
                  "baseline does not — device-path gates are vacuous until "
                  "the baseline is refreshed with bench_batch --devices")
        return []
    if cur_sh is None:
        print("note: baseline has a sharded section but the current report "
              "was benched without --devices; device-path checks skipped "
              "(the devices-4 CI job runs the gating configuration)")
        return []
    errors: list[str] = []
    for algo, base in base_sh["algorithms"].items():
        cur = cur_sh["algorithms"].get(algo)
        if cur is None:
            errors.append(f"[sharded:{algo}] missing from current report")
            continue
        uns = current["algorithms"].get(algo)
        if uns is not None and cur["evaluated_lanes"] != uns["evaluated_lanes"]:
            errors.append(
                f"[sharded:{algo}] lane count diverged from unsharded: "
                f"{cur['evaluated_lanes']} != {uns['evaluated_lanes']} "
                "(sharding must relocate lanes, never change their number)")
        floor = base.get("speedup_floor", base["speedup"] * (1.0 - tolerance))
        if cur["speedup"] < floor:
            errors.append(
                f"[sharded:{algo}] queries/sec regressed >{tolerance:.0%}: "
                f"speedup {cur['speedup']:.2f}x < {floor:.2f}x "
                f"(baseline {base['speedup']:.2f}x)")
    return errors


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("current", help="fresh bench_batch --json report")
    ap.add_argument("baseline", help="committed baseline JSON")
    ap.add_argument("--tolerance", type=float, default=0.25,
                    help="allowed fractional speedup regression (default .25)")
    args = ap.parse_args()
    with open(args.current) as f:
        current = json.load(f)
    with open(args.baseline) as f:
        baseline = json.load(f)
    if current.get("queries") != baseline.get("queries") or \
            current.get("seed") != baseline.get("seed"):
        print("note: stream shape differs from baseline "
              f"(current {current.get('queries')}q/seed {current.get('seed')} "
              f"vs baseline {baseline.get('queries')}q/seed "
              f"{baseline.get('seed')}); lane comparison may be vacuous")
    errors = check(current, baseline, args.tolerance)
    for algo, a in sorted((current.get("algorithms") or {}).items()):
        print(f"[{algo}] qps {a['qps']:.2f} speedup {a['speedup']:.2f}x "
              f"lanes {a['evaluated_lanes']}")
    if "sharded" in current:
        d = current["sharded"]["devices"]
        for algo, a in sorted(current["sharded"]["algorithms"].items()):
            print(f"[sharded:{algo}@{d}dev] qps {a['qps']:.2f} "
                  f"({a['qps_per_device']:.2f}/device) speedup "
                  f"{a['speedup']:.2f}x scaling {a['scaling_vs_1dev']:.2f}x "
                  f"lanes {a['evaluated_lanes']}")
    if "mixed_joins" in current:
        m = current["mixed_joins"]
        print(f"[mixed-joins:{m['algorithm']}] qps {m['qps']:.2f} "
              f"oracle_valid {m['oracle_valid']} "
              f"(exhaustive on {m['oracle_checked']}) "
              f"costs_equal_solo {m['costs_equal_solo']} "
              f"inner_lanes_unchanged {m['inner_lanes_unchanged']} "
              f"lanes {m['evaluated_lanes']} retraces {m['retraces']}")
    if "pipeline" in current:
        p = current["pipeline"]
        print(f"[pipeline:{p['algorithm']}] qps {p['qps']:.2f} "
              f"({p['speedup_vs_sync']:.2f}x vs sync) "
              f"costs_equal {p['costs_equal']} retraces {p['retraces']}")
    if "policy" in current:
        p = current["policy"]
        print(f"[policy:{p['algorithm']}] qps {p['qps']:.2f} "
              f"({p['speedup_vs_static']:.2f}x vs static) "
              f"costs_equal {p['costs_equal']} retraces {p['retraces']} "
              f"lanes on/off {p['on_evaluated_lanes']}/"
              f"{p['off_evaluated_lanes']}")
    if "lattice" in current:
        lat = current["lattice"]
        d = lat["devices"]
        for c in lat["cases"]:
            print(f"[lattice:{c['space']}@{d}dev] n={c['n']} "
                  f"wall {c['wall_s']:.3f}s "
                  f"({c['speedup_vs_solo']:.2f}x vs solo) "
                  f"collectives {c['collectives']}/{c['levels']}")
        print(f"[lattice] costs_equal_solo {lat['costs_equal_solo']} "
              f"costs_equal_1dev {lat['costs_equal_1dev']} "
              f"collectives_ok {lat['collectives_ok']} "
              f"retraces {lat['retraces']}")
    if "uniondp_quality" in current:
        u = current["uniondp_quality"]
        print(f"[uniondp] worst vs goo {u['worst_ratio_vs_goo']:.4f}x "
              f"geomean improvement {u['geomean_improvement_skewed']:.2f}x "
              f"pipeline_equal {u['pipeline_costs_equal']} "
              f"({len(u['queries'])} queries)")
    if "daemon" in current:
        d = current["daemon"]
        print(f"[daemon] cold {d.get('cold_wall_s', 0):.2f}s warm "
              f"{d.get('warm_wall_s', 0)*1e3:.1f}ms; compile deltas "
              f"warm/proc2/fresh {d.get('warm_compile_delta')}/"
              f"{d.get('proc2_compile_delta')}/"
              f"{d.get('fresh_compile_delta')} "
              f"(fresh retraces {d.get('fresh_retrace_delta')}); "
              f"proc2 hits {d.get('proc2_cache_hits')}; "
              f"drain_clean {d.get('drain_clean')}")
        ld = d.get("load", {})
        if ld:
            print(f"[daemon:load] {ld['completed']}/{ld['arrivals']} "
                  f"completed, {ld['shed']} shed; p99 "
                  f"{ld['latency_s']['p99']*1e3:.1f}ms (reported only)")
    if "chaos" in current:
        ch = current["chaos"]
        print(f"[chaos] {ch.get('completed')}/{ch.get('requests')} "
              f"completed, {ch.get('shed')} shed, {ch.get('retried')} "
              f"retried, {ch.get('failed')} failed, {ch.get('hung')} hung; "
              f"degraded {ch.get('degraded')} valid "
              f"{ch.get('degraded_valid')}; worker restarts "
              f"{ch.get('worker_restarts')}; drain_clean "
              f"{ch.get('drain_clean')}")
    if errors:
        print("\nBENCHMARK REGRESSION:")
        for e in errors:
            print("  " + e)
        return 1
    print("\nno regression vs baseline")
    return 0


if __name__ == "__main__":
    sys.exit(main())
