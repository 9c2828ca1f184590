"""Batched multi-query MPDP: B queries through one level-synchronous DP.

``ExactEngine`` serves one query per host loop; a stream of small/medium
queries leaves the device mostly idle (a 2^15-lane chunk runs with a few
hundred live lanes) and pays per-query dispatch overhead.  ``BatchEngine``
pads B queries into one (NMAX, EMAX, CHUNK) bucket and folds the batch into
the *lane* dimension of the same unrank -> filter -> evaluate -> prune ->
scatter pipeline:

  * queries are stacked: ``adj`` becomes ``(bcap, NMAX)``, the dense memo
    tables become one flat ``(bcap << NMAX)`` buffer (query q owns the
    ``[q << NMAX, (q+1) << NMAX)`` region, i.e. logically ``(B, 1 << NMAX)``);
  * each DP level concatenates every query's lane space; a lane decodes its
    query id with a searchsorted over per-query lane offsets — alongside the
    (set index, subset rank) decode the single-query kernels already do;
  * pruning stays one ``segment_min`` per (query, set) segment: segments are
    globally contiguous because lanes are ordered by (query, set, subset).

Computed costs are **bit-identical** to per-query ``engine.optimize`` (plan-
cache hits are instead re-costed on the probing graph's exact stats, so a
quantized-signature hit can differ at the 1/4096-log2 epsilon): memo rows come
from the shared host-side ``cost.np_rows_for_sets`` (independent of padding
buckets), leaf costs from the same ``np_scan_cost``, per-lane candidate costs
from the same elementwise f32 kernel ops over identically-shaped chunks, and
the per-set reduction is an exact f32 min over the same CCP candidate set.

The batched evaluate supports the same per-topology *lane spaces* as the
single-query ``ExactEngine``: DPSUB (``sets x 2^i``), MPDP:Tree
(``sets x m`` — per-lane (query, set, edge) decode), and MPDP-general
(block prefix-sum — phase A reuses the shared host driver
``blocks.np_pairs_for_sets`` per query, phase B fuses every query's
(set, block) pairs into one lane space).  ``optimize_many``'s dispatcher
picks the space per (NMAX, topology) bucket: all-acyclic buckets run the
tree lanes, everything else the general lanes — cutting evaluated lanes on
sparse batches the way MPDP does for single queries, with candidate minima
(and therefore costs/plans) bit-identical across spaces.

``REPRO_PALLAS=1`` routes the per-lane bit-twiddling of every batched
evaluator through the Pallas TPU kernels (``kernels.ccp_eval`` batched
variants: the (bcap, NMAX) adjacency table is scalar-prefetched to SMEM and
a static select loop materializes each lane's own adjacency row); the
pure-XLA vector path below stays the ``REPRO_PALLAS=0`` fallback.  The flag
is threaded as a *static* jit arg so both traces coexist in one process.

``pipeline=True`` (or ``REPRO_PIPELINE=1``) runs the level loop *pipelined*:
each level's evaluate chunks are dispatched asynchronously (device refs held,
no ``np.asarray`` sync) while the host concurrently fetches + compacts the
next level's connectivity filter, computes its memo rows, and (general space)
runs its block-decomposition phase A — the stage that is host-bound on small
buckets.  The chunk grids, kernels and merge order are unchanged, so results
stay bit-identical to the synchronous default; only dispatch order differs.
The memo-update scatters donate their input buffers (``donate_argnums``), so
the staged double-buffer writes alias in place instead of copy-on-write.

All kernel entry points are served by ``exec_cache.EXEC`` — one compiled
executable per (space, nmax, bcap, chunk, pallas) key for the whole process,
with trace counting exposed on ``BatchEngine.stats`` (repeated bucket shapes
across IDP2/UnionDP partition rounds, UnionDP re-optimization passes and
service flights must hit zero retraces — the heuristics re-enter this module
many times per query with recurring (nmax, bcap) shapes, which is exactly
the access pattern the process-wide cache exists for).

``optimize_many`` is the public entry point; it also consults an optional
``PlanCache`` (canonical-signature keyed) before touching the device.
"""
from __future__ import annotations

import time
from collections import deque
from math import comb

import numpy as np
import jax
import jax.numpy as jnp

from . import bitset as bs
from . import blocks as bl
from . import cost as cm
from . import faults
from . import unrank as ur
from .config import (MAX_FLIGHT, UNSET, OptimizerConfig, alias_kwarg,
                     resolve_config)
from .engine import (CHUNK, CYC_CAP_DEFAULT, INF, _cap,
                     _merge_best, _merge_scattered, _prune, _scatter_f32,
                     _scatter_i32, _typed_lane_cost, _use_pallas,
                     _use_pipeline)
from .exec_cache import EXEC
from .joingraph import JoinGraph, typed_edge_arrays
from .plan import Counters, OptimizeResult, extract_plan, leaf_plan
from .telemetry import fetch, span

NMAX_BATCH = 16          # memo is (bcap << NMAX): past 16 fall back to solo
MAX_BATCH = MAX_FLIGHT   # sub-batch cap: bounds memo memory + recompiles
                         # (canonical name: ``config.MAX_FLIGHT``)
_CLIP = 1 << 30          # offset clip (same trick as the general kernel)
PEND_WINDOW = 8          # in-flight chunks per level: dispatching a level
                         # queues at most this many un-fetched chunk results
                         # (backpressure — bounds transient device memory
                         # while still overlapping host merges with later
                         # chunks' device execution)


def _bcap(b: int) -> int:
    return _cap(b, 4)


# ================================================================= kernels ==
# Raw (unjitted) chunk kernels: ``BatchEngine`` jits them through the
# process-wide ``exec_cache.EXEC`` (one executable per static key, with
# compile accounting); ``core.shard`` wraps the same bodies in shard_map.

def _bfilter_chunk(foff, k, binom, adj_b, *, nmax: int, chunk: int, bcap: int,
                   pallas: bool = False):
    """Batched unrank + connectivity filter.

    foff: i32[bcap+1] chunk-local per-query rank offsets (prefix sums of
    C(n_q, k), minus the chunk base, clipped).  Lane t belongs to query
    ``searchsorted(foff, t) - 1`` with rank ``t - foff[qid]``.
    """
    t = jnp.arange(chunk, dtype=jnp.int32)
    qid = jnp.clip(jnp.searchsorted(foff, t, side="right").astype(jnp.int32) - 1,
                   0, bcap - 1)
    rank = t - foff[qid]
    live = t < foff[bcap]
    S = ur.unrank_ksubset(jnp.maximum(rank, 0), k, binom, nmax)
    if pallas:
        from ..kernels import ops as _ko
        conn = (_ko.bconnectivity(S, qid, adj_b, nmax, bcap) != 0) & live
    else:
        adjq = adj_b[qid]                              # (chunk, nmax)
        conn = bs.is_connected_rows(S, adjq) & live
    return S, conn, qid


def _beval_dpsub_chunk(all_sets, eoff, loff, soff, seg0, i,
                       adj_b, memo_cost, memo_rows,
                       ekind_b=None, elm_b=None, erm_b=None,
                       etes_l_b=None, etes_r_b=None,
                       *, nmax: int, chunk: int, nseg: int, bcap: int,
                       pallas: bool = False, typed: bool = False):
    """Batched DPSUB evaluate: lane -> (query, set, subset) decode.

    eoff: i32[bcap+1] chunk-local per-query lane offsets (prefix of ns_q<<i).
    loff: i32[bcap]   per-query base into all_sets (region + level offset).
    soff: i32[bcap]   per-query global set-index prefix (segment ids).
    """
    t = jnp.arange(chunk, dtype=jnp.int32)
    qid = jnp.clip(jnp.searchsorted(eoff, t, side="right").astype(jnp.int32) - 1,
                   0, bcap - 1)
    local = t - eoff[qid]
    live = t < eoff[bcap]
    set_idx = local >> i
    sub = local & ((jnp.int32(1) << i) - 1)
    S = all_sets[loff[qid] + set_idx]
    if pallas:
        from ..kernels import ops as _ko
        lb, rb, ccp_i = _ko.bccp_eval(S, sub, qid, adj_b, nmax, bcap)
        ccp = live & (ccp_i != 0)
    else:
        adjq = adj_b[qid]
        lb = bs.pdep(sub, S, nmax)
        rb = S & ~lb
        nonempty = (lb != 0) & (rb != 0)
        conn_l = bs.is_connected_rows(lb, adjq)
        conn_r = bs.is_connected_rows(rb, adjq)
        cross = (bs.neighbors_rows(lb, adjq) & rb) != 0
        ccp = live & nonempty & conn_l & conn_r & cross
    mbase = qid << nmax                                # per-query memo region
    rows_S = memo_rows[mbase | S]
    cl = memo_cost[mbase | lb]
    cr = memo_cost[mbase | rb]
    if typed:
        cand, lbx = _typed_lane_cost(
            lb, rb, rows_S, ccp, cl, cr,
            memo_rows[mbase | lb], memo_rows[mbase | rb],
            ekind_b[qid], elm_b[qid], erm_b[qid],
            etes_l_b[qid], etes_r_b[qid])
    else:
        jc = cm.join_cost(memo_rows[mbase | lb], memo_rows[mbase | rb], rows_S)
        cand = jnp.where(ccp, cl + cr + jc, INF)
        lbx = lb
    seg = jnp.clip(soff[qid] + set_idx - seg0, 0, nseg - 1)
    seg_cost, seg_left = _prune(seg, cand, lbx, nseg)
    ev_q = jax.ops.segment_sum(live.astype(jnp.int32), qid, num_segments=bcap)
    ccp_q = jax.ops.segment_sum(ccp.astype(jnp.int32), qid, num_segments=bcap)
    return seg_cost, seg_left, ev_q, ccp_q


def _beval_tree_chunk(all_sets, eoff, loff, soff, seg0, m_b,
                      adj_b, emu_b, emv_b, memo_cost, memo_rows,
                      ekind_b=None, elm_b=None, erm_b=None,
                      etes_l_b=None, etes_r_b=None,
                      *, nmax: int, chunk: int, nseg: int, bcap: int,
                      pallas: bool = False, typed: bool = False):
    """Batched MPDP:Tree evaluate: lane -> (query, set, edge) decode.

    eoff: i32[bcap+1] chunk-local per-query lane offsets (prefix of ns_q*m_q).
    m_b:  i32[bcap]   per-query edge count (lane-minor dimension).
    emu_b/emv_b: i32[bcap, emax] per-query edge endpoint bitmaps (0 pad).
    Every enumerated in-set edge IS a CCP pair (Theorem 3): the tree lane
    space is ``sets x m`` instead of DPSUB's ``sets x 2^i``.
    """
    t = jnp.arange(chunk, dtype=jnp.int32)
    qid = jnp.clip(jnp.searchsorted(eoff, t, side="right").astype(jnp.int32) - 1,
                   0, bcap - 1)
    local = t - eoff[qid]
    live = t < eoff[bcap]
    mq = jnp.maximum(m_b[qid], 1)
    set_idx = local // mq
    e = local % mq
    S = all_sets[loff[qid] + set_idx]
    ub = emu_b[qid, e]
    vb = emv_b[qid, e]
    if pallas:
        from ..kernels import ops as _ko
        S_left, in_i = _ko.btree_eval(S, ub, vb, qid, adj_b, nmax, bcap)
        edge_in = live & (in_i != 0)
    else:
        adjq = adj_b[qid]
        edge_in = live & ((S & ub) != 0) & ((S & vb) != 0)
        S_left = bs.grow_excl_edge_rows(ub, S, adjq, ub, vb)
    S_right = S & ~S_left
    evaluated = edge_in                                # Theorem 3: all CCP
    ccp = edge_in
    mbase = qid << nmax
    rows_S = memo_rows[mbase | S]
    cl = memo_cost[mbase | S_left]
    cr = memo_cost[mbase | S_right]
    if typed:
        cand, lbx = _typed_lane_cost(
            S_left, S_right, rows_S, ccp, cl, cr,
            memo_rows[mbase | S_left], memo_rows[mbase | S_right],
            ekind_b[qid], elm_b[qid], erm_b[qid],
            etes_l_b[qid], etes_r_b[qid])
    else:
        jc = cm.join_cost(memo_rows[mbase | S_left], memo_rows[mbase | S_right],
                          rows_S)
        cand = jnp.where(ccp, cl + cr + jc, INF)
        lbx = S_left
    seg = jnp.clip(soff[qid] + set_idx - seg0, 0, nseg - 1)
    seg_cost, seg_left = _prune(seg, cand, lbx, nseg)
    ev_q = jax.ops.segment_sum(evaluated.astype(jnp.int32), qid,
                               num_segments=bcap)
    ccp_q = jax.ops.segment_sum(ccp.astype(jnp.int32), qid, num_segments=bcap)
    return seg_cost, seg_left, ev_q, ccp_q


def _beval_general_chunk(pair_set, pair_block, pair_qid, off_local, n_pairs,
                         lane_count, adj_b, memo_cost, memo_rows,
                         ekind_b=None, elm_b=None, erm_b=None,
                         etes_l_b=None, etes_r_b=None,
                         *, nmax: int, chunk: int, pcap: int, bcap: int,
                         pallas: bool = False, typed: bool = False):
    """Batched MPDP-general evaluate: lane -> (query, set, block, rank).

    Phase A (host, per query) compacted every set's blocks into sorted
    (set, block) pairs; the fused lane space is the block prefix-sum over
    *all* queries' pairs.  Lane -> pair via searchsorted on ``off_local``;
    the pair carries its query id for the memo-region / adjacency decode.
    """
    t = jnp.arange(chunk, dtype=jnp.int32)
    live = t < lane_count
    p = jnp.clip(jnp.searchsorted(off_local, t, side="right").astype(jnp.int32) - 1,
                 0, n_pairs - 1)
    r = t - off_local[p]
    S = pair_set[p]
    block = pair_block[p]
    qid = pair_qid[p]
    if pallas:
        from ..kernels import ops as _ko
        lb, S_left, ccp_i = _ko.bgeneral_eval(S, block, r, qid, adj_b, nmax,
                                              bcap)
        rb = block & ~lb
        enum_ok = live & (lb != 0) & (rb != 0)             # Alg.3 line 6/7
        ccp_blk = enum_ok & (ccp_i != 0)
    else:
        adjq = adj_b[qid]
        lb = bs.pdep(r, block, nmax)
        rb = block & ~lb
        enum_ok = live & (lb != 0) & (rb != 0)             # Alg.3 line 6/7
        conn_l = bs.is_connected_rows(lb, adjq)
        conn_r = bs.is_connected_rows(rb, adjq)
        cross = (bs.neighbors_rows(lb, adjq) & rb) != 0
        ccp_blk = enum_ok & conn_l & conn_r & cross
        S_left = bs.grow_rows(lb, S & ~rb, adjq)           # Alg.3 line 17
    S_right = S & ~S_left
    mbase = qid << nmax
    rows_S = memo_rows[mbase | S]
    cl = memo_cost[mbase | S_left]
    cr = memo_cost[mbase | S_right]
    if typed:
        cand, lbx = _typed_lane_cost(
            S_left, S_right, rows_S, ccp_blk, cl, cr,
            memo_rows[mbase | S_left], memo_rows[mbase | S_right],
            ekind_b[qid], elm_b[qid], erm_b[qid],
            etes_l_b[qid], etes_r_b[qid])
    else:
        jc = cm.join_cost(memo_rows[mbase | S_left], memo_rows[mbase | S_right],
                          rows_S)
        cand = jnp.where(ccp_blk, cl + cr + jc, INF)
        lbx = S_left
    seg_cost, seg_left = _prune(p, cand, lbx, pcap)
    ev_q = jax.ops.segment_sum(enum_ok.astype(jnp.int32), qid,
                               num_segments=bcap)
    ccp_q = jax.ops.segment_sum(ccp_blk.astype(jnp.int32), qid,
                                num_segments=bcap)
    return seg_cost, seg_left, ev_q, ccp_q


# ============================================================== host driver ==

class _LevelLoop:
    """Shared level-loop drivers for the batched engines.

    ``BatchEngine`` and ``ShardedBatchEngine`` expose the same per-level
    hooks (``_filter_dispatch``/``_filter_collect``, ``_register_level``,
    ``_pairs_level``, ``_eval[_general]_dispatch``/``_eval[_general]_finalize``)
    over different set containers (per-query lists vs per-shard nests); the
    drivers treat those containers as opaque, so the synchronous loop and
    the pipelined rotation live here exactly once — a fix to the overlap
    schedule cannot diverge between the sharded and unsharded engines.

    Both drivers honor the engine's cooperative ``deadline_s``: the clock
    (``faults.now``, monkeypatchable) is read once at ``run_levels`` start
    and once at the top of every level; past the deadline the remaining
    levels are abandoned and ``collect`` stitches best-effort plans from
    the committed memo prefix (``self.degraded`` records why).
    """

    def _arm_deadline(self) -> None:
        self._deadline_at = (None if self.deadline_s is None
                             else faults.now() + self.deadline_s)

    def _expired(self, i: int, max_n: int) -> bool:
        """One check per DP level; with ``deadline_s=None`` this is a single
        attribute test — zero behavior change."""
        if self._deadline_at is None:
            return False
        if faults.now() < self._deadline_at:
            return False
        self.degraded = {"reason": "deadline", "deadline_s": self.deadline_s,
                         "levels_done": i - 1, "levels_total": max_n}
        return True

    def run_levels(self) -> None:
        """Run the level-synchronous DP; the memo stays on device (fetch it
        with ``collect``).  The pipelined driver produces bit-identical memo
        contents — same chunk grids, same kernels, same merge order — it
        only overlaps host compaction with in-flight device work.

        Spans: ``engine.levels`` around the loop, one ``level.filter``,
        ``level.register``, ``level.pairs`` (general space) and
        ``level.eval`` per step of a level, ``level.fetch`` inside them
        around each blocking fetch (the engines' drains)."""
        t0 = time.perf_counter()
        max_n = max(g.n for g in self.graphs)
        general = self.algorithm == "mpdp_general"
        self._arm_deadline()
        with span("engine.levels"):
            if self.pipeline:
                self._run_levels_pipelined(max_n, general)
            else:
                for i in range(2, max_n + 1):
                    if self._expired(i, max_n):
                        break
                    with span("level.filter"):
                        sets = self._filter_collect(self._filter_dispatch(i))
                    with span("level.register"):
                        self._register_level(i, sets)
                    if general:
                        with span("level.pairs"):
                            pairs = self._pairs_level(sets)
                        with span("level.eval"):
                            ctx = self._eval_general_dispatch(i, sets, pairs)
                            self._eval_general_finalize(i, sets, ctx)
                    else:
                        with span("level.eval"):
                            self._eval_finalize(i, sets,
                                                self._eval_dispatch(i, sets))
        self._wall += time.perf_counter() - t0

    def _run_levels_pipelined(self, max_n: int, general: bool) -> None:
        """Pipelined level loop.  Per level i:

          1. dispatch level i+1's (memo-independent) filter chunks *first*,
             so they clear the device queue early;
          2. dispatch level i's evaluate chunks — the bulk device work;
          3. while those execute, fetch + compact the filter results, cost
             the new sets' rows, register them (rows/all_sets scatters touch
             buffers eval(i) only reads; stream order keeps them safe), and
             run phase A for the general space — the host-bound stage;
          4. only then sync on eval(i)'s tail, merge and commit.

        Each step gets its own span, so a level shows two ``level.filter``
        and two ``level.eval`` spans (dispatch, then collect/finalize).
        """
        with span("level.filter"):
            sets = self._filter_collect(self._filter_dispatch(2))
        with span("level.register"):
            self._register_level(2, sets)
        pairs = None
        if general:
            with span("level.pairs"):
                pairs = self._pairs_level(sets)
        for i in range(2, max_n + 1):
            if self._expired(i, max_n):
                break
            fpend = None
            if i < max_n:
                with span("level.filter"):
                    fpend = self._filter_dispatch(i + 1)
            with span("level.eval"):
                if general:
                    ctx = self._eval_general_dispatch(i, sets, pairs)
                else:
                    ctx = self._eval_dispatch(i, sets)
            nxt = nxt_pairs = None
            if fpend is not None:
                with span("level.filter"):
                    nxt = self._filter_collect(fpend)
                with span("level.register"):
                    self._register_level(i + 1, nxt)
                if general:
                    with span("level.pairs"):
                        nxt_pairs = self._pairs_level(nxt)
            with span("level.eval"):
                if general:
                    self._eval_general_finalize(i, sets, ctx)
                else:
                    self._eval_finalize(i, sets, ctx)
            sets, pairs = nxt, nxt_pairs

    def run(self) -> list[OptimizeResult]:
        self.run_levels()
        with span("engine.collect"):
            return self.collect()


class BatchEngine(_LevelLoop):
    """Level-synchronous DP over a batch of queries in one device pipeline.

    ``algorithm`` selects the evaluate lane space: ``dpsub`` (``sets x 2^i``),
    ``mpdp_tree`` (``sets x m``; requires every query to be acyclic) or
    ``mpdp_general`` (block prefix-sum).  All three enumerate the same CCP
    candidate minima, so costs/plans are identical — only the evaluated-lane
    counts differ.

    ``pipeline`` (default: the ``REPRO_PIPELINE`` env flag) switches the
    level loop to the pipelined driver: level i's evaluate is dispatched
    asynchronously while the host compacts level i+1 — bit-identical
    results, overlapped host/device time.
    """

    def __init__(self, graphs: list[JoinGraph], chunk: int = CHUNK,
                 algorithm: str = "dpsub", cyc_cap: int = CYC_CAP_DEFAULT,
                 pipeline: bool | None = None,
                 pend_window: int | None = None,
                 deadline_s: float | None = None):
        if not graphs:
            raise ValueError("empty batch")
        if algorithm not in ("dpsub", "mpdp_tree", "mpdp_general"):
            raise ValueError(f"unknown batched lane space {algorithm!r}")
        for g in graphs:
            if g.n < 2:
                raise ValueError("BatchEngine needs n >= 2 (leaf queries are "
                                 "handled by optimize_many)")
            if not g.is_connected():
                raise ValueError("query graph must be connected (no cross products)")
            if algorithm == "mpdp_tree" and not g.is_tree():
                raise ValueError("mpdp_tree lane space needs acyclic queries")
        self.graphs = graphs
        self.algorithm = algorithm
        self.cyc_cap = cyc_cap
        self.pallas = _use_pallas()        # read per engine; static jit arg
        self.pipeline = _use_pipeline() if pipeline is None else bool(pipeline)
        # drain-window override (learned policies shrink it for flights
        # whose levels dispatch few chunks) + host-side dispatch tally for
        # telemetry; neither touches device values, so results are
        # bit-identical for any pend_window >= 0
        self.pend_window = (PEND_WINDOW if pend_window is None
                            else int(pend_window))
        self.deadline_s = deadline_s
        self._deadline_at: float | None = None
        self.degraded: dict | None = None
        self.chunks_dispatched = 0
        self.blocks_sets = 0               # phase A: sets / launched slots
        self.blocks_slots = 0
        self._exec_keys: set[tuple] = set()
        self._wall = 0.0
        self.B = len(graphs)
        self.bcap = _bcap(self.B)
        self.nmax = max(bs.nmax_bucket(g.n) for g in graphs)
        if self.nmax > NMAX_BATCH:
            raise ValueError(f"batched path supports nmax <= {NMAX_BATCH}")
        self.chunk = chunk
        self.size = 1 << self.nmax
        self.flat = self.bcap << self.nmax
        with span("engine.setup"):
            self.binom = jnp.asarray(ur.binom_table(self.nmax))
            adj = np.zeros((self.bcap, self.nmax), np.int32)
            for q, g in enumerate(graphs):
                for (u, v) in g.edges:
                    adj[q, u] |= 1 << v
                    adj[q, v] |= 1 << u
            self.adj_b = jnp.asarray(adj)
            # per-query edge arrays: endpoint bitmaps (tree lane decode) and
            # endpoint indices (general phase A), stacked on a shared EMAX
            # bucket
            max_m = max(g.m for g in graphs)
            self.emax = max(8, int(np.ceil(max(max_m, 1) / 8.0)) * 8)
            emu = np.zeros((self.bcap, self.emax), np.int32)
            emv = np.zeros((self.bcap, self.emax), np.int32)
            eui = np.full((self.bcap, self.emax), -1, np.int32)
            evi = np.full((self.bcap, self.emax), -1, np.int32)
            eliv = np.zeros((self.bcap, self.emax), bool)
            for q, g in enumerate(graphs):
                for i, (u, v) in enumerate(g.edges):
                    emu[q, i] = 1 << u
                    emv[q, i] = 1 << v
                    eui[q, i], evi[q, i], eliv[q, i] = u, v, True
            self.emu_b = jnp.asarray(emu)
            self.emv_b = jnp.asarray(emv)
            self.eu_idx_b = jnp.asarray(eui)
            self.ev_idx_b = jnp.asarray(evi)
            self.edge_live_b = jnp.asarray(eliv)
            # typed-edge conflict channel: stacked (bcap, emax) kind /
            # operand / TES arrays, present only when some query has a
            # non-inner edge.  Inner-only batches pass no extra args and
            # carry typed=False, so their kernel traces (and bits) are
            # exactly the pre-typed ones.
            self.typed = any(g.typed for g in graphs)
            if self.typed:
                tarr = [np.zeros((self.bcap, self.emax), np.int32)
                        for _ in range(5)]
                for q, g in enumerate(graphs):
                    for a, col in zip(tarr, typed_edge_arrays(g, self.emax)):
                        a[q] = col
                self._targs = tuple(jnp.asarray(a) for a in tarr)
            else:
                self._targs = ()
            self.m_b = jnp.asarray(
                np.array([g.m for g in graphs] + [0] * (self.bcap - self.B),
                         np.int32))
            self.counters = [Counters() for _ in graphs]
            self._init_memo()

    # ------------------------------------------------------------- memo ----
    def _init_memo(self):
        self.memo_cost = jnp.full(self.flat, INF, jnp.float32)
        self.memo_rows = jnp.zeros(self.flat, jnp.float32)
        self.memo_left = jnp.zeros(self.flat, jnp.int32)
        self.all_sets = jnp.zeros(self.flat, jnp.int32)
        self._next_off = [g.n for g in self.graphs]
        self._level_off = [{1: 0} for _ in self.graphs]
        idx_l, cost_l, rows_l, pos_l, set_l = [], [], [], [], []
        for q, g in enumerate(self.graphs):
            leaves = np.array([1 << v for v in range(g.n)], np.int32)
            lrows = g.log2_card.astype(np.float32)
            lcost = cm.np_scan_cost(lrows).astype(np.float32)
            base = q << self.nmax
            idx_l.append(base + leaves.astype(np.int64))
            cost_l.append(lcost)
            rows_l.append(lrows)
            pos_l.append(base + np.arange(g.n, dtype=np.int64))
            set_l.append(leaves)
        self._scatter(np.concatenate(idx_l), cost=np.concatenate(cost_l),
                      rows=np.concatenate(rows_l))
        self._set_all_sets(np.concatenate(pos_l), np.concatenate(set_l))

    def _scatter(self, idx_np, cost=None, rows=None, left=None):
        cap = _cap(len(idx_np))
        idx = np.full(cap, self.flat, np.int64)        # OOB pad -> dropped
        idx[: len(idx_np)] = idx_np
        idx_d = jnp.asarray(idx.astype(np.int32))

        def pad(x, dt):
            b = np.zeros(cap, dt)
            b[: len(idx_np)] = x
            return jnp.asarray(b)

        if cost is not None:
            self.memo_cost = _scatter_f32(self.memo_cost, idx_d,
                                          pad(cost, np.float32),
                                          size=self.flat, cap=cap)
        if rows is not None:
            self.memo_rows = _scatter_f32(self.memo_rows, idx_d,
                                          pad(rows, np.float32),
                                          size=self.flat, cap=cap)
        if left is not None:
            self.memo_left = _scatter_i32(self.memo_left, idx_d,
                                          pad(left, np.int32),
                                          size=self.flat, cap=cap)

    def _set_all_sets(self, pos_np, sets_np):
        cap = _cap(len(pos_np))
        pos = np.full(cap, self.flat, np.int64)
        pos[: len(pos_np)] = pos_np
        buf = np.zeros(cap, np.int32)
        buf[: len(pos_np)] = sets_np
        self.all_sets = _scatter_i32(self.all_sets, jnp.asarray(pos.astype(np.int32)),
                                     jnp.asarray(buf), size=self.flat, cap=cap)

    # ---------------------------------------------------------- exec cache -
    def _jit(self, name: str, impl, **statics):
        """Kernel entry via the process-wide executable cache; the engine
        remembers its keys so ``stats`` can report compile counts."""
        self._exec_keys.add(EXEC.key(name, statics))
        return EXEC.jit(name, impl, **statics)

    @property
    def stats(self) -> dict:
        """Executable-cache accounting for this engine's kernel keys:
        ``{"compiles": {key: traces}, "retraces": n, "pipeline": bool}`` —
        repeated same-shape buckets must show zero retraces."""
        return EXEC.stats_for(self._exec_keys, pipeline=self.pipeline)

    # ------------------------------------------------------------ filter ---
    def _filter_dispatch(self, i: int) -> dict:
        """Dispatch level i's unrank+filter chunks, keeping at most
        ``PEND_WINDOW`` un-fetched (older chunks drain into the context's
        accumulators as newer ones execute).  The final fetch is
        ``_filter_collect``'s job, so the pipelined driver can slot the
        tail compaction under the level's evaluate."""
        totals = np.array([comb(g.n, i) if g.n >= i else 0
                           for g in self.graphs], np.int64)
        foff = np.zeros(self.B + 1, np.int64)
        np.cumsum(totals, out=foff[1:])
        total = int(foff[-1])
        kf = self._jit("bfilter", _bfilter_chunk, nmax=self.nmax,
                       chunk=self.chunk, bcap=self.bcap, pallas=self.pallas)
        ctx = {"pend": deque(),
               "per_q": [[] for _ in range(self.B)]}
        for lane0 in range(0, total, self.chunk):
            fl = np.clip(foff - lane0, -_CLIP, _CLIP)
            fpad = np.full(self.bcap + 1, fl[self.B], np.int32)
            fpad[: self.B + 1] = fl
            ctx["pend"].append(kf(jnp.asarray(fpad), jnp.int32(i),
                                  self.binom, self.adj_b))
            faults.fire("chunk")
            self.chunks_dispatched += 1
            self._filter_drain(ctx, self.pend_window)
        return ctx

    def _filter_drain(self, ctx: dict, limit: int) -> None:
        """Fetch + compact pending filter chunks down to ``limit``."""
        pend, per_q = ctx["pend"], ctx["per_q"]
        while len(pend) > limit:
            S, conn, qid = pend.popleft()
            c = fetch(conn)
            if c.any():
                S, qid = fetch((S, qid))
                Sc = S[c]
                qc = qid[c]
                for q in np.unique(qc):
                    per_q[q].append(Sc[qc == q])

    def _filter_collect(self, ctx: dict) -> list[np.ndarray]:
        """Drain the remaining filter chunks and build the per-query set
        lists (in pipelined mode this runs under device evaluate of the
        previous level)."""
        self._filter_drain(ctx, 0)
        sets_by_q = [np.concatenate(l) if l else np.zeros(0, np.int32)
                     for l in ctx["per_q"]]
        return sets_by_q

    def _register_level(self, i: int, sets_by_q: list[np.ndarray]) -> None:
        """Host rows (canonical helper) + all_sets/memo_rows registration."""
        idx_l, rows_l, pos_l, set_l = [], [], [], []
        for q, sets_q in enumerate(sets_by_q):
            self._level_off[q][i] = self._next_off[q]
            if not len(sets_q):
                continue
            base = q << self.nmax
            rows_q = cm.np_rows_for_sets(sets_q, self.graphs[q])
            idx_l.append(base + sets_q.astype(np.int64))
            rows_l.append(rows_q)
            pos_l.append(base + self._next_off[q]
                         + np.arange(len(sets_q), dtype=np.int64))
            set_l.append(sets_q)
            self._next_off[q] += len(sets_q)
        if idx_l:
            self._scatter(np.concatenate(idx_l), rows=np.concatenate(rows_l))
            self._set_all_sets(np.concatenate(pos_l), np.concatenate(set_l))

    # ---------------------------------------------------------- evaluate ---
    def _commit_best(self, sets_by_q, best_cost, best_left) -> None:
        """Commit a level: per-query slices of the fused best arrays."""
        idx_l, cost_l, left_l = [], [], []
        off = 0
        for q, sets_q in enumerate(sets_by_q):
            nsq = len(sets_q)
            bc = best_cost[off: off + nsq]
            blft = best_left[off: off + nsq]
            off += nsq
            fin = np.isfinite(bc)
            if fin.any():
                idx_l.append((q << self.nmax) + sets_q[fin].astype(np.int64))
                cost_l.append(bc[fin])
                left_l.append(blft[fin])
        if idx_l:
            self._scatter(np.concatenate(idx_l), cost=np.concatenate(cost_l),
                          left=np.concatenate(left_l))

    def _eval_dispatch(self, i: int, sets_by_q: list[np.ndarray]):
        """Segmented lane spaces (DPSUB ``sets x 2^i``, tree ``sets x m``):
        lanes of query q are contiguous, ``ns_q * mult_q`` long.  Dispatches
        every chunk and returns the level context with pending device
        results; ``_eval_finalize`` fetches, merges and commits."""
        ns = np.array([len(s) for s in sets_by_q], np.int64)
        if self.algorithm == "mpdp_tree":
            mult = np.array([g.m for g in self.graphs], np.int64)
        else:
            mult = np.full(self.B, np.int64(1) << i, np.int64)
        lanes = ns * mult
        eoff = np.zeros(self.B + 1, np.int64)
        np.cumsum(lanes, out=eoff[1:])
        total = int(eoff[-1])
        if total == 0:
            return None
        soff = np.zeros(self.B + 1, np.int64)
        np.cumsum(ns, out=soff[1:])
        loff = np.zeros(self.bcap, np.int64)
        for q in range(self.B):
            loff[q] = (q << self.nmax) + self._level_off[q][i]
        loff_d = jnp.asarray(loff.astype(np.int32))
        spad = np.full(self.bcap, soff[self.B], np.int64)
        spad[: self.B] = soff[: self.B]
        soff_d = jnp.asarray(spad.astype(np.int32))
        nseg = self.chunk + 2
        if self.algorithm == "mpdp_tree":
            kernel = self._jit("btree", _beval_tree_chunk, nmax=self.nmax,
                               chunk=self.chunk, nseg=nseg, bcap=self.bcap,
                               pallas=self.pallas, typed=self.typed)
        else:
            kernel = self._jit("bdpsub", _beval_dpsub_chunk, nmax=self.nmax,
                               chunk=self.chunk, nseg=nseg, bcap=self.bcap,
                               pallas=self.pallas, typed=self.typed)
        ctx = {"pend": deque(),
               "best_cost": np.full(int(soff[-1]), INF, np.float32),
               "best_left": np.zeros(int(soff[-1]), np.int32),
               "ev": np.zeros(self.B, np.int64),
               "ccp": np.zeros(self.B, np.int64)}
        for lane0 in range(0, total, self.chunk):
            el = np.clip(eoff - lane0, -_CLIP, _CLIP)
            epad = np.full(self.bcap + 1, el[self.B], np.int32)
            epad[: self.B + 1] = el
            p0 = int(np.searchsorted(eoff, lane0, side="right")) - 1
            p0 = min(max(p0, 0), self.B - 1)
            seg0 = int(soff[p0] + (lane0 - eoff[p0]) // mult[p0])
            if self.algorithm == "mpdp_tree":
                out = kernel(self.all_sets, jnp.asarray(epad), loff_d, soff_d,
                             jnp.int32(seg0), self.m_b, self.adj_b,
                             self.emu_b, self.emv_b, self.memo_cost,
                             self.memo_rows, *self._targs)
            else:
                out = kernel(self.all_sets, jnp.asarray(epad), loff_d, soff_d,
                             jnp.int32(seg0), jnp.int32(i), self.adj_b,
                             self.memo_cost, self.memo_rows, *self._targs)
            ctx["pend"].append((seg0, out))
            faults.fire("chunk")
            self.chunks_dispatched += 1
            self._eval_drain(ctx, self.pend_window)
        return ctx

    def _eval_drain(self, ctx: dict, limit: int) -> None:
        """Fetch pending chunk results down to ``limit``, folding them into
        the level's best arrays (cost min, max-left tie-break — chunk order,
        identical to the synchronous path)."""
        pend = ctx["pend"]
        while len(pend) > limit:
            seg0, out = pend.popleft()
            sc, sl, ev_q, ccp_q = fetch(out)
            ctx["ev"] += ev_q[: self.B]
            ctx["ccp"] += ccp_q[: self.B]
            _merge_best(ctx["best_cost"], ctx["best_left"], seg0, sc, sl)

    def _eval_finalize(self, i: int, sets_by_q: list[np.ndarray], ctx) -> None:
        """Drain the level's remaining chunk results and commit the level's
        best (cost, left) per set to the memo."""
        if ctx is None:
            return
        self._eval_drain(ctx, 0)
        for q in range(self.B):
            self.counters[q].evaluated += int(ctx["ev"][q])
            self.counters[q].ccp += int(ctx["ccp"][q])
        self._commit_best(sets_by_q, ctx["best_cost"], ctx["best_left"])

    # ------------------------------------------------- MPDP-general phase --
    def _pairs_level(self, sets_by_q: list[np.ndarray]):
        """Phase A per query (shared ``blocks.np_pairs_for_sets`` driver),
        fused into global (set, block, qid, segment) pair arrays."""
        soff = 0
        ps_l, pb_l, pq_l, pk_l = [], [], [], []
        for q, sets_q in enumerate(sets_by_q):
            if not len(sets_q):
                continue
            ps_q, pb_q, slots = bl.np_pairs_for_sets(
                sets_q, self.graphs[q], self.adj_b[q], self.eu_idx_b[q],
                self.ev_idx_b[q], self.edge_live_b[q],
                nmax=self.nmax, emax=self.emax, cyc_cap=self.cyc_cap)
            self.blocks_sets += len(sets_q)
            self.blocks_slots += slots
            ps_l.append(ps_q)
            pb_l.append(pb_q)
            pq_l.append(np.full(len(ps_q), q, np.int32))
            # sets_q is ascending (colex rank order == ascending bitmap)
            pk_l.append(soff + np.searchsorted(sets_q, ps_q).astype(np.int64))
            soff += len(sets_q)
        if not ps_l:
            z = np.zeros(0, np.int32)
            return z, z, z, np.zeros(0, np.int64)
        return (np.concatenate(ps_l), np.concatenate(pb_l),
                np.concatenate(pq_l), np.concatenate(pk_l))

    def _eval_general_dispatch(self, i: int, sets_by_q: list[np.ndarray],
                               pairs):
        """Dispatch the level's block prefix-sum chunks over the fused pair
        arrays from ``_pairs_level`` (phase A, host).  No host sync."""
        ps, pb, pq, pk = pairs
        if not len(ps):
            return None
        sizes = bs.np_popcount(pb).astype(np.int64)
        lane_sz = (np.int64(1) << sizes).astype(np.int64)
        offs = np.zeros(len(ps) + 1, np.int64)
        np.cumsum(lane_sz, out=offs[1:])
        total = int(offs[-1])
        ctx = {"pend": deque(), "pk": pk,
               "total_sets": sum(len(s) for s in sets_by_q),
               "ev": np.zeros(self.B, np.int64),
               "ccp": np.zeros(self.B, np.int64),
               "k": [], "c": [], "l": []}
        for lane0 in range(0, total, self.chunk):
            lane1 = min(lane0 + self.chunk, total)
            p0 = int(np.searchsorted(offs, lane0, side="right")) - 1
            p1 = int(np.searchsorted(offs, lane1, side="left"))
            npair = p1 - p0
            pcap = _cap(npair, 256)
            psl = np.zeros(pcap, np.int32)
            pbl = np.zeros(pcap, np.int32)
            pql = np.zeros(pcap, np.int32)
            ofl = np.full(pcap, np.int64(1 << 40), np.int64)
            psl[:npair] = ps[p0:p1]
            pbl[:npair] = pb[p0:p1]
            pql[:npair] = pq[p0:p1]
            ofl[:npair] = offs[p0:p1] - lane0
            ofl = np.clip(ofl, -_CLIP, _CLIP).astype(np.int32)
            kernel = self._jit("bgeneral", _beval_general_chunk,
                               nmax=self.nmax, chunk=self.chunk, pcap=pcap,
                               bcap=self.bcap, pallas=self.pallas,
                               typed=self.typed)
            out = kernel(jnp.asarray(psl), jnp.asarray(pbl), jnp.asarray(pql),
                         jnp.asarray(ofl), jnp.int32(npair),
                         jnp.int32(lane1 - lane0), self.adj_b,
                         self.memo_cost, self.memo_rows, *self._targs)
            ctx["pend"].append((p0, npair, out))
            faults.fire("chunk")
            self.chunks_dispatched += 1
            self._eval_general_drain(ctx, self.pend_window)
        return ctx

    def _eval_general_drain(self, ctx: dict, limit: int) -> None:
        """Fetch pending pair chunks down to ``limit``, collecting finite
        per-pair candidates for the scattered merge."""
        pend, pk = ctx["pend"], ctx["pk"]
        while len(pend) > limit:
            p0, npair, out = pend.popleft()
            sc, sl, ev_q, ccp_q = fetch(out)
            ctx["ev"] += ev_q[: self.B]
            ctx["ccp"] += ccp_q[: self.B]
            scn = sc[:npair]
            fin = np.isfinite(scn)
            ctx["k"].append(pk[p0: p0 + npair][fin])
            ctx["c"].append(scn[fin])
            ctx["l"].append(sl[:npair][fin])

    def _eval_general_finalize(self, i: int, sets_by_q: list[np.ndarray],
                               ctx) -> None:
        if ctx is None:
            return
        self._eval_general_drain(ctx, 0)
        best_cost = np.full(ctx["total_sets"], INF, np.float32)
        best_left = np.zeros(ctx["total_sets"], np.int32)
        for q in range(self.B):
            self.counters[q].evaluated += int(ctx["ev"][q])
            self.counters[q].ccp += int(ctx["ccp"][q])
        if ctx["k"]:
            _merge_scattered(best_cost, best_left, np.concatenate(ctx["k"]),
                             np.concatenate(ctx["c"]),
                             np.concatenate(ctx["l"]))
        self._commit_best(sets_by_q, best_cost, best_left)

    # ------------------------------------------------------------ driver ---
    def collect(self) -> list[OptimizeResult]:
        """Fetch the memo and extract one ``OptimizeResult`` per query.  In
        the streaming service this host-only finalize is deferred so it
        overlaps the next flight's device work."""
        t0 = time.perf_counter()
        cost_all, left_all = fetch((self.memo_cost, self.memo_left))
        out = []
        wall = self._wall + time.perf_counter() - t0
        for q, g in enumerate(self.graphs):
            base = q << self.nmax
            cost = float(cost_all[base + g.full_set])
            if np.isfinite(cost):
                p = extract_plan(g.full_set, left_all[base: base + self.size],
                                 g)
                r = OptimizeResult(plan=p, cost=cost,
                                   counters=self.counters[q],
                                   algorithm=f"batch_{self.algorithm}",
                                   wall_s=wall / self.B, levels=g.n)
            elif self.degraded is not None:
                # deadline expired mid-batch: anytime stitch over this
                # query's committed memo prefix (exact islands + GOO finish)
                from ..heuristics.idp import stitch_partial_memo
                p, c, dinfo = stitch_partial_memo(
                    g, cost_all[base: base + self.size],
                    left_all[base: base + self.size])
                r = OptimizeResult(plan=p, cost=c, counters=self.counters[q],
                                   algorithm=f"batch_{self.algorithm}",
                                   wall_s=wall / self.B,
                                   levels=self.degraded["levels_done"])
                r.info["degraded"] = {**self.degraded, **dinfo}
            else:
                raise RuntimeError(f"no plan found for batch query {q}")
            out.append(r)
        return out



# ============================================================ public entry ==

def _lane_space(g: JoinGraph, algorithm: str) -> str | None:
    """Batched lane space for one query under the requested algorithm, or
    ``None`` when the query must fall back to per-query ``optimize``.

    ``auto``/``mpdp`` pick the cheap MPDP space from the query's topology
    (acyclic -> tree lanes, else general), so a bucket fuses only queries
    sharing one lane-space decode; ``dpsub`` keeps the full ``sets x 2^i``
    space; explicit ``mpdp_general`` forces the block prefix-sum lanes (it
    is valid for trees too); explicit ``mpdp_tree`` batches only acyclic
    queries (cyclic ones keep sequential ``mpdp_tree`` semantics per query).
    """
    if algorithm in ("auto", "mpdp"):
        return "mpdp_tree" if g.is_tree() else "mpdp_general"
    if algorithm == "dpsub":
        return "dpsub"
    if algorithm == "mpdp_general":
        return "mpdp_general"
    if algorithm == "mpdp_tree":
        return "mpdp_tree" if g.is_tree() else None
    return None


# Stream-admission building blocks, shared verbatim by ``optimize_many``
# and the streaming service (``core.service``) — the service's bit-identity
# with ``optimize_many`` rests on both using exactly these steps.

def probe_stream(graphs, results, cache, algorithm: str) -> list[int]:
    """Upfront cache probe + single-relation short-circuit: fills hits and
    leaf plans into ``results`` (in place), returns the stream indices that
    still need an engine."""
    pending: list[int] = []
    for qi, g in enumerate(graphs):
        if results[qi] is not None:
            continue
        if cache is not None:
            hit = cache.get(g)
            if hit is not None:
                results[qi] = hit
                continue
        if g.n == 1:
            p = leaf_plan(0, g)
            results[qi] = OptimizeResult(plan=p, cost=p.cost,
                                         counters=Counters(),
                                         algorithm=algorithm, levels=1)
            continue
        pending.append(qi)
    return pending


def dedup_pending(graphs, pending: list[int], cache):
    """Intra-stream dedup (caching only): canonically-equal queries compute
    once; duplicates are deferred and resolve as cache hits after their
    representative lands.  Returns ``(kept, deferred, dup_rep)``."""
    if cache is None:
        return pending, [], {}
    from .plancache import canonical_signature
    rep_of: dict = {}
    kept: list[int] = []
    deferred: list[int] = []
    dup_rep: dict[int, int] = {}          # duplicate index -> representative
    for qi in pending:
        key, _ = canonical_signature(graphs[qi])
        if key in rep_of:
            deferred.append(qi)
            dup_rep[qi] = rep_of[key]
        else:
            rep_of[key] = qi
            kept.append(qi)
    return kept, deferred, dup_rep


def bucket_pending(graphs, pending: list[int], algorithm: str):
    """Admission grouping: (NMAX bucket, lane space, typed) -> stream
    indices.  Typed queries (some non-inner edge) bucket separately from
    inner-only ones so the latter keep their pre-typed kernel traces —
    the byte-identity guarantee for inner-only streams.  Queries no batched
    space can serve (forced ``mpdp_tree`` on a cyclic graph,
    ``nmax_bucket(n) > NMAX_BATCH``) come back in the solo list."""
    buckets: dict[tuple[int, str, bool], list[int]] = {}
    solo: list[int] = []
    for qi in pending:
        b = bs.nmax_bucket(graphs[qi].n)
        space = _lane_space(graphs[qi], algorithm)
        if space is not None and b <= NMAX_BATCH:
            buckets.setdefault((b, space, graphs[qi].typed), []).append(qi)
        else:
            solo.append(qi)
    return buckets, solo


def lattice_pending(graphs, solo: list[int], algorithm: str):
    """Split the solo fallback list into lattice-sharded flights and true
    solos (mesh runs only).  A query is lattice-eligible when it has a
    batched lane space but is too big for the stacked batch memo
    (``nmax_bucket(n) > NMAX_BATCH``) and still fits the lattice cap —
    exactly the queries that used to pay the single-device memory-capped
    ``engine.optimize`` path.  Returns ``(lattice, rest)`` with ``lattice``
    a list of ``(stream index, lane space)``.
    """
    from .lattice import NMAX_LATTICE
    lattice: list[tuple[int, str]] = []
    rest: list[int] = []
    for qi in solo:
        g = graphs[qi]
        space = _lane_space(g, algorithm)
        if (space is not None and g.n >= 2
                and bs.nmax_bucket(g.n) > NMAX_BATCH and g.n <= NMAX_LATTICE):
            lattice.append((qi, space))
        else:
            rest.append(qi)
    return lattice, rest


def resolve_deferred(graphs, results, cache, deferred, dup_rep) -> None:
    """Resolve deduped duplicates as cache hits (re-inserting the
    representative when a tiny LRU evicted it mid-stream)."""
    for qi in deferred:
        hit = cache.get(graphs[qi])
        if hit is None:
            rep = dup_rep[qi]
            cache.put(graphs[rep], results[rep])
            hit = cache.get(graphs[qi])
        results[qi] = hit


def optimize_many(graphs: list[JoinGraph], algorithm=UNSET, chunk=UNSET,
                  cache=UNSET, max_flight=UNSET, devices=UNSET, mesh=UNSET,
                  pipeline=UNSET, max_batch=UNSET, policy=UNSET, *,
                  config: OptimizerConfig | None = None
                  ) -> list[OptimizeResult]:
    """Optimize a stream of queries, batching compatible ones per device pass.

    All knobs can be passed as one ``config=OptimizerConfig(...)`` instead
    of the legacy kwargs (never both; ``max_batch=`` is the deprecated
    alias of the canonical ``max_flight=``).

    * ``cache``: optional ``plancache.PlanCache`` consulted first; computed
      plans are inserted back.
    * ``algorithm``: {auto, mpdp, dpsub, mpdp_tree, mpdp_general} run the
      batched engine; ``auto``/``mpdp`` dispatch each (NMAX, topology) bucket
      to the cheapest lane space (all-acyclic -> MPDP:Tree ``sets x m``, else
      MPDP-general block prefix-sum; see ``_lane_space``).  All lane spaces
      enumerate the same CCP candidate minima -> identical optimal costs;
      anything else falls back to per-query ``engine.optimize``.
    * ``devices`` / ``mesh``: shard each bucket's batch dimension across a
      1-D device mesh (``shard.ShardedBatchEngine``): ``devices=N`` builds a
      mesh over the first N devices (raising, never truncating, when fewer
      exist), ``mesh=`` supplies one.  Both default to the single-device
      in-process ``BatchEngine``; costs/plans are bit-identical either way,
      a 1-device mesh being the degenerate case.  With a mesh present the
      dispatcher also routes *oversized* solo queries
      (``nmax_bucket(n) > NMAX_BATCH``, ``n <= lattice.NMAX_LATTICE``) to
      the intra-query ``lattice.LatticeShardedEngine`` — the lane space of
      the single query sharded over the same mesh — instead of the
      memory-capped per-query fallback.
    * ``pipeline``: run the batched engines pipelined (host compaction of
      level i+1 under device evaluate of level i; bit-identical results).
      ``None`` defers to the ``REPRO_PIPELINE`` env flag.
    * ``policy``: optional ``policy.PolicyTable``.  Under ``auto``/``mpdp``
      dispatch it may swap a bucket's lane space for a learned-faster one
      and shrink the chunk / drain window; every flight's telemetry is fed
      back.  All spaces enumerate the same CCP minima, so costs and plans
      are identical either way; ``None`` (default) is the static path.
    * queries with ``nmax_bucket(n) > NMAX_BATCH`` (memo would not fit the
      stacked layout) and single-relation queries are handled per query.

    Results are returned in input order.
    """
    from . import engine as _eng
    max_flight = alias_kwarg(max_flight, max_batch, "max_batch", "max_flight")
    cfg = resolve_config(config, algorithm=algorithm, chunk=chunk,
                         cache=cache, max_flight=max_flight, devices=devices,
                         mesh=mesh, pipeline=pipeline, policy=policy)
    algorithm, chunk, cache = cfg.algorithm, cfg.chunk, cfg.cache
    pipeline = cfg.pipeline
    # learned policies only steer the auto dispatcher: an explicit lane
    # space is a user decision the policy must not override
    adaptive = cfg.policy if algorithm in ("auto", "mpdp") else None
    shard_mesh = None
    if cfg.mesh is not None or cfg.devices is not None:
        from . import shard as _shard
        shard_mesh = _shard.batch_mesh(
            cfg.mesh if cfg.mesh is not None else cfg.devices)
    results: list[OptimizeResult | None] = [None] * len(graphs)
    lattice: list[tuple[int, str]] = []
    with span("service.admit"):
        pending = probe_stream(graphs, results, cache, algorithm)
        pending, deferred, dup_rep = dedup_pending(graphs, pending, cache)
        buckets, solo = bucket_pending(graphs, pending, algorithm)
        if shard_mesh is not None:
            lattice, solo = lattice_pending(graphs, solo, algorithm)

    # one absolute deadline for the whole stream: each engine gets the time
    # still remaining, so sequential buckets share the budget instead of
    # each restarting it
    deadline_at = (None if cfg.deadline_s is None
                   else faults.now() + cfg.deadline_s)

    def _left() -> float | None:
        if deadline_at is None:
            return None
        return max(deadline_at - faults.now(), 1e-9)

    # sub-batch step: per-shard sub-batches stay capped at max_flight
    step = cfg.max_flight if shard_mesh is None else \
        cfg.max_flight * _shard.mesh_size(shard_mesh)
    for (b, space, _typed), idxs in sorted(buckets.items()):
        for s0 in range(0, len(idxs), step):
            group = idxs[s0: s0 + step]
            run_space, run_chunk, run_kw = space, chunk, {}
            if adaptive is not None:
                dec = adaptive.choose(b, space, default_chunk=chunk,
                                      default_pend=PEND_WINDOW)
                if dec.space is not None:
                    run_space = dec.space
                if dec.chunk is not None:
                    run_chunk = dec.chunk
                if dec.pend_window is not None:
                    run_kw["pend_window"] = dec.pend_window
                t_fl = time.perf_counter()
            if shard_mesh is None:
                eng = BatchEngine([graphs[qi] for qi in group],
                                  chunk=run_chunk, algorithm=run_space,
                                  pipeline=pipeline, deadline_s=_left(),
                                  **run_kw)
                rs = eng.run()
                redispatched = False
            else:
                eng = _shard.ShardedBatchEngine(
                    [graphs[qi] for qi in group], shard_mesh, chunk=run_chunk,
                    algorithm=run_space, pipeline=pipeline,
                    deadline_s=_left(), **run_kw)
                try:
                    rs = eng.run()
                    redispatched = False
                except _shard.REDISPATCH_ERRORS as e:
                    # device-runtime failure on the mesh: re-dispatch the
                    # bucket on the in-process single-device engine
                    _shard.log_redispatch(e, len(group))
                    eng = BatchEngine([graphs[qi] for qi in group],
                                      chunk=run_chunk, algorithm=run_space,
                                      pipeline=pipeline, deadline_s=_left(),
                                      **run_kw)
                    rs = eng.run()
                    redispatched = True
            if adaptive is not None:
                from . import telemetry as _tele
                adaptive.observe(b, space, run_space, _tele.capture(
                    eng, rs, nmax=b, queries=len(group),
                    wall_s=time.perf_counter() - t_fl))
            with span("service.finalize"):
                for qi, r in zip(group, rs):
                    if redispatched:
                        r.info["redispatched"] = True
                    results[qi] = r
                    # degraded plans are best-effort, never cached: a later
                    # undegraded run must not hit a deadline-truncated plan
                    if cache is not None and "degraded" not in r.info:
                        cache.put(graphs[qi], r)
    for qi, space in lattice:
        from .lattice import LatticeShardedEngine
        r = LatticeShardedEngine(graphs[qi], shard_mesh, chunk=chunk,
                                 algorithm=space, pipeline=pipeline,
                                 deadline_s=_left()).run()[0]
        results[qi] = r
        if cache is not None and "degraded" not in r.info:
            cache.put(graphs[qi], r)
    for qi in solo:
        if cfg.deadline_s is None:
            r = _eng.optimize(graphs[qi], algorithm, chunk=chunk)
        else:
            r = _eng.optimize(graphs[qi], config=OptimizerConfig(
                algorithm=algorithm, chunk=chunk, cyc_cap=cfg.cyc_cap,
                enum=cfg.enum, deadline_s=_left()))
        results[qi] = r
        if cache is not None and "degraded" not in r.info:
            cache.put(graphs[qi], r)
    resolve_deferred(graphs, results, cache, deferred, dup_rep)
    return results
