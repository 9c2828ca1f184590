"""Level-synchronous massively-parallel DP engine (paper Alg. 5, TPU-adapted).

The GPU pipeline *unrank -> filter -> evaluate -> prune -> scatter* maps to:

  unrank    combinatorial-number-system unranking inside the filter kernel
  filter    connectivity mask on rank chunks; the host compacts (playing the
            role of the paper's CPU driver / thrust::remove)
  evaluate  algorithm-specific flat *lane space* per DP level, processed in
            fixed-size chunks: DPSUB ``sets x 2^i``, MPDP:Tree ``sets x m``,
            MPDP-general ``sum over (set, block) pairs of 2^|block|`` decoded
            via searchsorted on a prefix-sum (the warp/thread grid becomes a
            dense vector of lanes; invalid pairs are masked lanes — the TPU
            analogue of Collaborative Context Collection)
  prune     in-chunk ``segment_min`` per set + argmin-by-equality (the paper's
            in-warp reduction; one memo write per set)
  scatter   dense memo tables indexed by subset bitmap (the TPU-native
            replacement of the Murmur3 GPU hash table)

All kernels take the query (adjacency bitmaps, edge masks, stats) as *dynamic*
inputs, so one compilation per (NMAX, EMAX, CHUNK) bucket serves every query
and every IDP2/UnionDP subproblem.
"""
from __future__ import annotations

import time
from functools import partial
from math import comb

import numpy as np
import jax
import jax.numpy as jnp

from . import bitset as bs
from . import blocks as bl
from . import conflicts as cf
from . import cost as cm
from . import faults
from . import unrank as ur
# CHUNK / CYC_CAP_DEFAULT live in core.config (the root of the constant
# DAG) and are re-exported here for the historical import path
from .config import (CHUNK, CYC_CAP_DEFAULT, UNSET, OptimizerConfig,
                     alias_kwarg, resolve_config)
from .joingraph import DeviceGraph, JoinGraph
from .plan import Counters, OptimizeResult, extract_plan
from .telemetry import fetch, fetch_in_waves, span

INF = np.float32(np.inf)


def _use_pallas() -> bool:
    """REPRO_PALLAS=1 routes the bit-twiddling evaluate phase through the
    Pallas TPU kernels (compiled on a TPU, interpreted on the CPU backend;
    see ``kernels.ops.interpret_mode``)."""
    import os
    return os.environ.get("REPRO_PALLAS", "0") == "1"


def _use_pipeline() -> bool:
    """REPRO_PIPELINE=1 makes the batched engines run pipelined: device
    evaluation of level i is dispatched asynchronously while the host
    compacts (and rows-costs, and block-decomposes) level i+1.  Results are
    bit-identical to the synchronous default — only dispatch order changes."""
    import os
    return os.environ.get("REPRO_PIPELINE", "0") == "1"


def _cap(n: int, lo: int = 1024) -> int:
    c = lo
    while c < n:
        c <<= 1
    return c


# =========================================================== jitted kernels ==

@partial(jax.jit, static_argnames=("nmax", "chunk"))
def _filter_chunk(rank0, total, k, binom, adj, *, nmax: int, chunk: int):
    """unrank + connectivity filter: each lane's k-subset where it is
    connected, else 0 (no k-subset of k >= 1 is 0).  Rows are costed on the
    host afterwards, via the canonical ``cost.np_rows_for_sets`` shared
    with BatchEngine."""
    t = jnp.arange(chunk, dtype=jnp.int32)
    ranks = rank0 + t
    mask = ranks < total
    S = ur.unrank_ksubset(jnp.minimum(ranks, total - 1), k, binom, nmax)
    if _use_pallas():
        from ..kernels import ops as _ko
        conn = (_ko.connectivity(S, adj, nmax) != 0) & mask
    else:
        conn = bs.is_connected(S, adj) & mask
    return jnp.where(conn, S, 0)


@partial(jax.jit, static_argnames=("nmax", "cap"))
def _expand_chunk(sets_pad, n_valid, adj, *, nmax: int, cap: int):
    """Beyond-paper enumeration: grow level-(i-1) connected sets by one
    neighbour each (host dedups) — skips unranking the full C(n,i) space."""
    S = sets_pad
    nbr = bs.neighbors(S, adj) & ~S                    # (cap,)
    shifts = jnp.arange(nmax, dtype=jnp.int32)
    has = ((nbr[:, None] >> shifts) & 1) == 1          # (cap, nmax)
    cand = jnp.where(has, S[:, None] | (jnp.int32(1) << shifts), 0)
    live = (jnp.arange(cap) < n_valid)[:, None]
    return jnp.where(live, cand, 0)


@partial(jax.jit, static_argnames=("size", "cap"), donate_argnums=(0,))
def _scatter_f32(buf, idx, val, *, size: int, cap: int):
    return buf.at[idx].set(val, mode="drop")


@partial(jax.jit, static_argnames=("size", "cap"), donate_argnums=(0,))
def _scatter_i32(buf, idx, val, *, size: int, cap: int):
    return buf.at[idx].set(val, mode="drop")


# a solo memo write is padded to SCATTER_SMALL entries, or split into pieces
# of SCATTER_BIG: few executables, since each takes the compiler a while
SCATTER_SMALL, SCATTER_BIG = 1 << 12, 1 << 16


@partial(jax.jit, donate_argnums=(0,))
def _scatter_sorted(buf, idx, val):
    """``buf[idx] = val`` for ascending, distinct ``idx``; those past the
    end are dropped.  The promise spares XLA its handling of repeated
    indices: compiling a 2^16-entry scatter for a v5e takes 0.1 s so
    made, and 8 s without it."""
    return buf.at[idx].set(val, mode="drop", indices_are_sorted=True,
                           unique_indices=True)


def _lane_cost(S_left, S_right, S_rows, memo_cost, memo_rows):
    cl = memo_cost[S_left]
    cr = memo_cost[S_right]
    jc = cm.join_cost(memo_rows[S_left], memo_rows[S_right], S_rows)
    return cl + cr + jc


def _typed_lane_cost(lb, rb, S_rows, ccp, cl, cr, rl, rr,
                     ekind, elm, erm, etes_l, etes_r):
    """Typed twin of ``_lane_cost``: evaluates BOTH operand orientations of
    the (lb, rb) split under the conflict mask and returns the cheaper valid
    candidate plus its chosen left bitmap (ties prefer lb, the
    enumeration-order operand).  ``cl``/``cr``/``rl``/``rr`` are the
    pre-gathered per-lane memo cost/rows of lb/rb (the batch engines gather
    with their region offsets).  Cost addition order matches ``_lane_cost``
    (``(cl + cr) + jc``) so the host oracle reproduces every bit."""
    va, vb, lk = cf.lane_valid_kinds(lb, rb, ekind, elm, erm, etes_l, etes_r)
    base = cl + cr
    cand_a = jnp.where(ccp & va, base + cm.join_cost_kind(rl, rr, S_rows, lk),
                       INF)
    cand_b = jnp.where(ccp & vb, base + cm.join_cost_kind(rr, rl, S_rows, lk),
                       INF)
    return jnp.minimum(cand_a, cand_b), jnp.where(cand_b < cand_a, rb, lb)


def _merge_best(best_cost, best_left, base, seg_cost, seg_left):
    """Fold a chunk's per-segment minima into the level's host-side best
    arrays (min cost, ties broken by max left bitmap).  Shared by ExactEngine
    and BatchEngine — the tie-break must stay identical to keep batched and
    sequential plans in lockstep."""
    nseg = len(seg_cost)
    idx = base + np.arange(nseg)
    ok = (idx >= 0) & (idx < len(best_cost))
    idx = idx[ok]
    sc = seg_cost[ok]
    sl = seg_left[ok]
    better = (sc < best_cost[idx]) | ((sc == best_cost[idx]) & (sl > best_left[idx]))
    upd = idx[better]
    best_cost[upd] = sc[better]
    best_left[upd] = sl[better]


def _merge_scattered(best_cost, best_left, ks, cs, ls):
    """Fold scattered per-key candidate (cost, left) pairs into host-side
    best arrays: min cost per key, ties broken by max left bitmap.  Shared
    by MPDP-general (sequential and batched) and DPSIZE — like
    ``_merge_best``, the tie-break must stay identical everywhere to keep
    batched and sequential plans in lockstep."""
    np.minimum.at(best_cost, ks, cs)
    tie = cs == best_cost[ks]
    np.maximum.at(best_left, ks[tie], ls[tie])


def _prune(seg, cand_cost, cand_left, nseg: int):
    """Two-pass in-chunk prune: segment-min cost then max-left among ties."""
    seg_cost = jax.ops.segment_min(cand_cost, seg, num_segments=nseg,
                                   indices_are_sorted=True)
    is_best = cand_cost == seg_cost[seg]
    left_cand = jnp.where(is_best & jnp.isfinite(cand_cost), cand_left, 0)
    seg_left = jax.ops.segment_max(left_cand, seg, num_segments=nseg,
                                   indices_are_sorted=True)
    return seg_cost, seg_left


@partial(jax.jit, static_argnames=("nmax", "chunk", "nseg", "typed"))
def _eval_dpsub_chunk(all_sets, level_off, base_set, base_sub, i, lane_count,
                      adj, memo_cost, memo_rows,
                      ekind=None, elm=None, erm=None, etes_l=None, etes_r=None,
                      *, nmax: int, chunk: int, nseg: int, typed: bool = False):
    t = jnp.arange(chunk, dtype=jnp.int32)
    sub_g = base_sub + t
    set_idx = base_set + (sub_g >> i)
    sub = sub_g & ((jnp.int32(1) << i) - 1)
    live = t < lane_count
    S = all_sets[level_off + set_idx]
    evaluated = live                                    # Alg.1 line 9
    if _use_pallas():
        from ..kernels import ops as _ko
        lb, rb, ccp_i = _ko.ccp_eval(S, sub, adj, nmax)
        ccp = live & (ccp_i != 0)
    else:
        lb = bs.pdep(sub, S, nmax)
        rb = S & ~lb
        nonempty = (lb != 0) & (rb != 0)
        conn_l = bs.is_connected(lb, adj)
        conn_r = bs.is_connected(rb, adj)
        cross = (bs.neighbors(lb, adj) & rb) != 0
        ccp = live & nonempty & conn_l & conn_r & cross
    rows_S = memo_rows[S]
    if typed:
        cand, lbx = _typed_lane_cost(
            lb, rb, rows_S, ccp, memo_cost[lb], memo_cost[rb],
            memo_rows[lb], memo_rows[rb], ekind, elm, erm, etes_l, etes_r)
    else:
        cand = jnp.where(ccp, _lane_cost(lb, rb, rows_S, memo_cost, memo_rows), INF)
        lbx = lb
    seg = set_idx - base_set
    seg_cost, seg_left = _prune(seg, cand, lbx, nseg)
    return seg_cost, seg_left, evaluated.sum(), ccp.sum()


@partial(jax.jit, static_argnames=("nmax", "chunk", "nseg", "typed"))
def _eval_tree_chunk(all_sets, level_off, base_set, base_e, m, lane_count,
                     adj, emask_u, emask_v, memo_cost, memo_rows,
                     ekind=None, elm=None, erm=None, etes_l=None, etes_r=None,
                     *, nmax: int, chunk: int, nseg: int, typed: bool = False):
    t = jnp.arange(chunk, dtype=jnp.int32)
    e_g = base_e + t
    set_idx = base_set + e_g // m
    e = e_g % m
    live = t < lane_count
    S = all_sets[level_off + set_idx]
    ub = emask_u[e]
    vb = emask_v[e]
    edge_in = live & ((S & ub) != 0) & ((S & vb) != 0)
    S_left = bs.grow_excl_edge(ub, S, adj, ub, vb)
    S_right = S & ~S_left
    # MPDP:Tree — every enumerated pair IS a CCP pair (Theorem 3)
    evaluated = edge_in
    ccp = edge_in
    rows_S = memo_rows[S]
    if typed:
        cand, lbx = _typed_lane_cost(
            S_left, S_right, rows_S, ccp, memo_cost[S_left],
            memo_cost[S_right], memo_rows[S_left], memo_rows[S_right],
            ekind, elm, erm, etes_l, etes_r)
    else:
        cand = jnp.where(ccp, _lane_cost(S_left, S_right, rows_S, memo_cost, memo_rows), INF)
        lbx = S_left
    seg = set_idx - base_set
    seg_cost, seg_left = _prune(seg, cand, lbx, nseg)
    return seg_cost, seg_left, evaluated.sum(), ccp.sum()


def _lane_pairs(off_local, n_pairs, chunk: int):
    """The pair of every lane of a chunk, ``searchsorted(off_local, t,
    side="right") - 1`` for the ascending pair offsets ``off_local``: the
    count of offsets at or below the lane, by a scatter of the offsets and
    a prefix sum.  A binary search's gathers cost a v5e 3 ms more a
    32,768-lane chunk.  Only the first pair can start before the chunk,
    and those past its end scatter to slots of their own, so the indices
    are ascending and distinct."""
    pcap = off_local.shape[0]
    idx = jnp.where(off_local >= chunk,
                    chunk + jnp.arange(pcap, dtype=jnp.int32),
                    jnp.maximum(off_local, 0))
    starts = jnp.zeros(chunk + pcap, jnp.int32).at[idx].set(
        1, indices_are_sorted=True, unique_indices=True)
    return jnp.clip(jnp.cumsum(starts[:chunk]) - 1, 0, n_pairs - 1)


@partial(jax.jit, static_argnames=("nmax", "chunk", "pcap", "typed"))
def _eval_general_chunk(pairs, n_pairs, lane_count,
                        adj, memo_cost, memo_rows,
                        ekind=None, elm=None, erm=None, etes_l=None, etes_r=None,
                        *, nmax: int, chunk: int, pcap: int,
                        typed: bool = False):
    """MPDP-general evaluate of one chunk.  ``pairs`` is i32[3, pcap]: the
    chunk's pair sets, blocks and lane offsets, one transfer.  Returns one
    i32[2 * pcap + 2]: the per-pair best costs (f32 bits), their left
    operands, and the evaluated and CCP lane counts, one fetch."""
    pair_set, pair_block, off_local = pairs[0], pairs[1], pairs[2]
    t = jnp.arange(chunk, dtype=jnp.int32)
    live = t < lane_count
    p = _lane_pairs(off_local, n_pairs, chunk)
    r = t - off_local[p]
    S = pair_set[p]
    block = pair_block[p]
    lb = bs.pdep(r, block, nmax)
    rb = block & ~lb
    enum_ok = live & (lb != 0) & (rb != 0)                 # Alg.3 line 6/7
    conn_l = bs.is_connected(lb, adj)
    conn_r = bs.is_connected(rb, adj)
    cross = (bs.neighbors(lb, adj) & rb) != 0
    ccp_blk = enum_ok & conn_l & conn_r & cross
    S_left = bs.grow(lb, S & ~rb, adj)                     # Alg.3 line 17
    S_right = S & ~S_left
    rows_S = memo_rows[pair_set][p]                        # one memo read a pair
    if typed:
        cand, lbx = _typed_lane_cost(
            S_left, S_right, rows_S, ccp_blk, memo_cost[S_left],
            memo_cost[S_right], memo_rows[S_left], memo_rows[S_right],
            ekind, elm, erm, etes_l, etes_r)
    else:
        cand = jnp.where(ccp_blk, _lane_cost(S_left, S_right, rows_S,
                                             memo_cost, memo_rows), INF)
        lbx = S_left
    seg_cost, seg_left = _prune(p, cand, lbx, pcap)
    return jnp.concatenate([
        jax.lax.bitcast_convert_type(seg_cost, jnp.int32), seg_left,
        jnp.stack([enum_ok.sum(), ccp_blk.sum()]).astype(jnp.int32)])


@partial(jax.jit, static_argnames=("nmax", "chunk"))
def _eval_dpsize_chunk(all_sets, off_a, off_b, count_b, base_a, base_b,
                       lane_count, adj, memo_cost, memo_rows,
                       card_l2, emask_u, emask_v, esel_l2,
                       *, nmax: int, chunk: int):
    """DPSIZE: cross product of the level-a and level-b set lists.

    Candidate minima are returned per lane-pair union set; the host merges
    (DPSIZE unions are scattered, no contiguous segments).
    """
    t = jnp.arange(chunk, dtype=jnp.int32)
    g = base_b + t
    ia = base_a + g // count_b
    ib = g % count_b
    live = t < lane_count
    A = all_sets[off_a + ia]
    B = all_sets[off_b + ib]
    evaluated = live
    disjoint = (A & B) == 0
    cross = (bs.neighbors(A, adj) & B) != 0
    ccp = live & disjoint & cross                          # A,B connected by construction
    S = A | B
    mem = bs.member_matrix(S, nmax).astype(jnp.float32)
    rows = mem @ card_l2
    inside = ((S[:, None] & emask_u[None, :]) != 0) & ((S[:, None] & emask_v[None, :]) != 0)
    rows = jnp.maximum(rows + jnp.where(inside, esel_l2[None, :], 0.0).sum(axis=1), 0.0)
    cand = jnp.where(ccp, _lane_cost(A, B, rows, memo_cost, memo_rows), INF)
    return S, rows, cand, A, evaluated.sum(), ccp.sum()


# ============================================================== host driver ==

# lanes of one unrank-filter launch (at least the engine's chunk): a lane
# costs the filter little, so a launch of 2^17 lanes instead of 2^15 saves
# three quarters of the launches' dispatches and profiler events (272
# launches a 25-relation query instead of 1,040)
FILTER_CHUNK = 1 << 17

class MemoTooLarge(ValueError):
    """The solo engine's dense memo does not fit the device's free memory:
    refused before anything is allocated."""

    def __init__(self, n: int, need: int, free: int):
        super().__init__(
            f"the exact memo of a {n}-relation query needs {need} device "
            f"bytes; the device has {free} free")
        self.n, self.need, self.free = n, need, free


def memo_bytes(nmax: int) -> int:
    """Bytes of the solo engine's four dense ``1 << nmax`` tables (memo
    cost, rows and left operand, and the packed level buffer), 4 B each."""
    return 16 << nmax


def memo_need_bytes(n: int, nmax: int) -> int:
    """A lower bound on the device bytes a solo run holds at its peak: the
    memo, plus one level's transfer buffers (set ids, positions, values and
    scatter indices, 4 B each, padded to a power of two) at the largest
    level, ``C(n, n // 2)`` sets.  Executables and the evaluate chunks'
    buffers are not counted: at n = 25 a TPU v5e's measured peak was 14 %
    above this, so a query just under the free bytes can still fail to
    allocate."""
    return memo_bytes(nmax) + 16 * _cap(comb(n, n // 2))


def device_free_bytes() -> int | None:
    """Free bytes of the default device, or None where it reports no memory
    statistics (the CPU backend)."""
    stats = jax.devices()[0].memory_stats()
    if not stats or "bytes_limit" not in stats:
        return None
    return int(stats["bytes_limit"]) - int(stats.get("bytes_in_use", 0))


class ExactEngine:
    """Runs one exact algorithm (dpsub / mpdp / dpsize) over a JoinGraph."""

    def __init__(self, g: JoinGraph, chunk: int = CHUNK,
                 cyc_cap: int = CYC_CAP_DEFAULT, enum: str = "unrank",
                 deadline_s: float | None = None):
        if not g.is_connected():
            raise ValueError("query graph must be connected (no cross products)")
        self.g = g
        self.deadline_s = deadline_s
        self._deadline_at: float | None = None
        self.degraded: dict | None = None
        self.enum = enum              # "unrank" (paper Alg.5) | "expand"
        self.dg = DeviceGraph.from_graph(g)
        self.n = g.n
        self.nmax = self.dg.nmax
        self.emax = self.dg.emax
        self.chunk = chunk
        self.cyc_cap = cyc_cap
        self.size = 1 << self.nmax
        with span("engine.setup"):
            self.binom = jnp.asarray(ur.binom_table(self.nmax))
            # edge vertex indices (for block finding)
            eu = np.full(self.emax, -1, np.int32)
            ev = np.full(self.emax, -1, np.int32)
            lv = np.zeros(self.emax, bool)
            for i, (u, v) in enumerate(g.edges):
                eu[i], ev[i], lv[i] = u, v, True
            self.eu_idx = jnp.asarray(eu)
            self.ev_idx = jnp.asarray(ev)
            self.edge_live = jnp.asarray(lv)
            # typed-edge conflict arrays: passed to the eval kernels (with
            # the typed=True static) only when the query has non-inner
            # edges, so the inner-only trace stays byte-identical to the
            # pre-typed engine
            self.typed = g.typed
            self._targs = ((self.dg.ekind, self.dg.elm, self.dg.erm,
                            self.dg.etes_l, self.dg.etes_r)
                           if self.typed else (None,) * 5)
            self.counters = Counters(memo_bytes=memo_bytes(self.nmax))
            need, free = memo_need_bytes(self.n, self.nmax), device_free_bytes()
            if free is not None and need > free:
                raise MemoTooLarge(self.n, need, free)
            self._init_memo()

    # ------------------------------------------------------------- memo ----
    def _init_memo(self):
        size = self.size
        self.memo_cost = jnp.full(size, INF, jnp.float32)
        self.memo_rows = jnp.zeros(size, jnp.float32)
        self.memo_left = jnp.zeros(size, jnp.int32)
        self.all_sets = jnp.zeros(size, jnp.int32)
        leaves = np.array([1 << v for v in range(self.n)], np.int32)
        lrows = self.g.log2_card.astype(np.float32)
        lcost = cm.np_scan_cost(lrows).astype(np.float32)
        self._scatter(leaves, cost=lcost, rows=lrows)
        self.all_sets = self.all_sets.at[jnp.arange(self.n)].set(jnp.asarray(leaves))
        self.level_off = {1: 0}
        self.level_cnt = {1: self.n}
        self._next_off = self.n

    def _scatter(self, sets_np, cost=None, rows=None, left=None):
        """Memo writes at ``sets_np``, ascending bitmaps (every level's sets
        and leaves are)."""
        if cost is not None:
            self.memo_cost = self._write(self.memo_cost, sets_np, cost)
        if rows is not None:
            self.memo_rows = self._write(self.memo_rows, sets_np, rows)
        if left is not None:
            self.memo_left = self._write(self.memo_left, sets_np, left)

    def _write(self, buf, idx, val):
        """``buf[idx] = val`` for ascending, distinct ``idx``: in pieces of
        at most ``SCATTER_BIG`` entries, each padded to ``SCATTER_SMALL``
        or ``SCATTER_BIG`` with distinct indices past the memo's end, which
        ``_scatter_sorted`` drops.  Two executables a dtype."""
        if np.any(np.diff(idx) <= 0):
            raise ValueError("memo writes need ascending, distinct indices")
        for s0 in range(0, len(idx), SCATTER_BIG):
            n = min(SCATTER_BIG, len(idx) - s0)
            cap = SCATTER_SMALL if n <= SCATTER_SMALL else SCATTER_BIG
            pi = self.size + np.arange(cap, dtype=np.int64)
            pi[:n] = idx[s0: s0 + n]
            pv = np.zeros(cap, buf.dtype)
            pv[:n] = val[s0: s0 + n]
            buf = _scatter_sorted(buf, pi.astype(np.int32), pv)
        return buf

    # ------------------------------------------------------------ filter ---
    def _level_sets(self, i: int):
        """Connected sets of level i (unrank+filter, or frontier expansion)."""
        with span("level.filter"):
            if self.enum == "expand":
                sets_np = self._level_sets_expand(i)
            else:
                sets_np = self._level_sets_unrank(i)
        self._prev_level = sets_np
        self.counters.filter_sets += len(sets_np)
        # scatter rows for this level; register in the packed level buffer
        if len(sets_np):
            with span("level.register"):
                self._scatter(sets_np,
                              rows=cm.np_rows_for_sets(sets_np, self.g))
                self.all_sets = self._write(
                    self.all_sets, self._next_off + np.arange(len(sets_np)),
                    sets_np)
        self.level_off[i] = self._next_off
        self.level_cnt[i] = len(sets_np)
        self._next_off += len(sets_np)
        return sets_np

    def _level_sets_unrank(self, i: int):
        """Paper Alg.5: unrank the full C(n, i) space, mask connectivity."""
        total = comb(self.n, i)
        chunk = max(self.chunk, FILTER_CHUNK)
        sets_l = []
        for _, S in fetch_in_waves(
                lambda rank0: (None, _filter_chunk(
                    np.int32(rank0), np.int32(total), np.int32(i),
                    self.binom, self.dg.adj, nmax=self.nmax, chunk=chunk)),
                range(0, total, chunk)):
            self.counters.filter_lanes += chunk
            sets_l.append(S[S != 0])
        if sets_l:
            return np.concatenate(sets_l)
        return np.zeros(0, np.int32)

    def _level_sets_expand(self, i: int):
        """Beyond-paper: expand level i-1 connected sets by one neighbour and
        dedup — O(|L_{i-1}| * deg) instead of O(C(n, i)); big win on sparse
        graphs where most subsets are disconnected."""
        if i == 2:
            prev = np.array([1 << v for v in range(self.n)], np.int32)
        else:
            prev = self._prev_level
        if not len(prev):
            return np.zeros(0, np.int32)
        cand_l = []
        for s0 in range(0, len(prev), self.chunk):
            sl = prev[s0: s0 + self.chunk]
            cap = _cap(len(sl))
            pad = np.zeros(cap, np.int32)
            pad[: len(sl)] = sl
            cand = _expand_chunk(jnp.asarray(pad), jnp.int32(len(sl)),
                                 self.dg.adj, nmax=self.nmax, cap=cap)
            self.counters.filter_lanes += cap * self.nmax
            c = fetch(cand).ravel()
            cand_l.append(c[c != 0])
        return np.unique(np.concatenate(cand_l)) if cand_l else np.zeros(0, np.int32)

    # ----------------------------------------------------------- merging ---
    def _commit_level(self, sets_np, best_cost, best_left):
        fin = np.isfinite(best_cost)
        self._scatter(sets_np[fin], cost=best_cost[fin], left=best_left[fin])

    # ---------------------------------------------------------- deadline ---
    def _arm_deadline(self):
        """Start the cooperative deadline clock (one ``faults.now()`` call;
        no-op without ``deadline_s``)."""
        self._deadline_at = (None if self.deadline_s is None
                             else faults.now() + self.deadline_s)

    def _expired(self, i: int) -> bool:
        """Checked once at the top of every DP level: past the deadline the
        run abandons levels >= i and ``result`` stitches a best-effort plan
        from the committed memo prefix."""
        if self._deadline_at is None:
            return False
        if faults.now() < self._deadline_at:
            return False
        self.degraded = {"reason": "deadline", "deadline_s": self.deadline_s,
                         "levels_done": i - 1, "levels_total": self.n}
        return True

    def _run_levels(self, eval_level) -> None:
        """The solo level loop: connected sets of each level, then
        ``eval_level(i, sets)`` evaluates and commits them, under an
        ``engine.levels`` span (the batched engines' ``_LevelLoop`` names
        its steps alike)."""
        self._arm_deadline()
        with span("engine.levels"):
            for i in range(2, self.n + 1):
                if self._expired(i):
                    break
                sets_np = self._level_sets(i)
                if len(sets_np):
                    eval_level(i, sets_np)

    # -------------------------------------------------------------- DPSUB --
    def run_dpsub(self) -> None:
        self._run_levels(self._eval_dpsub)

    def _eval_dpsub(self, i: int, sets_np) -> None:
        with span("level.eval"):
            ns = len(sets_np)
            lanes = ns << i
            best_cost = np.full(ns, INF, np.float32)
            best_left = np.zeros(ns, np.int32)
            off = self.level_off[i]
            for lane0, (sc, sl, ev, cc) in fetch_in_waves(
                    lambda lane0: (lane0, _eval_dpsub_chunk(
                        self.all_sets, np.int32(off), np.int32(lane0 >> i),
                        np.int32(lane0 & ((1 << i) - 1)), np.int32(i),
                        np.int32(min(self.chunk, lanes - lane0)),
                        self.dg.adj, self.memo_cost, self.memo_rows,
                        *self._targs, nmax=self.nmax, chunk=self.chunk,
                        nseg=self.chunk + 1, typed=self.typed)),
                    range(0, lanes, self.chunk)):
                self.counters.evaluated += int(ev)
                self.counters.ccp += int(cc)
                _merge_best(best_cost, best_left, lane0 >> i, sc, sl)
            self._commit_level(sets_np, best_cost, best_left)

    # ---------------------------------------------------------- MPDP tree --
    def run_mpdp_tree(self) -> None:
        self._run_levels(self._eval_mpdp_tree)

    def _eval_mpdp_tree(self, i: int, sets_np) -> None:
        m = self.g.m
        with span("level.eval"):
            ns = len(sets_np)
            lanes = ns * m
            best_cost = np.full(ns, INF, np.float32)
            best_left = np.zeros(ns, np.int32)
            off = self.level_off[i]
            for lane0, (sc, sl, ev, cc) in fetch_in_waves(
                    lambda lane0: (lane0, _eval_tree_chunk(
                        self.all_sets, np.int32(off), np.int32(lane0 // m),
                        np.int32(lane0 % m), np.int32(m),
                        np.int32(min(self.chunk, lanes - lane0)),
                        self.dg.adj, self.dg.emask_u, self.dg.emask_v,
                        self.memo_cost, self.memo_rows, *self._targs,
                        nmax=self.nmax, chunk=self.chunk,
                        nseg=self.chunk + 1, typed=self.typed)),
                    range(0, lanes, self.chunk)):
                self.counters.evaluated += int(ev)
                self.counters.ccp += int(cc)
                _merge_best(best_cost, best_left, lane0 // m, sc, sl)
            self._commit_level(sets_np, best_cost, best_left)

    # ------------------------------------------------------- MPDP general --
    def _find_blocks_host(self, sets_np):
        """Phase A: per-set blocks -> compacted (set, block) pair arrays
        (shared host driver in ``blocks.np_pairs_for_sets``).  Every launch
        holds ``blocks.SCAP`` sets: one executable, and a padded launch
        costs little beside the solo engine's levels."""
        ps, pb, _ = bl.np_pairs_for_sets(
            sets_np, self.g, self.dg.adj, self.eu_idx, self.ev_idx,
            self.edge_live, nmax=self.nmax, emax=self.emax,
            cyc_cap=self.cyc_cap, scap_min=bl.SCAP)
        return ps, pb

    def _launch_general(self, ps, pb, offs, total: int, lane0: int):
        """Dispatch the evaluate chunk of lanes ``[lane0, lane0 + chunk)``:
        its pairs ``[p0, p1)`` and the chunk's device outputs."""
        lane1 = min(lane0 + self.chunk, total)
        p0 = int(np.searchsorted(offs, lane0, side="right")) - 1
        p1 = int(np.searchsorted(offs, lane1, side="left"))
        npair = p1 - p0
        pcap = _cap(npair, 4096)                 # 3 executables at CHUNK
        pairs = np.zeros((3, pcap), np.int32)
        pairs[0, :npair] = ps[p0:p1]
        pairs[1, :npair] = pb[p0:p1]
        pairs[2] = 1 << 30                       # past the chunk's end
        pairs[2, :npair] = np.clip(offs[p0:p1] - lane0, -(1 << 30), 1 << 30)
        return (p0, p1), _eval_general_chunk(
            pairs, np.int32(npair), np.int32(lane1 - lane0),
            self.dg.adj, self.memo_cost, self.memo_rows, *self._targs,
            nmax=self.nmax, chunk=self.chunk, pcap=pcap, typed=self.typed)

    def run_mpdp_general(self) -> None:
        self._run_levels(self._eval_mpdp_general)

    def _eval_mpdp_general(self, i: int, sets_np) -> None:
        with span("level.pairs"):
            ps, pb = self._find_blocks_host(sets_np)
        if not len(ps):
            return
        with span("level.eval"):
            sizes = bs.np_popcount(pb).astype(np.int64)
            lane_sz = (1 << sizes).astype(np.int64)
            offs = np.zeros(len(ps) + 1, np.int64)
            np.cumsum(lane_sz, out=offs[1:])
            total = int(offs[-1])
            # sets_np is ascending (colex rank order == ascending bitmap), so
            # pair -> local set index is a vectorised searchsorted
            pk = np.searchsorted(sets_np, ps).astype(np.int64)
            best_cost = np.full(len(sets_np), INF, np.float32)
            best_left = np.zeros(len(sets_np), np.int32)
            k_all, c_all, l_all = [], [], []
            for (p0, p1), out in fetch_in_waves(
                    lambda lane0: self._launch_general(ps, pb, offs, total,
                                                       lane0),
                    range(0, total, self.chunk)):
                pcap = (len(out) - 2) // 2
                self.counters.evaluated += int(out[-2])
                self.counters.ccp += int(out[-1])
                scn = out[:p1 - p0].view(np.float32)
                fin = np.isfinite(scn)
                k_all.append(pk[p0:p1][fin])
                c_all.append(scn[fin])
                l_all.append(out[pcap:pcap + p1 - p0][fin])
            if k_all:
                _merge_scattered(best_cost, best_left, np.concatenate(k_all),
                                 np.concatenate(c_all), np.concatenate(l_all))
            self._commit_level(sets_np, best_cost, best_left)

    # ------------------------------------------------------------- DPSIZE --
    def run_dpsize(self) -> None:
        if self.typed:
            raise ValueError(
                "dpsize does not support non-inner join edges (use dpsub / "
                "mpdp / dpccp — the conflict-masked lane spaces)")
        self._run_levels(self._eval_dpsize)

    def _eval_dpsize(self, i: int, sets_np) -> None:
        with span("level.eval"):
            s_all, c_all, l_all = [], [], []
            for a in range(1, i):
                b = i - a
                ca, cb = self.level_cnt[a], self.level_cnt[b]
                if ca == 0 or cb == 0:
                    continue
                lanes = ca * cb
                for lane0 in range(0, lanes, self.chunk):
                    cnt = min(self.chunk, lanes - lane0)
                    S, rows, cand, A, ev, cc = _eval_dpsize_chunk(
                        self.all_sets, jnp.int32(self.level_off[a]),
                        jnp.int32(self.level_off[b]), jnp.int32(cb),
                        jnp.int32(lane0 // cb), jnp.int32(lane0 % cb),
                        jnp.int32(cnt), self.dg.adj, self.memo_cost,
                        self.memo_rows, self.dg.card_l2, self.dg.emask_u,
                        self.dg.emask_v, self.dg.esel_l2,
                        nmax=self.nmax, chunk=self.chunk)
                    S, cn, A, ev, cc = fetch((S, cand, A, ev, cc))
                    self.counters.evaluated += int(ev)
                    self.counters.ccp += int(cc)
                    fin = np.isfinite(cn)
                    s_all.append(S[fin])
                    c_all.append(cn[fin])
                    l_all.append(A[fin])
            if s_all:
                ss = np.concatenate(s_all).astype(np.int64)
                scratch_c = np.full(1 << self.n, INF, np.float32)
                scratch_l = np.zeros(1 << self.n, np.int32)
                _merge_scattered(scratch_c, scratch_l, ss,
                                 np.concatenate(c_all), np.concatenate(l_all))
                ks = np.flatnonzero(np.isfinite(scratch_c)).astype(np.int32)
                self._scatter(ks, cost=scratch_c[ks], left=scratch_l[ks])

    # ------------------------------------------------------------ finish ---
    def result(self, algorithm: str, t0: float) -> OptimizeResult:
        full = self.g.full_set
        cost = float(fetch(self.memo_cost[full]))
        if np.isfinite(cost):
            p = extract_plan(full, fetch(self.memo_left), self.g)
            return OptimizeResult(plan=p, cost=cost, counters=self.counters,
                                  algorithm=algorithm,
                                  wall_s=time.perf_counter() - t0,
                                  levels=self.n)
        if self.degraded is None:
            raise RuntimeError("no plan found — disconnected graph?")
        # deadline expired before the full set was memoized: stitch the
        # committed memo prefix with a GOO completion (anytime contract)
        from ..heuristics.idp import stitch_partial_memo
        p, c, dinfo = stitch_partial_memo(
            self.g, *fetch((self.memo_cost, self.memo_left)))
        r = OptimizeResult(plan=p, cost=c, counters=self.counters,
                           algorithm=algorithm,
                           wall_s=time.perf_counter() - t0,
                           levels=self.degraded["levels_done"])
        r.info["degraded"] = {**self.degraded, **dinfo}
        return r


def optimize(g: JoinGraph, algorithm=UNSET, chunk=UNSET, cyc_cap=UNSET,
             enum=UNSET, lattice_devices=UNSET, lattice_mesh=UNSET, *,
             config: OptimizerConfig | None = None) -> OptimizeResult:
    """Exact join-order optimization.  algorithm in
    {auto, mpdp, mpdp_tree, mpdp_general, dpsub, dpsize, dpccp};
    enum in {unrank (paper Alg.5), expand (beyond-paper frontier growth)}.

    All knobs can be passed as one ``config=OptimizerConfig(...)`` instead
    of the legacy kwargs (never both; see ``core.config``).  With
    ``config.lattice=True`` the query's DP lane space is sharded across the
    config's ``devices``/``mesh`` (``core.lattice``): the memo drops from
    one ``1 << nmax_bucket(n)`` table to a replicated
    ``1 << lattice_bucket(n)`` table per device and each device evaluates
    only its lane slice — bit-identical costs/plans, with exactly one
    collective per committed level.  Supported for the dpsub / mpdp_tree /
    mpdp_general lane spaces (``auto``/``mpdp`` resolve by topology).
    ``lattice_devices=``/``lattice_mesh=`` are the deprecated kwarg
    spelling of ``devices``/``mesh`` + ``lattice=True``."""
    from . import dpccp as _dpccp
    devices = mesh = UNSET
    lattice = UNSET
    if lattice_devices is not UNSET or lattice_mesh is not UNSET:
        devices = alias_kwarg(UNSET, lattice_devices,
                              "lattice_devices", "config.devices")
        mesh = alias_kwarg(UNSET, lattice_mesh,
                           "lattice_mesh", "config.mesh")
        # the old kwargs passed None to mean "no lattice": preserve that
        if (devices is not UNSET and devices is not None) or \
                (mesh is not UNSET and mesh is not None):
            lattice = True
    cfg = resolve_config(config, algorithm=algorithm, chunk=chunk,
                         cyc_cap=cyc_cap, enum=enum, devices=devices,
                         mesh=mesh, lattice=lattice)
    if cfg.lattice:
        from . import lattice as _lat
        return _lat.optimize_lattice(g, config=cfg.replace(lattice=False))
    algorithm, chunk = cfg.algorithm, cfg.chunk
    if algorithm == "dpccp":
        return _dpccp.solve(g)
    if g.n == 1:
        from .plan import leaf_plan
        p = leaf_plan(0, g)
        return OptimizeResult(plan=p, cost=p.cost, counters=Counters(),
                              algorithm=algorithm, levels=1)
    t0 = time.perf_counter()
    eng = ExactEngine(g, chunk=chunk, cyc_cap=cfg.cyc_cap, enum=cfg.enum,
                      deadline_s=cfg.deadline_s)
    algo = algorithm
    if algorithm in ("auto", "mpdp"):
        algo = "mpdp_tree" if g.is_tree() else "mpdp_general"
    if algo == "mpdp_tree":
        eng.run_mpdp_tree()
    elif algo == "mpdp_general":
        eng.run_mpdp_general()
    elif algo == "dpsub":
        eng.run_dpsub()
    elif algo == "dpsize":
        eng.run_dpsize()
    else:
        raise ValueError(f"unknown algorithm {algorithm!r}")
    with span("engine.collect"):
        return eng.result(algo, t0)


def optimize_many(graphs, algorithm=UNSET, chunk=UNSET, cache=UNSET,
                  max_flight=UNSET, devices=UNSET, mesh=UNSET,
                  pipeline=UNSET, max_batch=UNSET, policy=UNSET, *,
                  config: OptimizerConfig | None = None):
    """Batched multi-query optimization — see ``batch.optimize_many``.

    Pads compatible queries into one (NMAX, EMAX, CHUNK) bucket and runs the
    level-synchronous DP with the batch folded into the lane dimension;
    returns one ``OptimizeResult`` per input graph.  ``auto``/``mpdp``
    dispatch each bucket to the cheapest MPDP lane space by topology
    (all-acyclic -> MPDP:Tree ``sets x m``, else MPDP-general block
    prefix-sum), mirroring the single-query ``optimize`` selection.
    ``devices=N`` (or ``mesh=``) additionally shards each bucket's batch
    dimension across a 1-D device mesh (``core.shard``); results stay
    bit-identical at any device count.  ``pipeline=True`` (default: the
    ``REPRO_PIPELINE`` env flag) overlaps each level's device evaluate with
    the host compaction of the next level — same results, fewer idle device
    cycles.
    Freshly-computed results have costs bit-identical to per-query
    ``optimize``; plan-cache hits are instead re-costed canonically on the
    probing graph's exact stats (the cache key quantizes stats at 1/4096
    log2, so a hit's cost can differ at that epsilon).

    This is the single device entry point of the heuristics tier: every
    IDP2 round, UnionDP partition round AND UnionDP re-optimization pass
    ships its vertex-disjoint subproblems through one call — so
    ``devices``/``mesh``/``pipeline`` compose with the heuristics for free,
    and the bit-identity guarantee extends to their whole search
    (``tests/test_uniondp_quality.py`` gates it end to end).

    ``max_flight`` is the canonical sub-batch cap (``max_batch=`` is the
    deprecated alias); ``policy=`` takes a ``policy.PolicyTable`` for
    learned lane-space/chunk/drain-window dispatch (default ``None``:
    static dispatch, byte-identical to a policy-free build); all knobs can
    be passed as one ``config=OptimizerConfig(...)`` instead of the kwargs
    (never both).
    """
    from . import batch as _batch
    max_flight = alias_kwarg(max_flight, max_batch, "max_batch", "max_flight")
    cfg = resolve_config(config, algorithm=algorithm, chunk=chunk,
                         cache=cache, max_flight=max_flight, devices=devices,
                         mesh=mesh, pipeline=pipeline, policy=policy)
    return _batch.optimize_many(graphs, config=cfg)
