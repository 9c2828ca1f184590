"""Intra-query lattice sharding: one query's lane space across the mesh.

``core.shard`` parallelizes the *batch* axis — whole queries are dealt to
devices, so the exact-DP frontier per query stays capped by one device's
memo (``NMAX_BATCH``).  This module shards the other axis: the subset
lattice / MPDP lane space of a **single** query is partitioned over the 1-D
``batch`` device mesh, Trummer & Koch's shared-nothing plan-space
partitioning (arXiv 1511.01768) applied inside one query:

  * every DP level's lanes — DPSUB ``sets x 2^i`` subsets, MPDP:Tree
    ``sets x m`` (set, edge) lanes, MPDP-general block prefix-sum
    (set, block, rank) lanes — are split into contiguous balanced ranges by
    ``distributed.sharding.partition_lanes``; device ``d`` evaluates only
    its range, through the *unchanged* ``core.batch`` chunk kernels under
    ``shard_map`` (``core.shard._sharded``, ``bcap=1``: the single query
    owns the whole per-device memo region);
  * the memo is **replicated**: every device holds the full
    ``(1 << nmax)`` cost/rows/left tables, so lane evaluation reads memo
    entries without any communication;
  * devices exchange data **only at level commit**: one
    ``distributed.collectives.min_left_commit`` call per committed level
    combines the per-device partial minima with the same associative
    (min cost, max-left tie-break) semiring the host merges use and
    scatters the result into every replica.  ``engine.collectives`` counts
    the exchanges; tests and the bench gate pin ``== n - 1``.

The per-device offset trick that lets the batched kernels run unchanged:
device ``d``'s chunk at base ``c`` passes ``eoff = [-(start_d + c),
end_d - start_d - c]`` (clipped), so the kernel's lane decode
``local = t - eoff[qid]`` reconstructs the *global* lane id and
``live = t < eoff[1]`` masks everything past the device's range — dead
lanes carry INF candidates and cannot win a merge.  Filter ranks are split
the same way; concatenating per-device survivors in device order preserves
the global colex set order the commit/searchsorted logic relies on.

Bit-identity to the single-device engines holds by the same argument as
``core.shard``: the partition is an exact disjoint cover of the lane space
and every reduction (in-chunk segment prune, host ``_merge_best`` /
``_merge_scattered``, the commit exchange) is the associative/commutative
(f32 min, max-left) semiring — so *where* a candidate is evaluated cannot
change the result, and evaluated/CCP counters sum to exactly the
single-device figures.  ``tests/test_lattice_shard.py`` pins this
differentially on 1/2/4 emulated devices for all three lane spaces.

Because the engine runs one query, it can afford **finer NMAX buckets**
than ``bitset.nmax_bucket`` (whose coarse 16 -> 24 jump exists to share
executables across many queries): ``lattice_bucket`` adds 18 and 20, so an
``n = 17`` query costs a ``2 ** 18``-entry memo per device instead of the
solo engine's ``2 ** 24`` — a 64x memory drop, which is what moves the
exact frontier from ~14 toward ~18+ relations on a 4-device mesh
(``NMAX_LATTICE``).  Per-level work also drops ~D-fold per device;
wall-clock scaling is reported by ``benchmarks/bench_batch.py --lattice``
but never gated on CPU-emulated meshes.
"""
from __future__ import annotations

import time
from collections import deque
from math import comb

import numpy as np
import jax
import jax.numpy as jnp

from ..distributed import collectives as coll
from ..distributed.sharding import partition_lanes
from . import bitset as bs
from . import blocks as bl
from . import cost as cm
from . import faults
from . import unrank as ur
from .batch import (PEND_WINDOW, _CLIP, _LevelLoop, _beval_dpsub_chunk,
                    _beval_general_chunk, _beval_tree_chunk, _bfilter_chunk,
                    _lane_space)
from .config import UNSET, OptimizerConfig, resolve_config
from .engine import (CHUNK, CYC_CAP_DEFAULT, INF, _cap,
                     _merge_best, _merge_scattered, _use_pallas,
                     _use_pipeline)
from .exec_cache import EXEC
from .joingraph import JoinGraph, typed_edge_arrays
from .plan import Counters, OptimizeResult, extract_plan
from .telemetry import fetch, span
from .shard import (BATCH_AXIS, _exec_key, _set_drop, _sharded, batch_mesh,
                    mesh_size)

# Finer buckets than ``bitset.nmax_bucket`` above 16: the lattice engine is
# per-query, so a recompile per 2-relation step is cheap and the replicated
# ``1 << nmax`` memo dominates — bucket 18/20 instead of jumping to 24.
LATTICE_BUCKETS = (8, 16, 18, 20)
NMAX_LATTICE = LATTICE_BUCKETS[-1]


def lattice_bucket(n: int) -> int:
    """NMAX bucket for the lattice-sharded path (<= ``NMAX_LATTICE``)."""
    for b in LATTICE_BUCKETS:
        if n <= b:
            return b
    raise ValueError(
        f"n={n} beyond the lattice-sharded cap {NMAX_LATTICE} "
        f"(heuristics handle larger queries; see docs/heuristics.md)")


class LatticeShardedEngine(_LevelLoop):
    """Level-synchronous exact DP for ONE query, lanes sharded over devices.

    Same ``_LevelLoop`` hook protocol as the batched engines (so the sync
    and pipelined drivers are shared verbatim); see the module docstring
    for the partition/replication/commit layout.  ``mesh`` is a 1-D
    ``batch`` mesh from ``shard.batch_mesh`` (default: all devices); the
    1-device mesh is the degenerate case and still bit-identical.
    """

    def __init__(self, g: JoinGraph, mesh=None, chunk: int = CHUNK,
                 algorithm: str = "mpdp_general",
                 cyc_cap: int = CYC_CAP_DEFAULT,
                 pipeline: bool | None = None,
                 deadline_s: float | None = None):
        if algorithm not in ("dpsub", "mpdp_tree", "mpdp_general"):
            raise ValueError(f"unknown lattice lane space {algorithm!r}")
        if g.n < 2:
            raise ValueError("LatticeShardedEngine needs n >= 2 (leaf "
                             "queries are handled by optimize_many)")
        if not g.is_connected():
            raise ValueError("query graph must be connected (no cross products)")
        if algorithm == "mpdp_tree" and not g.is_tree():
            raise ValueError("mpdp_tree lane space needs acyclic queries")
        self.g = g
        self.graphs = [g]                  # _LevelLoop drives max(g.n)
        self.mesh = batch_mesh(mesh)
        self.D = mesh_size(self.mesh)
        self.algorithm = algorithm
        self.cyc_cap = cyc_cap
        self.chunk = chunk
        self.pallas = _use_pallas()
        self.pipeline = _use_pipeline() if pipeline is None else bool(pipeline)
        self.nmax = lattice_bucket(g.n)
        self.flat = 1 << self.nmax         # bcap = 1: one query per region
        self.deadline_s = deadline_s
        self._deadline_at: float | None = None
        self.degraded: dict | None = None
        self.collectives = 0               # min_left_commit dispatches
        self.chunks_dispatched = 0         # telemetry: chunk dispatch tally
        self.blocks_sets = 0               # phase A: sets / launched slots
        self.blocks_slots = 0
        self._exec_keys: set[tuple] = set()
        self._wall = 0.0
        self.counters = [Counters()]
        with span("engine.setup"):
            D, nmax = self.D, self.nmax
            from jax.sharding import NamedSharding, PartitionSpec as P
            self._shard1 = NamedSharding(self.mesh, P(BATCH_AXIS))
            bt = np.asarray(ur.binom_table(nmax))
            self.binom_b = self._put(np.broadcast_to(bt, (D,) + bt.shape))
            adj = np.zeros((1, nmax), np.int32)
            for (u, v) in g.edges:
                adj[0, u] |= 1 << v
                adj[0, v] |= 1 << u
            self.adj_b = self._put(np.broadcast_to(adj, (D, 1, nmax)))
            self.emax = max(8, int(np.ceil(max(g.m, 1) / 8.0)) * 8)
            # typed-join edge metadata, replicated (D, 1, emax) like emu/emv
            self.typed = g.typed
            if self.typed:
                self._targs = tuple(
                    self._put(np.broadcast_to(a, (D, 1, self.emax)))
                    for a in typed_edge_arrays(g, self.emax))
            else:
                self._targs = ()
            if algorithm == "mpdp_tree":
                emu = np.zeros((1, self.emax), np.int32)
                emv = np.zeros((1, self.emax), np.int32)
                for ei, (u, v) in enumerate(g.edges):
                    emu[0, ei] = 1 << u
                    emv[0, ei] = 1 << v
                self.emu_b = self._put(np.broadcast_to(emu, (D, 1, self.emax)))
                self.emv_b = self._put(np.broadcast_to(emv, (D, 1, self.emax)))
                self.m_b = self._put(np.full((D, 1), g.m, np.int32))
            if algorithm == "mpdp_general":
                # phase A is host-side and shared: one run per level feeds
                # every device's pair windows (unlike core.shard, where each
                # shard has its own queries and hence its own phase A)
                eui = np.full(self.emax, -1, np.int32)
                evi = np.full(self.emax, -1, np.int32)
                eliv = np.zeros(self.emax, bool)
                for ei, (u, v) in enumerate(g.edges):
                    eui[ei], evi[ei], eliv[ei] = u, v, True
                self._phase_a_row = (jnp.asarray(adj[0]), jnp.asarray(eui),
                                     jnp.asarray(evi), jnp.asarray(eliv))
            self._init_memo()

    # ----------------------------------------------------------- plumbing --
    def _put(self, x):
        """Commit a stacked ``(D, ...)`` host array, sharded over devices."""
        return jax.device_put(jnp.asarray(x), self._shard1)

    def _bcast(self, x: np.ndarray):
        """Replicate a per-device-identical host row to the stacked layout."""
        return self._put(np.broadcast_to(x, (self.D,) + x.shape))

    def _kernel(self, fn, donate: tuple = (), **statics):
        self._exec_keys.add(_exec_key(fn, self.mesh, statics))
        return _sharded(fn, self.mesh, donate=donate, **statics)

    @property
    def stats(self) -> dict:
        """Executable-cache accounting for this engine's sharded kernel keys
        (see ``BatchEngine.stats``); keys carry ``devices=D`` and ``bcap=1``
        statics, so they never collide with the batch-axis engines'."""
        return EXEC.stats_for(self._exec_keys, pipeline=self.pipeline)

    # --------------------------------------------------------------- memo --
    def _init_memo(self):
        D, g = self.D, self.g
        self.memo_cost = self._put(np.full((D, self.flat), INF, np.float32))
        self.memo_rows = self._put(np.zeros((D, self.flat), np.float32))
        self.memo_left = self._put(np.zeros((D, self.flat), np.int32))
        self.all_sets = self._put(np.zeros((D, self.flat), np.int32))
        self._next_off = g.n
        self._level_off = {1: 0}
        leaves = np.array([1 << v for v in range(g.n)], np.int32)
        lrows = g.log2_card.astype(np.float32)
        self._scatter(leaves.astype(np.int64), cost=cm.np_scan_cost(lrows),
                      rows=lrows)
        self._set_all_sets(np.arange(g.n, dtype=np.int64), leaves)

    def _scatter(self, idx_np, cost=None, rows=None, left=None):
        """Replicated memo scatter: identical (idx, val) rows on every
        device (pad index ``flat`` -> dropped), so replicas stay equal."""
        cap = _cap(len(idx_np))
        idx = np.full(cap, self.flat, np.int64)
        idx[: len(idx_np)] = idx_np
        idx_d = self._bcast(idx.astype(np.int32))

        def pad(x, dt):
            buf = np.zeros(cap, dt)
            buf[: len(x)] = x
            return self._bcast(buf)

        scat_f = self._kernel(_set_drop, donate=(0,), cap=cap,
                              flat=self.flat, kind="f32")
        if cost is not None:
            self.memo_cost = scat_f(self.memo_cost, idx_d,
                                    pad(cost, np.float32))
        if rows is not None:
            self.memo_rows = scat_f(self.memo_rows, idx_d,
                                    pad(rows, np.float32))
        if left is not None:
            scat_i = self._kernel(_set_drop, donate=(0,), cap=cap,
                                  flat=self.flat, kind="i32")
            self.memo_left = scat_i(self.memo_left, idx_d,
                                    pad(left, np.int32))

    def _set_all_sets(self, pos_np, sets_np):
        cap = _cap(len(pos_np))
        pos = np.full(cap, self.flat, np.int64)
        pos[: len(pos_np)] = pos_np
        vals = np.zeros(cap, np.int32)
        vals[: len(sets_np)] = sets_np
        scatter = self._kernel(_set_drop, donate=(0,), cap=cap,
                               flat=self.flat, kind="i32")
        self.all_sets = scatter(self.all_sets,
                                self._bcast(pos.astype(np.int32)),
                                self._bcast(vals))

    def _commit_level(self, sets_np, best_cost, best_left) -> None:
        """THE collective: one ``min_left_commit`` exchange for the level.

        Stacks each device's partial best arrays (pad slots are (INF, 0),
        inert under min/max) and dispatches the fused cross-device reduce +
        replicated memo scatter.  Counted host-side — the lattice hot path
        has exactly ``n - 1`` of these per query, one per committed level.
        """
        ns = len(sets_np)
        cap = _cap(ns)
        idx = np.full(cap, self.flat, np.int64)
        idx[:ns] = sets_np.astype(np.int64)
        cost = np.full((self.D, cap), INF, np.float32)
        left = np.zeros((self.D, cap), np.int32)
        for d in range(self.D):
            cost[d, :ns] = best_cost[d]
            left[d, :ns] = best_left[d]
        kc = self._kernel(coll.min_left_commit, donate=(0, 1),
                          axis=BATCH_AXIS, cap=cap, flat=self.flat)
        self.memo_cost, self.memo_left = kc(
            self.memo_cost, self.memo_left,
            self._bcast(idx.astype(np.int32)),
            self._put(cost), self._put(left))
        self.collectives += 1
        coll.STATS.record_commit()

    # ------------------------------------------------------------- filter --
    def _filter_dispatch(self, i: int) -> dict:
        """Partition level i's ``C(n, i)`` colex ranks over devices and
        dispatch the (unchanged, bcap=1) batched filter kernel per chunk.
        Device d's window starts at global rank ``roff[d]``, so
        ``foff = [-(roff[d] + c), roff[d+1] - roff[d] - c]`` makes the
        kernel decode global ranks and mask past the window's end."""
        total = comb(self.g.n, i)
        roff = partition_lanes(total, self.D)
        steps_max = int(np.diff(roff).max())
        kf = self._kernel(_bfilter_chunk, nmax=self.nmax, chunk=self.chunk,
                          bcap=1, pallas=self.pallas)
        k_arr = jnp.asarray(np.full(self.D, i, np.int32))
        ctx = {"pend": deque(), "per_dev": [[] for _ in range(self.D)]}
        for c0 in range(0, steps_max, self.chunk):
            base = roff[:-1] + c0
            fl = np.stack([-base, roff[1:] - base], axis=1)
            fpad = np.clip(fl, -_CLIP, _CLIP).astype(np.int32)
            ctx["pend"].append(kf(jnp.asarray(fpad), k_arr, self.binom_b,
                                  self.adj_b))
            faults.fire("chunk")
            self.chunks_dispatched += 1
            self._filter_drain(ctx, PEND_WINDOW)
        return ctx

    def _filter_drain(self, ctx: dict, limit: int) -> None:
        pend, per_dev = ctx["pend"], ctx["per_dev"]
        while len(pend) > limit:
            Sn, c, _ = fetch(pend.popleft())
            for d in range(self.D):
                if c[d].any():
                    per_dev[d].append(Sn[d][c[d]])

    def _filter_collect(self, ctx: dict) -> np.ndarray:
        """Drain and concatenate survivors in device order — per-device rank
        windows are contiguous ascending, so this IS the global colex order
        the single-device filter produces."""
        self._filter_drain(ctx, 0)
        parts = [a for d in range(self.D) for a in ctx["per_dev"][d]]
        sets = np.concatenate(parts) if parts else np.zeros(0, np.int32)
        return sets

    def _register_level(self, i: int, sets_np: np.ndarray) -> None:
        self._level_off[i] = self._next_off
        if len(sets_np):
            rows = cm.np_rows_for_sets(sets_np, self.g)
            self._scatter(sets_np.astype(np.int64), rows=rows)
            self._set_all_sets(
                self._next_off + np.arange(len(sets_np), dtype=np.int64),
                sets_np)
            self._next_off += len(sets_np)

    # ----------------------------------------------------------- evaluate --
    def _eval_dispatch(self, i: int, sets_np: np.ndarray):
        """Segmented lane spaces (DPSUB ``sets x 2^i``, tree ``sets x m``):
        partition the level's lanes over devices, reuse the batched chunk
        kernels with per-device global-offset windows (module docstring)."""
        ns = len(sets_np)
        if ns == 0:
            return None
        D = self.D
        mult = self.g.m if self.algorithm == "mpdp_tree" else (1 << i)
        lane_off = partition_lanes(ns * mult, D)
        sizes = np.diff(lane_off)
        nseg = self.chunk + 2
        if self.algorithm == "mpdp_tree":
            kernel = self._kernel(_beval_tree_chunk, nmax=self.nmax,
                                  chunk=self.chunk, nseg=nseg, bcap=1,
                                  pallas=self.pallas, typed=self.typed)
        else:
            kernel = self._kernel(_beval_dpsub_chunk, nmax=self.nmax,
                                  chunk=self.chunk, nseg=nseg, bcap=1,
                                  pallas=self.pallas, typed=self.typed)
        loff_d = jnp.asarray(
            np.full((D, 1), self._level_off[i], np.int32))
        soff_d = jnp.asarray(np.zeros((D, 1), np.int32))
        i_arr = jnp.asarray(np.full(D, i, np.int32))
        ctx = {"pend": deque(), "sizes": sizes,
               "best_cost": [np.full(ns, INF, np.float32) for _ in range(D)],
               "best_left": [np.zeros(ns, np.int32) for _ in range(D)],
               "ev": np.zeros((D, 1), np.int64),
               "ccp": np.zeros((D, 1), np.int64)}
        for c0 in range(0, int(sizes.max()), self.chunk):
            base = lane_off[:-1] + c0
            el = np.stack([-base, lane_off[1:] - base], axis=1)
            epad = np.clip(el, -_CLIP, _CLIP).astype(np.int32)
            seg0 = base // mult            # global set index of first lane
            seg0_d = jnp.asarray(np.clip(seg0, -_CLIP, _CLIP).astype(np.int32))
            if self.algorithm == "mpdp_tree":
                out = kernel(self.all_sets, jnp.asarray(epad), loff_d, soff_d,
                             seg0_d, self.m_b, self.adj_b, self.emu_b,
                             self.emv_b, self.memo_cost, self.memo_rows,
                             *self._targs)
            else:
                out = kernel(self.all_sets, jnp.asarray(epad), loff_d, soff_d,
                             seg0_d, i_arr, self.adj_b, self.memo_cost,
                             self.memo_rows, *self._targs)
            ctx["pend"].append((c0, seg0, out))
            faults.fire("chunk")
            self.chunks_dispatched += 1
            self._eval_drain(ctx, PEND_WINDOW)
        return ctx

    def _eval_drain(self, ctx: dict, limit: int) -> None:
        pend, sizes = ctx["pend"], ctx["sizes"]
        while len(pend) > limit:
            c0, seg0, out = pend.popleft()
            scn, sln, evn, ccpn = fetch(out)
            ctx["ev"] += evn
            ctx["ccp"] += ccpn
            for d in range(self.D):
                if c0 < sizes[d]:          # device d still live this step
                    _merge_best(ctx["best_cost"][d], ctx["best_left"][d],
                                int(seg0[d]), scn[d], sln[d])

    def _eval_finalize(self, i: int, sets_np: np.ndarray, ctx) -> None:
        if ctx is None:
            return
        self._eval_drain(ctx, 0)
        self.counters[0].evaluated += int(ctx["ev"].sum())
        self.counters[0].ccp += int(ctx["ccp"].sum())
        self._commit_level(sets_np, ctx["best_cost"], ctx["best_left"])

    # ------------------------------------------------- MPDP-general phase --
    def _pairs_level(self, sets_np: np.ndarray):
        """Phase A once on the host over the full level (shared by all
        devices — only the lane ranges differ per device)."""
        if not len(sets_np):
            z = np.zeros(0, np.int32)
            return z, z, np.zeros(0, np.int64)
        adj_q, eu_q, ev_q, eliv_q = self._phase_a_row
        ps, pb, slots = bl.np_pairs_for_sets(
            sets_np, self.g, adj_q, eu_q, ev_q, eliv_q, nmax=self.nmax,
            emax=self.emax, cyc_cap=self.cyc_cap)
        self.blocks_sets += len(sets_np)
        self.blocks_slots += slots
        pk = np.searchsorted(sets_np, ps).astype(np.int64)
        return ps, pb, pk

    def _eval_general_dispatch(self, i: int, sets_np: np.ndarray, pairs):
        """Partition the block prefix-sum lane space over devices; each
        device's chunk gets its own pair window (a pair whose lanes straddle
        a partition boundary appears in both windows with the rank offset
        preserved, so each side enumerates exactly its lane range)."""
        ps, pb, pk = pairs
        if not len(ps):
            return None
        D = self.D
        sizes = bs.np_popcount(pb).astype(np.int64)
        offs = np.zeros(len(ps) + 1, np.int64)
        np.cumsum((np.int64(1) << sizes).astype(np.int64), out=offs[1:])
        lane_off = partition_lanes(int(offs[-1]), D)
        dsz = np.diff(lane_off)
        ctx = {"pend": deque(), "pk": pk,
               "ev": np.zeros((D, 1), np.int64),
               "ccp": np.zeros((D, 1), np.int64),
               "k": [[] for _ in range(D)],
               "c": [[] for _ in range(D)],
               "l": [[] for _ in range(D)]}
        for c0 in range(0, int(dsz.max()), self.chunk):
            base = lane_off[:-1] + c0
            lane1 = np.minimum(base + self.chunk, lane_off[1:])
            p0s = np.zeros(D, np.int64)
            npairs = np.zeros(D, np.int64)
            for d in range(D):
                if lane1[d] <= base[d]:
                    continue
                p0s[d] = int(np.searchsorted(offs, base[d], side="right")) - 1
                npairs[d] = (int(np.searchsorted(offs, lane1[d], side="left"))
                             - p0s[d])
            pcap = _cap(int(max(npairs.max(), 1)), 256)
            psl = np.zeros((D, pcap), np.int32)
            pbl = np.zeros((D, pcap), np.int32)
            pql = np.zeros((D, pcap), np.int32)
            ofl = np.full((D, pcap), np.int64(1 << 40), np.int64)
            lane_cnt = np.zeros(D, np.int32)
            for d in range(D):
                np_d, p0 = int(npairs[d]), int(p0s[d])
                if not np_d:
                    continue
                psl[d, :np_d] = ps[p0: p0 + np_d]
                pbl[d, :np_d] = pb[p0: p0 + np_d]
                ofl[d, :np_d] = offs[p0: p0 + np_d] - base[d]
                lane_cnt[d] = int(lane1[d] - base[d])
            ofl = np.clip(ofl, -_CLIP, _CLIP).astype(np.int32)
            kernel = self._kernel(_beval_general_chunk, nmax=self.nmax,
                                  chunk=self.chunk, pcap=pcap, bcap=1,
                                  pallas=self.pallas, typed=self.typed)
            out = kernel(
                jnp.asarray(psl), jnp.asarray(pbl), jnp.asarray(pql),
                jnp.asarray(ofl),
                jnp.asarray(np.maximum(npairs, 1).astype(np.int32)),
                jnp.asarray(lane_cnt), self.adj_b, self.memo_cost,
                self.memo_rows, *self._targs)
            ctx["pend"].append((p0s, npairs, out))
            faults.fire("chunk")
            self.chunks_dispatched += 1
            self._eval_general_drain(ctx, PEND_WINDOW)
        return ctx

    def _eval_general_drain(self, ctx: dict, limit: int) -> None:
        pend, pk = ctx["pend"], ctx["pk"]
        while len(pend) > limit:
            p0s, npairs, out = pend.popleft()
            scn_all, sln_all, evn, ccpn = fetch(out)
            ctx["ev"] += evn
            ctx["ccp"] += ccpn
            for d in range(self.D):
                np_d, p0 = int(npairs[d]), int(p0s[d])
                if not np_d:
                    continue
                scn = scn_all[d][:np_d]
                fin = np.isfinite(scn)
                ctx["k"][d].append(pk[p0: p0 + np_d][fin])
                ctx["c"][d].append(scn[fin])
                ctx["l"][d].append(sln_all[d][:np_d][fin])

    def _eval_general_finalize(self, i: int, sets_np: np.ndarray, ctx) -> None:
        if ctx is None:
            return
        self._eval_general_drain(ctx, 0)
        ns = len(sets_np)
        best_cost = [np.full(ns, INF, np.float32) for _ in range(self.D)]
        best_left = [np.zeros(ns, np.int32) for _ in range(self.D)]
        for d in range(self.D):
            if ctx["k"][d]:
                _merge_scattered(best_cost[d], best_left[d],
                                 np.concatenate(ctx["k"][d]),
                                 np.concatenate(ctx["c"][d]),
                                 np.concatenate(ctx["l"][d]))
        self.counters[0].evaluated += int(ctx["ev"].sum())
        self.counters[0].ccp += int(ctx["ccp"].sum())
        self._commit_level(sets_np, best_cost, best_left)

    # ------------------------------------------------------------- driver --
    # (run / run_levels / the pipelined rotation come from _LevelLoop)
    def collect(self) -> list[OptimizeResult]:
        """Fetch one memo replica (they are identical after every commit —
        ``tests/test_lattice_shard.py`` asserts it) and extract the plan."""
        t0 = time.perf_counter()
        g = self.g
        cost_all, left_all = fetch((self.memo_cost, self.memo_left))
        cost = float(cost_all[0, g.full_set])
        wall = self._wall + time.perf_counter() - t0
        if np.isfinite(cost):
            p = extract_plan(g.full_set, left_all[0], g)
            r = OptimizeResult(plan=p, cost=cost, counters=self.counters[0],
                               algorithm=f"lattice_{self.algorithm}",
                               wall_s=wall, levels=g.n)
        elif self.degraded is not None:
            # deadline expired: anytime stitch over the committed replicated
            # memo prefix (see BatchEngine.collect)
            from ..heuristics.idp import stitch_partial_memo
            p, c, dinfo = stitch_partial_memo(g, cost_all[0], left_all[0])
            r = OptimizeResult(plan=p, cost=c, counters=self.counters[0],
                               algorithm=f"lattice_{self.algorithm}",
                               wall_s=wall,
                               levels=self.degraded["levels_done"])
            r.info["degraded"] = {**self.degraded, **dinfo}
        else:
            raise RuntimeError("no plan found for lattice-sharded query")
        return [r]

    def memo_replicas(self) -> tuple[np.ndarray, np.ndarray]:
        """Fetch the stacked ``(D, flat)`` cost/left memo for replication
        checks (tests only — the hot path never fetches mid-run)."""
        return np.asarray(self.memo_cost), np.asarray(self.memo_left)


# ============================================================ public entry ==

def optimize_lattice(g: JoinGraph, algorithm=UNSET, chunk=UNSET,
                     cyc_cap=UNSET, devices=UNSET, mesh=UNSET,
                     pipeline=UNSET, *,
                     config: OptimizerConfig | None = None) -> OptimizeResult:
    """Exact optimization of one query with its lane space sharded over a
    device mesh (``engine.optimize(config.lattice=True)`` lands here).

    ``algorithm`` resolves through the shared ``batch._lane_space`` dispatch
    (``auto``/``mpdp`` -> tree lanes on acyclic queries, general otherwise);
    spaces with no lattice form (``dpsize``, ``dpccp``, forced ``mpdp_tree``
    on a cyclic query) raise.  ``devices``/``mesh`` as in ``optimize_many``;
    all knobs can be passed as one ``config=OptimizerConfig(...)`` instead
    of the legacy kwargs (never both).
    """
    cfg = resolve_config(config, algorithm=algorithm, chunk=chunk,
                         cyc_cap=cyc_cap, devices=devices, mesh=mesh,
                         pipeline=pipeline)
    if g.n == 1:
        from .plan import leaf_plan
        p = leaf_plan(0, g)
        return OptimizeResult(plan=p, cost=p.cost, counters=Counters(),
                              algorithm=cfg.algorithm, levels=1)
    space = _lane_space(g, cfg.algorithm)
    if space is None:
        raise ValueError(
            f"algorithm {cfg.algorithm!r} has no lattice-sharded lane space "
            "for this query (lattice supports dpsub / mpdp_tree / "
            "mpdp_general)")
    eng = LatticeShardedEngine(
        g, cfg.mesh if cfg.mesh is not None else cfg.devices,
        chunk=cfg.chunk, algorithm=space, cyc_cap=cfg.cyc_cap,
        pipeline=cfg.pipeline, deadline_s=cfg.deadline_s)
    return eng.run()[0]
