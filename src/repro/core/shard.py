"""Multi-device sharded BatchEngine: shard_map over the batch dimension.

``BatchEngine`` folds B queries into the *lane* dimension of one device
pipeline; this module folds a 1-D **device mesh** over the *batch* dimension
on top of it.  Every per-query stacked structure — the ``(bcap, NMAX)``
adjacency rows, the flat ``(bcap << NMAX)`` memo tables (logically
``(B, 1 << NMAX)``), the per-level lane offsets — gains a leading device
axis sharded with ``NamedSharding``/``shard_map`` over ``batch``:

  * the B queries of a (NMAX, topology) bucket are padded up to a device
    multiple with *inert* 2-relation queries and dealt round-robin, so
    every shard holds exactly ``ceil(B / D)`` queries and all shards share
    one set of static shapes.  The contract, precisely:

      - **deal**: bucket entry ``j`` lands on shard ``j % D``, local slot
        ``j // D`` — a pure index bijection, so result collection is
        ``results[j] = shard[j % D][j // D]`` with no search and no
        device-order dependence;
      - **padding**: the ``(-B) % D`` pad slots are appended *after* the
        real queries, so they always occupy the highest (shard, slot)
        pairs; a pad query is a fixed 2-relation join (``_pad_graph``)
        whose lanes execute normally — keeping every shard's chunk grid
        identical — but whose memo region no real query ever reads and
        whose result slot is simply dropped at collection;
      - **inertness**: pads are static and tiny (NMAX bucket unchanged,
        level count 2), so they cannot move a bucket into a different
        executable-cache key, and ``tests/test_shard.py`` asserts a padded
        uneven batch returns bit-identical results to the unpadded batch;
  * each device runs the level-synchronous unrank -> filter -> evaluate ->
    prune pipeline on its own slice: the ``shard_map`` body strips the
    leading device axis and calls the *single-shard* batched kernels of
    ``core.batch`` unchanged, so the DPSUB, MPDP:Tree and MPDP-general lane
    spaces — vector and Pallas variants alike — run per device exactly as
    they do on one device;
  * host-side compaction (connected-set dedup, per-level ``_merge_best`` /
    ``_merge_scattered``, MPDP-general phase A) stays **per shard**: one
    fused device step per chunk, then a cheap numpy loop over shards.  There
    are no cross-device collectives on the hot path — shards never
    communicate (Trummer & Koch's shared-nothing partitioning, arXiv
    1511.01768, applied to the batch axis).

Costs/plans are **bit-identical** to sequential ``engine.optimize`` at any
device count: each shard's chunk grid enumerates exactly the candidate set a
standalone ``BatchEngine`` over the same queries would, and the per-set
reductions (exact f32 ``segment_min`` + max-left tie-break) are associative,
so neither the round-robin partition nor the inert padding can perturb a
real query's result.  The 1-device mesh is the degenerate case.

``pipeline=True`` (or ``REPRO_PIPELINE=1``) runs the same pipelined driver
as ``BatchEngine``: a level's fused evaluate steps are dispatched without a
host sync while the host compacts the next level's filter output, costs its
rows and (general space) runs phase A — per-shard numerics and merge order
unchanged, so the bit-identity guarantee carries over verbatim.  Sharded
kernel wrappers are trace-counted in ``exec_cache.EXEC`` (see
``ShardedBatchEngine.stats``).

CPU has one device by default; multi-device runs are emulated with

    XLA_FLAGS=--xla_force_host_platform_device_count=4

set **before the first jax import** (``tests/conftest.py`` does this for the
test session; ``benchmarks/bench_batch.py --devices N`` does it for itself).
"""
from __future__ import annotations

import logging
import time
from collections import deque
from math import comb

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..distributed.compat import shard_map_compat
from . import bitset as bs
from . import blocks as bl
from . import cost as cm
from . import faults
from . import unrank as ur
from .batch import (NMAX_BATCH, PEND_WINDOW, _CLIP, _LevelLoop, _bcap,
                    _beval_dpsub_chunk, _beval_general_chunk,
                    _beval_tree_chunk, _bfilter_chunk)
from .engine import (CHUNK, CYC_CAP_DEFAULT, INF, _cap,
                     _merge_best, _merge_scattered, _use_pallas,
                     _use_pipeline)
from .exec_cache import EXEC
from .joingraph import JoinGraph, typed_edge_arrays
from .plan import Counters, OptimizeResult, extract_plan
from .telemetry import fetch, span

BATCH_AXIS = "batch"

# A sharded flight that fails with one of these is re-run on one device
# (the degenerate 1-device case is bit-identical, tests/test_shard.py): a
# device-runtime failure, or the fault plane's injected chunk failure.  Any
# other exception (a shape bug, a bad mesh, a TypeError) propagates.
REDISPATCH_ERRORS = (jax.errors.JaxRuntimeError, faults.InjectedFault)
_log = logging.getLogger(__name__)


def log_redispatch(err: BaseException, queries: int) -> None:
    """Say why a sharded flight is being re-run on one device."""
    _log.warning("sharded flight of %d queries failed on the mesh (%s: %s); "
                 "re-running it on one device", queries,
                 type(err).__name__, err)


# ============================================================ mesh helpers ==

def take_devices(n: int | None = None, *, backend: str | None = None) -> list:
    """First ``n`` available devices, or all of them when ``n`` is None.

    Unlike the old ``jax.devices()[:n]`` idiom this never silently truncates:
    asking for more devices than exist raises with the actual count (and the
    CPU-emulation recipe), so a mesh built for N workers cannot quietly
    degrade into an (N-k)-way one.
    """
    devs = list(jax.devices(backend) if backend else jax.devices())
    if n is None:
        return devs
    if n < 1:
        raise ValueError(f"need at least 1 device, requested {n}")
    if n > len(devs):
        plat = devs[0].platform if devs else "cpu"
        raise ValueError(
            f"requested {n} devices but only {len(devs)} {plat} device(s) "
            f"exist; on CPU, emulate more with XLA_FLAGS="
            f"--xla_force_host_platform_device_count={n} set before the "
            f"first jax import")
    return devs[:n]


def batch_mesh(devices=None) -> Mesh:
    """1-D mesh over the ``batch`` axis.

    ``devices`` may be an existing ``Mesh`` (returned as-is), an int (first
    N devices via ``take_devices``; CPU emulation counts included), an
    explicit device list, or None (all devices).
    """
    if isinstance(devices, Mesh):
        return devices
    if devices is None or isinstance(devices, int):
        devs = take_devices(devices)
    else:
        devs = list(devices)
    return Mesh(np.asarray(devs), (BATCH_AXIS,))


def mesh_size(mesh: Mesh) -> int:
    return int(np.prod(mesh.devices.shape))


# ====================================================== shard_map wrappers ==

_WRAP_CACHE: dict = {}


def _set_drop(buf, idx, val, *, cap: int = 0, flat: int = 0, kind: str = ""):
    """Single-shard scatter body (OOB pad indices are dropped).  The keyword
    statics only disambiguate the executable-cache key — one key per
    (pad cap, memo size, value dtype) compile signature."""
    return buf.at[idx].set(val, mode="drop")


def _exec_key(fn, mesh: Mesh, statics: dict) -> tuple:
    """Executable-cache accounting key for a sharded kernel: identity-free
    (name + statics + device count), so equal bucket shapes share a key."""
    return EXEC.key("sharded:" + fn.__name__.lstrip("_"),
                    dict(statics, devices=int(np.prod(mesh.devices.shape))))


def _sharded(fn, mesh: Mesh, donate: tuple = (), **statics):
    """shard_map a single-shard kernel over the ``batch`` mesh axis.

    Every array argument and output carries a leading device axis sharded
    ``P(batch)``; the body strips it (each device's block has leading dim 1)
    and calls ``fn`` — one of the raw ``core.batch`` chunk kernels, the
    scatter body, or the lattice level-commit exchange — unchanged, so
    per-device numerics are exactly the single-device ones.  The chunk/
    scatter bodies are collective-free; only the lattice commit body
    (``distributed.collectives.min_left_commit``) reduces over the ``batch``
    axis, and it is dispatched once per committed level.  Wrappers are cached
    per (fn, mesh, statics) so each bucket shape compiles once; traces are
    counted in ``exec_cache.EXEC`` under the identity-free key.
    """
    key = (fn, mesh, donate, tuple(sorted(statics.items())))
    wrapped = _WRAP_CACHE.get(key)
    if wrapped is None:
        ckey = _exec_key(fn, mesh, statics)

        def inner(*args):
            EXEC.record(ckey)          # runs at trace time only
            out = fn(*[a[0] for a in args], **statics)
            if isinstance(out, tuple):
                return tuple(y[None] for y in out)
            return out[None]

        sm = shard_map_compat(inner, mesh, in_specs=P(BATCH_AXIS),
                              out_specs=P(BATCH_AXIS))
        wrapped = jax.jit(sm, donate_argnums=donate)
        _WRAP_CACHE[key] = wrapped
    return wrapped


def _pad_graph() -> JoinGraph:
    """Inert batch-padding query: a trivial 2-relation join whose lanes run
    on the device but whose result is discarded.  A tree, so it is valid in
    every lane space and never widens the bucket's NMAX/EMAX."""
    return JoinGraph.make(2, [(0, 1)], [2.0, 2.0], [0.5])


# ============================================================== host driver ==

class ShardedBatchEngine(_LevelLoop):
    """Level-synchronous DP over a batch of queries, sharded across devices.

    Mirrors ``BatchEngine`` (same lane spaces, same kernels, same host
    merges) with a leading device axis on every stacked array; see the
    module docstring for the layout.  ``mesh`` is a 1-D ``batch`` mesh from
    ``batch_mesh`` (default: all devices).
    """

    def __init__(self, graphs: list[JoinGraph], mesh: Mesh | None = None,
                 chunk: int = CHUNK, algorithm: str = "dpsub",
                 cyc_cap: int = CYC_CAP_DEFAULT,
                 pipeline: bool | None = None,
                 pend_window: int | None = None,
                 deadline_s: float | None = None):
        if not graphs:
            raise ValueError("empty batch")
        if algorithm not in ("dpsub", "mpdp_tree", "mpdp_general"):
            raise ValueError(f"unknown batched lane space {algorithm!r}")
        for g in graphs:
            if g.n < 2:
                raise ValueError("ShardedBatchEngine needs n >= 2 (leaf "
                                 "queries are handled by optimize_many)")
            if not g.is_connected():
                raise ValueError("query graph must be connected (no cross products)")
            if algorithm == "mpdp_tree" and not g.is_tree():
                raise ValueError("mpdp_tree lane space needs acyclic queries")
        self.mesh = batch_mesh(mesh)
        self.D = mesh_size(self.mesh)
        self.graphs = list(graphs)
        self.algorithm = algorithm
        self.cyc_cap = cyc_cap
        self.pallas = _use_pallas()        # read per engine; static jit arg
        self.pipeline = _use_pipeline() if pipeline is None else bool(pipeline)
        # see BatchEngine: drain-window override + telemetry dispatch tally,
        # both host-only — results are bit-identical for any pend_window
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
        npad = (-self.B) % self.D
        padded = self.graphs + [_pad_graph() for _ in range(npad)]
        # round-robin deal: stream entry j -> (shard j % D, slot j // D)
        self.Bs = len(padded) // self.D
        self.shard_graphs = [[padded[s * self.D + d] for s in range(self.Bs)]
                             for d in range(self.D)]
        self.bcap = _bcap(self.Bs)
        self.nmax = max(bs.nmax_bucket(g.n) for g in self.graphs)
        if self.nmax > NMAX_BATCH:
            raise ValueError(f"batched path supports nmax <= {NMAX_BATCH}")
        self.chunk = chunk
        self.size = 1 << self.nmax
        self.flat = self.bcap << self.nmax
        with span("engine.setup"):
            self._shard1 = NamedSharding(self.mesh, P(BATCH_AXIS))
            D, bcap, nmax = self.D, self.bcap, self.nmax
            bt = np.asarray(ur.binom_table(nmax))
            self.binom_b = self._put(np.broadcast_to(bt, (D,) + bt.shape))
            adj = np.zeros((D, bcap, nmax), np.int32)
            max_m = 1
            for d, sh in enumerate(self.shard_graphs):
                for q, g in enumerate(sh):
                    max_m = max(max_m, g.m)
                    for (u, v) in g.edges:
                        adj[d, q, u] |= 1 << v
                        adj[d, q, v] |= 1 << u
            self.adj_b = self._put(adj)
            self.emax = max(8, int(np.ceil(max_m / 8.0)) * 8)
            emu = np.zeros((D, bcap, self.emax), np.int32)
            emv = np.zeros((D, bcap, self.emax), np.int32)
            eui = np.full((D, bcap, self.emax), -1, np.int32)
            evi = np.full((D, bcap, self.emax), -1, np.int32)
            eliv = np.zeros((D, bcap, self.emax), bool)
            m_np = np.zeros((D, bcap), np.int32)
            for d, sh in enumerate(self.shard_graphs):
                for q, g in enumerate(sh):
                    m_np[d, q] = g.m
                    for ei, (u, v) in enumerate(g.edges):
                        emu[d, q, ei] = 1 << u
                        emv[d, q, ei] = 1 << v
                        eui[d, q, ei], evi[d, q, ei] = u, v
                        eliv[d, q, ei] = True
            self.emu_b = self._put(emu)
            self.emv_b = self._put(emv)
            self.m_b = self._put(m_np)
            # typed-join edge metadata, stacked (D, bcap, emax) like emu/emv;
            # pad graphs are inner-only so their rows stay all-zero (mask-true)
            self.typed = any(g.typed for g in self.graphs)
            if self.typed:
                tarr = [np.zeros((D, bcap, self.emax), np.int32)
                        for _ in range(5)]
                for d, sh in enumerate(self.shard_graphs):
                    for q, g in enumerate(sh):
                        cols = typed_edge_arrays(g, self.emax)
                        for a, col in zip(tarr, cols):
                            a[d, q] = col
                self._targs = tuple(self._put(a) for a in tarr)
            else:
                self._targs = ()
            if algorithm == "mpdp_general":
                # phase A runs per (shard, query) on the host driver every
                # level — build its per-query device rows once, not per level
                self._phase_a_rows = [
                    [(jnp.asarray(adj[d, q]), jnp.asarray(eui[d, q]),
                      jnp.asarray(evi[d, q]), jnp.asarray(eliv[d, q]))
                     for q in range(self.Bs)] for d in range(D)]
            self.counters = [Counters() for _ in self.graphs]
            self._init_memo()

    def _put(self, x):
        """Commit a stacked host array to the mesh, sharded over ``batch``."""
        return jax.device_put(jnp.asarray(x), self._shard1)

    def _kernel(self, fn, donate: tuple = (), **statics):
        """Sharded kernel via ``_sharded``, with the engine remembering the
        executable-cache key so ``stats`` can report compile counts."""
        self._exec_keys.add(_exec_key(fn, self.mesh, statics))
        return _sharded(fn, self.mesh, donate=donate, **statics)

    @property
    def stats(self) -> dict:
        """Executable-cache accounting for this engine's sharded kernel
        keys (see ``BatchEngine.stats``)."""
        return EXEC.stats_for(self._exec_keys, pipeline=self.pipeline)

    # ------------------------------------------------------------- memo ----
    def _init_memo(self):
        D = self.D
        self.memo_cost = self._put(np.full((D, self.flat), INF, np.float32))
        self.memo_rows = self._put(np.zeros((D, self.flat), np.float32))
        self.memo_left = self._put(np.zeros((D, self.flat), np.int32))
        self.all_sets = self._put(np.zeros((D, self.flat), np.int32))
        self._next_off = [[g.n for g in sh] for sh in self.shard_graphs]
        self._level_off = [[{1: 0} for _ in sh] for sh in self.shard_graphs]
        idx_d, cost_d, rows_d, pos_d, set_d = [], [], [], [], []
        for sh in self.shard_graphs:
            idx_l, cost_l, rows_l, pos_l, set_l = [], [], [], [], []
            for q, g in enumerate(sh):
                leaves = np.array([1 << v for v in range(g.n)], np.int32)
                lrows = g.log2_card.astype(np.float32)
                lcost = cm.np_scan_cost(lrows).astype(np.float32)
                base = q << self.nmax
                idx_l.append(base + leaves.astype(np.int64))
                cost_l.append(lcost)
                rows_l.append(lrows)
                pos_l.append(base + np.arange(g.n, dtype=np.int64))
                set_l.append(leaves)
            idx_d.append(np.concatenate(idx_l))
            cost_d.append(np.concatenate(cost_l))
            rows_d.append(np.concatenate(rows_l))
            pos_d.append(np.concatenate(pos_l))
            set_d.append(np.concatenate(set_l))
        self._scatter(idx_d, cost=cost_d, rows=rows_d)
        self._set_all_sets(pos_d, set_d)

    def _stack(self, cols, cap, dt, fill=0):
        buf = np.full((self.D, cap), fill, dt)
        for d, x in enumerate(cols):
            buf[d, : len(x)] = x
        return jnp.asarray(buf)

    def _scatter(self, idx_by_d, cost=None, rows=None, left=None):
        """Stacked memo scatter: per-shard index lists, OOB-padded to a
        common cap (pad index ``flat`` -> dropped inside the shard body)."""
        cap = _cap(max(len(x) for x in idx_by_d))
        idx = self._stack([x.astype(np.int64) for x in idx_by_d], cap,
                          np.int64, fill=self.flat).astype(jnp.int32)
        scat_f = self._kernel(_set_drop, donate=(0,), cap=cap,
                              flat=self.flat, kind="f32")
        if cost is not None:
            self.memo_cost = scat_f(self.memo_cost, idx,
                                    self._stack(cost, cap, np.float32))
        if rows is not None:
            self.memo_rows = scat_f(self.memo_rows, idx,
                                    self._stack(rows, cap, np.float32))
        if left is not None:
            scat_i = self._kernel(_set_drop, donate=(0,), cap=cap,
                                  flat=self.flat, kind="i32")
            self.memo_left = scat_i(self.memo_left, idx,
                                    self._stack(left, cap, np.int32))

    def _set_all_sets(self, pos_by_d, sets_by_d):
        cap = _cap(max(len(x) for x in pos_by_d))
        pos = self._stack([x.astype(np.int64) for x in pos_by_d], cap,
                          np.int64, fill=self.flat).astype(jnp.int32)
        scatter = self._kernel(_set_drop, donate=(0,), cap=cap,
                               flat=self.flat, kind="i32")
        self.all_sets = scatter(self.all_sets,
                                pos, self._stack(sets_by_d, cap, np.int32))

    # ------------------------------------------------------------ filter ---
    def _filter_dispatch(self, i: int) -> list:
        """Dispatch level i's fused filter chunks (all D shards per step);
        no host sync — ``_filter_collect`` fetches, so the pipelined driver
        can overlap the compaction with in-flight device evaluate."""
        D, Bs, bcap = self.D, self.Bs, self.bcap
        totals = np.array([[comb(g.n, i) if g.n >= i else 0 for g in sh]
                           for sh in self.shard_graphs], np.int64)
        foff = np.zeros((D, Bs + 1), np.int64)
        np.cumsum(totals, axis=1, out=foff[:, 1:])
        total_max = int(foff[:, -1].max())
        kf = self._kernel(_bfilter_chunk, nmax=self.nmax,
                          chunk=self.chunk, bcap=bcap, pallas=self.pallas)
        k_arr = jnp.asarray(np.full(D, i, np.int32))
        ctx = {"pend": deque(),
               "per_q": [[[] for _ in range(Bs)] for _ in range(D)]}
        for lane0 in range(0, total_max, self.chunk):
            fl = np.clip(foff - lane0, -_CLIP, _CLIP)
            fpad = np.broadcast_to(fl[:, -1:], (D, bcap + 1)).astype(np.int32).copy()
            fpad[:, : Bs + 1] = fl
            ctx["pend"].append(kf(jnp.asarray(fpad), k_arr, self.binom_b,
                                  self.adj_b))
            faults.fire("chunk")
            self.chunks_dispatched += 1
            self._filter_drain(ctx, self.pend_window)
        return ctx

    def _filter_drain(self, ctx: dict, limit: int) -> None:
        """Fetch + compact pending filter chunks down to ``limit`` (one
        fused ``device_get`` per chunk covers all D shards)."""
        pend, per_q = ctx["pend"], ctx["per_q"]
        while len(pend) > limit:
            Sn, c, qn = fetch(pend.popleft())
            for d in range(self.D):
                if c[d].any():
                    Sc = Sn[d][c[d]]
                    qc = qn[d][c[d]]
                    for q in np.unique(qc):
                        per_q[d][q].append(Sc[qc == q])

    def _filter_collect(self, ctx: dict) -> list[list[np.ndarray]]:
        """Drain the remaining filter chunks and build the per-shard
        per-query set lists."""
        self._filter_drain(ctx, 0)
        sets = [[np.concatenate(l) if l else np.zeros(0, np.int32)
                 for l in ctx["per_q"][d]] for d in range(self.D)]
        return sets

    def _register_level(self, i: int, sets) -> None:
        """Host rows (shared ``cost.np_rows_for_sets``) + registration, per
        shard per query — identical to ``BatchEngine._register_level``."""
        idx_d, rows_d, pos_d, set_d = [], [], [], []
        z64, z32 = np.zeros(0, np.int64), np.zeros(0, np.int32)
        zf = np.zeros(0, np.float32)
        for d in range(self.D):
            idx_l, rows_l, pos_l, set_l = [], [], [], []
            for q, sets_q in enumerate(sets[d]):
                self._level_off[d][q][i] = self._next_off[d][q]
                if not len(sets_q):
                    continue
                base = q << self.nmax
                rows_q = cm.np_rows_for_sets(sets_q, self.shard_graphs[d][q])
                idx_l.append(base + sets_q.astype(np.int64))
                rows_l.append(rows_q)
                pos_l.append(base + self._next_off[d][q]
                             + np.arange(len(sets_q), dtype=np.int64))
                set_l.append(sets_q)
                self._next_off[d][q] += len(sets_q)
            idx_d.append(np.concatenate(idx_l) if idx_l else z64)
            rows_d.append(np.concatenate(rows_l) if rows_l else zf)
            pos_d.append(np.concatenate(pos_l) if pos_l else z64)
            set_d.append(np.concatenate(set_l) if set_l else z32)
        if any(len(x) for x in idx_d):
            self._scatter(idx_d, rows=rows_d)
            self._set_all_sets(pos_d, set_d)

    # ---------------------------------------------------------- evaluate ---
    def _bump_counters(self, ev_acc, ccp_acc) -> None:
        """Fold per-(shard, slot) lane counts back onto the real queries
        (inert padding slots are simply never read)."""
        for qi in range(self.B):
            d, s = qi % self.D, qi // self.D
            self.counters[qi].evaluated += int(ev_acc[d, s])
            self.counters[qi].ccp += int(ccp_acc[d, s])

    def _commit_best(self, sets, best_cost, best_left) -> None:
        """Commit a level: per-(shard, query) slices of the per-shard best
        arrays, one stacked scatter."""
        idx_d, cost_d, left_d = [], [], []
        z64, z32 = np.zeros(0, np.int64), np.zeros(0, np.int32)
        zf = np.zeros(0, np.float32)
        for d in range(self.D):
            idx_l, cost_l, left_l = [], [], []
            off = 0
            for q, sets_q in enumerate(sets[d]):
                nsq = len(sets_q)
                bc = best_cost[d][off: off + nsq]
                blft = best_left[d][off: off + nsq]
                off += nsq
                fin = np.isfinite(bc)
                if fin.any():
                    idx_l.append((q << self.nmax) + sets_q[fin].astype(np.int64))
                    cost_l.append(bc[fin])
                    left_l.append(blft[fin])
            idx_d.append(np.concatenate(idx_l) if idx_l else z64)
            cost_d.append(np.concatenate(cost_l) if cost_l else zf)
            left_d.append(np.concatenate(left_l) if left_l else z32)
        if any(len(x) for x in idx_d):
            self._scatter(idx_d, cost=cost_d, left=left_d)

    def _eval_dispatch(self, i: int, sets):
        """Segmented lane spaces (DPSUB ``sets x 2^i``, tree ``sets x m``):
        each shard's lane space is chunked on the same grid a standalone
        ``BatchEngine`` would use; shorter shards run dead (all-masked)
        chunks at the tail, whose all-INF segments merge as no-ops.
        Dispatch only — ``_eval_finalize`` fetches, merges and commits."""
        D, Bs, bcap = self.D, self.Bs, self.bcap
        ns = np.array([[len(s) for s in sets[d]] for d in range(D)], np.int64)
        if self.algorithm == "mpdp_tree":
            mult = np.array([[g.m for g in sh] for sh in self.shard_graphs],
                            np.int64)
        else:
            mult = np.full((D, Bs), np.int64(1) << i, np.int64)
        lanes = ns * mult
        eoff = np.zeros((D, Bs + 1), np.int64)
        np.cumsum(lanes, axis=1, out=eoff[:, 1:])
        totals = eoff[:, -1]
        total_max = int(totals.max())
        if total_max == 0:
            return None
        soff = np.zeros((D, Bs + 1), np.int64)
        np.cumsum(ns, axis=1, out=soff[:, 1:])
        loff = np.zeros((D, bcap), np.int64)
        for d in range(D):
            for q in range(Bs):
                loff[d, q] = (q << self.nmax) + self._level_off[d][q][i]
        loff_d = jnp.asarray(loff.astype(np.int32))
        spad = np.broadcast_to(soff[:, -1:], (D, bcap)).copy()
        spad[:, :Bs] = soff[:, :Bs]
        soff_d = jnp.asarray(spad.astype(np.int32))
        nseg = self.chunk + 2
        if self.algorithm == "mpdp_tree":
            kernel = self._kernel(_beval_tree_chunk, nmax=self.nmax,
                                  chunk=self.chunk, nseg=nseg, bcap=bcap,
                                  pallas=self.pallas, typed=self.typed)
        else:
            kernel = self._kernel(_beval_dpsub_chunk, nmax=self.nmax,
                                  chunk=self.chunk, nseg=nseg, bcap=bcap,
                                  pallas=self.pallas, typed=self.typed)
        i_arr = jnp.asarray(np.full(D, i, np.int32))
        ctx = {"pend": deque(), "totals": totals,
               "best_cost": [np.full(int(soff[d, -1]), INF, np.float32)
                             for d in range(D)],
               "best_left": [np.zeros(int(soff[d, -1]), np.int32)
                             for d in range(D)],
               "ev": np.zeros((D, Bs), np.int64),
               "ccp": np.zeros((D, Bs), np.int64)}
        for lane0 in range(0, total_max, self.chunk):
            el = np.clip(eoff - lane0, -_CLIP, _CLIP)
            epad = np.broadcast_to(el[:, -1:], (D, bcap + 1)).astype(np.int32).copy()
            epad[:, : Bs + 1] = el
            seg0 = np.zeros(D, np.int64)
            for d in range(D):
                p0 = int(np.searchsorted(eoff[d], lane0, side="right")) - 1
                p0 = min(max(p0, 0), Bs - 1)
                seg0[d] = soff[d, p0] + (lane0 - eoff[d, p0]) // mult[d, p0]
            seg0_d = jnp.asarray(np.clip(seg0, -_CLIP, _CLIP).astype(np.int32))
            if self.algorithm == "mpdp_tree":
                out = kernel(
                    self.all_sets, jnp.asarray(epad), loff_d, soff_d, seg0_d,
                    self.m_b, self.adj_b, self.emu_b, self.emv_b,
                    self.memo_cost, self.memo_rows, *self._targs)
            else:
                out = kernel(
                    self.all_sets, jnp.asarray(epad), loff_d, soff_d, seg0_d,
                    i_arr, self.adj_b, self.memo_cost, self.memo_rows,
                    *self._targs)
            ctx["pend"].append((lane0, seg0, out))
            faults.fire("chunk")
            self.chunks_dispatched += 1
            self._eval_drain(ctx, self.pend_window)
        return ctx

    def _eval_drain(self, ctx: dict, limit: int) -> None:
        """Fetch pending fused chunk results down to ``limit``, folding them
        into the per-shard best arrays (chunk order, as synchronous)."""
        Bs, totals = self.Bs, ctx["totals"]
        pend = ctx["pend"]
        while len(pend) > limit:
            lane0, seg0, out = pend.popleft()
            scn, sln, evn, ccpn = fetch(out)
            ctx["ev"] += evn[:, :Bs]
            ctx["ccp"] += ccpn[:, :Bs]
            for d in range(self.D):
                if lane0 < totals[d]:
                    _merge_best(ctx["best_cost"][d], ctx["best_left"][d],
                                int(seg0[d]), scn[d], sln[d])

    def _eval_finalize(self, i: int, sets, ctx) -> None:
        if ctx is None:
            return
        self._eval_drain(ctx, 0)
        self._bump_counters(ctx["ev"], ctx["ccp"])
        self._commit_best(sets, ctx["best_cost"], ctx["best_left"])

    # ------------------------------------------------- MPDP-general phase --
    def _pairs_level(self, sets):
        """Phase A per shard per query (shared ``blocks.np_pairs_for_sets``
        host driver), fused into per-shard (set, block, qid, segment) pair
        arrays — the per-shard analogue of ``BatchEngine._pairs_level``."""
        out = []
        for d in range(self.D):
            soff = 0
            ps_l, pb_l, pq_l, pk_l = [], [], [], []
            for q, sets_q in enumerate(sets[d]):
                if not len(sets_q):
                    continue
                g = self.shard_graphs[d][q]
                adj_q, eu_q, ev_q, eliv_q = self._phase_a_rows[d][q]
                ps_q, pb_q, slots = bl.np_pairs_for_sets(
                    sets_q, g, adj_q, eu_q, ev_q, eliv_q,
                    nmax=self.nmax, emax=self.emax, cyc_cap=self.cyc_cap)
                self.blocks_sets += len(sets_q)
                self.blocks_slots += slots
                ps_l.append(ps_q)
                pb_l.append(pb_q)
                pq_l.append(np.full(len(ps_q), q, np.int32))
                pk_l.append(soff + np.searchsorted(sets_q, ps_q).astype(np.int64))
                soff += len(sets_q)
            if ps_l:
                out.append((np.concatenate(ps_l), np.concatenate(pb_l),
                            np.concatenate(pq_l), np.concatenate(pk_l)))
            else:
                z = np.zeros(0, np.int32)
                out.append((z, z, z, np.zeros(0, np.int64)))
        return out

    def _eval_general_dispatch(self, i: int, sets, pairs):
        """Dispatch the block prefix-sum chunks over the per-shard pair
        arrays from ``_pairs_level`` (phase A, host); no host sync."""
        D = self.D
        if not any(len(p[0]) for p in pairs):
            return None
        offs_by_d, totals = [], np.zeros(D, np.int64)
        for d, (ps, pb, _, _) in enumerate(pairs):
            sizes = bs.np_popcount(pb).astype(np.int64)
            offs = np.zeros(len(ps) + 1, np.int64)
            np.cumsum((np.int64(1) << sizes).astype(np.int64), out=offs[1:])
            offs_by_d.append(offs)
            totals[d] = offs[-1]
        total_max = int(totals.max())
        ctx = {"pend": deque(), "pairs": pairs,
               "ev": np.zeros((D, self.Bs), np.int64),
               "ccp": np.zeros((D, self.Bs), np.int64),
               "k": [[] for _ in range(D)],
               "c": [[] for _ in range(D)],
               "l": [[] for _ in range(D)]}
        for lane0 in range(0, total_max, self.chunk):
            p0s, npairs = np.zeros(D, np.int64), np.zeros(D, np.int64)
            for d in range(D):
                lane1 = min(lane0 + self.chunk, int(totals[d]))
                if lane1 <= lane0:
                    continue
                offs = offs_by_d[d]
                p0s[d] = int(np.searchsorted(offs, lane0, side="right")) - 1
                npairs[d] = int(np.searchsorted(offs, lane1, side="left")) - p0s[d]
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
                ps, pb, pq, _ = pairs[d]
                psl[d, :np_d] = ps[p0: p0 + np_d]
                pbl[d, :np_d] = pb[p0: p0 + np_d]
                pql[d, :np_d] = pq[p0: p0 + np_d]
                ofl[d, :np_d] = offs_by_d[d][p0: p0 + np_d] - lane0
                lane_cnt[d] = min(lane0 + self.chunk, int(totals[d])) - lane0
            ofl = np.clip(ofl, -_CLIP, _CLIP).astype(np.int32)
            kernel = self._kernel(_beval_general_chunk, nmax=self.nmax,
                                  chunk=self.chunk, pcap=pcap, bcap=self.bcap,
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
            self._eval_general_drain(ctx, self.pend_window)
        return ctx

    def _eval_general_drain(self, ctx: dict, limit: int) -> None:
        """Fetch pending fused pair chunks down to ``limit``, collecting
        finite per-pair candidates per shard for the scattered merge."""
        Bs, pairs = self.Bs, ctx["pairs"]
        pend = ctx["pend"]
        while len(pend) > limit:
            p0s, npairs, out = pend.popleft()
            scn_all, sln_all, evn, ccpn = fetch(out)
            ctx["ev"] += evn[:, :Bs]
            ctx["ccp"] += ccpn[:, :Bs]
            for d in range(self.D):
                np_d, p0 = int(npairs[d]), int(p0s[d])
                if not np_d:
                    continue
                scn = scn_all[d][:np_d]
                fin = np.isfinite(scn)
                ctx["k"][d].append(pairs[d][3][p0: p0 + np_d][fin])
                ctx["c"][d].append(scn[fin])
                ctx["l"][d].append(sln_all[d][:np_d][fin])

    def _eval_general_finalize(self, i: int, sets, ctx) -> None:
        if ctx is None:
            return
        D = self.D
        self._eval_general_drain(ctx, 0)
        best_cost = [np.full(sum(len(s) for s in sets[d]), INF, np.float32)
                     for d in range(D)]
        best_left = [np.zeros(sum(len(s) for s in sets[d]), np.int32)
                     for d in range(D)]
        self._bump_counters(ctx["ev"], ctx["ccp"])
        for d in range(D):
            if ctx["k"][d]:
                _merge_scattered(best_cost[d], best_left[d],
                                 np.concatenate(ctx["k"][d]),
                                 np.concatenate(ctx["c"][d]),
                                 np.concatenate(ctx["l"][d]))
        self._commit_best(sets, best_cost, best_left)

    # ------------------------------------------------------------ driver ---
    # (run / run_levels / the pipelined rotation come from _LevelLoop)
    def collect(self) -> list[OptimizeResult]:
        """Fetch the stacked memo and extract per-query results (see
        ``BatchEngine.collect``)."""
        t0 = time.perf_counter()
        cost_all, left_all = fetch((self.memo_cost, self.memo_left))
        out = []
        wall = self._wall + time.perf_counter() - t0
        for qi, g in enumerate(self.graphs):
            d, s = qi % self.D, qi // self.D
            base = s << self.nmax
            cost = float(cost_all[d, base + g.full_set])
            if np.isfinite(cost):
                p = extract_plan(g.full_set,
                                 left_all[d, base: base + self.size], g)
                r = OptimizeResult(plan=p, cost=cost,
                                   counters=self.counters[qi],
                                   algorithm=f"batch_{self.algorithm}",
                                   wall_s=wall / self.B, levels=g.n)
            elif self.degraded is not None:
                # deadline expired mid-batch: anytime stitch over this
                # query's committed memo prefix (see BatchEngine.collect)
                from ..heuristics.idp import stitch_partial_memo
                p, c, dinfo = stitch_partial_memo(
                    g, cost_all[d, base: base + self.size],
                    left_all[d, base: base + self.size])
                r = OptimizeResult(plan=p, cost=c,
                                   counters=self.counters[qi],
                                   algorithm=f"batch_{self.algorithm}",
                                   wall_s=wall / self.B,
                                   levels=self.degraded["levels_done"])
                r.info["degraded"] = {**self.degraded, **dinfo}
            else:
                raise RuntimeError(f"no plan found for batch query {qi}")
            out.append(r)
        return out
