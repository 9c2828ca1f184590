"""Pallas TPU kernel for the evaluate-phase bit-twiddling hot spot.

The paper's GPU *evaluate* phase (warp per set, thread per Join-Pair,
Collaborative Context Collection against divergence) becomes a dense VPU
kernel: lanes are tiled (ROWS x 128) int32 blocks in VMEM; the adjacency
bitmaps live in SMEM via scalar prefetch and are combined with the lane
vectors through a static NMAX-step select-OR loop (no gathers, no
divergence — masked lanes are the TPU-native CCC).

Per lane (DPSUB/MPDP-general inner enumeration):
    lb   = pdep(sub, S)            # bit-deposit enumeration index onto S
    rb   = S & ~lb
    ccp  = lb,rb nonempty & connected(lb) & connected(rb) & cross-edge(lb,rb)
grow(lb | rb) runs as a fixed NMAX-sweep frontier expansion.

The matching pure-jnp oracle is kernels/ref.py.  ``interpret`` has no
default: ops.py passes it from the backend (interpreted on the CPU test
backend, compiled through Mosaic on a TPU).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128      # TPU vector lane width
SUBLANE = 8     # int32 sublane tile


def _neighbors_smem(cur, adj_ref, nmax: int):
    """OR_{v in cur} adj[v] with adj in SMEM: static select-OR loop."""
    acc = jnp.zeros_like(cur)
    for v in range(nmax):
        a_v = adj_ref[v]                      # scalar read (SMEM)
        take = ((cur >> v) & 1) != 0
        acc = jnp.where(take, acc | a_v, acc)
    return acc


def _grow_block(src, restrict, adj_ref, nmax: int):
    cur = src & restrict
    for _ in range(nmax):                     # diameter-bounded sweeps
        cur = (cur | _neighbors_smem(cur, adj_ref, nmax)) & restrict
    return cur


def _lsb(x):
    return x & (~x + jnp.int32(1))


def _pdep_block(rank, mask, nmax: int):
    out = jnp.zeros_like(mask)
    k = jnp.zeros_like(mask)
    for b in range(nmax):
        mbit = (mask >> b) & 1
        take = (rank >> k) & 1                # vector-by-vector shift
        out = out | (((mbit & take) != 0).astype(jnp.int32) << b)
        k = k + mbit
    return out


def ccp_eval_kernel(adj_ref, s_ref, sub_ref, lb_ref, rb_ref, ccp_ref,
                    *, nmax: int):
    """One (ROWS, LANE) block of lanes."""
    S = s_ref[...]
    sub = sub_ref[...]
    lb = _pdep_block(sub, S, nmax)
    rb = S & ~lb
    conn_l = _grow_block(_lsb(lb), lb, adj_ref, nmax) == lb
    conn_r = _grow_block(_lsb(rb), rb, adj_ref, nmax) == rb
    cross = (_neighbors_smem(lb, adj_ref, nmax) & rb) != 0
    ccp = (lb != 0) & (rb != 0) & conn_l & conn_r & cross
    lb_ref[...] = lb
    rb_ref[...] = rb
    ccp_ref[...] = ccp.astype(jnp.int32)


def connectivity_kernel(adj_ref, s_ref, conn_ref, *, nmax: int):
    """Filter-phase block: is G[S] connected, per lane."""
    S = s_ref[...]
    reach = _grow_block(_lsb(S), S, adj_ref, nmax)
    conn_ref[...] = (reach == S).astype(jnp.int32)


def grow_pair_kernel(adj_ref, s_ref, lb_ref, rb_ref, sl_ref, sr_ref,
                     *, nmax: int):
    """MPDP-general: grow the block-level seed (lb, rb) to (S_left, S_right)."""
    S = s_ref[...]
    lb = lb_ref[...]
    rb = rb_ref[...]
    sl = _grow_block(lb, S & ~rb, adj_ref, nmax)
    sl_ref[...] = sl
    sr_ref[...] = S & ~sl


# ------------------------------------------------------ batched-query lanes --
# BatchEngine folds B stacked queries into the lane dimension: every lane
# carries a query id alongside its set/subset decode.  The (bcap, nmax)
# adjacency table is scalar-prefetched into SMEM; a static (q, v) select loop
# materializes each lane's own adjacency row (the batched analogue of the
# single-query select-OR above — no gathers, masked lanes stay the CCC).

def _select_adj_rows(qid, adj_ref, nb: int, nmax: int):
    """Per-lane adjacency rows: rows[v] = adj[qid_of_lane, v] (vector)."""
    rows = []
    for v in range(nmax):
        acc = jnp.zeros_like(qid)
        for q in range(nb):
            a_qv = adj_ref[q, v]              # scalar read (SMEM)
            acc = jnp.where(qid == q, a_qv, acc)
        rows.append(acc)
    return rows


def _neighbors_rows(cur, rows, nmax: int):
    acc = jnp.zeros_like(cur)
    for v in range(nmax):
        take = ((cur >> v) & 1) != 0
        acc = jnp.where(take, acc | rows[v], acc)
    return acc


def _grow_rows(src, restrict, rows, nmax: int):
    cur = src & restrict
    for _ in range(nmax):
        cur = (cur | _neighbors_rows(cur, rows, nmax)) & restrict
    return cur


def bconnectivity_kernel(adj_ref, s_ref, qid_ref, conn_ref, *, nmax: int,
                         nb: int):
    """Batched filter block: is G_q[S] connected, per (query, set) lane."""
    S = s_ref[...]
    rows = _select_adj_rows(qid_ref[...], adj_ref, nb, nmax)
    reach = _grow_rows(_lsb(S), S, rows, nmax)
    conn_ref[...] = (reach == S).astype(jnp.int32)


def bccp_eval_kernel(adj_ref, s_ref, sub_ref, qid_ref, lb_ref, rb_ref,
                     ccp_ref, *, nmax: int, nb: int):
    """Batched DPSUB evaluate block: per-lane (query, set, subset)."""
    S = s_ref[...]
    sub = sub_ref[...]
    rows = _select_adj_rows(qid_ref[...], adj_ref, nb, nmax)
    lb = _pdep_block(sub, S, nmax)
    rb = S & ~lb
    conn_l = _grow_rows(_lsb(lb), lb, rows, nmax) == lb
    conn_r = _grow_rows(_lsb(rb), rb, rows, nmax) == rb
    cross = (_neighbors_rows(lb, rows, nmax) & rb) != 0
    ccp = (lb != 0) & (rb != 0) & conn_l & conn_r & cross
    lb_ref[...] = lb
    rb_ref[...] = rb
    ccp_ref[...] = ccp.astype(jnp.int32)


def btree_eval_kernel(adj_ref, s_ref, ub_ref, vb_ref, qid_ref, sl_ref,
                      in_ref, *, nmax: int, nb: int):
    """Batched MPDP:Tree evaluate block: per-lane (query, set, edge).

    Deleting the lane's tree edge (u, v) splits S: S_left is the grow() of
    u's bit over S on the edge-deleted graph (per-lane exclusion masks)."""
    S = s_ref[...]
    ub = ub_ref[...]
    vb = vb_ref[...]
    rows = _select_adj_rows(qid_ref[...], adj_ref, nb, nmax)
    edge_in = ((S & ub) != 0) & ((S & vb) != 0)
    cur = ub & S
    for _ in range(nmax):
        acc = jnp.zeros_like(cur)
        for v in range(nmax):
            take = ((cur >> v) & 1) != 0
            u_is_v = ((ub >> v) & 1) != 0
            v_is_v = ((vb >> v) & 1) != 0
            excl = jnp.where(u_is_v, vb, 0) | jnp.where(v_is_v, ub, 0)
            acc = jnp.where(take, acc | (rows[v] & ~excl), acc)
        cur = (cur | acc) & S
    sl_ref[...] = cur
    in_ref[...] = edge_in.astype(jnp.int32)


def bgeneral_eval_kernel(adj_ref, s_ref, blk_ref, r_ref, qid_ref, lb_ref,
                         sl_ref, ccp_ref, *, nmax: int, nb: int):
    """Batched MPDP-general evaluate block: per-lane (query, set, block, rank).

    The block-level seed (lb, rb) is CCP-checked on the lane's own query
    graph, then grown to the full (S_left, S_right) split of S."""
    S = s_ref[...]
    block = blk_ref[...]
    r = r_ref[...]
    rows = _select_adj_rows(qid_ref[...], adj_ref, nb, nmax)
    lb = _pdep_block(r, block, nmax)
    rb = block & ~lb
    conn_l = _grow_rows(_lsb(lb), lb, rows, nmax) == lb
    conn_r = _grow_rows(_lsb(rb), rb, rows, nmax) == rb
    cross = (_neighbors_rows(lb, rows, nmax) & rb) != 0
    ccp = (lb != 0) & (rb != 0) & conn_l & conn_r & cross
    sl = _grow_rows(lb, S & ~rb, rows, nmax)
    lb_ref[...] = lb
    sl_ref[...] = sl
    ccp_ref[...] = ccp.astype(jnp.int32)


def _pad2d(x, rows_blk: int):
    n = x.shape[0]
    rows = -(-n // LANE)
    rows_pad = -(-rows // rows_blk) * rows_blk
    flat = jnp.zeros(rows_pad * LANE, x.dtype).at[:n].set(x)
    return flat.reshape(rows_pad, LANE), n


@functools.partial(jax.jit, static_argnames=("nmax", "rows_blk", "interpret"))
def ccp_eval(S, sub, adj, *, nmax: int, rows_blk: int = 32,
             interpret: bool):
    """(L,) int32 lanes -> (lb, rb, ccp int32) via the Pallas kernel."""
    S2, n = _pad2d(S, rows_blk)
    sub2, _ = _pad2d(sub, rows_blk)
    rows = S2.shape[0]
    grid = (rows // rows_blk,)
    blk = pl.BlockSpec((rows_blk, LANE), lambda i, *_: (i, 0))
    out_shape = [jax.ShapeDtypeStruct((rows, LANE), jnp.int32)] * 3
    lb, rb, ccp = pl.pallas_call(
        functools.partial(ccp_eval_kernel, nmax=nmax),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=grid,
            in_specs=[blk, blk], out_specs=[blk, blk, blk]),
        out_shape=out_shape,
        interpret=interpret,
    )(adj, S2, sub2)
    return (lb.reshape(-1)[:n], rb.reshape(-1)[:n], ccp.reshape(-1)[:n])


@functools.partial(jax.jit, static_argnames=("nmax", "rows_blk", "interpret"))
def connectivity(S, adj, *, nmax: int, rows_blk: int = 32,
                 interpret: bool):
    S2, n = _pad2d(S, rows_blk)
    rows = S2.shape[0]
    blk = pl.BlockSpec((rows_blk, LANE), lambda i, *_: (i, 0))
    conn = pl.pallas_call(
        functools.partial(connectivity_kernel, nmax=nmax),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(rows // rows_blk,),
            in_specs=[blk], out_specs=blk),
        out_shape=jax.ShapeDtypeStruct((rows, LANE), jnp.int32),
        interpret=interpret,
    )(adj, S2)
    return conn.reshape(-1)[:n]


@functools.partial(jax.jit, static_argnames=("nmax", "nb", "rows_blk",
                                             "interpret"))
def bconnectivity(S, qid, adj_b, *, nmax: int, nb: int, rows_blk: int = 32,
                  interpret: bool):
    """(L,) lanes + per-lane query ids -> connectivity against adj_b[qid]."""
    S2, n = _pad2d(S, rows_blk)
    q2, _ = _pad2d(qid, rows_blk)
    rows = S2.shape[0]
    blk = pl.BlockSpec((rows_blk, LANE), lambda i, *_: (i, 0))
    conn = pl.pallas_call(
        functools.partial(bconnectivity_kernel, nmax=nmax, nb=nb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(rows // rows_blk,),
            in_specs=[blk, blk], out_specs=blk),
        out_shape=jax.ShapeDtypeStruct((rows, LANE), jnp.int32),
        interpret=interpret,
    )(adj_b, S2, q2)
    return conn.reshape(-1)[:n]


@functools.partial(jax.jit, static_argnames=("nmax", "nb", "rows_blk",
                                             "interpret"))
def bccp_eval(S, sub, qid, adj_b, *, nmax: int, nb: int, rows_blk: int = 32,
              interpret: bool):
    """Batched DPSUB lanes -> (lb, rb, ccp int32) via the Pallas kernel."""
    S2, n = _pad2d(S, rows_blk)
    sub2, _ = _pad2d(sub, rows_blk)
    q2, _ = _pad2d(qid, rows_blk)
    rows = S2.shape[0]
    blk = pl.BlockSpec((rows_blk, LANE), lambda i, *_: (i, 0))
    out_shape = [jax.ShapeDtypeStruct((rows, LANE), jnp.int32)] * 3
    lb, rb, ccp = pl.pallas_call(
        functools.partial(bccp_eval_kernel, nmax=nmax, nb=nb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(rows // rows_blk,),
            in_specs=[blk, blk, blk], out_specs=[blk, blk, blk]),
        out_shape=out_shape,
        interpret=interpret,
    )(adj_b, S2, sub2, q2)
    return (lb.reshape(-1)[:n], rb.reshape(-1)[:n], ccp.reshape(-1)[:n])


@functools.partial(jax.jit, static_argnames=("nmax", "nb", "rows_blk",
                                             "interpret"))
def btree_eval(S, ub, vb, qid, adj_b, *, nmax: int, nb: int,
               rows_blk: int = 32, interpret: bool):
    """Batched MPDP:Tree lanes -> (S_left, edge_in int32)."""
    S2, n = _pad2d(S, rows_blk)
    ub2, _ = _pad2d(ub, rows_blk)
    vb2, _ = _pad2d(vb, rows_blk)
    q2, _ = _pad2d(qid, rows_blk)
    rows = S2.shape[0]
    blk = pl.BlockSpec((rows_blk, LANE), lambda i, *_: (i, 0))
    out_shape = [jax.ShapeDtypeStruct((rows, LANE), jnp.int32)] * 2
    sl, edge_in = pl.pallas_call(
        functools.partial(btree_eval_kernel, nmax=nmax, nb=nb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(rows // rows_blk,),
            in_specs=[blk, blk, blk, blk], out_specs=[blk, blk]),
        out_shape=out_shape,
        interpret=interpret,
    )(adj_b, S2, ub2, vb2, q2)
    return sl.reshape(-1)[:n], edge_in.reshape(-1)[:n]


@functools.partial(jax.jit, static_argnames=("nmax", "nb", "rows_blk",
                                             "interpret"))
def bgeneral_eval(S, block, r, qid, adj_b, *, nmax: int, nb: int,
                  rows_blk: int = 32, interpret: bool):
    """Batched MPDP-general lanes -> (lb, S_left, ccp int32)."""
    S2, n = _pad2d(S, rows_blk)
    blk2, _ = _pad2d(block, rows_blk)
    r2, _ = _pad2d(r, rows_blk)
    q2, _ = _pad2d(qid, rows_blk)
    rows = S2.shape[0]
    blk = pl.BlockSpec((rows_blk, LANE), lambda i, *_: (i, 0))
    out_shape = [jax.ShapeDtypeStruct((rows, LANE), jnp.int32)] * 3
    lb, sl, ccp = pl.pallas_call(
        functools.partial(bgeneral_eval_kernel, nmax=nmax, nb=nb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(rows // rows_blk,),
            in_specs=[blk, blk, blk, blk], out_specs=[blk, blk, blk]),
        out_shape=out_shape,
        interpret=interpret,
    )(adj_b, S2, blk2, r2, q2)
    return (lb.reshape(-1)[:n], sl.reshape(-1)[:n], ccp.reshape(-1)[:n])


@functools.partial(jax.jit, static_argnames=("nmax", "rows_blk", "interpret"))
def grow_pair(S, lb, rb, adj, *, nmax: int, rows_blk: int = 32,
              interpret: bool):
    S2, n = _pad2d(S, rows_blk)
    lb2, _ = _pad2d(lb, rows_blk)
    rb2, _ = _pad2d(rb, rows_blk)
    rows = S2.shape[0]
    blk = pl.BlockSpec((rows_blk, LANE), lambda i, *_: (i, 0))
    out_shape = [jax.ShapeDtypeStruct((rows, LANE), jnp.int32)] * 2
    sl, sr = pl.pallas_call(
        functools.partial(grow_pair_kernel, nmax=nmax),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(rows // rows_blk,),
            in_specs=[blk, blk, blk], out_specs=[blk, blk]),
        out_shape=out_shape,
        interpret=interpret,
    )(adj, S2, lb2, rb2)
    return sl.reshape(-1)[:n], sr.reshape(-1)[:n]
