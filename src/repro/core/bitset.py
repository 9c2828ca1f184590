"""int32 bitmap-set primitives shared by every optimizer kernel.

Conventions
-----------
* A *relation set* is an int32 whose bits 0..NMAX-1 mark member relations.
* NMAX <= 30 so that every bitmap (and every dense-memo index derived from a
  bitmap) is a non-negative int32 — safe for jnp shifts, Pallas TPU lanes and
  numpy alike.
* ``adj`` is an ``int32[NMAX]`` array: ``adj[v]`` is the neighbour bitmap of
  vertex ``v`` in the join graph.  It is a *dynamic* input everywhere so that
  one compiled kernel serves every query / IDP-UnionDP subproblem of the same
  NMAX bucket.

Both jnp (device) and numpy (host mirror/oracle) flavours live here; the two
must agree bit-for-bit.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

NMAX_HARD = 30  # int32-sign-safe ceiling for exact algorithms


def nmax_bucket(n: int) -> int:
    """Static NMAX bucket for a query of ``n`` relations (limits recompiles).

    Up to 24 relations the buckets are 8, 16 and 24, so queries of nearby
    sizes share compiled executables.  Above 24 the bucket is ``n`` itself:
    only the solo engine runs there, its dense memo holds ``1 << nmax``
    entries, and a coarse 30 bucket would ask 16 GiB of a 25-relation
    query where 512 MiB suffice."""
    if n > NMAX_HARD:
        raise ValueError(f"exact bitmap algorithms support n <= {NMAX_HARD}, got {n}")
    for b in (8, 16, 24):
        if n <= b:
            return b
    return n


# ---------------------------------------------------------------------------
# jnp flavour (lane-vectorised: every function maps int32[...] -> int32[...])
# ---------------------------------------------------------------------------

def popcount(x: jnp.ndarray) -> jnp.ndarray:
    return jax.lax.population_count(x)


def lsb(x: jnp.ndarray) -> jnp.ndarray:
    """Lowest set bit of ``x`` (0 if x == 0).  int32-safe: x & (~x + 1)."""
    return x & (~x + jnp.int32(1))


def bit(v: jnp.ndarray) -> jnp.ndarray:
    return jnp.int32(1) << v


def member_matrix(s: jnp.ndarray, nmax: int) -> jnp.ndarray:
    """(..., ) int32 -> (..., nmax) int32 0/1 membership of each vertex."""
    shifts = jnp.arange(nmax, dtype=jnp.int32)
    return (s[..., None] >> shifts) & jnp.int32(1)


def neighbors(s: jnp.ndarray, adj: jnp.ndarray) -> jnp.ndarray:
    """OR of ``adj[v]`` over all v in s.  s: (...,) int32, adj: (nmax,) int32."""
    nmax = adj.shape[0]
    mem = member_matrix(s, nmax).astype(bool)             # (..., nmax)
    sel = jnp.where(mem, adj, jnp.int32(0))               # (..., nmax)
    return jnp.bitwise_or.reduce(sel, axis=-1)


def grow(src: jnp.ndarray, restrict: jnp.ndarray, adj: jnp.ndarray) -> jnp.ndarray:
    """Paper §3.2.1 grow(): all vertices of ``restrict`` reachable from ``src``.

    Batched fixed-point: iterates until no lane changes (diameter-bounded, so
    usually just a few sweeps instead of NMAX).
    """
    src = src & restrict

    def cond(state):
        cur, changed = state
        return changed

    def body(state):
        cur, _ = state
        nxt = (cur | neighbors(cur, adj)) & restrict
        return nxt, jnp.any(nxt != cur)

    out, _ = jax.lax.while_loop(cond, body, (src, jnp.bool_(True)))
    return out


def grow_excl_edge(src, restrict, adj, ubit, vbit):
    """grow() on the graph with one edge (u, v) removed — per-lane ubit/vbit.

    Used by MPDP:Tree: deleting tree edge e splits S into the two CCP sides.
    ``adj`` may be the shared ``(nmax,)`` table or per-lane ``(..., nmax)``
    rows — the broadcasting body serves both.
    """
    nmax = adj.shape[-1]
    shifts = jnp.arange(nmax, dtype=jnp.int32)

    def nbr(cur):
        mem = ((cur[..., None] >> shifts) & 1).astype(bool)       # (..., nmax)
        row_is_u = ((ubit[..., None] >> shifts) & 1).astype(bool)  # row v==u?
        row_is_v = ((vbit[..., None] >> shifts) & 1).astype(bool)
        excl = (jnp.where(row_is_u, vbit[..., None], 0)
                | jnp.where(row_is_v, ubit[..., None], 0))
        sel = jnp.where(mem, adj & ~excl, jnp.int32(0))
        return jnp.bitwise_or.reduce(sel, axis=-1)

    src = src & restrict

    def cond(state):
        return state[1]

    def body(state):
        cur, _ = state
        nxt = (cur | nbr(cur)) & restrict
        return nxt, jnp.any(nxt != cur)

    out, _ = jax.lax.while_loop(cond, body, (src, jnp.bool_(True)))
    return out


def is_connected(s: jnp.ndarray, adj: jnp.ndarray) -> jnp.ndarray:
    """G[s] connected? (singletons/empty count as connected)."""
    return grow(lsb(s), s, adj) == s


# -- batched-query variants: each lane carries its own adjacency row ---------
# (``adjq`` is i32[..., nmax]: lane l of a batch chunk sees the adjacency of
# the query it was decoded to, so one kernel serves B stacked queries.)

def neighbors_rows(s: jnp.ndarray, adjq: jnp.ndarray) -> jnp.ndarray:
    """Like neighbors(), but with per-lane adjacency rows adjq: (..., nmax)."""
    nmax = adjq.shape[-1]
    mem = member_matrix(s, nmax).astype(bool)
    sel = jnp.where(mem, adjq, jnp.int32(0))
    return jnp.bitwise_or.reduce(sel, axis=-1)


def grow_rows(src: jnp.ndarray, restrict: jnp.ndarray,
              adjq: jnp.ndarray) -> jnp.ndarray:
    """grow() with per-lane adjacency rows (batched-query fixed point)."""
    src = src & restrict

    def cond(state):
        return state[1]

    def body(state):
        cur, _ = state
        nxt = (cur | neighbors_rows(cur, adjq)) & restrict
        return nxt, jnp.any(nxt != cur)

    out, _ = jax.lax.while_loop(cond, body, (src, jnp.bool_(True)))
    return out


def is_connected_rows(s: jnp.ndarray, adjq: jnp.ndarray) -> jnp.ndarray:
    """is_connected() with per-lane adjacency rows."""
    return grow_rows(lsb(s), s, adjq) == s


def grow_excl_edge_rows(src, restrict, adjq, ubit, vbit):
    """grow_excl_edge() with per-lane adjacency rows adjq: (..., nmax) — the
    batched MPDP:Tree evaluate, where each lane deletes its own query's tree
    edge.  Same body (one traversal to keep batched and sequential plans in
    lockstep); this alias just mirrors the ``*_rows`` naming of the other
    batched-query variants."""
    return grow_excl_edge(src, restrict, adjq, ubit, vbit)


def pdep(rank: jnp.ndarray, mask: jnp.ndarray, nmax: int) -> jnp.ndarray:
    """Parallel bit deposit: scatter the low ``popcount(mask)`` bits of rank
    onto the set bit positions of ``mask`` (paper §2.2.1, x86 PDEP analogue).
    """
    shifts = jnp.arange(nmax, dtype=jnp.int32)
    below = (jnp.int32(1) << shifts) - 1                    # (nmax,)
    k = popcount(mask[..., None] & below)                   # bits of mask below b
    mask_bit = (mask[..., None] >> shifts) & 1
    take = (rank[..., None] >> k) & 1
    out = (mask_bit & take) << shifts
    return jnp.bitwise_or.reduce(out, axis=-1)


# ---------------------------------------------------------------------------
# numpy flavour (host mirror — used by oracles, heuristics on <=NMAX subgraphs)
# ---------------------------------------------------------------------------

def np_popcount(x: np.ndarray) -> np.ndarray:
    x = x.astype(np.uint32)
    x = x - ((x >> 1) & 0x55555555)
    x = (x & 0x33333333) + ((x >> 2) & 0x33333333)
    x = (x + (x >> 4)) & 0x0F0F0F0F
    return ((x * 0x01010101) >> 24).astype(np.int32)


def np_neighbors(s: int, adj: np.ndarray) -> int:
    out = 0
    v = 0
    ss = int(s)
    while ss:
        if ss & 1:
            out |= int(adj[v])
        ss >>= 1
        v += 1
    return out


def np_grow(src: int, restrict: int, adj: np.ndarray) -> int:
    cur = int(src) & int(restrict)
    while True:
        nxt = (cur | np_neighbors(cur, adj)) & int(restrict)
        if nxt == cur:
            return cur
        cur = nxt


def np_is_connected(s: int, adj: np.ndarray) -> bool:
    if s == 0:
        return True
    return np_grow(s & (-s), s, adj) == s


def iter_bits(s: int):
    v = 0
    while s:
        if s & 1:
            yield v
        s >>= 1
        v += 1


def np_pdep(rank: int, mask: int) -> int:
    out = 0
    for b in iter_bits(mask):
        if rank & 1:
            out |= 1 << b
        rank >>= 1
    return out
