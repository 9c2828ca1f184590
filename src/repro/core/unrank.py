"""Combinatorial-number-system unranking of k-subsets (paper §2.2.1 / Alg. 5).

rank r in [0, C(n, k)) -> bitmap of the r-th k-subset of {0..n-1} in
colexicographic order.  ``n``/``k`` are *dynamic* (traced) so one compiled
kernel covers every level of every query in an NMAX bucket; the binomial
table is a small int32 input.
"""
from __future__ import annotations

from functools import lru_cache
from math import comb

import numpy as np
import jax
import jax.numpy as jnp


@lru_cache(maxsize=None)
def binom_table(nmax: int) -> np.ndarray:
    """int32[(nmax+1), (nmax+1)] Pascal table, clamped to int32 max."""
    t = np.zeros((nmax + 1, nmax + 1), dtype=np.int64)
    for i in range(nmax + 1):
        for j in range(nmax + 1):
            t[i, j] = min(comb(i, j), np.iinfo(np.int32).max)
    return t.astype(np.int32)


def unrank_ksubset(rank: jnp.ndarray, k: jnp.ndarray, binom: jnp.ndarray,
                   nmax: int) -> jnp.ndarray:
    """Vectorised colex unranking.  rank: i32[...], k: i32 scalar -> i32[...].

    ``C(v, kk)`` is picked from row ``v`` of the table by a chain of
    selects over the ``nmax`` possible ``kk > 0``: a per-lane gather from
    the table costs a TPU about 14 times as much (6.8 ms against 0.49 ms
    for 32,768 lanes at nmax 25 on a v5e)."""

    def body(i, state):
        r, kk, out = state
        v = jnp.int32(nmax - 1 - i)
        row = jax.lax.dynamic_index_in_dim(binom, v, keepdims=False)
        c = jnp.zeros_like(r)
        for j in range(1, nmax + 1):
            c = jnp.where(kk == j, row[j], c)   # C(v, kk)
        take = (kk > 0) & (r >= c)
        out = jnp.where(take, out | (jnp.int32(1) << v), out)
        r = jnp.where(take, r - c, r)
        kk = jnp.where(take, kk - 1, kk)
        return r, kk, out

    r0 = rank.astype(jnp.int32)
    out0 = jnp.zeros_like(r0)
    k0 = jnp.broadcast_to(jnp.int32(k), r0.shape)
    _, _, out = jax.lax.fori_loop(0, nmax, body, (r0, k0, out0))
    return out


def np_unrank_ksubset(rank: int, k: int, n: int) -> int:
    out = 0
    r = rank
    kk = k
    for v in range(n - 1, -1, -1):
        if kk == 0:
            break
        c = comb(v, kk)
        if r >= c:
            out |= 1 << v
            r -= c
            kk -= 1
    return out
