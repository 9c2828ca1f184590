"""Biconnected components ("blocks", paper §2.4/§3.2) of induced subgraphs.

Two implementations:

* ``np_find_blocks`` — host Hopcroft-Tarjan (DFS lowpoint) oracle, used by
  tests and by the sequential baselines.
* ``find_blocks_batch`` — branch-free, fixed-shape jnp version ``vmap``-able
  over millions of sets (the TPU adaptation of the paper's warp-cooperative
  Slota-Madduri step):
      1. BFS spanning tree (parent/depth) of G[S];
      2. fundamental cycle per non-tree edge (LCA walk, vertex bitmaps);
      3. merge cycles sharing >= 2 vertices (union of two cycles sharing two
         vertices is 2-connected; within a block the fundamental cycles are
         transitively edge-connected and edge-sharing implies >= 2 shared
         vertices, so the closure is exactly the block);
      4. uncovered tree edges are bridges => 2-vertex blocks.
"""
from __future__ import annotations

from functools import partial

import numpy as np
import jax
import jax.numpy as jnp

from . import bitset as bs
from .telemetry import fetch_in_waves


# ------------------------------------------------------------------ oracle --

def np_find_blocks(s: int, edges, n: int) -> list[int]:
    """Blocks of G[s] as vertex bitmaps (Hopcroft-Tarjan, iterative DFS)."""
    verts = [v for v in range(n) if (s >> v) & 1]
    adj = {v: [] for v in verts}
    for (u, v) in edges:
        if ((s >> u) & 1) and ((s >> v) & 1):
            adj[u].append(v)
            adj[v].append(u)
    disc, low = {}, {}
    blocks, stack, time = [], [], [0]

    for root in verts:
        if root in disc:
            continue
        # iterative DFS
        it = {v: 0 for v in verts}
        dfs = [(root, None)]
        disc[root] = low[root] = time[0]
        time[0] += 1
        while dfs:
            v, parent = dfs[-1]
            advanced = False
            while it[v] < len(adj[v]):
                w = adj[v][it[v]]
                it[v] += 1
                if w not in disc:
                    stack.append((v, w))
                    disc[w] = low[w] = time[0]
                    time[0] += 1
                    dfs.append((w, v))
                    advanced = True
                    break
                elif w != parent and disc[w] < disc[v]:
                    stack.append((v, w))
                    low[v] = min(low[v], disc[w])
            if advanced:
                continue
            dfs.pop()
            if dfs:
                p = dfs[-1][0]
                low[p] = min(low[p], low[v])
                if low[v] >= disc[p]:
                    blk = 0
                    while stack:
                        (a, b) = stack.pop()
                        blk |= (1 << a) | (1 << b)
                        if (a, b) == (p, v):
                            break
                    if blk:
                        blocks.append(blk)
    return blocks


def np_cut_vertices(s: int, adj_np: np.ndarray) -> int:
    """Bitmap of cut vertices of G[s] (oracle, via component counting)."""
    out = 0
    for v in bs.iter_bits(s):
        rest = s & ~(1 << v)
        if rest == 0:
            continue
        if bs.np_grow(rest & (-rest), rest, adj_np) != rest:
            out |= 1 << v
    return out


# ------------------------------------------------------------- jnp batched --

def _bfs_tree(S, adj, nmax: int):
    """Batched BFS tree of G[S] from lsb(S): parent idx i32[nmax], depth."""
    root = bs.lsb(S)
    shifts = jnp.arange(nmax, dtype=jnp.int32)

    def lowest_idx(bm):
        # index of lowest set bit (0 if bm == 0) — popcount(lsb-1)
        l = bs.lsb(bm)
        return bs.popcount(l - 1) * (bm != 0)

    def body(d, state):
        visited, frontier, parent, depth = state
        nbr = bs.neighbors(frontier, adj)
        new = nbr & S & ~visited
        # vertex-parallel: each newly visited v picks lowest-index neighbour
        # inside the frontier as its parent
        vbits = jnp.int32(1) << shifts                       # (nmax,)
        isnew = (new[..., None] & vbits) != 0                # (..., nmax)
        pbm = adj & frontier[..., None]                      # (..., nmax)
        pidx = lowest_idx(pbm)
        parent = jnp.where(isnew, pidx, parent)
        depth = jnp.where(isnew, d + 1, depth)
        return visited | new, new, parent, depth

    visited0 = root
    parent0 = jnp.full(S.shape + (nmax,), -1, jnp.int32)
    depth0 = jnp.where(((root[..., None] >> shifts) & 1) == 1, 0, jnp.int32(1 << 20))
    state = (visited0, root, parent0, depth0)
    state = jax.lax.fori_loop(0, nmax, body, state)
    visited, _, parent, depth = state
    return parent, depth


def _pick(vec, idx, nmax: int):
    """``vec[idx]`` for a per-set ``vec`` of ``nmax`` entries, by a chain
    of selects: under the vmap over sets a gather from so small a table
    costs a TPU more than the ``nmax`` selects."""
    out = jnp.zeros(idx.shape, vec.dtype)
    for v in range(nmax):
        out = jnp.where(idx == v, vec[v], out)
    return out


def _fundamental_cycles(S, parent, depth, eu_idx, ev_idx, active, nmax: int):
    """Vertex bitmap of the fundamental cycle of each (non-tree) edge: both
    endpoints climb the BFS tree to their LCA, every edge at once."""

    def body(_, st):
        a, b, cyc = st
        da = _pick(depth, a, nmax)
        db = _pick(depth, b, nmax)
        # move deeper endpoint(s) up; when equal depth and a != b move both
        step_a = (a != b) & (da >= db)
        step_b = (a != b) & (db > da)
        both = (a != b) & (da == db)
        cyc = cyc | (jnp.int32(1) << a) | (jnp.int32(1) << b)
        na = jnp.where(step_a | both, _pick(parent, a, nmax), a)
        nb = jnp.where(step_b | both, _pick(parent, b, nmax), b)
        return jnp.maximum(na, 0), jnp.maximum(nb, 0), cyc

    a, b, cyc = jax.lax.fori_loop(
        0, 2 * nmax, body, (jnp.maximum(eu_idx, 0), jnp.maximum(ev_idx, 0),
                            jnp.zeros(eu_idx.shape, jnp.int32)))
    cyc = cyc | (jnp.int32(1) << a)  # the LCA
    return jnp.where(active, cyc, jnp.int32(0))


def _merge_cycles(cycles, emax: int):
    """Transitive closure of 'share >= 2 vertices' by iterated bitmap OR."""

    def cond(state):
        cur, changed = state
        return changed

    def body(state):
        cur, _ = state
        inter = bs.popcount(cur[:, None] & cur[None, :])      # (emax, emax)
        share = (inter >= 2) & (cur[:, None] != 0) & (cur[None, :] != 0)
        nxt = jnp.where(share, cur[None, :], 0)
        nxt = jnp.bitwise_or.reduce(nxt, axis=1) | cur
        return nxt, jnp.any(nxt != cur)

    out, _ = jax.lax.while_loop(cond, body, (cycles, jnp.bool_(True)))
    # dedupe: zero out any row equal to an earlier row
    idx = jnp.arange(emax)
    dup = (out[:, None] == out[None, :]) & (idx[None, :] < idx[:, None]) & (out[:, None] != 0)
    return jnp.where(jnp.any(dup, axis=1), 0, out)


def find_blocks_one(S, adj, eu_idx, ev_idx, edge_live, nmax: int):
    """Blocks of G[S] for one set.  Returns (cycle_blocks i32[emax],
    bridge_blocks i32[nmax]).  Zero entries are padding.  vmap over S.
    """
    emax = eu_idx.shape[0]
    parent, depth = _bfs_tree(S[None], adj, nmax)
    parent = parent[0]
    depth = depth[0]
    ubit = jnp.where(eu_idx >= 0, jnp.int32(1) << jnp.maximum(eu_idx, 0), 0)
    vbit = jnp.where(ev_idx >= 0, jnp.int32(1) << jnp.maximum(ev_idx, 0), 0)
    in_s = edge_live & ((ubit & S) != 0) & ((vbit & S) != 0)
    pu = _pick(parent, jnp.maximum(eu_idx, 0), nmax)
    pv = _pick(parent, jnp.maximum(ev_idx, 0), nmax)
    is_tree = in_s & ((pu == ev_idx) | (pv == eu_idx))
    non_tree = in_s & ~is_tree
    cycles = _fundamental_cycles(S, parent, depth, eu_idx, ev_idx, non_tree, nmax)
    merged = _merge_cycles(cycles, emax)

    # bridges: per non-root vertex v in S, is tree edge (v, parent[v]) covered
    # by some fundamental cycle?  (cycle bitmaps are tree paths closed by one
    # non-tree edge, so containing both endpoints implies containing the edge)
    shifts = jnp.arange(nmax, dtype=jnp.int32)
    vbits = jnp.int32(1) << shifts
    has_parent = (parent >= 0) & ((S & vbits) != 0)
    pbits = jnp.where(has_parent, jnp.int32(1) << jnp.maximum(parent, 0), 0)
    pair = vbits | pbits                                     # (nmax,)
    cov = (cycles[None, :] & pair[:, None]) == pair[:, None]  # (nmax, emax)
    cov = cov & (cycles[None, :] != 0)
    covered = jnp.any(cov, axis=1)
    bridge_blocks = jnp.where(has_parent & ~covered, pair, 0)
    return merged, bridge_blocks


def find_blocks_batch(S, adj, eu_idx, ev_idx, edge_live, nmax: int):
    f = jax.vmap(lambda s: find_blocks_one(s, adj, eu_idx, ev_idx, edge_live, nmax))
    return f(S)


def has_cut_vertex_batch(S, adj, nmax: int):
    """True per set iff G[S] has a cut vertex (used for the clique early-out)."""
    shifts = jnp.arange(nmax, dtype=jnp.int32)
    vbits = (jnp.int32(1) << shifts)[None, :]               # (1, nmax)
    rest = S[:, None] & ~vbits                               # (B, nmax)
    in_s = (S[:, None] & vbits) != 0
    reach = bs.grow(bs.lsb(rest), rest, adj)
    cut = in_s & (reach != rest) & (rest != 0)
    return jnp.any(cut, axis=1)


# --------------------------------------------- phase A (MPDP-general) host --
# Shared by ExactEngine.run_mpdp_general and BatchEngine's general lane
# space: chunked device block finding + host compaction into sorted
# (set, block) pair arrays.

SCAP = 4096       # sets per full phase-A launch
SCAP_MIN = 256    # fewest set slots of a launch (bounds the compile keys)


def _launches(sets_np, scap_min: int = SCAP_MIN):
    """Phase A's launches over a level: ``(s0, sets, padded)`` per launch.
    Full launches hold ``SCAP`` sets; the last (or only) one is sized to
    the next power of two at or above its set count, at least
    ``scap_min``, so a small level does not pay for 4096 slots."""
    for s0 in range(0, len(sets_np), SCAP):
        sl = sets_np[s0: s0 + SCAP]
        scap = max(scap_min, 1 << (len(sl) - 1).bit_length())
        pad = np.zeros(scap, np.int32)
        pad[: len(sl)] = sl
        yield s0, sl, pad


@partial(jax.jit, static_argnames=("nmax", "emax", "cyc_cap", "scap"))
def blocks_chunk(sets_pad, n_valid, adj, eu_idx, ev_idx, edge_live,
                 *, nmax: int, emax: int, cyc_cap: int, scap: int):
    """Phase A of MPDP-general: blocks of every set in the chunk."""
    S = sets_pad

    def per_set(s):
        parent, depth = _bfs_tree(s[None], adj, nmax)
        parent, depth = parent[0], depth[0]
        ubit = jnp.where(eu_idx >= 0, jnp.int32(1) << jnp.maximum(eu_idx, 0), 0)
        vbit = jnp.where(ev_idx >= 0, jnp.int32(1) << jnp.maximum(ev_idx, 0), 0)
        in_s = edge_live & ((ubit & s) != 0) & ((vbit & s) != 0)
        pu = _pick(parent, jnp.maximum(eu_idx, 0), nmax)
        pv = _pick(parent, jnp.maximum(ev_idx, 0), nmax)
        non_tree = in_s & ~((pu == ev_idx) | (pv == eu_idx))
        # compact non-tree edge endpoints into cyc_cap slots (by selects:
        # slot c takes the one non-tree edge that counts c before it)
        pos = jnp.cumsum(non_tree.astype(jnp.int32)) - 1
        match = non_tree[None, :] & (pos[None, :]
                                     == jnp.arange(cyc_cap)[:, None])
        act = jnp.any(match, axis=1)
        cu = jnp.where(act, jnp.where(match, eu_idx[None, :], 0).sum(1), -1)
        cv = jnp.where(act, jnp.where(match, ev_idx[None, :], 0).sum(1), -1)
        cycles = _fundamental_cycles(s, parent, depth, cu, cv, act, nmax)
        merged = _merge_cycles(cycles, cyc_cap)
        shifts = jnp.arange(nmax, dtype=jnp.int32)
        vbits = jnp.int32(1) << shifts
        has_parent = (parent >= 0) & ((s & vbits) != 0)
        pbits = jnp.where(has_parent, jnp.int32(1) << jnp.maximum(parent, 0), 0)
        pair = vbits | pbits
        cov = ((cycles[None, :] & pair[:, None]) == pair[:, None]) & (cycles[None, :] != 0)
        bridge = jnp.where(has_parent & ~jnp.any(cov, axis=1), pair, 0)
        return merged, bridge

    merged, bridge = jax.vmap(per_set)(S)
    idx = jnp.arange(scap)
    merged = jnp.where((idx < n_valid)[:, None], merged, 0)
    bridge = jnp.where((idx < n_valid)[:, None], bridge, 0)
    return merged, bridge


def np_pairs_for_sets(sets_np, g, adj, eu_idx, ev_idx, edge_live,
                      *, nmax: int, emax: int, cyc_cap: int,
                      scap_min: int = SCAP_MIN):
    """Phase A on the host: compacted (set, block) pair arrays for a level,
    and the set slots its launches held (``_launches``; the engines tally
    them as ``blocks_slots`` beside ``blocks_sets`` += ``len(sets_np)``).
    Launches are fetched a wave at a time (``telemetry.fetch_in_waves``).

    ``adj``/``eu_idx``/``ev_idx``/``edge_live`` are the device-side arrays of
    the query (one query at a time — BatchEngine loops its sub-batch here,
    the lane fusion happens in phase B).  Pairs come back sorted by set so
    downstream lane segments stay contiguous.
    """
    mu = g.m - g.n + 1
    pair_set, pair_block = [], []
    slots = 0
    if mu <= cyc_cap:
        # cyclomatic number of any induced subgraph <= mu(G): size the
        # static fundamental-cycle slots to the query, not the ceiling
        # (perf log: 24 -> mu slots cut phase A ~4x on near-tree graphs)
        eff_cap = max(1, min(cyc_cap, mu))
        for sl, (mg, br) in fetch_in_waves(
                lambda launch: (launch[1], blocks_chunk(
                    launch[2], np.int32(len(launch[1])), adj,
                    eu_idx, ev_idx, edge_live, nmax=nmax, emax=emax,
                    cyc_cap=eff_cap, scap=len(launch[2]))),
                _launches(sets_np, scap_min)):
            slots += len(mg)
            both = np.concatenate([mg[: len(sl)], br[: len(sl)]], axis=1)
            snp = np.repeat(sl[:, None], both.shape[1], axis=1)
            nz = both != 0
            pair_set.append(snp[nz])
            pair_block.append(both[nz])
    else:
        # dense path: no-cut-vertex sets are single blocks (cliques);
        # rare cut-vertex sets fall back to the host oracle
        flags = np.zeros(len(sets_np), bool)
        for (s0, n), hc in fetch_in_waves(
                lambda launch: ((launch[0], len(launch[1])),
                                has_cut_vertex_batch(launch[2], adj, nmax)),
                _launches(sets_np, scap_min)):
            slots += len(hc)
            flags[s0: s0 + n] = hc[:n]
        easy = sets_np[~flags]
        pair_set.append(easy)
        pair_block.append(easy)
        for s in sets_np[flags]:
            for b in np_find_blocks(int(s), g.edges, g.n):
                pair_set.append(np.array([s], np.int32))
                pair_block.append(np.array([b], np.int32))
    ps = np.concatenate(pair_set) if pair_set else np.zeros(0, np.int32)
    pb = np.concatenate(pair_block) if pair_block else np.zeros(0, np.int32)
    # order pairs by set (stable) so lane segments stay contiguous
    order = np.argsort(ps, kind="stable")
    return ps[order], pb[order], slots
