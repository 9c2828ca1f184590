"""Biconnected components: vectorized vs Hopcroft-Tarjan oracle (hypothesis
optional — see tests.helpers for the fixed-example fallback)."""
import numpy as np
import jax.numpy as jnp
import pytest

from tests.helpers import given, rand_graph, settings, st
from repro.core import blocks as bl, bitset as bs

NMAX = 16


def _device_edges(g):
    emax = max(8, ((g.m + 7) // 8) * 8)
    eu = np.full(emax, -1, np.int32)
    ev = np.full(emax, -1, np.int32)
    live = np.zeros(emax, bool)
    for i, (u, v) in enumerate(g.edges):
        eu[i], ev[i], live[i] = u, v, True
    adj = np.zeros(NMAX, np.int32)
    for u, v in g.edges:
        adj[u] |= 1 << v
        adj[v] |= 1 << u
    return (jnp.asarray(adj), jnp.asarray(eu), jnp.asarray(ev),
            jnp.asarray(live))


@settings(max_examples=12, deadline=None)
@given(st.integers(4, 11), st.integers(0, 6), st.integers(0, 10_000))
def test_blocks_match_oracle(n, extra, seed):
    g = rand_graph(n, extra, seed)
    adj, eu, ev, live = _device_edges(g)
    adj_np = g.adjacency()
    rng = np.random.default_rng(seed)
    for _ in range(6):
        # random connected subset via random walk
        s = 1 << int(rng.integers(0, n))
        for _ in range(int(rng.integers(1, n))):
            nb = bs.np_neighbors(s, adj_np) & ~s
            if not nb:
                break
            s |= 1 << list(bs.iter_bits(nb))[int(rng.integers(0, bin(nb).count('1')))]
        if bin(s).count("1") < 2:
            continue
        cyc, brg = bl.find_blocks_batch(jnp.array([s], jnp.int32), adj, eu, ev,
                                        live, NMAX)
        got = sorted(int(x) for x in
                     np.concatenate([np.asarray(cyc[0]), np.asarray(brg[0])])
                     if x)
        assert got == sorted(bl.np_find_blocks(s, g.edges, n))


def test_paper_fig5_blocks():
    edges9 = [(0, 1), (1, 2), (2, 3), (0, 3), (0, 2), (3, 4), (4, 8), (5, 6),
              (6, 7), (7, 8), (5, 8)]
    got = sorted(bl.np_find_blocks((1 << 9) - 1, edges9, 9))
    assert got == [0b1111, 0b11000, 0b100010000, 0b111100000]


# ------------------------------------------- phase A launches sized to level --

def _connected_sets(g, count):
    """The first ``count`` connected sets of >= 2 relations, ascending."""
    adj_np = g.adjacency()
    out = [s for s in range(1, 1 << g.n)
           if bin(s).count("1") >= 2 and bs.np_grow(s & -s, s, adj_np) == s]
    assert len(out) >= count
    return np.array(out[:count], np.int32)


LEVEL = rand_graph(16, 6, 3)            # cyclic: mu = 6


@pytest.fixture(scope="module")
def level_sets():
    return _connected_sets(LEVEL, 4097)


def _fixed_launch_pairs(sets_np, adj, eu, ev, live, eff_cap):
    """Phase A as every level launched it before: 4096 set slots a launch,
    two fetches, then the same compaction and stable sort by set."""
    ps, pb = [], []
    for s0 in range(0, len(sets_np), 4096):
        sl = sets_np[s0: s0 + 4096]
        pad = np.zeros(4096, np.int32)
        pad[: len(sl)] = sl
        merged, bridge = bl.blocks_chunk(
            jnp.asarray(pad), jnp.int32(len(sl)), adj, eu, ev, live,
            nmax=NMAX, emax=eu.shape[0], cyc_cap=eff_cap, scap=4096)
        both = np.concatenate([np.asarray(merged)[: len(sl)],
                               np.asarray(bridge)[: len(sl)]], axis=1)
        nz = both != 0
        ps.append(np.repeat(sl[:, None], both.shape[1], axis=1)[nz])
        pb.append(both[nz])
    ps, pb = np.concatenate(ps), np.concatenate(pb)
    order = np.argsort(ps, kind="stable")
    return ps[order], pb[order]


def _blocks_by_set(ps, pb):
    out = {}
    for s, b in zip(ps.tolist(), pb.tolist()):
        out.setdefault(s, []).append(b)
    return out


# level size -> set slots launched: full 4096-set chunks, the tail at the
# next power of two at or above its size, at least 256
SLOTS = {1: 256, 255: 256, 256: 256, 257: 512, 4095: 4096, 4096: 4096,
         4097: 4096 + 256}


@pytest.mark.parametrize("size", sorted(SLOTS))
def test_level_sized_launch_matches_fixed_launch(size, level_sets):
    sets = level_sets[:size]
    adj, eu, ev, live = _device_edges(LEVEL)
    mu = LEVEL.m - LEVEL.n + 1
    ps, pb, slots = bl.np_pairs_for_sets(
        sets, LEVEL, adj, eu, ev, live, nmax=NMAX, emax=eu.shape[0],
        cyc_cap=24)
    assert slots == SLOTS[size]
    ref_ps, ref_pb = _fixed_launch_pairs(sets, adj, eu, ev, live, mu)
    np.testing.assert_array_equal(ps, ref_ps)
    np.testing.assert_array_equal(pb, ref_pb)
    got = _blocks_by_set(ps, pb)
    assert sorted(got) == sorted(sets.tolist())
    for s in sets.tolist():
        assert sorted(got[s]) == sorted(bl.np_find_blocks(s, LEVEL.edges,
                                                          LEVEL.n))


@pytest.mark.parametrize("size", [255, 257, 4097])
def test_dense_path_launches_sized_to_level(size, level_sets):
    """Past ``cyc_cap`` the cut-vertex test launches by the same rule; a set
    without a cut vertex is its own block, the others go to the oracle."""
    sets = level_sets[:size]
    adj, eu, ev, live = _device_edges(LEVEL)
    ps, pb, slots = bl.np_pairs_for_sets(
        sets, LEVEL, adj, eu, ev, live, nmax=NMAX, emax=eu.shape[0],
        cyc_cap=1)
    assert slots == SLOTS[size]
    adj_np = LEVEL.adjacency()
    want = [(s, b) for s in sets.tolist()
            for b in ([s] if bl.np_cut_vertices(s, adj_np) == 0
                      else bl.np_find_blocks(s, LEVEL.edges, LEVEL.n))]
    assert list(zip(ps.tolist(), pb.tolist())) == want


def test_empty_level_launches_nothing():
    adj, eu, ev, live = _device_edges(LEVEL)
    ps, pb, slots = bl.np_pairs_for_sets(
        np.zeros(0, np.int32), LEVEL, adj, eu, ev, live, nmax=NMAX,
        emax=eu.shape[0], cyc_cap=24)
    assert len(ps) == len(pb) == slots == 0
