"""Pallas kernels (interpret mode) vs pure-jnp oracle: shape/graph sweeps."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core.joingraph import DeviceGraph
from repro.kernels import ops, ref
from repro.workloads import generators as gen

GRAPHS = [gen.musicbrainz_query(12, 7), gen.star(9, 1), gen.clique(7, 2),
          gen.chain(14, 3)]
SIZES = [1, 127, 128, 129, 1000, 4096]


@pytest.mark.parametrize("g", GRAPHS, ids=["mb12", "star9", "clique7", "chain14"])
@pytest.mark.parametrize("L", SIZES)
def test_ccp_eval_matches_ref(g, L):
    dg = DeviceGraph.from_graph(g)
    rng = np.random.default_rng(L)
    S = jnp.asarray(rng.integers(1, 1 << g.n, L).astype(np.int32))
    sub = jnp.asarray(rng.integers(0, 1 << 10, L).astype(np.int32))
    got = ops.ccp_eval(S, sub, dg.adj, dg.nmax)
    exp = ref.ccp_eval_ref(S, sub, dg.adj, dg.nmax)
    for a, b in zip(got, exp):
        assert (np.asarray(a) == np.asarray(b)).all()


@pytest.mark.parametrize("g", GRAPHS[:2], ids=["mb12", "star9"])
@pytest.mark.parametrize("L", [64, 1000])
def test_connectivity_and_grow_pair_match_ref(g, L):
    dg = DeviceGraph.from_graph(g)
    rng = np.random.default_rng(L + 1)
    S = rng.integers(1, 1 << g.n, L).astype(np.int32)
    Sd = jnp.asarray(S)
    assert (np.asarray(ops.connectivity(Sd, dg.adj, dg.nmax))
            == np.asarray(ref.connectivity_ref(Sd, dg.adj, dg.nmax))).all()
    lb = jnp.asarray(S & (-S))
    rb = jnp.asarray(S & ~(S & -S))
    g1 = ops.grow_pair(Sd, lb, rb, dg.adj, dg.nmax)
    g2 = ref.grow_pair_ref(Sd, lb, rb, dg.adj, dg.nmax)
    for a, b in zip(g1, g2):
        assert (np.asarray(a) == np.asarray(b)).all()


@pytest.mark.parametrize("backend,interpret", [("cpu", True), ("tpu", False),
                                               ("gpu", False)])
def test_interpret_mode_follows_backend(backend, interpret, monkeypatch):
    """Pallas interprets only on the CPU backend; a TPU always compiles."""
    monkeypatch.setattr(ops.jax, "default_backend", lambda: backend)
    assert ops.interpret_mode() is interpret
