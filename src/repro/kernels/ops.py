"""jit'd public wrappers around the Pallas kernels.

Interpret mode follows the backend: on a TPU the kernels compile through
Mosaic; on the CPU backend (the test suites) Pallas interprets the kernel
body (semantics identical, speed not representative).  The engines call
these wrappers only under REPRO_PALLAS=1 (``core.engine._use_pallas``); the
XLA lane path stays the default.
"""
from __future__ import annotations

import jax

from . import ccp_eval as _k


def interpret_mode() -> bool:
    """Interpret the kernels only where Mosaic cannot compile them: the CPU
    backend.  Never true on a TPU."""
    return jax.default_backend() == "cpu"


def ccp_eval(S, sub, adj, nmax: int):
    return _k.ccp_eval(S, sub, adj, nmax=nmax, interpret=interpret_mode())


def connectivity(S, adj, nmax: int):
    return _k.connectivity(S, adj, nmax=nmax, interpret=interpret_mode())


def grow_pair(S, lb, rb, adj, nmax: int):
    return _k.grow_pair(S, lb, rb, adj, nmax=nmax, interpret=interpret_mode())


# -- batched-query variants (BatchEngine: per-lane adjacency rows) ------------

def bconnectivity(S, qid, adj_b, nmax: int, nb: int):
    return _k.bconnectivity(S, qid, adj_b, nmax=nmax, nb=nb,
                            interpret=interpret_mode())


def bccp_eval(S, sub, qid, adj_b, nmax: int, nb: int):
    return _k.bccp_eval(S, sub, qid, adj_b, nmax=nmax, nb=nb,
                        interpret=interpret_mode())


def btree_eval(S, ub, vb, qid, adj_b, nmax: int, nb: int):
    return _k.btree_eval(S, ub, vb, qid, adj_b, nmax=nmax, nb=nb,
                         interpret=interpret_mode())


def bgeneral_eval(S, block, r, qid, adj_b, nmax: int, nb: int):
    return _k.bgeneral_eval(S, block, r, qid, adj_b, nmax=nmax, nb=nb,
                            interpret=interpret_mode())
