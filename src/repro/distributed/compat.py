"""The one shard_map call site.

Every shard_map use in the repo — the batch-axis wrappers in
``core.shard``, the lattice level-commit exchange in
``distributed.collectives``, the compressed gradient reductions — imports
``shard_map_compat`` from here.  ``tests/test_lattice_shard.py`` pins that
with a regression test asserting all import sites resolve to this single
function object, so the replication-check flag cannot drift between
copies.
"""
from __future__ import annotations

import jax


def shard_map_compat(f, mesh, in_specs, out_specs, check=False):
    """``jax.shard_map`` with its ``check_vma`` replication check set to
    ``check`` (off by default: the lattice commit returns values that are
    replicated by construction)."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check)
