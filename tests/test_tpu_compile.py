"""Compile the main path's kernels for a described (not attached) TPU v5e.

Interpret-mode tests prove the kernels' semantics on the CPU; only Mosaic
and the TPU compiler can refuse a block shape, a scratch-memory overrun or
a program that does not fit the chip's 16 GB.  These tests compile, at the
served path's real widths (32-query flights, nmax 16, ``CHUNK`` lanes), for
a ``v5e:2x2`` topology that the installed TPU compiler can describe without
a chip.  Nothing runs; results and times come from ``chip_smoke.py``.

The topology is built in a module fixture (never at import), so every
test worker collects the same tests and only the one given this file
loads the TPU compiler library.
"""
import functools
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core import batch
from repro.core.config import CHUNK
from repro.core.shard import BATCH_AXIS, _sharded
from repro.distributed import collectives as coll
from repro.kernels import ccp_eval as k

NB, NMAX, LANES = 32, 16, 1 << 15          # one served flight's chunk
HBM_BYTES = 16 * 10 ** 9                   # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    # the TPU compiler library logs under /tmp unless told not to
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip lands in the persistent cache but can
    # never be read back without one: keep the cache off for this module
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _i32(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=sharding)


def _f32(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)


BATCHED = {
    "bconnectivity": (k.bconnectivity, 1),   # lane args before (qid, adj_b)
    "bccp_eval": (k.bccp_eval, 2),
    "btree_eval": (k.btree_eval, 3),
    "bgeneral_eval": (k.bgeneral_eval, 3),
}


@pytest.mark.parametrize("name", sorted(BATCHED))
def test_batched_pallas_kernel_compiles_for_v5e(name, one_chip):
    fn, lane_args = BATCHED[name]
    lane = _i32((LANES,), one_chip)
    args = [lane] * lane_args + [lane, _i32((NB, NMAX), one_chip)]
    compiled = jax.jit(functools.partial(
        fn, nmax=NMAX, nb=NB, interpret=False)).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_solo_ccp_eval_compiles_for_v5e_at_nmax_24(one_chip):
    lane = _i32((LANES,), one_chip)
    compiled = jax.jit(functools.partial(
        k.ccp_eval, nmax=24, interpret=False)).lower(
            lane, lane, _i32((24,), one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _fits(compiled) -> int:
    m = compiled.memory_analysis()
    used = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes)
    assert 0 < used < HBM_BYTES
    return used


@pytest.mark.parametrize("space", ["filter", "btree", "bgeneral"])
def test_vector_flight_chunk_fits_one_v5e(space, one_chip):
    """The vector path's filter and evaluate chunks of a full flight."""
    bcap, flat, nseg, emax = NB, NB << NMAX, CHUNK + 2, 32
    i32 = functools.partial(_i32, sharding=one_chip)
    f32 = functools.partial(_f32, sharding=one_chip)
    adj_b, memo = i32((bcap, NMAX)), f32((flat,))
    if space == "filter":
        fn = functools.partial(batch._bfilter_chunk, nmax=NMAX, chunk=CHUNK,
                               bcap=bcap)
        args = (i32((bcap + 1,)), i32(()), i32((NMAX + 1, NMAX + 1)), adj_b)
    elif space == "btree":
        fn = functools.partial(batch._beval_tree_chunk, nmax=NMAX,
                               chunk=CHUNK, nseg=nseg, bcap=bcap)
        args = (i32((flat,)), i32((bcap + 1,)), i32((bcap,)), i32((bcap,)),
                i32(()), i32((bcap,)), adj_b, i32((bcap, emax)),
                i32((bcap, emax)), memo, memo)
    else:
        pcap = CHUNK                  # pairs in one chunk (one lane each)
        fn = functools.partial(batch._beval_general_chunk, nmax=NMAX,
                               chunk=CHUNK, pcap=pcap, bcap=bcap)
        args = (i32((pcap,)), i32((pcap,)), i32((pcap,)), i32((pcap,)),
                i32(()), i32(()), adj_b, memo, memo)
    _fits(jax.jit(fn).lower(*args).compile())


def test_lattice_level_commit_compiles_for_2x2(topo):
    """The one collective per committed lattice level, on four chips."""
    mesh = Mesh(np.array(topo.devices[:4]), (BATCH_AXIS,))
    sh = NamedSharding(mesh, P(BATCH_AXIS))
    cap, flat = 1 << 14, 1 << 20
    fn = _sharded(coll.min_left_commit, mesh, donate=(0, 1),
                  axis=BATCH_AXIS, cap=cap, flat=flat)
    compiled = fn.lower(_f32((4, flat), sh), _i32((4, flat), sh),
                        _i32((4, cap), sh), _f32((4, cap), sh),
                        _i32((4, cap), sh)).compile()
    assert "all-reduce" in compiled.as_text()
    _fits(compiled)
