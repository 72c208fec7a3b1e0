"""Compile the SpMV main path for a TPU v5e that is described, not attached.

Each test lowers one program of the serving path at the widths of the G7
stand-in (soc_pokec: 1.63M rows, ~30.6M nnz; 45,000 tiles of 8 x 128
slots, above the 32,751 its fp32 plan holds; 8192-wide x segments;
12,736 lane-local rows; the same live slots in row order for the XLA
executor) and compiles it with the chip's own compiler, so a change that
the chip would refuse fails here without a chip.  Nothing runs: these say
nothing about results or times.  Each XLA program is also read for a
scatter over the non-zeros, which the row-ordered executor must not hold.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every xdist worker
imports this file.  The persistent compilation cache is off around the
compiles (an entry written for a described chip cannot be read back).
"""
import math
import os
import re

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro import compat
from repro.kernels import ops, serpens_spmv
from repro.solvers.power_iteration import _pagerank_epilogue

TILES, SUB, LANES, W = 45_000, 8, 128, 8192
NUM_SEGMENTS = 199                      # ceil(1.63M / 8192)
ROWS_PADDED = 12_736 * LANES            # lane-local rows x lanes
NNZ = 30_593_458                        # live slots of the fp32 plan
HBM_BYTES = 16 * 2**30                  # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    old_log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was_on)
        cc.reset_cache()
        if old_log_dir is None:
            os.environ.pop("TPU_LOG_DIR", None)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shard(sharding, tiles=TILES, nnz=NNZ, rows=ROWS_PADDED, lead=()):
    """One shard's device arrays (:class:`ops.ShardArrays`) as shapes."""
    def a(shape, dtype):
        return jax.ShapeDtypeStruct(lead + shape, dtype, sharding=sharding)
    slots = -(-(nnz + 1) // ops.BLOCK) * ops.BLOCK
    tile = (tiles, SUB, LANES)
    return ops.ShardArrays(
        idx=a(tile, jnp.int32), val=a(tile, jnp.float32),
        seg_chunk=a((tiles,), jnp.int32), keys=a((slots,), jnp.int32),
        vals=a((slots,), jnp.float32), ends=a((rows,), jnp.int32))


def _fits_one_chip(compiled):
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < HBM_BYTES, mem


def _no_scatter_over_nonzeros(compiled, nnz=NNZ):
    """No scatter in the program has an update count of the order of the
    non-zeros (a tenth of them or more)."""
    hlo = compiled.as_text()
    shapes = dict(re.findall(r"%([\w.-]+) = \w+\[([\d,]*)\]", hlo))
    for operands in re.findall(r" scatter\(([^)]*)\)", hlo):
        names = [o.strip().lstrip("%") for o in operands.split(",")]
        for name in names[2:]:                   # the updates
            dims = shapes[name].split(",") if shapes[name] else []
            count = math.prod(int(d) for d in dims)
            assert count < nnz // 10, (name, shapes[name])


def test_spmv_stream_xla_compiles(one_chip):
    dev = _shard(one_chip)
    x = jax.ShapeDtypeStruct((NUM_SEGMENTS * W,), jnp.float32,
                             sharding=one_chip)
    compiled = ops.spmv_stream_xla.lower(
        dev.keys, dev.vals, dev.ends, x, num_rows_padded=ROWS_PADDED,
        segment_width=W).compile()
    _fits_one_chip(compiled)
    _no_scatter_over_nonzeros(compiled)


def test_spmm_stream_xla_compiles_at_n16(one_chip):
    dev = _shard(one_chip)
    x = jax.ShapeDtypeStruct((NUM_SEGMENTS * W, 16), jnp.float32,
                             sharding=one_chip)
    compiled = ops.spmm_stream_xla.lower(
        dev.keys, dev.vals, dev.ends, x, num_rows_padded=ROWS_PADDED,
        segment_width=W).compile()
    _fits_one_chip(compiled)
    _no_scatter_over_nonzeros(compiled)


def test_fused_pagerank_step_compiles_on_xla(one_chip):
    dev = _shard(one_chip)
    x = jax.ShapeDtypeStruct((NUM_SEGMENTS * W,), jnp.float32,
                             sharding=one_chip)
    acc2 = jax.ShapeDtypeStruct((ROWS_PADDED // LANES, LANES), jnp.float32,
                                sharding=one_chip)
    consts = jax.ShapeDtypeStruct((1, 2), jnp.float32, sharding=one_chip)

    def step(dev, x, r2, mask2, consts):
        return ops.run_stream_fused(
            dev, x, epilogue=_pagerank_epilogue,
            extras=(r2, mask2, consts), num_rows_padded=ROWS_PADDED,
            segment_width=W, backend="xla")

    compiled = jax.jit(step).lower(dev, x, acc2, acc2, consts).compile()
    _fits_one_chip(compiled)
    _no_scatter_over_nonzeros(compiled)


def test_row_plan_shard_map_compiles_on_four_chips(topo):
    mesh = Mesh(topo.devices, ("chips",))
    sharded = NamedSharding(mesh, P("chips"))
    n = mesh.size
    rows_per_shard = -(-ROWS_PADDED // n // LANES) * LANES
    dev = _shard(sharded, tiles=-(-TILES // n), nnz=-(-NNZ // n),
                 rows=rows_per_shard, lead=(n,))
    x = jax.ShapeDtypeStruct((NUM_SEGMENTS * W,), jnp.float32,
                             sharding=NamedSharding(mesh, P()))

    def body(dev, x):
        return ops.run_stream(jax.tree.map(lambda a: a[0], dev), x,
                              num_rows_padded=rows_per_shard,
                              segment_width=W, backend="xla")[None]

    f = compat.shard_map(body, mesh=mesh, in_specs=(P("chips"), P()),
                         out_specs=P("chips"), check_rep=False)
    compiled = jax.jit(f).lower(dev, x).compile()
    _fits_one_chip(compiled)
    _no_scatter_over_nonzeros(compiled, nnz=-(-NNZ // n))


@pytest.mark.xfail(
    strict=True, raises=ValueError,
    reason="Mosaic refuses the Serpens kernel: the (1, W) x block is not "
           "divisible by 8 in its second-to-last dimension; behind it the "
           "1-D gather xseg[cols] and the scatter-add do not lower")
def test_spmv_pallas_compiles_for_the_chip(one_chip):
    dev = _shard(one_chip, tiles=1024)
    x2d = jax.ShapeDtypeStruct((NUM_SEGMENTS, W), jnp.float32,
                               sharding=one_chip)
    serpens_spmv.spmv_pallas.lower(
        dev.idx, dev.val, dev.seg_chunk, x2d, num_rows_padded=ROWS_PADDED,
        segment_width=W,
        tiles_per_chunk=1, interpret=False).compile()
