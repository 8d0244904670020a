"""Compile-only checks of the main path for a described TPU v5e.

Nothing runs: each case lowers and compiles for a v5e 2x2 topology that is
described, not attached, and asserts that the compiled program holds the
Pallas kernel (``tpu_custom_call``).  The compiler refuses here what
interpret mode accepts -- tiles not aligned to Mosaic's (8, 128) tiling,
scalar-prefetch operands that overflow SMEM -- so these guard the chip path
without a chip.  The topology is described inside a fixture, so collecting
this file loads nothing; keep every such case in this one file.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.coded import CodedMatmulConfig, plan
from repro.core.coded_matmul import (
    _make_block_sparse_fused_decode, _plan_t_tiling, pack_worker_tiles)
from repro.kernels.spmm_block import _spmm_block_fused_decode_pallas
from repro.sparse import dense_to_block_ell


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 -- any failure means "cannot describe"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a described chip's compile can be written to the persistent cache
        # but not read back without one: keep the cache out of it
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        yield desc
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def tpu_lane(monkeypatch):
    # the lane policy reads the CPU backend here; steer it to the compiled
    # TPU kernel for these compiles
    monkeypatch.setenv("REPRO_KERNEL_LANE", "tpu")
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")


def _has_kernel(lowered) -> bool:
    return "tpu_custom_call" in lowered.compile().as_text()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int8])
def test_fused_decode_kernel_smoke_width(one_chip, dtype):
    """The one-chip smoke's kernel: s = t = 16384, bs = 128, 128 column
    blocks of 26 slots, one decode row."""
    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    CB, L, bs, s, bt = 128, 26, 128, 16384, 16384
    args = (sds((CB, L, bs, bs), dtype), sds((CB, L, 2), jnp.int32),
            sds((CB, L), jnp.float32), sds((1,), jnp.float32),
            sds((s, bt), jnp.float32))
    fn = jax.jit(lambda *a: _spmm_block_fused_decode_pallas(*a, bt=bt))
    assert _has_kernel(fn.lower(*args))


@pytest.mark.parametrize("mn,dtype", [(1, jnp.float32), (2, jnp.float32),
                                      (1, jnp.bfloat16), (1, jnp.int8)])
def test_fused_decode_kernel_planned_tile_at_the_cells_shape(one_chip, mn,
                                                             dtype):
    """The benchmark cell's kernel -- 128 column blocks of 45 slots, bs =
    128, a 16384-wide column group -- at the column tile the staging code
    plans for it: wider than 128, and within the chip's default VMEM."""
    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    CB, L, bs, s, bt = 128, 45, 128, 16384, 16384
    t_tile, bt_pad = _plan_t_tiling(bt, "tpu", bs=bs, mn=mn,
                                    itemsize=jnp.dtype(dtype).itemsize)
    assert bt_pad == bt and t_tile > 128
    args = (sds((CB, L, bs, bs), dtype), sds((CB, L, 2), jnp.int32),
            sds((CB, L), jnp.float32), sds((mn,), jnp.float32),
            sds((s, bt), jnp.float32))
    fn = jax.jit(lambda *a: _spmm_block_fused_decode_pallas(
        *a, bt=bt, t_tile=t_tile))
    assert _has_kernel(fn.lower(*args))


def test_slot_table_that_overflowed_smem_compiles(one_chip):
    """(CB, L) = (64, 64) at bs = 128: as (CB, L, 2) the src table padded to
    2 MiB of the 1 MiB SMEM; flat, it fits."""
    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    CB, L, bs, s, bt = 64, 64, 128, 8192, 4096
    args = (sds((CB, L, bs, bs), jnp.float32), sds((CB, L, 2), jnp.int32),
            sds((CB, L), jnp.float32), sds((4,), jnp.float32),
            sds((s, bt), jnp.float32))
    fn = jax.jit(lambda *a: _spmm_block_fused_decode_pallas(*a, bt=bt))
    assert _has_kernel(fn.lower(*args))


def _op_and_pack(cfg, m, n, N, s, r):
    """An unbound block_sparse op and the pack of a half-live random A."""
    rng = np.random.default_rng(0)
    bs = cfg.block_size
    mask = rng.random((s // bs, r // bs)) < 0.5
    A = (rng.standard_normal((s, r)) * np.kron(mask, np.ones((bs, bs)))
         ).astype(np.float32)
    op = plan(cfg, m=m, n=n, num_workers=N, seed=0)
    return op, pack_worker_tiles(dense_to_block_ell(A, block_size=bs),
                                 op.base_plan)


def test_column_group_without_128_divisor_compiles(one_chip, tpu_lane):
    """bt = 40 (n = 2 groups, t = 80): no multiple of 128 divides it, so
    the staged TPU lane pads each group to 128 columns and slices back."""
    s, r, bt = 256, 256, 40
    cfg = CodedMatmulConfig(backend="block_sparse", block_size=128)
    op, pack = _op_and_pack(cfg, m=1, n=2, N=4, s=s, r=r)
    arrays, fn = _make_block_sparse_fused_decode(op.base_plan, pack, bt)

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    args = (sds((s, r), jnp.float32), sds((s, 2 * bt), jnp.float32),
            sds((2,), jnp.float32),
            *(sds(a.shape[1:], a.dtype) for a in arrays))
    assert _has_kernel(jax.jit(fn).lower(*args))


@pytest.mark.parametrize("out_sharded", [False, True],
                         ids=["psum", "reduce_scatter"])
def test_staged_coded_op_four_devices(topo, tpu_lane, out_sharded):
    """The 4-worker CodedOp program on a mesh of the described chips, from
    shapes: the kernel, the decode collective, and each worker's pack rows
    on its own device."""
    s, r, t = 1024, 512, 512
    m, n, N = 2, 1, 4
    cfg = CodedMatmulConfig(backend="block_sparse", block_size=128,
                            out_sharded=out_sharded)
    op, pack = _op_and_pack(cfg, m=m, n=n, N=N, s=s, r=r)
    mesh = Mesh(np.array(topo.devices[:N]), ("model",))
    bound = op.bind(mesh)
    rep = NamedSharding(mesh, P())
    A = jax.ShapeDtypeStruct((s, r), jnp.float32, sharding=rep)
    B = jax.ShapeDtypeStruct((s, t), jnp.float32, sharding=rep)
    compiled = bound.lower(A, B, pack=pack).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert ("reduce-scatter" if out_sharded else "all-reduce") in text
    for sh in compiled.input_shardings[0][2:]:   # the per-worker operands
        rows = {d.id: idx[0] for d, idx in sh.devices_indices_map((N,)).items()}
        assert sorted((sl.start, sl.stop) for sl in rows.values()) == [
            (k, k + 1) for k in range(N)]
