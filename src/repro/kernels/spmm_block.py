"""Block-sparse SpMM Pallas kernels: C = A^T B with A in block-ELL (TPU target).

TPU adaptation of the paper's sparse local products (DESIGN.md section 3;
the coded-matmul "block_sparse" backend in repro.core.coded_matmul is the
SPMD consumer of these kernels):
unstructured CSR gathers do not map to the MXU, so A is stored as packed
bs x bs tiles (repro.sparse.BlockELL).  Each output row-block rb consumes its
stripe vals[rb, :] of packed tiles; the tile's *source row-block in B* is
scalar-prefetched from idx[rb, l], so the B tile DMA is issued ahead of the
matmul.  Compute and HBM traffic scale with the number of LIVE tiles
(nnz-proportional -- the paper's whole point), not with the dense dimensions.

Two entry points:

* ``spmm_block``   -- the plain kernel: idx addresses row-blocks of the B
  operand as given.  The coded-matmul consumer formerly pre-stacked
  B_k = vstack_l(w_kl B_{j_l}) on device to use it, which materialized an
  O(max_degree * s) dense intermediate per worker.
* ``spmm_block_fused`` -- the fused-gather kernel: the scalar prefetch
  carries, per (cb, l) slot, the source *row-block* AND source *column
  group* of the original (s, t) B plus a per-slot f32 weight; the BlockSpec
  index_map DMAs tiles straight out of B and the kernel scales by the
  prefetched weight.  No stacked copy of B ever exists -- HBM traffic is
  live tiles only.  ``_spmm_block_fused_jnp`` is the XLA gather/einsum
  path with identical semantics (the CPU lane).
* ``spmm_block_fused_decode`` -- the ONE-LAUNCH variant: the survivor
  decode column d = D[:, k] * alive_k enters as a third scalar-prefetched
  operand and the decode combine ``contrib[c] = d[c] * C~_k`` happens in
  the kernel's epilogue -- the local product accumulates into a VMEM
  scratch tile (double-buffered tile DMA exactly as in the fused kernel)
  and on the last slot each of the mn decode-weighted copies is written
  straight to the output block.  The separate ``D @ C~`` contraction (a
  second launch plus an HBM round-trip of C~) disappears from the staged
  program; ``repro.analysis.jaxpr_check.decode_contraction_offenders``
  enforces its absence on the trace.

Grid: (CB, t_tiles, L) -- L innermost so each (rb, tt) output tile stays
VMEM-resident across its accumulation; zero-padded slots multiply zero tiles
(fused: weight 0.0) and add nothing.  For the fused kernels t_tiles is
bt / t_tile, and the staging code picks the widest t_tile whose VMEM
footprint (``fused_vmem_bytes``) fits ``VMEM_TILE_BYTES``: each grid step
then contracts one A tile with a (bs, t_tile) slab of B, and A's tiles are
fetched once per column tile rather than once per 128 columns.

Scalar prefetch lives in SMEM, where a multi-dimensional operand is padded
to 128 words on its last axis.  The slot tables therefore go in flat (1-D)
and are indexed arithmetically; ``check_slot_table_fits`` refuses, before
anything compiles, a pack whose table would still overflow SMEM.

Platform lanes: TPU runs this module's compiled Pallas kernels; CPU runs the
XLA gather path (or the Pallas kernels under the interpreter for parity
tests).  ``resolve_lane`` is the single policy: REPRO_KERNEL_LANE=tpu|xla
overrides, then the default backend picks.  Any other backend is an error,
never a silent fallback.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(idx_ref, vals_ref, b_ref, o_ref):
    l = pl.program_id(2)

    @pl.when(l == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    tile = vals_ref[0, 0].astype(jnp.float32)   # (bs, bs) tile of A
    b = b_ref[0].astype(jnp.float32)            # (bs, t_tile) rows of B
    # C[rb] += tile^T @ B[idx]
    o_ref[...] += jax.lax.dot_general(
        tile, b, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )


def _backend_default(on_tpu, on_cpu, what: str):
    backend = jax.default_backend()
    if backend == "tpu":
        return on_tpu
    if backend == "cpu":
        return on_cpu
    raise RuntimeError(
        f"no {what} for JAX backend {backend!r}: the kernels run compiled on "
        "TPU and interpreted (or on the XLA lane) on CPU only")


def resolve_interpret(interpret: bool | None = None) -> bool:
    """The single interpret-mode policy for every Pallas kernel here.

    Explicit argument wins, then the REPRO_PALLAS_INTERPRET env override,
    then the backend: compiled on TPU, the Pallas interpreter on CPU (it
    runs the same body faithfully, BlockSpec tiling included).  Any other
    backend raises.
    """
    if interpret is not None:
        return interpret
    env = os.environ.get("REPRO_PALLAS_INTERPRET")
    if env is not None:
        return env != "0"
    return _backend_default(False, True, "Pallas interpret policy")


@functools.partial(jax.jit, static_argnames=("t_tile", "interpret"))
def spmm_block(vals, idx, B, *, t_tile: int = 128,
               interpret: bool | None = None):
    """C = A^T B, A in block-ELL.

    vals: (CB, L, bs, bs), idx: (CB, L) int32, B: (s, t).
    Returns (CB * bs, t) f32.  t must divide by t_tile, s by bs.
    interpret=None defers to ``resolve_interpret`` (env, then backend).
    """
    if interpret is None:
        interpret = resolve_interpret()
    CB, L, bs, _ = vals.shape
    s, t = B.shape
    if t % t_tile:
        raise ValueError(f"t={t} not divisible by t_tile={t_tile}")
    if s % bs:
        raise ValueError(f"s={s} not divisible by block size {bs}")

    grid = (CB, t // t_tile, L)

    vals_spec = pl.BlockSpec(
        (1, 1, bs, bs), lambda cb, tt, l, idx_ref: (cb, l, 0, 0)
    )
    # B viewed as (s/bs, bs, t): pick row-block idx[cb, l], column tile tt.
    b_spec = pl.BlockSpec(
        (1, bs, t_tile), lambda cb, tt, l, idx_ref: (idx_ref[cb, l], 0, tt)
    )
    o_spec = pl.BlockSpec((bs, t_tile), lambda cb, tt, l, idx_ref: (cb, tt))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[vals_spec, b_spec],
        out_specs=o_spec,
    )
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((CB * bs, t), jnp.float32),
        interpret=interpret,
    )(idx.astype(jnp.int32), vals, B.reshape(s // bs, bs, t))


# ------------------------------ fused gather --------------------------------

#: SMEM bytes the scalar-prefetched slot tables of one launch may take.  A
#: v5e TensorCore has 1 MiB of SMEM; a compile-only probe for a described
#: v5e refused flat tables of 1057280 bytes as "Used 1.01M of 1.00M smem"
#: (about 600 bytes besides the tables) and took 1032704, so 4 KiB is left
#: for the kernel's other scalars.
SMEM_PREFETCH_BYTES = (1 << 20) - 4096


#: VMEM bytes the blocks of one fused-kernel launch may take, as
#: ``fused_vmem_bytes`` counts them.  It stays under the 16 MiB default
#: scoped-VMEM limit of a v5e TensorCore, so no ``vmem_limit_bytes`` is
#: needed.  On one v5e, at CB = 128, L = 45, bs = 128 and a 16384-wide f32
#: column group, the kernel took 392.0, 161.3, 125.4, 110.5 and 103.6 ms at
#: t_tile 128, 512, 1024, 2048 and 4096, with bit-identical results; 8192
#: needs more than the default limit.  This budget admits 4096 for one
#: decode row of an f32 or bf16 pack (14.1 MiB counted), and 2048 for two.
VMEM_TILE_BYTES = 15 << 20


def fused_vmem_bytes(bs: int, mn: int, t_tile: int, itemsize: int) -> int:
    """VMEM of a fused-decode launch at column tile ``t_tile``: the
    double-buffered A tile (``bs * bs * itemsize`` each), B tile and
    (mn, bs, t_tile) output block, the f32 accumulator, and about two
    result-sized temporaries (the dot's result and its weighted copy).
    Mosaic's own scoped allocation for the kernel counts the blocks and the
    accumulator alone, so the temporaries are a margin."""
    result = bs * t_tile * 4
    return 2 * bs * bs * itemsize + 2 * result + 2 * mn * result + 3 * result


def _smem_words(n: int) -> int:
    # counted in whole 128-word rows: an upper bound for a flat table
    return -(-n // 128) * 128


def check_slot_table_fits(CB: int, L: int, bs: int, mn: int = 0) -> None:
    """Raise ValueError if a (CB, L)-slot pack cannot be scalar-prefetched.

    The flat tables are src (2 int32 per slot), wslot (1 f32 per slot) and,
    for the decode-fused kernel, the (mn,) decode column.  Fewer, larger
    tiles (a larger ``block_size``) shrink CB * L.
    """
    need = 4 * (_smem_words(2 * CB * L) + _smem_words(CB * L)
                + (_smem_words(mn) if mn else 0))
    if need > SMEM_PREFETCH_BYTES:
        raise ValueError(
            f"block_size={bs} packs {CB} x {L} tile slots per worker, whose "
            f"scalar-prefetched slot tables need {need} bytes of SMEM > the "
            f"{SMEM_PREFETCH_BYTES}-byte limit; use a larger block_size")


def _fused_kernel(src_ref, w_ref, vals_ref, b_ref, o_ref):
    cb = pl.program_id(0)
    l = pl.program_id(2)

    @pl.when(l == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    w = w_ref[cb * pl.num_programs(2) + l]      # per-slot code weight
    tile = vals_ref[0, 0].astype(jnp.float32)   # (bs, bs) tile of A
    b = b_ref[0].astype(jnp.float32)            # (bs, t_tile) rows of B
    # C[cb] += w * tile^T @ B[src_rb, :, src_jb-th column group]
    o_ref[...] += w * jax.lax.dot_general(
        tile, b, (((0,), (0,)), ((), ())), precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("bt", "t_tile"))
def _spmm_block_fused_jnp(vals, src, wslot, B, *, bt: int, t_tile: int = 0):
    """XLA gather/einsum path with the fused kernel's exact semantics.

    The only intermediates are (CB, L, bs, bt) -- proportional to packed
    tile slots, never to max_degree * s.  The CPU lane, where compiled
    Pallas is unavailable and the interpreter is too slow to be a backend.
    """
    del t_tile  # tiling is the compiler's business here
    CB, L, bs, _ = vals.shape
    s, t = B.shape
    B4 = B.reshape(s // bs, bs, t // bt, bt)
    bsel = B4[src[..., 0], :, src[..., 1], :]                # (CB, L, bs, bt)
    scaled = vals.astype(jnp.float32) * wslot[..., None, None].astype(jnp.float32)
    out = jnp.einsum("clio,clit->cot", scaled, bsel.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST,
                     preferred_element_type=jnp.float32)
    return out.reshape(CB * bs, bt)


def _fused_specs(L, bs, bt, t_tile):
    """The tile BlockSpecs both fused kernels share.  The B tile of slot
    (cb, l) is row-block src[2*(cb*L+l)] and column tile tt of column group
    src[2*(cb*L+l)+1] of B viewed as (s/bs, bs, t): the gather happens in
    the DMA, no stacked B copy is ever built."""
    tpg = bt // t_tile  # t_tiles per column group

    def b_index(cb, tt, l, src_ref, *_):
        slot = 2 * (cb * L + l)
        return (src_ref[slot], 0, src_ref[slot + 1] * tpg + tt)

    vals_spec = pl.BlockSpec((1, 1, bs, bs), lambda cb, tt, l, *_: (cb, l, 0, 0))
    return vals_spec, pl.BlockSpec((1, bs, t_tile), b_index)


def _check_fused_shapes(CB, L, bs, s, t, bt, t_tile, mn=0):
    if bt % t_tile:
        raise ValueError(f"bt={bt} not divisible by t_tile={t_tile}")
    if t % bt:
        raise ValueError(f"t={t} not divisible by column-group width bt={bt}")
    if s % bs:
        raise ValueError(f"s={s} not divisible by block size {bs}")
    check_slot_table_fits(CB, L, bs, mn)


@functools.partial(jax.jit, static_argnames=("bt", "t_tile", "interpret"))
def _spmm_block_fused_pallas(vals, src, wslot, B, *, bt: int,
                             t_tile: int = 128, interpret: bool = False):
    CB, L, bs, _ = vals.shape
    s, t = B.shape
    _check_fused_shapes(CB, L, bs, s, t, bt, t_tile)
    vals_spec, b_spec = _fused_specs(L, bs, bt, t_tile)
    o_spec = pl.BlockSpec((bs, t_tile), lambda cb, tt, l, *_: (cb, tt))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(CB, bt // t_tile, L),
        in_specs=[vals_spec, b_spec],
        out_specs=o_spec,
    )
    return pl.pallas_call(
        _fused_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((CB * bs, bt), jnp.float32),
        interpret=interpret,
    )(src.astype(jnp.int32).reshape(-1), wslot.astype(jnp.float32).reshape(-1),
      vals, B.reshape(s // bs, bs, t))


# ------------------------- fused gather + decode ----------------------------

#: the implementations of the decode-fused local product, keyed by the name
#: ``resolve_lane`` returns
KERNEL_LANES = ("tpu", "xla")


def resolve_lane(lane: str | None = None) -> str:
    """The single platform-dispatch policy for the fused kernels.

    Explicit argument wins, then the REPRO_KERNEL_LANE env override, then
    the REPRO_PALLAS_INTERPRET escape hatch (which forces the TPU-kernel
    lane, run under the interpreter off-TPU), then the default backend:
    compiled Pallas-TPU on TPU, the XLA gather path on CPU.  Any other
    backend raises.
    """
    if lane is not None:
        if lane not in KERNEL_LANES:
            raise ValueError(f"kernel lane {lane!r} not in {KERNEL_LANES}")
        return lane
    env = os.environ.get("REPRO_KERNEL_LANE")
    if env:
        if env not in KERNEL_LANES:
            raise ValueError(
                f"REPRO_KERNEL_LANE={env!r} not in {KERNEL_LANES}")
        return env
    pallas_env = os.environ.get("REPRO_PALLAS_INTERPRET")
    if pallas_env is not None and pallas_env != "0":
        return "tpu"
    return _backend_default("tpu", "xla", "kernel lane")


def _fused_decode_kernel(src_ref, w_ref, d_ref, vals_ref, b_ref, o_ref,
                         acc_ref):
    cb = pl.program_id(0)
    l = pl.program_id(2)
    nl = pl.num_programs(2)

    @pl.when(l == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w = w_ref[cb * nl + l]                      # per-slot code weight
    tile = vals_ref[0, 0].astype(jnp.float32)   # (bs, bs) tile of A
    b = b_ref[0].astype(jnp.float32)            # (bs, t_tile) rows of B
    # C~[cb] += w * tile^T @ B[src_rb, :, src_jb-th column group] -- the
    # SAME accumulation (order and all) as the two-step kernel, into VMEM
    # scratch instead of the output ref
    acc_ref[...] += w * jax.lax.dot_general(
        tile, b, (((0,), (0,)), ((), ())), precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)

    @pl.when(l == nl - 1)
    def _epilogue():
        # decode combine, fused: contrib[c] = d[c] * C~[cb] written per
        # output block -- no separate D @ C~ launch, no HBM round-trip of
        # C~.  mn is static (the output block's leading dim), so this is a
        # compile-time loop of scalar-from-SMEM broadcasts.
        acc = acc_ref[...]
        for c in range(o_ref.shape[0]):
            o_ref[c] = d_ref[c] * acc


@functools.partial(jax.jit, static_argnames=("bt", "t_tile", "interpret"))
def _spmm_block_fused_decode_pallas(vals, src, wslot, dvec, B, *, bt: int,
                                    t_tile: int = 128,
                                    interpret: bool = False):
    CB, L, bs, _ = vals.shape
    s, t = B.shape
    (mn,) = dvec.shape
    _check_fused_shapes(CB, L, bs, s, t, bt, t_tile, mn)
    vals_spec, b_spec = _fused_specs(L, bs, bt, t_tile)
    o_spec = pl.BlockSpec((mn, bs, t_tile), lambda cb, tt, l, *_: (0, cb, tt))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(CB, bt // t_tile, L),
        in_specs=[vals_spec, b_spec],
        out_specs=o_spec,
        scratch_shapes=[pltpu.VMEM((bs, t_tile), jnp.float32)],
    )
    return pl.pallas_call(
        _fused_decode_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((mn, CB * bs, bt), jnp.float32),
        interpret=interpret,
        name="spmm_block_fused_decode",
    )(src.astype(jnp.int32).reshape(-1), wslot.astype(jnp.float32).reshape(-1),
      dvec.astype(jnp.float32), vals, B.reshape(s // bs, bs, t))


@functools.partial(jax.jit, static_argnames=("bt",))
def _spmm_block_fused_decode_jnp(vals, src, wslot, dvec, B, *, bt: int):
    """XLA lane of the decode-fused local product.

    The local product is the fused-gather einsum, the decode combine the
    broadcast multiply XLA fuses into it -- bit-identical to staging the
    two steps separately (same ops in the same order), kept as the CPU
    lane where compiled Pallas is unavailable.
    """
    out = _spmm_block_fused_jnp(vals, src, wslot, B, bt=bt)   # (CB*bs, bt)
    return dvec.astype(jnp.float32)[:, None, None] * out[None]
