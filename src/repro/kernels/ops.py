"""Public jit'd entry points for the Pallas kernels.

Interpret mode is auto-selected from the backend: compiled kernels on TPU,
the Pallas interpreter on CPU (it runs the kernel body faithfully,
including BlockSpec tiling).  Override per-call with ``interpret=`` or
globally with REPRO_PALLAS_INTERPRET=0/1 (one shared policy:
``repro.kernels.spmm_block.resolve_interpret``).

The fused kernels additionally dispatch across PLATFORM LANES (one policy:
``repro.kernels.spmm_block.resolve_lane``, REPRO_KERNEL_LANE=tpu|xla to
override): compiled Pallas-TPU on TPU and the XLA gather path on CPU,
where the interpreter would bury the nnz-proportional win.
"""

from __future__ import annotations

from repro.kernels.coded_accum import coded_accum as _coded_accum
from repro.kernels.spmm_block import (
    resolve_interpret,
    resolve_lane,
    spmm_block as _spmm_block,
    _spmm_block_fused_decode_jnp,
    _spmm_block_fused_decode_pallas,
    _spmm_block_fused_jnp,
    _spmm_block_fused_pallas,
)
from repro.kernels import ref as ref  # re-export oracle for callers/tests


def coded_accum(A, B, cols, weights, *, m: int, n: int, s_chunk: int = 128,
                interpret: bool | None = None):
    return _coded_accum(A, B, cols, weights, m=m, n=n, s_chunk=s_chunk,
                        interpret=resolve_interpret(interpret))


def spmm_block(vals, idx, B, *, t_tile: int = 128, interpret: bool | None = None):
    return _spmm_block(vals, idx, B, t_tile=t_tile,
                       interpret=resolve_interpret(interpret))


def spmm_block_fused(vals, src, wslot, B, *, bt: int, t_tile: int = 128,
                     interpret: bool | None = None, lane: str | None = None):
    """C_k = sum of w * tile^T @ B[row-block, column-group] over packed slots.

    The fused-gather local product: A's packed tiles address the ORIGINAL
    (s, t) operand B directly, so no (max_degree * s, bt) stacked copy is
    materialized.

    vals : (CB, L, bs, bs)  this worker's packed tiles of sparse A
    src  : (CB, L, 2) int32 [source row-block of B (in s/bs), source column
           group (in t/bt)]
    wslot: (CB, L) f32      per-slot code weight (0.0 on padded slots)
    B    : (s, t) with t divisible by bt, the column-group width.

    Returns (CB * bs, bt) f32.  The XLA lane runs the gather/einsum path
    unless ``interpret`` is given explicitly, which forces the Pallas kernel
    (interpreted or compiled).
    """
    if resolve_lane(lane) == "xla" and interpret is None:
        return _spmm_block_fused_jnp(vals, src, wslot, B, bt=bt)
    return _spmm_block_fused_pallas(vals, src, wslot, B, bt=bt, t_tile=t_tile,
                                    interpret=resolve_interpret(interpret))


def spmm_block_fused_decode(vals, src, wslot, dvec, B, *, bt: int,
                            t_tile: int = 128, interpret: bool | None = None,
                            lane: str | None = None):
    """One-launch coded local product + decode combine: (mn, CB*bs, bt) f32.

    dvec is this worker's survivor decode column ``D[:, k] * alive_k``
    (mn,); the output stacks the mn decode-weighted copies of the local
    product, ready for the psum that replaces the old ``D @ C~``
    contraction.
    """
    if resolve_lane(lane) == "xla":
        return _spmm_block_fused_decode_jnp(vals, src, wslot, dvec, B, bt=bt)
    return _spmm_block_fused_decode_pallas(
        vals, src, wslot, dvec, B, bt=bt, t_tile=t_tile,
        interpret=resolve_interpret(interpret))
