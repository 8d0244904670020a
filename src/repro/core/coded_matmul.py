"""Coded distributed matmul as a JAX/shard_map primitive.

The public entry point is ``repro.coded`` (scheme registry +
``CodedMatmulConfig`` + ``CodedOp`` plan->bind->apply; DESIGN.md section
7); this module holds the device-path machinery it stages --
``CodedMatmulPlan``/``make_plan``, tile packing, backend local-product
factories, and ``stage_coded_matmul`` -- plus the deprecated flat-kwarg
``coded_matmul`` shim.

Maps the paper's master/worker protocol onto an SPMD mesh axis:

* worker k  = device k on the ``workers`` mesh axis (N devices);
* its task  = row k of the coefficient matrix M (sampled on host, static);
* local compute = sum_{l} w_kl * A_{i_l}^T B_{j_l}, via a pluggable backend
  (registered in ``repro.core.coded_backends``);
* decode    = blocks = D @ C~  with D = pinv(M) precomputed on host, executed
  as one psum over the axis (decoding a full-rank linear code is linear, so
  on-device it collapses to a single fused contraction; the peeling/rooting
  schedule is the *host* decode used by the runtime layer).

Local-compute backends:

* ``"dense_scan"``   -- einsum over the (padded) task slots as a lax.scan:
  exactly ``max_degree`` dense block products per worker.  Cost scales with
  the dense block dims regardless of sparsity.
* ``"block_sparse"`` -- A is packed host-side into per-worker fused-gather
  tiles (``pack_worker_tiles``: tile values + source row-block/column-group
  addresses into the ORIGINAL B + per-slot weights) and the local product
  dispatches ``repro.kernels.spmm_block_fused``, which DMAs tiles straight
  out of the untouched (s, t) B.  No stacked ``B_tall`` copy is ever
  materialized, so local compute AND HBM traffic scale with the number of
  LIVE tiles -- the paper's nnz-proportional claim (Theorem 1) end-to-end
  on the device path.

Decode layout: by default the decode psum replicates the full
``(mn, br, bt)`` block tensor to every device.  With ``out_sharded=True``
the decode is a ``psum_scatter`` instead -- each device reduces only its
1/N shard of the (zero-padded to a multiple of N) block dimension, so
decode traffic is also nnz-proportional; the final block->C assembly is
left to XLA outside the shard_map and only gathers if a consumer demands
replication.

TPU adaptation notes (DESIGN.md section 3):
  - SPMD lockstep means every device pays for the *maximum* degree in the
    batch, not its own degree.  The distribution is therefore truncated at
    ``max_degree`` (default ~ 2 ln(mn), preserving decodability -- validated
    empirically in tests) and every device runs exactly max_degree padded
    slots (zero weights contribute nothing numerically).
  - Fault tolerance: ``survivors`` masks dead/straggling devices; the decode
    matrix is re-derived from the surviving rows on host (any full-rank K
    subset suffices -- Theorem 2), and dead devices' contributions are zeroed
    on device.  This is the any-K-of-N property that lets a multi-pod step
    tolerate a lost pod without recompute.
"""

from __future__ import annotations

import dataclasses
import warnings

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np
import scipy.sparse as sp
from jax.sharding import PartitionSpec as P

from repro import compat, obs
from repro.core import coded_backends
from repro.core.decoder import DecodingError, decode_matrix
from repro.core.encoder import (
    SparseCodeSpec,
    chunk_slices,
    generate_coefficient_matrix,
)
from repro.kernels import ops, spmm_block
from repro.sparse.blocksparse import BlockELL, dense_to_block_ell

# Snapshot of the registered backend names at import time; prefer
# ``repro.core.coded_backends.backend_names()`` for an always-fresh view.
BACKENDS = coded_backends.backend_names()


def chunk_mask_progress(mask: np.ndarray, num_workers: int) -> np.ndarray:
    """(N, q) per-chunk completion mask -> (N,) completed-prefix counts.

    Sub-task streams are ordered, so only prefix-form rows (all True then
    all False) describe a physical state; a True after a False means the
    caller skipped a chunk and is rejected rather than silently reread.
    """
    mask = np.asarray(mask, dtype=bool)
    if mask.ndim != 2:
        raise ValueError(f"chunk mask must be 2-D (N, q), got shape {mask.shape}")
    if mask.shape[0] != num_workers:
        raise ValueError(
            f"chunk mask has {mask.shape[0]} rows for {num_workers} workers")
    progress = mask.sum(axis=1)
    prefix = np.take_along_axis(
        np.cumsum(mask, axis=1),
        np.maximum(progress[:, None] - 1, 0), axis=1).reshape(-1)
    bad = np.flatnonzero((progress > 0) & (prefix != progress))
    if bad.size:
        raise ValueError(
            f"chunk mask rows {bad.tolist()} are not prefix-form: ordered "
            "sub-task streams complete chunk c only after chunks 0..c-1")
    return progress.astype(np.int64)


@dataclasses.dataclass(frozen=True)
class CodedMatmulPlan:
    """Host-side static plan: tasks + decode matrix, ready to stage to device."""

    spec: SparseCodeSpec
    cols: np.ndarray      # (N, Lmax) int32 block ids, padded with 0
    weights: np.ndarray   # (N, Lmax) f32, padded with 0.0
    decode: np.ndarray    # (mn, N) f32: D s.t. blocks = D @ C~
    max_degree: int

    @property
    def m(self) -> int:
        return self.spec.m

    @property
    def n(self) -> int:
        return self.spec.n

    @property
    def num_workers(self) -> int:
        return self.spec.num_workers

    def coefficient_matrix(self) -> np.ndarray:
        """Dense M (N, mn) reconstructed from the padded task table.

        Padded slots carry weight 0.0 and contribute nothing (they land on
        block id 0 but add zero).
        """
        M = np.zeros((self.num_workers, self.m * self.n), dtype=np.float64)
        rows = np.repeat(np.arange(self.num_workers), self.cols.shape[1])
        np.add.at(M, (rows, self.cols.reshape(-1).astype(np.int64)),
                  self.weights.reshape(-1).astype(np.float64))
        return M

    def with_survivors(self, survivors: np.ndarray) -> "CodedMatmulPlan":
        """Re-derive the decode matrix using only surviving workers' rows.

        survivors: boolean mask (N,) -- worker liveness -- or (N, q) -- the
        per-chunk completion mask of the chunked protocol, dispatched to
        ``with_chunk_progress`` (a device that completed its first chunks
        contributes those slots to the decode instead of being zeroed
        wholesale).  Requires the surviving submatrix to be full column rank
        (Theorem 2 says w.h.p. it is once >= ~mn survive); raises
        ``DecodingError`` (a ValueError subclass) otherwise.
        """
        survivors = np.asarray(survivors, dtype=bool)
        if survivors.ndim == 2:
            return self.with_chunk_progress(
                chunk_mask_progress(survivors, self.num_workers),
                survivors.shape[1])
        survivors = survivors.reshape(-1)
        if survivors.shape[0] != self.num_workers:
            raise ValueError(
                f"survivors mask has {survivors.shape[0]} entries for "
                f"{self.num_workers} workers")
        if survivors.all():
            return self
        d = self.m * self.n
        M_surv = self.coefficient_matrix() * survivors[:, None]
        rank = int(np.linalg.matrix_rank(M_surv))
        if rank < d:
            raise DecodingError(
                f"only {int(survivors.sum())}/{self.num_workers} survivors: "
                f"surviving coefficient rows have rank {rank} < {d} -- cannot "
                "decode; any full-column-rank subset would do (Theorem 2)")
        D = np.linalg.pinv(M_surv)
        return dataclasses.replace(self, decode=D.astype(np.float32))

    def with_chunk_progress(
        self, progress: np.ndarray, num_chunks: int
    ) -> "CodedMatmulPlan":
        """Partial-straggler rebind: keep each worker's completed slot prefix.

        Chunk boundaries follow the SAME rule as the host task model
        (``chunk_slices`` over each worker's actual degree -- its live slots
        occupy a prefix of the padded table, padded slots carry weight 0 and
        belong to no chunk), so "device k completed chunk c" and "worker k
        completed chunk c" denote the same slots and host-observed progress
        can drive this rebind directly.  ``progress[k]`` = chunks device k
        completed; slots beyond its completed prefix get weight 0, the
        decode matrix is the pseudo-inverse of the prefix-truncated
        coefficient matrix, and the psum then sums exactly the completed
        work.  Raises ``DecodingError`` when the completed prefixes lose
        column rank.  Tile packs stay valid: they depend only on the *base*
        task table, and the block_sparse local product re-reads weights from
        the staged plan.
        """
        progress = np.asarray(progress, dtype=np.int64).reshape(-1)
        if progress.shape[0] != self.num_workers:
            raise ValueError(
                f"progress has {progress.shape[0]} entries for "
                f"{self.num_workers} workers")
        if progress.min() < 0 or progress.max() > num_chunks:
            raise ValueError(
                f"progress must lie in [0, {num_chunks}], got {progress}")
        if (progress == num_chunks).all():
            return self
        L = self.cols.shape[1]
        degrees = np.count_nonzero(self.weights, axis=1)
        keep = np.zeros((self.num_workers, L), dtype=bool)
        for k, (deg, p) in enumerate(zip(degrees, progress)):
            if p > 0:
                keep[k, :chunk_slices(int(deg), num_chunks)[p - 1].stop] = True
        weights = np.where(keep, self.weights, 0.0).astype(np.float32)
        masked = dataclasses.replace(self, weights=weights)
        d = self.m * self.n
        M_eff = masked.coefficient_matrix()
        rank = int(np.linalg.matrix_rank(M_eff))
        if rank < d:
            raise DecodingError(
                f"completed chunk prefixes (progress={progress.tolist()}, "
                f"q={num_chunks}) have rank {rank} < {d} -- cannot decode; "
                "more chunks must finish")
        D = np.linalg.pinv(M_eff)
        return dataclasses.replace(masked, decode=D.astype(np.float32))


def make_plan(
    m: int,
    n: int,
    num_workers: int,
    distribution: str = "wave_soliton",
    weight_kind: str = "symmetric",
    max_degree: int | None = None,
    seed: int = 0,
    max_resample: int = 50,
) -> CodedMatmulPlan:
    """Sample a (P,S)-sparse code and build the SPMD plan.

    The degree distribution is truncated at max_degree (lockstep SPMD pays for
    the max anyway); resamples until M is full rank (Theorem 2: succeeds
    immediately w.h.p.).
    """
    d = m * n
    max_degree = max_degree or max(1, min(d, int(np.ceil(2 * np.log(max(d, 2)) + 1))))
    for attempt in range(max_resample):
        spec = SparseCodeSpec(m=m, n=n, num_workers=num_workers,
                              distribution=distribution,
                              weight_kind=weight_kind, seed=seed + attempt)
        M = generate_coefficient_matrix(spec)
        # truncate: rows with degree > max_degree keep their first max_degree
        cols = np.zeros((num_workers, max_degree), dtype=np.int32)
        weights = np.zeros((num_workers, max_degree), dtype=np.float32)
        Mt = sp.lil_matrix((num_workers, d))
        for k in range(num_workers):
            lo, hi = M.indptr[k], M.indptr[k + 1]
            take = min(hi - lo, max_degree)
            cs = M.indices[lo:lo + take]
            ws = M.data[lo:lo + take]
            cols[k, :take] = cs
            weights[k, :take] = ws
            Mt[k, cs] = ws
        Mt = Mt.tocsr()
        if np.linalg.matrix_rank(Mt.toarray()) >= d:
            D = decode_matrix(Mt).astype(np.float32)
            return CodedMatmulPlan(spec=spec, cols=cols, weights=weights,
                                   decode=D, max_degree=max_degree)
    raise RuntimeError(f"no full-rank coefficient matrix after {max_resample} tries")


# ------------------------- local-compute backends ---------------------------

def _local_dense_scan(A, B, cols_k, w_k, m: int, n: int):
    """One worker's combination: sum_l w_l A_{i_l}^T B_{j_l} (scan over slots)."""
    s, r = A.shape
    _, t = B.shape
    br, bt = r // m, t // n

    def body(acc, slot):
        col, w = slot
        i = col // n
        j = col % n
        Ai = jax.lax.dynamic_slice(A, (0, i * br), (s, br))
        Bj = jax.lax.dynamic_slice(B, (0, j * bt), (s, bt))
        prod = jnp.einsum("sr,st->rt", Ai, Bj,
                          precision=jax.lax.Precision.HIGHEST,
                          preferred_element_type=jnp.float32)
        return acc + w.astype(jnp.float32) * prod, None

    acc0 = jnp.zeros((br, bt), jnp.float32)
    acc, _ = jax.lax.scan(body, acc0, (cols_k, w_k))
    return acc


@dataclasses.dataclass(frozen=True)
class WorkerTilePack:
    """Per-worker fused-gather tiles of the sparse operand.

    Worker k's local product sum_l w_kl A_{i_l}^T B_{j_l} runs as ONE
    fused-gather SpMM (``kernels.spmm_block_fused``): each packed tile of A
    carries the address of the B tile it multiplies -- source row-block in
    the original (s, t) B plus the source column group j_l -- and the slot's
    code weight.  Nothing of B is ever stacked or copied:

      vals : (N, br/bs, Lw, bs, bs)  live tiles, zero-padded to Lw slots
      src  : (N, br/bs, Lw, 2) int32 [row-block of B in s/bs, column group
             j in n]
      wslot: (N, br/bs, Lw) f32      the slot's code weight w_kl (0 on pads)
      slot_of: (N, br/bs, Lw) int32  originating task slot l of each tile
             (0 on pads -- gate on wslot != 0)

    Weights stay per-slot (not folded into the tile values), and the pack
    depends only on the BASE task table -- never on the decode matrix or
    the currently staged weights -- so one pack serves any survivor mask.
    ``slot_of`` is what makes that true under the chunked protocol: the
    local product gathers the *staged plan's* weight for each tile through
    it, so a chunk-masked plan (some slots zeroed by
    ``with_chunk_progress``) reuses the very same pack.

    Quantized coded compute: with ``compute_dtype`` "bfloat16" the tile
    values are stored rounded to bf16 (the kernels upcast to f32 for the
    MXU accumulate); with "int8" each tile is symmetric-quantized with its
    own scale ``amax(|tile|)/127`` recorded in ``tile_scale`` -- the scale
    is folded into the per-tile weight at staging time (the kernels never
    change), so dequantize cost is zero.  The coding weights are exact
    either way; only the operand tiles carry rounding error, which the
    config layer budgets against the scheme's ``cond_warn`` decode
    conditioning (DESIGN.md section 12).
    """

    vals: np.ndarray
    src: np.ndarray
    wslot: np.ndarray
    block_size: int
    live_tiles: np.ndarray  # (N,) total live tiles per worker (cost proxy)
    #: None only on packs from pre-chunking builders; the block_sparse
    #: factory REFUSES those (it cannot follow a chunk-masked plan's weights)
    slot_of: np.ndarray | None = None
    compute_dtype: str = "float32"
    #: (N, CBl, Lw) f32 per-tile dequant scale; None unless compute_dtype
    #: is "int8"
    tile_scale: np.ndarray | None = None


# re-export: the canonical table lives in the jax-free backend registry so
# the config layer can budget quantization without importing jax
QUANT_EPS = coded_backends.QUANT_EPS


def pack_worker_tiles(a_sparse: BlockELL, plan: CodedMatmulPlan,
                      compute_dtype: str = "float32") -> WorkerTilePack:
    """Re-stripe A's global block-ELL into per-worker fused-gather tiles.

    Fully vectorized (bucketed NumPy, no Python loop over N x L x CB):
    entries are laid out slot-major (l ascending, then the BlockELL tile
    order within the slot), the same order the old nested loops produced.

    ``compute_dtype`` quantizes the packed tile values ("bfloat16" rounds
    in place, "int8" symmetric-quantizes with a per-tile scale recorded in
    ``tile_scale``); coding weights and addresses stay exact f32/int32.
    """
    if compute_dtype not in QUANT_EPS:
        raise ValueError(
            f"compute_dtype {compute_dtype!r} not in {sorted(QUANT_EPS)}")
    s, r = a_sparse.shape
    bs = a_sparse.block_size
    m, n = plan.m, plan.n
    if r % m:
        raise ValueError(f"A cols {r} not divisible by m={m}")
    br = r // m
    if br % bs or s % bs:
        raise ValueError(
            f"block partition ({br} x {s}) not divisible by block_size {bs}")
    CBl = br // bs            # column blocks per worker output row-block
    N, L = plan.cols.shape

    live_slot = plan.weights != 0.0                     # (N, L)
    i_blk = (plan.cols // n).astype(np.int64)           # (N, L) source A column group
    j_blk = (plan.cols % n).astype(np.int32)            # (N, L) source B column group
    # global BlockELL stripe feeding (k, l, cb):  g = i * CBl + cb
    g = i_blk[:, :, None] * CBl + np.arange(CBl)[None, None, :]   # (N, L, CBl)
    cnt = np.where(live_slot[:, :, None], a_sparse.nnzb[g], 0)    # (N, L, CBl)
    per_kcb = cnt.transpose(0, 2, 1)                    # (N, CBl, L)
    Lw = max(1, int(per_kcb.sum(axis=-1).max(initial=0)))
    # destination slot of each stripe's first tile: exclusive cumsum over l
    off = np.cumsum(per_kcb, axis=-1) - per_kcb         # (N, CBl, L)

    E = a_sparse.slots
    valid = np.arange(E)[None, None, None, :] < per_kcb[..., None]  # (N,CBl,L,E)
    kk, cc, ll, ee = np.nonzero(valid)
    gg = g[kk, ll, cc]
    dst = off[kk, cc, ll] + ee

    vals = np.zeros((N, CBl, Lw, bs, bs), dtype=np.float32)
    src = np.zeros((N, CBl, Lw, 2), dtype=np.int32)
    wslot = np.zeros((N, CBl, Lw), dtype=np.float32)
    slot_of = np.zeros((N, CBl, Lw), dtype=np.int32)
    vals[kk, cc, dst] = a_sparse.vals[gg, ee]
    src[kk, cc, dst, 0] = a_sparse.idx[gg, ee]
    src[kk, cc, dst, 1] = j_blk[kk, ll]
    wslot[kk, cc, dst] = plan.weights[kk, ll]
    slot_of[kk, cc, dst] = ll
    live = per_kcb.sum(axis=(1, 2)).astype(np.int64)

    tile_scale = None
    if compute_dtype == "bfloat16":
        vals = vals.astype(ml_dtypes.bfloat16)
    elif compute_dtype == "int8":
        amax = np.abs(vals).max(axis=(-2, -1))              # (N, CBl, Lw)
        tile_scale = np.where(amax > 0, amax / 127.0, 1.0).astype(np.float32)
        vals = np.rint(vals / tile_scale[..., None, None]).astype(np.int8)
    return WorkerTilePack(vals=vals, src=src, wslot=wslot, block_size=bs,
                          live_tiles=live, slot_of=slot_of,
                          compute_dtype=compute_dtype, tile_scale=tile_scale)


# ------------------------------- entry point --------------------------------

def _plan_t_tiling(bt: int, lane: str, *, bs: int, mn: int,
                   itemsize: int) -> tuple[int, int]:
    """(t_tile, bt_pad) for the kernel grid over a bt-wide column group.

    On the TPU lane a ``bt`` that is not a multiple of 128 (the lane width
    Mosaic requires of a block's last dimension) is zero-padded up to one,
    and the caller slices the pad columns back off.  Zero columns contribute
    nothing, so the kept columns are bitwise unchanged.  ``t_tile`` is the
    widest multiple of 128 that divides ``bt_pad`` and whose VMEM footprint
    -- ``spmm_block.fused_vmem_bytes`` of the tile edge ``bs``, the ``mn``
    decode rows of the output block and the pack's ``itemsize`` -- fits
    ``spmm_block.VMEM_TILE_BYTES``; 128 where no wider one does.  The grid
    is then (CB, bt_pad / t_tile, L).  The XLA lane does not tile.
    """
    if lane == "xla":
        return bt, bt
    bt_pad = -(-bt // 128) * 128
    fits = [t for t in range(128, bt_pad + 1, 128)
            if bt_pad % t == 0 and spmm_block.fused_vmem_bytes(
                bs, mn, t, itemsize) <= spmm_block.VMEM_TILE_BYTES]
    return max(fits, default=128), bt_pad


def _kernel_grid(plan: CodedMatmulPlan, pack: WorkerTilePack, bt: int):
    """(lane, t_tile, bt_pad) of one worker's block-sparse kernel launch."""
    lane = ops.resolve_lane()
    t_tile, bt_pad = _plan_t_tiling(
        bt, lane, bs=pack.block_size, mn=plan.m * plan.n,
        itemsize=pack.vals.dtype.itemsize)
    return lane, t_tile, bt_pad


def _kernel_grid_steps(plan: CodedMatmulPlan, pack: WorkerTilePack,
                       bt: int) -> int:
    """Grid steps of one worker's kernel launch on the TPU lane,
    CB * (bt_pad / t_tile) * L; 0 on the XLA lane, which has no grid."""
    lane, t_tile, bt_pad = _kernel_grid(plan, pack, bt)
    if lane != "tpu":
        return 0
    _, CB, L = pack.wslot.shape
    return CB * (bt_pad // t_tile) * L


def _make_dense_scan_local_product(plan: CodedMatmulPlan, pack, bt: int):
    m, n = plan.m, plan.n

    def local_product(A_, B_, cols_k, w_k):
        return _local_dense_scan(A_, B_, cols_k, w_k, m, n)

    return (plan.cols, plan.weights), local_product


def _block_sparse_operands(plan: CodedMatmulPlan, pack: WorkerTilePack,
                           bt: int):
    """Shared staging of the block_sparse factories: the per-worker pack
    arrays (leading axis N), the kernel lane, and its (t_tile, bt_pad) grid
    plan with the matching column padding of B."""
    if pack.slot_of is None:
        # a pack without the tile->slot map cannot follow a chunk-masked
        # plan's weights; computing with its baked-in base weights would be
        # silently wrong under with_chunk_progress, so refuse outright
        raise ValueError(
            "WorkerTilePack has no slot_of map (built by a pre-chunking "
            "packer?); rebuild it with pack_worker_tiles")
    lane, t_tile, bt_pad = _kernel_grid(plan, pack, bt)
    # The pack carries the BASE task table's weights; the staged plan may
    # have zeroed some (chunk-prefix masking).  Re-read each live tile's
    # weight from the *current* plan through slot_of so one pack serves
    # every chunk-progress rebind; for an unmasked plan this reproduces
    # pack.wslot bit-for-bit (same f32 values, gathered instead of copied).
    N_ = plan.weights.shape[0]
    wsl_all = np.where(
        pack.wslot != 0.0,
        plan.weights[np.arange(N_)[:, None, None], pack.slot_of],
        np.float32(0.0))
    if pack.tile_scale is not None:
        # int8 pack: fold the per-tile dequant scale into the per-tile
        # weight -- w * (scale * tile_q) == (w * scale) * tile_q, and the
        # kernels already multiply by the weight, so dequantize is free
        wsl_all = wsl_all * pack.tile_scale

    def pad_cols(B_):
        # zero-pad each bt-wide column group up to bt_pad (no-op pass-through
        # when bt tiles fine); the kernel output is sliced back by the caller
        if bt_pad == bt:
            return B_
        s_, t_ = B_.shape
        return jnp.pad(
            B_.reshape(s_, t_ // bt, bt),
            ((0, 0), (0, 0), (0, bt_pad - bt))).reshape(s_, -1)

    arrays = (pack.vals, pack.src, wsl_all.astype(np.float32))
    return arrays, lane, t_tile, bt_pad, pad_cols


def _make_block_sparse_local_product(plan: CodedMatmulPlan, pack: WorkerTilePack,
                                     bt: int):
    arrays, lane, t_tile, bt_pad, pad_cols = _block_sparse_operands(
        plan, pack, bt)

    def local_product(A_, B_, vals, src, wsl):
        # fused gather: tiles address the original B directly -- no
        # stacked (max_degree * s, bt) copy is ever materialized
        out = ops.spmm_block_fused(vals, src, wsl, pad_cols(B_), bt=bt_pad,
                                   t_tile=t_tile, lane=lane)
        return out[:, :bt] if bt_pad != bt else out

    return arrays, local_product


def _make_block_sparse_fused_decode(plan: CodedMatmulPlan, pack: WorkerTilePack,
                                    bt: int):
    """The one-launch local product: decode combine fused into the epilogue.

    Returns ``(arrays, fn)``: ``fn(A, B, dvec, *arrays_k) -> (mn, br, bt)``
    takes worker k's rows of ``arrays`` and its survivor decode column
    ``dvec = D[:, k] * alive_k``; the output is already the stack of
    decode-weighted copies, ready for the psum -- the separate ``D @ C~``
    contraction never exists in the staged program.
    """
    arrays, lane, t_tile, bt_pad, pad_cols = _block_sparse_operands(
        plan, pack, bt)

    def local_product_decode(A_, B_, dvec, vals, src, wsl):
        out = ops.spmm_block_fused_decode(
            vals, src, wsl, dvec, pad_cols(B_), bt=bt_pad, t_tile=t_tile,
            lane=lane)
        return out[:, :, :bt] if bt_pad != bt else out

    return arrays, local_product_decode


coded_backends.get_backend("dense_scan").local_product_factory = (
    _make_dense_scan_local_product)
coded_backends.get_backend("block_sparse").local_product_factory = (
    _make_block_sparse_local_product)
coded_backends.get_backend("block_sparse").fused_local_product_factory = (
    _make_block_sparse_fused_decode)


def _check_operands(A, B, plan: CodedMatmulPlan, mesh, axis_name: str):
    """Shared shape/mesh validation; returns (N, s, r, t, br, bt)."""
    N = mesh.shape[axis_name]
    if N != plan.num_workers:
        raise ValueError(f"mesh axis {axis_name}={N} != plan workers {plan.num_workers}")
    m, n = plan.m, plan.n
    s, r = A.shape
    _, t = B.shape
    if r % m or t % n:
        raise ValueError(f"A cols {r} % m={m} or B cols {t} % n={n} nonzero")
    return N, s, r, t, r // m, t // n


def resolve_pack(
    A,
    plan: CodedMatmulPlan,
    *,
    pack: WorkerTilePack | None = None,
    a_sparse: BlockELL | None = None,
    block_size: int = 8,
    compute_dtype: str = "float32",
    num_workers: int,
    s: int,
    r: int,
    br: int,
) -> WorkerTilePack:
    """Obtain-and-validate the worker tile pack for the block_sparse backend.

    Accepts a prebuilt ``pack`` (e.g. from the runtime pack cache), an
    ``a_sparse`` host BlockELL of A (packed here), or a concrete A (packed
    with ``block_size``).  A pack built against different operands silently
    gathers garbage (XLA clamps out-of-range indices), so the result is
    always validated against the operand geometry before use -- including
    its ``compute_dtype``: a pack quantized differently than the config
    asked for computes subtly different numbers.
    """
    n = plan.n
    if pack is None:
        if a_sparse is None and isinstance(A, jax.core.Tracer):
            raise ValueError(
                "backend='block_sparse' under jit needs a_sparse= (a host "
                "BlockELL) or pack= (a WorkerTilePack): the tile pack is "
                "static metadata and cannot be derived from a traced "
                "operand")
        ell = a_sparse if a_sparse is not None else dense_to_block_ell(
            np.asarray(A, dtype=np.float32), block_size=block_size)
        if ell.shape != (s, r):
            raise ValueError(f"a_sparse shape {ell.shape} != A shape {(s, r)}")
        pack = pack_worker_tiles(ell, plan, compute_dtype=compute_dtype)
    if getattr(pack, "compute_dtype", "float32") != compute_dtype:
        raise ValueError(
            f"pack was quantized as {pack.compute_dtype!r} but the config "
            f"asks for compute_dtype={compute_dtype!r}; rebuild the pack")
    if pack.vals.shape[0] != num_workers:
        raise ValueError(
            f"pack built for {pack.vals.shape[0]} workers, mesh has {num_workers}")
    # a pack built against different operands silently gathers garbage
    # (XLA clamps out-of-range indices), so validate it against (s, r)
    bs_p = pack.block_size
    if s % bs_p or pack.vals.shape[1] * bs_p != br:
        raise ValueError(
            f"pack (block_size={bs_p}, {pack.vals.shape[1]} column "
            f"blocks) does not tile operands with s={s}, br={br}")
    if int(pack.src[..., 0].max(initial=0)) >= s // bs_p:
        raise ValueError(
            f"pack row-block indices exceed s//bs={s // bs_p}: the pack "
            "was built for a different A")
    if int(pack.src[..., 1].max(initial=0)) >= n:
        raise ValueError(
            f"pack column-group indices exceed n={n}: the pack was "
            "built for a different plan")
    return pack


@obs.span(obs.PREPARE)
def build_coded_program(
    plan: CodedMatmulPlan,
    mesh: jax.sharding.Mesh,
    bt: int,
    *,
    axis_name: str = "model",
    alive: np.ndarray | None = None,
    out_dtype=jnp.float32,
    backend: str = "dense_scan",
    pack: WorkerTilePack | None = None,
    out_sharded: bool = False,
):
    """The coded matmul as a pure function of its device operands.

    Returns ``(program, worker_arrays)``: ``program(A, B, *worker_arrays)``
    computes C, and ``worker_arrays`` are host arrays with a leading
    worker axis of size N -- the per-worker survivor decode columns, then
    the backend's per-worker operands (task table or tile pack).  The
    program takes them sharded over ``axis_name``, so each device holds
    only its own worker's rows and nothing is baked into the compiled
    program as a constant.  ``bt`` is the column-group width t / n.
    """
    entry = coded_backends.get_backend(backend)
    if entry.virtual:
        raise ValueError(
            f"backend {backend!r} is a dispatch pseudo-backend: resolve it "
            "to a concrete backend (CodedOp does this) before staging")
    N = mesh.shape[axis_name]
    m, n = plan.m, plan.n
    if entry.needs_pack and pack is None:
        raise ValueError(
            f"backend {backend!r} needs a resolved WorkerTilePack "
            "(see resolve_pack)")
    if entry.local_product_factory is None:
        raise ValueError(
            f"backend {backend!r} is registered but has no "
            "local_product_factory attached")
    fuse = entry.fused_decode and entry.fused_local_product_factory is not None
    factory = (entry.fused_local_product_factory if fuse
               else entry.local_product_factory)
    backend_arrays, local_product = factory(plan, pack, bt)

    alive_f = (np.ones((N,), np.float32) if alive is None
               else np.asarray(alive, dtype=np.float32))
    # row k: worker k's survivor decode column D[:, k] * alive_k, (N, mn)
    dvecs = np.ascontiguousarray(
        (np.asarray(plan.decode, np.float32) * alive_f[None, :]).T)

    mn = m * n
    mn_pad = -(-mn // N) * N  # scatter splits the block dim N ways

    def worker_fn(A_, B_, dvec, *arrays):
        dvec = dvec[0]
        arrays = [a[0] for a in arrays]
        if fuse:
            # one-launch path: the decode combine happens in the kernel
            # epilogue, so the (mn, br, bt) contribution comes out of the
            # local product directly -- no D @ C~ contraction is staged
            contrib = local_product(A_, B_, dvec, *arrays)
        else:
            Ct = local_product(A_, B_, *arrays)
            # decode contribution: blocks_c += D[c, k] * C~_k (zeroed if dead)
            contrib = dvec[:, None, None] * Ct[None]
        if out_sharded:
            contrib = jnp.pad(contrib, ((0, mn_pad - mn), (0, 0), (0, 0)))
            # each device reduces only its 1/N shard of the block dim
            return compat.psum_scatter(contrib, axis_name,
                                       scatter_dimension=0, tiled=True)
        blocks = jax.lax.psum(contrib, axis_name)          # (mn, br, bt)
        br = blocks.shape[1]
        C = blocks.reshape(m, n, br, bt).transpose(0, 2, 1, 3).reshape(m * br, n * bt)
        return C.astype(out_dtype)

    worker_arrays = (dvecs, *backend_arrays)
    fn = compat.shard_map(
        worker_fn, mesh=mesh,
        in_specs=(P(), P()) + (P(axis_name),) * len(worker_arrays),
        out_specs=P(axis_name) if out_sharded else P(),
        check_vma=False,
    )
    if not out_sharded:
        return fn, worker_arrays

    def program(A, B, *arrays):
        blocks = fn(A, B, *arrays)                         # (mn_pad, br, bt)
        br = blocks.shape[1]
        C = blocks[:mn].reshape(m, n, br, bt).transpose(0, 2, 1, 3)
        return C.reshape(m * br, n * bt).astype(out_dtype)

    return program, worker_arrays


def _jitted_program(A, B, plan, mesh, axis_name, **kwargs):
    *_, bt = _check_operands(A, B, plan, mesh, axis_name)
    program, worker_arrays = build_coded_program(
        plan, mesh, bt, axis_name=axis_name, **kwargs)
    by_worker = jax.sharding.NamedSharding(mesh, P(axis_name))
    return jax.jit(program), worker_arrays, by_worker


def stage_coded_matmul(
    A: jax.Array,
    B: jax.Array,
    plan: CodedMatmulPlan,
    mesh: jax.sharding.Mesh,
    *,
    axis_name: str = "model",
    **kwargs,
) -> jax.Array:
    """Compile and run the program of one coded matmul (the shared core).

    Keyword arguments are those of ``build_coded_program``.  ``plan`` must
    already be survivor-adjusted (its decode matrix re-derived via
    ``with_survivors``) and ``alive`` is the matching worker-liveness mask
    (None = all alive).  For backends with ``needs_pack``, ``pack`` must be
    pre-resolved (``resolve_pack``).  Both the legacy ``coded_matmul`` shim
    and ``repro.coded.CodedOp`` funnel through here, which is what makes
    old-vs-new bit-parity structural rather than coincidental.  The
    per-worker operands are placed one worker per device along
    ``axis_name``.  Each call stages a fresh program, so each call compiles.
    """
    program, worker_arrays, by_worker = _jitted_program(
        A, B, plan, mesh, axis_name, **kwargs)
    with obs.span(obs.UPLOAD):
        arrays = [jax.device_put(a, by_worker) for a in worker_arrays]
    obs.count("upload_bytes", sum(a.nbytes for a in worker_arrays))
    if coded_backends.get_backend(kwargs.get("backend", "dense_scan")).needs_pack:
        steps = _kernel_grid_steps(plan, kwargs["pack"], B.shape[1] // plan.n)
        if steps:
            obs.count("kernel_grid_steps", steps)
    with obs.span(obs.JIT):
        return program(A, B, *arrays)


def lower_coded_matmul(A, B, plan: CodedMatmulPlan, mesh: jax.sharding.Mesh,
                       *, axis_name: str = "model", **kwargs):
    """``jax.stages.Lowered`` of the program ``stage_coded_matmul`` runs.

    ``A`` and ``B`` may be arrays or ``jax.ShapeDtypeStruct``s (with their
    shardings); the per-worker operands enter as shapes sharded by worker,
    so ``.compile()`` works for a described topology with no chip attached.
    """
    program, worker_arrays, by_worker = _jitted_program(
        A, B, plan, mesh, axis_name, **kwargs)
    return program.lower(A, B, *(
        jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=by_worker)
        for a in worker_arrays))


def _coded_matmul(
    A: jax.Array,
    B: jax.Array,
    plan: CodedMatmulPlan,
    mesh: jax.sharding.Mesh,
    axis_name: str = "model",
    survivors: np.ndarray | None = None,
    out_dtype=jnp.float32,
    backend: str = "dense_scan",
    a_sparse: BlockELL | None = None,
    block_size: int = 8,
    pack: WorkerTilePack | None = None,
    out_sharded: bool = False,
) -> jax.Array:
    """Flat-kwarg implementation behind the deprecated ``coded_matmul`` shim."""
    coded_backends.get_backend(backend)  # raises "backend ... not in" early
    N, s, r, t, br, bt = _check_operands(A, B, plan, mesh, axis_name)

    alive = None
    if survivors is not None:
        surv = np.asarray(survivors, dtype=bool)
        plan = plan.with_survivors(surv)
        # per-chunk masks collapse to worker liveness for the psum gate --
        # the slot-level masking already lives in the rebuilt plan weights
        alive = (chunk_mask_progress(surv, N) > 0) if surv.ndim == 2 else surv

    if coded_backends.get_backend(backend).needs_pack:
        pack = resolve_pack(A, plan, pack=pack, a_sparse=a_sparse,
                            block_size=block_size, num_workers=N,
                            s=s, r=r, br=br)
    return stage_coded_matmul(A, B, plan, mesh, axis_name=axis_name,
                              alive=alive, out_dtype=out_dtype,
                              backend=backend, pack=pack,
                              out_sharded=out_sharded)


def coded_matmul(
    A: jax.Array,
    B: jax.Array,
    plan: CodedMatmulPlan,
    mesh: jax.sharding.Mesh,
    axis_name: str = "model",
    survivors: np.ndarray | None = None,
    out_dtype=jnp.float32,
    backend: str = "dense_scan",
    a_sparse: BlockELL | None = None,
    block_size: int = 8,
    pack: WorkerTilePack | None = None,
    out_sharded: bool = False,
) -> jax.Array:
    """DEPRECATED flat-kwarg entry point; use ``repro.coded`` instead.

    C = A^T B computed with the (P,S)-sparse code over a mesh axis.
    A: (s, r), B: (s, t), replicated over `axis_name` (the worker axis).
    Returns C (r, t).  r % m == 0, t % n == 0 required, and the mesh axis
    size must equal plan.num_workers.

    The replacement is the plan->bind->apply object API::

        from repro.coded import CodedMatmulConfig, from_plan
        op = from_plan(CodedMatmulConfig(backend=..., out_sharded=...),
                       plan).bind(mesh)
        C = op(A, B)                     # bit-identical to this function

    This shim stays bit-identical to the new API (both funnel through
    ``stage_coded_matmul``; parity is test-enforced) and will be removed
    after one deprecation cycle.  See DESIGN.md section 7 for the API and
    deprecation policy.
    """
    warnings.warn(
        "coded_matmul(...) is deprecated: use repro.coded "
        "(CodedMatmulConfig + plan/from_plan -> bind -> apply)",
        DeprecationWarning, stacklevel=2)
    return _coded_matmul(A, B, plan, mesh, axis_name=axis_name,
                         survivors=survivors, out_dtype=out_dtype,
                         backend=backend, a_sparse=a_sparse,
                         block_size=block_size, pack=pack,
                         out_sharded=out_sharded)


def uncoded_matmul_reference(A, B):
    """The plain product, for tests and overhead comparisons (fp32
    contraction on every backend)."""
    return jnp.einsum("sr,st->rt", A, B, precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)
