"""The coded-matmul op object: plan -> bind -> apply.

One object owns what the legacy flat-kwarg ``coded_matmul(...)`` spread
over 12 parameters and three layers of callers:

* **plan**   -- ``plan(config, m, n, num_workers)`` designs the code through
  the scheme registry (or ``from_plan(config, p)`` wraps a prebuilt
  ``CodedMatmulPlan``) and returns an unbound ``CodedOp``;
* **bind**   -- ``op.bind(mesh)`` attaches the mesh (validating the worker
  axis against the plan once, not on every call) and yields a callable;
* **apply**  -- ``op(A, B)`` stages and runs the shard_map program.  Backend
  dispatch, BlockELL packing, and the runtime pack cache consultation all
  live here -- callers never thread ``pack=``/``a_sparse=``/``survivors=``
  through intermediate layers;
* **rebind** -- ``op.with_survivors(mask)`` re-derives the decode matrix
  from surviving rows eagerly (raising ``DecodingError`` at rebind time,
  not mid-step) and reuses the existing tile pack, which depends only on
  the task table and never on the decode matrix.

Ops are frozen: every transition returns a new op, so a bound op can be
closed over by jit and shared across threads.  ``op.apply`` is
bit-identical to the legacy ``coded_matmul`` for the same inputs -- both
funnel into ``repro.core.coded_matmul.stage_coded_matmul`` (test-enforced
parity across backends x survivor masks x decode layouts).
"""

from __future__ import annotations

import dataclasses

import numpy as np

from repro import obs
from repro.coded.config import CodedMatmulConfig
from repro.coded import registry
from repro.core import coded_backends
from repro.core.coded_matmul import (
    CodedMatmulPlan,
    WorkerTilePack,
    _check_operands,
    chunk_mask_progress,
    lower_coded_matmul,
    resolve_pack,
    stage_coded_matmul,
)
from repro.sparse.blocksparse import BlockELL, dense_to_block_ell


@dataclasses.dataclass(frozen=True)
class CodedOp:
    """A coded matmul, fully described: design + execution config (+ mesh).

    Build with ``plan(...)`` / ``from_plan(...)``, not directly.
    ``plan_`` is the survivor-adjusted plan actually staged; ``base_plan``
    keeps the original design so tile packs (which depend only on the task
    table) are cached and reused across survivor rebinds.
    """

    config: CodedMatmulConfig
    plan_: CodedMatmulPlan
    base_plan: CodedMatmulPlan
    survivors: np.ndarray | None = None
    mesh: object | None = None
    chunk_progress: np.ndarray | None = None  # (N,) chunks completed, if partial

    # ------------------------------ lifecycle -------------------------------

    def bind(self, mesh=None) -> "CodedOp":
        """Attach a mesh (default: a fresh 1-D mesh over every visible
        device, axis named ``config.axis_name``) and validate the worker
        axis size against the plan once."""
        if mesh is None:
            import jax

            from repro import compat

            mesh = compat.make_mesh((len(jax.devices()),),
                                    (self.config.axis_name,))
        axis = self.config.axis_name
        if axis not in mesh.shape:
            raise ValueError(
                f"mesh has no axis {axis!r}: axes are {tuple(mesh.shape)}")
        if mesh.shape[axis] != self.plan_.num_workers:
            raise ValueError(
                f"mesh axis {axis}={mesh.shape[axis]} != plan workers "
                f"{self.plan_.num_workers}")
        return dataclasses.replace(self, mesh=mesh)

    @obs.span(obs.REBIND)
    def with_survivors(self, survivors) -> "CodedOp":
        """Rebind to a liveness mask (replaces any previous mask).

        ``survivors`` is an (N,) worker mask, or an (N, q) per-chunk
        completion mask (prefix-form rows: ordered sub-task streams) -- a
        device that completed only its first chunks contributes exactly
        those slots to the decode instead of being zeroed wholesale.  The
        decode matrix is re-derived NOW -- an undecodable mask raises
        ``DecodingError`` here, at rebind time.  Tile packs are reused
        either way: they depend only on the base task table.  Passing None
        (or an all-complete mask) restores the original plan.
        """
        obs.count("rebinds")
        if survivors is None:
            return dataclasses.replace(self, plan_=self.base_plan,
                                       survivors=None, chunk_progress=None)
        mask = np.asarray(survivors, dtype=bool)
        if mask.ndim == 2:
            progress = chunk_mask_progress(mask, self.base_plan.num_workers)
            return dataclasses.replace(
                self,
                plan_=self.base_plan.with_chunk_progress(
                    progress, mask.shape[1]),
                survivors=progress > 0, chunk_progress=progress)
        mask = mask.reshape(-1)
        return dataclasses.replace(
            self, plan_=self.base_plan.with_survivors(mask), survivors=mask,
            chunk_progress=None)

    # ------------------------------- execution ------------------------------

    def pack_for(self, a_sparse: BlockELL, *, use_cache: bool = True) -> WorkerTilePack:
        """The worker tile pack of ``a_sparse`` under this op's design,
        memoized in the runtime pack cache (packs depend only on the task
        table and the config's compute_dtype, so one pack serves every
        survivor rebind of this op)."""
        if use_cache:
            from repro.runtime import pack_cache

            return pack_cache.get_pack(a_sparse, self.base_plan,
                                       compute_dtype=self.config.compute_dtype)
        from repro.core.coded_matmul import pack_worker_tiles

        return pack_worker_tiles(a_sparse, self.base_plan,
                                 compute_dtype=self.config.compute_dtype)

    def _auto_backend(self, A, a_sparse, pack, s: int):
        """Resolve ``backend="auto"``: measure live-tile density, pick.

        Returns ``(backend_name, density, a_sparse)`` -- the BlockELL is
        passed back so a pack built from a concrete A is not rebuilt.
        """
        cfg = self.config
        if a_sparse is not None:
            frac = a_sparse.density()
        elif pack is not None:
            # dense-equivalent tile count of the pack: every live slot of
            # every worker could touch all s/bs row-blocks of its stripe
            degrees = np.count_nonzero(self.base_plan.weights, axis=1)
            cbl = pack.vals.shape[1]
            dense_eq = max(1, int(degrees.sum()) * cbl * (s // pack.block_size))
            frac = float(np.asarray(pack.live_tiles).sum()) / dense_eq
        else:
            import jax

            if isinstance(A, jax.core.Tracer):
                raise ValueError(
                    "backend='auto' under jit needs a_sparse= (a host "
                    "BlockELL) or pack= to measure live-tile density: it "
                    "cannot be derived from a traced operand")
            a_sparse = dense_to_block_ell(np.asarray(A, dtype=np.float32),
                                          block_size=cfg.block_size)
            frac = a_sparse.density()
        chosen = ("block_sparse" if frac <= cfg.auto_density_threshold
                  else "dense_scan")
        return chosen, frac, a_sparse

    @obs.span(obs.PREPARE)
    def _staging_kwargs(self, A, B, a_sparse, pack) -> dict:
        """Resolve backend and tile pack for ``A``, ``B``: the keyword
        arguments of ``stage_coded_matmul`` / ``lower_coded_matmul``."""
        if self.mesh is None:
            raise ValueError(
                "unbound CodedOp: call .bind(mesh) (or .bind()) first")
        cfg = self.config
        backend = cfg.backend
        entry = coded_backends.get_backend(backend)
        if not entry.needs_pack and (a_sparse is not None or pack is not None):
            raise ValueError(
                f"backend {backend!r} takes no a_sparse/pack operand")
        N, s, r, _, br, _ = _check_operands(A, B, self.plan_, self.mesh,
                                            cfg.axis_name)
        if entry.virtual:
            backend, _, a_sparse = self._auto_backend(A, a_sparse, pack, s)
            entry = coded_backends.get_backend(backend)
            if not entry.needs_pack:
                a_sparse = pack = None
        if entry.needs_pack:
            if pack is None and a_sparse is not None:
                pack = self.pack_for(a_sparse)
            pack = resolve_pack(
                A, self.base_plan, pack=pack, a_sparse=a_sparse,
                block_size=cfg.block_size, compute_dtype=cfg.compute_dtype,
                num_workers=N, s=s, r=r, br=br)
        return dict(axis_name=cfg.axis_name, alive=self.survivors,
                    out_dtype=cfg.np_dtype, backend=backend, pack=pack,
                    out_sharded=cfg.out_sharded)

    def apply(self, A, B, *, a_sparse: BlockELL | None = None,
              pack: WorkerTilePack | None = None):
        """C = A^T B under this op's code, config, and survivor mask.

        For pack-consuming backends (``block_sparse``), pass ``a_sparse``
        (a host BlockELL of A -- packed once and memoized via the runtime
        pack cache) or ``pack`` (a prebuilt ``WorkerTilePack``); a concrete
        (non-traced) A is packed automatically with ``config.block_size``.
        Backends that take no pack reject these operands outright instead
        of silently ignoring them.  ``backend="auto"`` measures the
        operand's live-tile fraction against
        ``config.auto_density_threshold`` and dispatches to block_sparse
        (sparse enough) or dense_scan; the density inputs are consumed by
        that decision and simply dropped when dense_scan wins.

        Each call is one product of ``repro.obs``: a ``repro.product`` span
        over the call, its staging spans beneath it.
        """
        with obs.product():
            return stage_coded_matmul(
                A, B, self.plan_, self.mesh,
                **self._staging_kwargs(A, B, a_sparse, pack))

    def lower(self, A, B, *, a_sparse: BlockELL | None = None,
              pack: WorkerTilePack | None = None):
        """``jax.stages.Lowered`` of the program ``apply`` would run.

        ``A`` and ``B`` may be ``jax.ShapeDtypeStruct``s when ``pack`` (or,
        for dense_scan, nothing) is given, so ``.compile()`` can target a
        described topology; ``.compile().as_text()`` shows the kernels and
        collectives, ``memory_analysis()`` the bytes per device.
        """
        return lower_coded_matmul(
            A, B, self.plan_, self.mesh,
            **self._staging_kwargs(A, B, a_sparse, pack))

    __call__ = apply

    # ------------------------------ introspection ---------------------------

    @property
    def num_workers(self) -> int:
        return self.plan_.num_workers

    @property
    def needs_pack(self) -> bool:
        """Whether this op's backend consumes host-side pack metadata."""
        return coded_backends.get_backend(self.config.backend).needs_pack

    @property
    def bound(self) -> bool:
        return self.mesh is not None

    def __repr__(self) -> str:  # the dataclass default dumps whole ndarrays
        surv = (None if self.survivors is None
                else int(self.survivors.sum()))
        chunks = ("" if self.chunk_progress is None
                  else f", chunk_progress={self.chunk_progress.tolist()}")
        return (f"CodedOp(scheme={self.config.scheme!r}, "
                f"backend={self.config.backend!r}, "
                f"m={self.plan_.m}, n={self.plan_.n}, "
                f"workers={self.num_workers}, "
                f"survivors={surv}{chunks}, bound={self.bound})")


def plan(config: CodedMatmulConfig, m: int, n: int,
         num_workers: int | None = None, *, seed: int = 0,
         max_degree: int | None = None, **scheme_kwargs) -> CodedOp:
    """Design a code for an (m x n)-blocked A^T B over ``num_workers``
    devices and wrap it in an unbound ``CodedOp``.

    The design comes from the scheme registry entry named by
    ``config.scheme``, so the host path (``get_scheme(...).instance``) and
    this device op realize the same generator matrix.
    """
    scheme = registry.get_scheme(config.scheme)
    p = scheme.plan(m, n, num_workers, max_degree=max_degree, seed=seed,
                    **scheme_kwargs)
    return CodedOp(config=config, plan_=p, base_plan=p)


def from_plan(config: CodedMatmulConfig, p: CodedMatmulPlan) -> CodedOp:
    """Wrap a prebuilt ``CodedMatmulPlan`` (e.g. from ``make_plan``) in an
    unbound ``CodedOp`` -- the migration path for callers that already own
    plan objects."""
    return CodedOp(config=config, plan_=p, base_plan=p)
