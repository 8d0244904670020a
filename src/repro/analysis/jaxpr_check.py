"""Reusable verification passes over staged (traced) coded matmuls.

The paper's structural guarantees survive staging as *shape and dtype facts
about the jaxpr*, so they can be proven on the trace without executing a
single multiply:

* ``stacked_intermediates`` -- the nnz-proportional claim (Theorem 1): the
  block_sparse program must never materialize an array with a
  ``max_degree * s`` leading dimension (the legacy stacked ``B_tall``
  gather).  This is THE detector: ``tests/spmd_coded_matmul_check.py`` and
  the ``repro.analysis`` CLI both call this one implementation, and
  ``assert_detector_sensitivity`` proves it still trips on the legacy
  construction it was built to catch.
* ``collective_axis_offenders`` -- every psum / reduce-scatter in the staged
  program names exactly the configured worker axis (a wrong or missing axis
  name decodes garbage silently under ``check_vma=False``).
* ``float64_offenders`` -- the dtype policy: no intermediate may be f64
  (silent promotion doubles HBM traffic and desyncs the f32 decode matrix).
* ``peak_equation_bytes`` -- per-equation operand+output byte accounting;
  the driver asserts the block_sparse path's peak stays within an
  nnz-proportional budget derived from the operands and the tile pack.

Every pass returns plain offender records; callers (tests, the CLI driver
``run_jaxpr_checks``) decide between asserting and emitting findings.
"""

from __future__ import annotations

import inspect
from typing import Iterator

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.findings import ERROR, WARNING, Finding

#: collectives whose axis names the staged program must get right
#: (psum_invariant is the spelling shard_map emits under check_vma=True)
_COLLECTIVE_PRIMS = ("psum", "psum_invariant", "reduce_scatter", "psum_scatter",
                     "all_gather", "all_to_all", "ppermute")


def _sub_jaxprs(val) -> Iterator:
    from jax.extend.core import ClosedJaxpr, Jaxpr

    if isinstance(val, ClosedJaxpr):
        yield val.jaxpr
    elif isinstance(val, Jaxpr):
        yield val
    elif isinstance(val, (list, tuple)):
        for v in val:
            yield from _sub_jaxprs(v)


def iter_eqns(jaxpr) -> Iterator:
    """Every equation of ``jaxpr``, descending into sub-jaxprs (shard_map
    bodies, scan bodies, cond branches, ...)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for param in eqn.params.values():
            for sub in _sub_jaxprs(param):
                yield from iter_eqns(sub)


def walk_avals(jaxpr) -> Iterator[tuple[str, object]]:
    """(primitive name, output aval) of every equation, recursively."""
    for eqn in iter_eqns(jaxpr):
        for v in eqn.outvars:
            yield eqn.primitive.name, v.aval


def _closed(j):
    return j.jaxpr if hasattr(j, "jaxpr") else j


# ------------------------- pass: no dense materialization --------------------

def stacked_intermediates(jaxpr, stacked_rows: int) -> list[tuple[str, tuple]]:
    """Offending (primitive, shape) pairs whose output aval has a leading
    dimension of exactly ``stacked_rows`` = ``max_degree * s`` -- the row
    count of the legacy stacked-operand (``B_tall``) copy the fused-gather
    path exists to avoid."""
    return [
        (prim, tuple(aval.shape))
        for prim, aval in walk_avals(_closed(jaxpr))
        if getattr(aval, "shape", ()) and aval.shape[0] == stacked_rows
    ]


def legacy_stacked_gather(B, max_degree: int, s: int, n: int, bt: int):
    """The OLD B_tall construction (gather + transpose + reshape into a
    (max_degree * s, bt) stack) -- kept as the detector's sensitivity probe,
    never as an execution path."""
    bsel = jnp.take(B.reshape(s, n, bt),
                    jnp.zeros((max_degree,), jnp.int32), axis=1)
    return bsel.transpose(1, 0, 2).reshape(max_degree * s, bt)


def assert_detector_sensitivity(max_degree: int, s: int, n: int, bt: int,
                                dtype=jnp.float32) -> None:
    """Prove ``stacked_intermediates`` still flags the legacy construction.

    A detector that silently went blind (e.g. after a jaxpr representation
    change upstream) would let the dense path regress unnoticed; both the
    CLI and the SPMD check run this self-test alongside the real pass.
    """
    B = jax.ShapeDtypeStruct((s, n * bt), dtype)
    closed = jax.make_jaxpr(
        lambda b: legacy_stacked_gather(b, max_degree, s, n, bt))(B)
    tripped = stacked_intermediates(closed, max_degree * s)
    if not tripped:
        raise AssertionError(
            "jaxpr walker failed to flag the legacy stacked gather "
            f"(max_degree={max_degree}, s={s}): the no-dense-materialization "
            "detector has lost sensitivity")


# ----------------------- pass: one-launch decode epilogue --------------------

def _iter_eqns_outside_kernels(jaxpr) -> Iterator:
    """Like ``iter_eqns`` but does NOT descend into pallas_call bodies: the
    decode combine living inside a kernel is exactly the fused epilogue the
    one-launch contract wants, never an offender."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call":
            continue
        for param in eqn.params.values():
            for sub in _sub_jaxprs(param):
                yield from _iter_eqns_outside_kernels(sub)


#: primitives a staged-out-of-kernel decode combine can appear as: the
#: broadcast multiply of the legacy epilogue or an explicit D @ C~ contraction
_DECODE_PRIMS = ("mul", "dot_general", "broadcast_in_dim")


def decode_contraction_offenders(jaxpr, mn: int, br: int) -> list[tuple[str, tuple]]:
    """Equations OUTSIDE any kernel that build the decode-weighted stack: a
    mul / dot_general / broadcast with a rank-3 ``(mn, br, *)`` output.  On
    the one-launch path that stack may only be born inside the fused
    kernel's epilogue, so any hit means a separate decode launch (and an
    HBM round-trip of C~) regressed into the staged program.  ``mn == 1``
    is skipped: a single-block decode is shape-indistinguishable from the
    local product itself."""
    if mn <= 1:
        return []
    return [
        (eqn.primitive.name, tuple(v.aval.shape))
        for eqn in _iter_eqns_outside_kernels(_closed(jaxpr))
        if eqn.primitive.name in _DECODE_PRIMS
        for v in eqn.outvars
        if getattr(v.aval, "shape", None) is not None
        and len(v.aval.shape) == 3
        and v.aval.shape[0] == mn and v.aval.shape[1] == br
    ]


def fused_epilogue_launches(jaxpr, mn: int) -> list[tuple]:
    """Output shapes of every pallas_call that emits the decode-fused stack
    (rank-3, leading dim mn).  Empty means the program never ran the
    one-launch kernel -- the epilogue contract is vacuous without it."""
    out = []
    for eqn in iter_eqns(_closed(jaxpr)):
        if eqn.primitive.name != "pallas_call":
            continue
        for v in eqn.outvars:
            shape = getattr(v.aval, "shape", None)
            if shape is not None and len(shape) == 3 and shape[0] == mn:
                out.append(tuple(shape))
    return out


def legacy_decode_combine(dvec, Ct):
    """The OLD two-step epilogue (broadcast multiply of the decode column
    against the local product) -- the decode detector's sensitivity probe,
    never an execution path."""
    return dvec[:, None, None] * Ct[None]


def assert_decode_detector_sensitivity(mn: int, br: int, bt: int,
                                       dtype=jnp.float32) -> None:
    """Prove ``decode_contraction_offenders`` still flags the legacy
    two-step combine (same blind-detector rationale as the stacked-gather
    self-test)."""
    dvec = jax.ShapeDtypeStruct((mn,), dtype)
    Ct = jax.ShapeDtypeStruct((br, bt), dtype)
    closed = jax.make_jaxpr(legacy_decode_combine)(dvec, Ct)
    if not decode_contraction_offenders(closed, mn, br):
        raise AssertionError(
            "jaxpr walker failed to flag the legacy decode combine "
            f"(mn={mn}, br={br}, bt={bt}): the one-launch-epilogue detector "
            "has lost sensitivity")


def verify_fused_epilogue(closed, *, mn: int, br: int, context: str) -> list[Finding]:
    """The one-launch contract for a kernel-lane staged fused program: the
    decode stack is born inside a pallas_call epilogue and nowhere else."""
    path, line = _staging_anchor()

    def finding(message):
        return Finding(rule="one-launch-epilogue", severity=ERROR, path=path,
                       line=line, message=f"{context}: {message}",
                       layer="jaxpr")

    out = []
    offenders = decode_contraction_offenders(closed, mn, br)
    if offenders:
        out.append(finding(
            f"separate decode contraction staged outside the kernel: "
            f"{offenders[:3]} -- the decode combine must ride the fused "
            "epilogue"))
    if mn > 1 and not fused_epilogue_launches(closed, mn):
        out.append(finding(
            "no pallas_call emits the (mn, br, bt) decode-fused stack: the "
            "one-launch kernel never ran"))
    return out


# --------------------------- pass: collective axes ---------------------------

def _eqn_axis_names(eqn) -> tuple:
    axes = eqn.params.get("axes", eqn.params.get("axis_name", ()))
    if not isinstance(axes, (tuple, list)):
        axes = (axes,)
    return tuple(a for a in axes if isinstance(a, str))


def collective_axis_offenders(jaxpr, axis_name: str) -> list[tuple[str, tuple]]:
    """Collectives whose named axes are not exactly ``(axis_name,)``."""
    out = []
    for eqn in iter_eqns(_closed(jaxpr)):
        if eqn.primitive.name not in _COLLECTIVE_PRIMS:
            continue
        names = _eqn_axis_names(eqn)
        if names != (axis_name,):
            out.append((eqn.primitive.name, names))
    return out


def collective_prims(jaxpr) -> list[str]:
    """Names of every collective equation in the program (the decode psum /
    reduce-scatter must exist at all -- zero collectives means the program
    never combined worker contributions)."""
    return [eqn.primitive.name for eqn in iter_eqns(_closed(jaxpr))
            if eqn.primitive.name in _COLLECTIVE_PRIMS]


# ----------------------------- pass: dtype policy ----------------------------

def float64_offenders(jaxpr) -> list[tuple[str, tuple, str]]:
    """(primitive, shape, dtype) of every f64 intermediate.  The device path
    is an f32 pipeline end to end (decode matrices are staged as f32); an
    f64 aval means a silent promotion leaked into the staged computation."""
    out = []
    for prim, aval in walk_avals(_closed(jaxpr)):
        dt = getattr(aval, "dtype", None)
        if dt is not None and np.dtype(dt) == np.float64:
            out.append((prim, tuple(aval.shape), str(dt)))
    return out


# ------------------------- pass: peak-bytes accounting -----------------------

def _aval_bytes(aval) -> int:
    shape = getattr(aval, "shape", None)
    dt = getattr(aval, "dtype", None)
    if shape is None or dt is None:
        return 0
    return int(np.prod(shape, dtype=np.int64)) * np.dtype(dt).itemsize


def peak_equation_bytes(jaxpr) -> tuple[int, str, list[tuple]]:
    """Max over equations of (operand + output bytes); returns
    (bytes, primitive, shapes) of the peak equation.  This is the static
    proxy for peak live memory: an equation that touches a
    max_degree-times-blown-up operand shows up here even if XLA later fuses
    it away, which is exactly the conservatism a CI gate wants."""
    peak, peak_prim, peak_shapes = 0, "<empty>", []
    for eqn in iter_eqns(_closed(jaxpr)):
        total = sum(_aval_bytes(v.aval) for v in (*eqn.invars, *eqn.outvars)
                    if hasattr(v, "aval"))
        if total > peak:
            peak = total
            peak_prim = eqn.primitive.name
            peak_shapes = [tuple(getattr(v.aval, "shape", ()))
                           for v in (*eqn.invars, *eqn.outvars)
                           if hasattr(v, "aval")]
    return peak, peak_prim, peak_shapes


def nnz_proportional_budget(plan, pack, s: int, r: int, t: int,
                            slack: float = 2.0) -> int:
    """Byte budget for one staged block_sparse equation: the operands, the
    packed live tiles, the (padded) decode contribution, and the result --
    nothing in the program may touch more than ``slack`` times their sum.
    The legacy stacked ``B_tall`` copy (``max_degree * s`` rows) blows past
    this the moment max_degree exceeds n, which is the regression the
    accounting exists to catch."""
    N = plan.num_workers
    m, n = plan.m, plan.n
    br, bt = r // m, t // n
    mn_pad = -(-m * n // N) * N
    itemsize = 4  # the staged pipeline is f32 end to end (dtype pass enforces)
    terms = [
        s * r,                      # A (replicated operand)
        s * t,                      # B (replicated operand)
        int(np.prod(pack.vals.shape)) if pack is not None else 0,
        mn_pad * br * bt,           # per-device decode contribution
        m * br * n * bt,            # the assembled C
    ]
    return int(slack * itemsize * sum(terms))


# ------------------------------- CLI driver ----------------------------------

def _staging_anchor() -> tuple[str, int]:
    """file:line of ``stage_coded_matmul`` -- the one place every verified
    program is staged from, hence the natural anchor for jaxpr findings."""
    from repro.core import coded_matmul

    try:
        _, line = inspect.getsourcelines(coded_matmul.stage_coded_matmul)
    except OSError:  # pragma: no cover - source unavailable (zipapp etc.)
        line = 0
    return "core/coded_matmul.py", line


def verify_staged_program(closed, *, axis_name: str, stacked_rows: int | None,
                          byte_budget: int | None,
                          context: str) -> list[Finding]:
    """Run every applicable pass over one staged program; findings only."""
    path, line = _staging_anchor()

    def finding(rule, message, severity=ERROR):
        return Finding(rule=rule, severity=severity, path=path, line=line,
                       message=f"{context}: {message}", layer="jaxpr")

    out = []
    if stacked_rows is not None:
        offenders = stacked_intermediates(closed, stacked_rows)
        if offenders:
            out.append(finding(
                "no-dense-materialization",
                f"program materializes {stacked_rows}-row intermediates "
                f"(max_degree * s): {offenders[:3]}"))
    bad_axes = collective_axis_offenders(closed, axis_name)
    if bad_axes:
        out.append(finding(
            "collective-axis",
            f"collectives over unexpected axes (want {axis_name!r}): "
            f"{bad_axes}"))
    if not collective_prims(closed):
        out.append(finding(
            "collective-axis",
            "no collective in the staged program: worker contributions are "
            "never combined"))
    f64 = float64_offenders(closed)
    if f64:
        out.append(finding(
            "dtype-policy",
            f"float64 intermediates in the staged f32 pipeline: {f64[:3]}"))
    if byte_budget is not None:
        peak, prim, shapes = peak_equation_bytes(closed)
        if peak > byte_budget:
            out.append(finding(
                "memory-budget",
                f"peak equation touches {peak} bytes > nnz-proportional "
                f"budget {byte_budget} (primitive {prim}, shapes "
                f"{shapes[:4]})"))
    return out


def run_jaxpr_checks(max_schemes: int | None = None) -> tuple[list[Finding], int]:
    """Stage coded matmuls for every device-capable registered scheme across
    backends x decode layouts and verify each trace.  Returns
    (findings, programs_verified).  Tracing only -- nothing executes on
    device, but a mesh over the visible devices is required to stage."""
    from repro import compat
    from repro.coded import CodedMatmulConfig, from_plan, get_scheme, scheme_names
    from repro.core.coded_matmul import pack_worker_tiles
    from repro.sparse import dense_to_block_ell

    path, line = _staging_anchor()
    findings: list[Finding] = []
    programs = 0

    # detector self-tests first: a blind detector must fail the run, not
    # silently bless it
    try:
        assert_detector_sensitivity(max_degree=6, s=32, n=2, bt=12)
    except AssertionError as exc:
        findings.append(Finding(
            rule="no-dense-materialization", severity=ERROR, path=path,
            line=line, layer="jaxpr", message=str(exc)))
        return findings, programs
    try:
        assert_decode_detector_sensitivity(mn=4, br=8, bt=12)
    except AssertionError as exc:
        findings.append(Finding(
            rule="one-launch-epilogue", severity=ERROR, path=path,
            line=line, layer="jaxpr", message=str(exc)))
        return findings, programs

    devices = jax.devices()
    m = n = 2
    names = [nm for nm in scheme_names()]
    if max_schemes is not None:
        names = names[:max_schemes]
    rng = np.random.default_rng(0)
    s, r, t = 32, 8 * m, 12 * n
    br, bt = r // m, t // n
    A_np = rng.standard_normal((s, r)).astype(np.float32)
    mask = rng.random((s // 8, r // 8)) < 0.5
    A_np *= np.kron(mask, np.ones((8, 8), np.float32))
    B_np = rng.standard_normal((s, t)).astype(np.float32)
    ell = dense_to_block_ell(A_np, block_size=8)

    for name in names:
        sch = get_scheme(name)
        N = m * n if sch.fixed_workers else max(len(devices), m * n + 2)
        if N > len(devices):
            findings.append(Finding(
                rule="coverage", severity=WARNING, path=path, line=line,
                layer="jaxpr",
                message=f"scheme {name!r}: needs {N} devices, only "
                        f"{len(devices)} visible -- staging skipped (run via "
                        "the CLI, which forces an 8-device host platform)"))
            continue
        try:
            plan = sch.plan(m, n, None if sch.fixed_workers else N, seed=5)
        except ValueError:
            continue  # not device-capable (e.g. mds): nothing to stage
        mesh = compat.make_mesh((plan.num_workers,), ("model",),
                                devices=devices[:plan.num_workers])
        pack = pack_worker_tiles(ell, plan)
        budget = nnz_proportional_budget(plan, pack, s, r, t)
        A = jnp.asarray(A_np)
        B = jnp.asarray(B_np)
        for backend in ("dense_scan", "block_sparse"):
            for out_sharded in (False, True):
                cfg = CodedMatmulConfig(backend=backend,
                                        out_sharded=out_sharded)
                op = from_plan(cfg, plan).bind(mesh)
                kw = {"a_sparse": ell} if backend == "block_sparse" else {}
                closed = jax.make_jaxpr(
                    lambda a, b: op.apply(a, b, **kw))(A, B)
                # max_degree == 1 would make the stacked row count collide
                # with the operands' own (s, ...) shapes: nothing to detect
                findings.extend(verify_staged_program(
                    closed, axis_name="model",
                    stacked_rows=(plan.max_degree * s
                                  if backend == "block_sparse"
                                  and plan.max_degree > 1 else None),
                    byte_budget=(budget if backend == "block_sparse"
                                 else None),
                    context=(f"scheme={name} backend={backend} "
                             f"out_sharded={out_sharded}")))
                programs += 1
                if backend != "block_sparse" or out_sharded:
                    continue
                # one-launch contract: re-stage on the TPU kernel lane (the
                # pallas_call appears in the trace regardless of the host
                # platform; nothing executes) and prove the decode combine
                # lives in the kernel epilogue, not as a separate launch
                import os

                prev = os.environ.get("REPRO_KERNEL_LANE")
                os.environ["REPRO_KERNEL_LANE"] = "tpu"
                try:
                    closed_k = jax.make_jaxpr(
                        lambda a, b: op.apply(a, b, **kw))(A, B)
                finally:
                    if prev is None:
                        del os.environ["REPRO_KERNEL_LANE"]
                    else:
                        os.environ["REPRO_KERNEL_LANE"] = prev
                findings.extend(verify_fused_epilogue(
                    closed_k, mn=m * n, br=br,
                    context=(f"scheme={name} backend={backend} "
                             "lane=tpu")))
                programs += 1
    return findings, programs
