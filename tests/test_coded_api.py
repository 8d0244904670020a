"""Tests for the repro.coded API redesign: scheme registry round-trips,
CodedMatmulConfig validation, CodedOp lifecycle, legacy-shim parity and
deprecation.  (The 8-device parity acceptance matrix lives in
spmd_coded_matmul_check.py; everything here runs on the default single
device.)"""

import dataclasses
import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import repro
from repro.coded import (
    CodedMatmulConfig,
    from_plan,
    get_scheme,
    plan as plan_op,
    register_scheme,
    scheme_names,
)
from repro.core import schemes as schemes_lib
from repro.core.coded_matmul import coded_matmul, make_plan, uncoded_matmul_reference
from repro.core.decoder import DecodingError
from repro.sparse import dense_to_block_ell


def _mesh_1d(name="model"):
    return jax.make_mesh((len(jax.devices()),), (name,))


# ------------------------------ scheme registry ------------------------------

def test_every_core_scheme_is_registered():
    # every scheme in core/schemes.py is reachable by name via the registry
    assert set(schemes_lib.SCHEMES) == set(scheme_names())


@pytest.mark.parametrize("name", sorted(schemes_lib.SCHEMES))
def test_registry_roundtrip_builds_decodable_instance(name):
    m, n, N = 2, 3, 18
    sch = get_scheme(name)
    inst = sch.instance(m, n, None if name == "uncoded" else N, seed=0)
    workers = list(range(inst.num_workers))
    assert inst.can_decode(workers), f"{name}: not decodable with all workers"
    assert inst.mn == m * n


@pytest.mark.parametrize("name", ["uncoded", "sparse_code", "lt_code",
                                  "sparse_mds", "polynomial", "product"])
def test_registry_builds_device_plan_with_left_inverse_decode(name):
    m, n = 2, 2
    sch = get_scheme(name)
    p = sch.plan(m, n, None if name == "uncoded" else 12, seed=0)
    M = p.coefficient_matrix()
    assert np.linalg.matrix_rank(M) == m * n
    np.testing.assert_allclose(p.decode @ M, np.eye(m * n), atol=1e-4)


def test_mds_scheme_has_no_device_plan():
    # mds assigns n generator rows per worker: no one-row-per-device mapping
    with pytest.raises(ValueError, match="multiple generator rows"):
        get_scheme("mds").plan(2, 2, 8)
    assert not get_scheme("mds").device_capable(2, 2, 8)
    assert get_scheme("sparse_code").device_capable(2, 2, 8)


def test_host_and_device_share_one_design():
    # the plan's coefficient matrix IS the instance's generator matrix (up
    # to lockstep degree truncation) when built from the same seed -- the
    # silent-disagreement failure mode the registry exists to kill
    m, n, N, seed = 2, 3, 16, 4
    sch = get_scheme("sparse_code")
    p = sch.plan(m, n, N, seed=seed, max_degree=m * n)  # no truncation
    inst = sch.instance(m, n, N, seed=p.spec.seed)      # the accepted resample
    np.testing.assert_allclose(p.coefficient_matrix(), inst.M.toarray(),
                               atol=1e-6)


def test_unknown_scheme_rejected_with_known_names():
    with pytest.raises(ValueError, match="sparse_code"):
        get_scheme("nope")


def test_register_scheme_decorator_and_config_pickup():
    name = "_test_identity_code"
    try:
        @register_scheme(name, fixed_workers=True)
        def _identity(m, n):
            return schemes_lib.uncoded(m, n)

        assert name in scheme_names()
        cfg = CodedMatmulConfig(scheme=name)   # registry-validated
        op = plan_op(cfg, 1, 1).bind(_mesh_1d())
        A = jnp.asarray(np.ones((8, 4)), jnp.float32)
        B = jnp.asarray(np.ones((8, 4)), jnp.float32)
        np.testing.assert_allclose(np.asarray(op(A, B)),
                                   np.asarray(uncoded_matmul_reference(A, B)),
                                   atol=1e-5)
    finally:
        from repro.coded import registry as registry_mod
        registry_mod._REGISTRY.pop(name, None)


# ----------------------------- CodedMatmulConfig -----------------------------

def test_config_validates_against_registries_at_construction():
    with pytest.raises(ValueError, match="backend"):
        CodedMatmulConfig(backend="csr")
    with pytest.raises(ValueError, match="scheme"):
        CodedMatmulConfig(scheme="csr")
    with pytest.raises(ValueError, match="block_size"):
        CodedMatmulConfig(block_size=0)
    with pytest.raises(ValueError, match="axis_name"):
        CodedMatmulConfig(axis_name="")


def test_config_normalizes_dtype_spellings():
    for spelling in ("float32", np.float32, jnp.float32, "f4"):
        assert CodedMatmulConfig(out_dtype=spelling).out_dtype == "float32"
    assert CodedMatmulConfig(out_dtype=jnp.bfloat16).out_dtype == "bfloat16"
    # frozen + normalized => usable as a dict key / hashable
    assert hash(CodedMatmulConfig()) == hash(CodedMatmulConfig(out_dtype="f4"))


def test_config_rejects_float64_spellings():
    # the analysis dtype-policy pass would flag a staged f64 program; the
    # config rejects every spelling of it at construction instead
    for spelling in ("float64", np.float64, "f8", "double", float):
        with pytest.raises(ValueError, match="f32-accumulated"):
            CodedMatmulConfig(out_dtype=spelling)
    with pytest.raises(ValueError, match="f32-accumulated"):
        CodedMatmulConfig(out_dtype="complex128")
    # reduced-precision spellings stay legal
    for ok in ("float16", "bfloat16", "float32"):
        assert CodedMatmulConfig(out_dtype=ok).out_dtype == ok


# --------------------------------- CodedOp -----------------------------------

def test_op_lifecycle_unbound_then_bound():
    p = make_plan(1, 1, num_workers=len(jax.devices()), max_degree=1, seed=3)
    op = from_plan(CodedMatmulConfig(), p)
    assert not op.bound
    A = jnp.zeros((8, 8), jnp.float32)
    with pytest.raises(ValueError, match="unbound"):
        op(A, A)
    bound = op.bind(_mesh_1d())
    assert bound.bound and not op.bound  # frozen: bind returns a new op
    assert "workers=1" in repr(bound)


def test_op_bind_validates_axis():
    p = make_plan(1, 1, num_workers=len(jax.devices()), max_degree=1, seed=3)
    with pytest.raises(ValueError, match="no axis"):
        from_plan(CodedMatmulConfig(axis_name="tp"), p).bind(_mesh_1d("model"))
    p9 = make_plan(2, 2, num_workers=9, seed=0)
    with pytest.raises(ValueError, match="workers"):
        from_plan(CodedMatmulConfig(), p9).bind(_mesh_1d())


def test_op_with_survivors_raises_eagerly_and_resets():
    p = make_plan(2, 2, num_workers=6, seed=1)
    op = from_plan(CodedMatmulConfig(), p)
    with pytest.raises(DecodingError, match="rank"):
        op.with_survivors(np.zeros(6, dtype=bool))   # at rebind, not apply
    # all-alive mask and None both restore the base plan
    assert op.with_survivors(np.ones(6, dtype=bool)).plan_ is p
    assert op.with_survivors(None).plan_ is p


def test_op_strict_about_pack_operands():
    p = make_plan(1, 1, num_workers=len(jax.devices()), max_degree=1, seed=3)
    op = from_plan(CodedMatmulConfig(backend="dense_scan"), p).bind(_mesh_1d())
    A = jnp.zeros((8, 8), jnp.float32)
    ell = dense_to_block_ell(np.zeros((8, 8), np.float32), block_size=8)
    with pytest.raises(ValueError, match="takes no a_sparse/pack"):
        op(A, A, a_sparse=ell)


def test_op_consults_runtime_pack_cache():
    from repro.runtime import pack_cache

    p = make_plan(1, 1, num_workers=len(jax.devices()), max_degree=1, seed=3)
    rng = np.random.default_rng(0)
    A_np = rng.standard_normal((16, 8)).astype(np.float32)
    A = jnp.asarray(A_np)
    B = jnp.asarray(rng.standard_normal((16, 8)), jnp.float32)
    ell = dense_to_block_ell(A_np, block_size=8)
    op = from_plan(CodedMatmulConfig(backend="block_sparse"), p).bind(_mesh_1d())
    pack_cache.clear()
    op(A, B, a_sparse=ell)
    op(A, B, a_sparse=ell)
    stats = pack_cache.cache_stats()
    assert stats["misses"] == 1 and stats["hits"] >= 1
    # survivor rebinds reuse the same pack (keyed on the base plan)
    op.with_survivors(np.ones(p.num_workers, dtype=bool))(A, B, a_sparse=ell)
    assert pack_cache.cache_stats()["misses"] == 1
    pack_cache.clear()


def test_out_dtype_flows_through_op():
    p = make_plan(1, 1, num_workers=len(jax.devices()), max_degree=1, seed=3)
    op = from_plan(CodedMatmulConfig(out_dtype="bfloat16"), p).bind(_mesh_1d())
    A = jnp.asarray(np.ones((8, 4)), jnp.float32)
    assert op(A, A).dtype == jnp.bfloat16


# ------------------------- legacy shim: parity + warning ---------------------

def test_legacy_coded_matmul_emits_deprecation_warning():
    p = make_plan(1, 1, num_workers=len(jax.devices()), max_degree=1, seed=3)
    A = jnp.asarray(np.ones((8, 4)), jnp.float32)
    with pytest.deprecated_call(match="repro.coded"):
        coded_matmul(A, A, p, _mesh_1d())


@pytest.mark.parametrize("backend", ["dense_scan", "block_sparse"])
@pytest.mark.parametrize("out_sharded", [False, True])
def test_old_new_bit_parity_single_device(backend, out_sharded):
    p = make_plan(1, 1, num_workers=len(jax.devices()), max_degree=1, seed=3)
    mesh = _mesh_1d()
    rng = np.random.default_rng(7)
    A_np = rng.standard_normal((24, 16)).astype(np.float32)
    A = jnp.asarray(A_np)
    B = jnp.asarray(rng.standard_normal((24, 12)), jnp.float32)
    ell = dense_to_block_ell(A_np, block_size=8)
    kw = {"a_sparse": ell} if backend == "block_sparse" else {}
    op = from_plan(CodedMatmulConfig(backend=backend, out_sharded=out_sharded),
                   p).bind(mesh)
    C_new = op(A, B, **kw)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        C_old = coded_matmul(A, B, p, mesh, backend=backend,
                             out_sharded=out_sharded, **kw)
    np.testing.assert_array_equal(np.asarray(C_new), np.asarray(C_old))


# ----------------------- auto backend + quantization -------------------------

def _density_ell(density, RB=16, CB=2, bs=8, seed=0):
    """A BlockELL with a controlled live-tile fraction."""
    rng = np.random.default_rng(seed)
    mask = rng.random((RB, CB)) < density
    mask[0, 0] = density > 0  # at least one live tile when density > 0
    A = rng.standard_normal((RB * bs, CB * bs)) * np.kron(mask, np.ones((bs, bs)))
    return A.astype(np.float32), dense_to_block_ell(A.astype(np.float32),
                                                    block_size=bs)


@pytest.mark.parametrize("density,expect", [
    (0.02, "block_sparse"),   # 2%: far under the 0.25 default threshold
    (0.30, "dense_scan"),     # 30%: above it
])
def test_auto_backend_picks_by_measured_density(density, expect):
    p = make_plan(1, 1, num_workers=len(jax.devices()), max_degree=1, seed=3)
    cfg = CodedMatmulConfig(backend="auto")
    op = from_plan(cfg, p).bind(_mesh_1d())
    A_np, ell = _density_ell(density)
    assert abs(ell.density() - density) < 0.15
    chosen, frac, _ = op._auto_backend(None, ell, None, A_np.shape[0])
    assert chosen == expect and abs(frac - ell.density()) < 1e-9
    # and end-to-end through apply: correct numbers either way
    A = jnp.asarray(A_np)
    B = jnp.asarray(np.random.default_rng(1).standard_normal(
        (A_np.shape[0], 12)), jnp.float32)
    C = op.apply(A, B, a_sparse=ell)
    np.testing.assert_allclose(
        np.asarray(C), np.asarray(uncoded_matmul_reference(A, B)),
        atol=5e-2, rtol=1e-3)


def test_auto_backend_threshold_is_configurable():
    p = make_plan(1, 1, num_workers=len(jax.devices()), max_degree=1, seed=3)
    _, ell = _density_ell(0.30)
    mesh = _mesh_1d()
    loose = from_plan(CodedMatmulConfig(backend="auto",
                                        auto_density_threshold=0.9), p).bind(mesh)
    assert loose._auto_backend(None, ell, None, 128)[0] == "block_sparse"
    tight = from_plan(CodedMatmulConfig(backend="auto",
                                        auto_density_threshold=0.01), p).bind(mesh)
    assert tight._auto_backend(None, ell, None, 128)[0] == "dense_scan"
    with pytest.raises(ValueError, match="auto_density_threshold"):
        CodedMatmulConfig(backend="auto", auto_density_threshold=1.5)


def test_auto_backend_concrete_A_and_tracer_rejection():
    p = make_plan(1, 1, num_workers=len(jax.devices()), max_degree=1, seed=3)
    op = from_plan(CodedMatmulConfig(backend="auto"), p).bind(_mesh_1d())
    A_np, _ = _density_ell(0.02)
    # concrete A: density measured by packing it on the spot
    chosen, frac, ell = op._auto_backend(jnp.asarray(A_np), None, None,
                                         A_np.shape[0])
    assert chosen == "block_sparse" and ell is not None
    # traced A with no density side-channel: loud error, not a silent guess
    with pytest.raises(ValueError, match="auto.*under jit|jit needs"):
        jax.jit(lambda a: op._auto_backend(a, None, None, 128))(
            jnp.asarray(A_np))


def test_auto_backend_is_virtual_everywhere_below_the_api():
    from repro.core import coded_backends
    from repro.core.coded_matmul import stage_coded_matmul

    assert coded_backends.get_backend("auto").virtual
    p = make_plan(1, 1, num_workers=len(jax.devices()), max_degree=1, seed=3)
    A = jnp.zeros((8, 8), jnp.float32)
    with pytest.raises(ValueError, match="pseudo-backend"):
        stage_coded_matmul(A, A, p, _mesh_1d(), backend="auto")


def test_compute_dtype_validation_and_cond_budget():
    # unknown dtype and pack-free backend both rejected at construction
    with pytest.raises(ValueError, match="compute_dtype"):
        CodedMatmulConfig(compute_dtype="fp8")
    with pytest.raises(ValueError, match="needs_pack|pack"):
        CodedMatmulConfig(backend="dense_scan", compute_dtype="int8")
    # sparse_code (cond_warn=1e8): eps * cond within the 1e6 budget
    CodedMatmulConfig(scheme="sparse_code", backend="block_sparse",
                      compute_dtype="int8")
    CodedMatmulConfig(scheme="sparse_code", backend="block_sparse",
                      compute_dtype="bfloat16")
    # product (cond_warn=1e11): quantization noise can amplify past budget
    for dt in ("int8", "bfloat16"):
        with pytest.raises(ValueError, match="product.*budget|budget.*product"):
            CodedMatmulConfig(scheme="product", backend="block_sparse",
                              compute_dtype=dt)


def test_quantized_pack_layout_and_cache_key():
    from repro.core.coded_matmul import pack_worker_tiles
    from repro.runtime import pack_cache

    p = make_plan(1, 1, num_workers=len(jax.devices()), max_degree=1, seed=3)
    A_np, ell = _density_ell(0.3, seed=5)
    pk8 = pack_worker_tiles(ell, p, compute_dtype="int8")
    assert pk8.vals.dtype == np.int8 and pk8.compute_dtype == "int8"
    assert pk8.tile_scale is not None
    assert pk8.tile_scale.shape == pk8.vals.shape[:-2]
    pk32 = pack_worker_tiles(ell, p)
    deq = pk8.vals.astype(np.float32) * pk8.tile_scale[..., None, None]
    amax = np.abs(pk32.vals).max()
    assert np.abs(deq - pk32.vals).max() <= amax / 127.0 + 1e-6
    pkbf = pack_worker_tiles(ell, p, compute_dtype="bfloat16")
    assert pkbf.vals.dtype.itemsize == 2 and pkbf.tile_scale is None
    with pytest.raises(ValueError, match="compute_dtype"):
        pack_worker_tiles(ell, p, compute_dtype="fp4")
    # the runtime cache keys on dtype: same (ell, plan) pair, two entries
    pack_cache.clear()
    pack_cache.get_pack(ell, p)
    pack_cache.get_pack(ell, p, compute_dtype="int8")
    pack_cache.get_pack(ell, p, compute_dtype="int8")
    st = pack_cache.cache_stats()
    assert st["misses"] == 2 and st["hits"] == 1
    pack_cache.clear()


@pytest.mark.parametrize("dtype,tol", [("bfloat16", 2e-2), ("int8", 2e-2)])
def test_quantized_coded_matmul_end_to_end(dtype, tol):
    """Quantized block_sparse apply stays within the declared dtype
    tolerance of the f32 result on well-conditioned data."""
    p = make_plan(1, 1, num_workers=len(jax.devices()), max_degree=1, seed=3)
    rng = np.random.default_rng(2)
    A_np, ell = _density_ell(0.4, seed=2)
    A = jnp.asarray(A_np)
    B = jnp.asarray(rng.standard_normal((A_np.shape[0], 12)), jnp.float32)
    mesh = _mesh_1d()
    C32 = from_plan(CodedMatmulConfig(backend="block_sparse"), p).bind(
        mesh).apply(A, B, a_sparse=ell)
    Cq = from_plan(CodedMatmulConfig(backend="block_sparse",
                                     compute_dtype=dtype), p).bind(
        mesh).apply(A, B, a_sparse=ell)
    scale = float(np.abs(np.asarray(C32)).max())
    np.testing.assert_allclose(np.asarray(Cq), np.asarray(C32),
                               atol=tol * scale, rtol=tol)
    # a stale f32 pack is rejected when the config asks for int8
    from repro.core.coded_matmul import pack_worker_tiles

    with pytest.raises(ValueError, match="compute_dtype"):
        from_plan(CodedMatmulConfig(backend="block_sparse",
                                    compute_dtype=dtype), p).bind(mesh).apply(
            A, B, pack=pack_worker_tiles(ell, p))


# ------------------------------ package surface ------------------------------

def test_top_level_exports():
    assert repro.CodedMatmulConfig is CodedMatmulConfig
    assert repro.get_scheme is get_scheme
    assert callable(repro.CodedOp)
    assert callable(repro.run_device_job)
    for name in repro.__all__:
        assert getattr(repro, name) is not None


# ------------------- staging: the program and pack are kept -------------------

_FOUR_DEVICES = r"""
import json, os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, sys.argv[1])
import test_coded_api
print(json.dumps(test_coded_api.cached_and_fresh()))
"""


def _masks(op) -> list:
    """None, each decodable single-loss mask, and one decodable (N, 2)
    chunk-progress mask that leaves a worker's second chunk out."""
    N = op.num_workers
    masks = [None]
    for k in range(N):
        alive = np.ones(N, bool)
        alive[k] = False
        try:
            op.with_survivors(alive)
        except DecodingError:
            continue
        masks.append(alive)
    for k in range(N):
        chunks = np.ones((N, 2), bool)
        chunks[k, 1] = False
        try:
            rebound = op.with_survivors(chunks)
        except DecodingError:
            continue
        if not np.array_equal(rebound.plan_.weights, op.base_plan.weights):
            masks.append(chunks)
            break
    return masks


def cached_and_fresh() -> dict:
    """On four host devices, for each backend and decode layout: whether
    each mask's product from the kept program equals the product staged
    afresh for it bit for bit, and whether the kept program's calls all
    counted ``program_hits``.  The psum layout runs m = 2, n = 1 over N = 4
    workers; the reduce-scatter layout m = 4, n = 1, since it splits the
    mn decoded blocks N ways (with mn = N no single loss decodes)."""
    from repro import obs
    from repro.core.coded_matmul import STAGED

    mesh = jax.make_mesh((4,), ("model",), devices=jax.devices()[:4])
    rng = np.random.default_rng(5)
    A_np = rng.standard_normal((32, 64)).astype(np.float32)
    A_np *= np.kron(rng.random((4, 8)) < 0.5, np.ones((8, 8)))
    out = {}
    for backend in ("dense_scan", "block_sparse"):
        for out_sharded, m in ((False, 2), (True, 4)):
            p = make_plan(m, 1, num_workers=4, seed=4)
            A = jnp.asarray(A_np[:, :16 * m])
            B = jnp.asarray(rng.standard_normal((32, 16)), jnp.float32)
            kw = ({"a_sparse": dense_to_block_ell(A_np[:, :16 * m], 8)}
                  if backend == "block_sparse" else {})
            op = from_plan(CodedMatmulConfig(backend=backend,
                                             out_sharded=out_sharded),
                           p).bind(mesh)
            masks = _masks(op)
            fresh = []
            for mask in masks:
                STAGED.clear()
                fresh.append(np.asarray(op.with_survivors(mask)(A, B, **kw)))
            STAGED.clear()
            op(A, B, **kw).block_until_ready()
            before = obs.RECORDER.counters().get("program_hits", 0)
            kept = [np.asarray(op.with_survivors(mask)(A, B, **kw))
                    for mask in masks]
            hits = obs.RECORDER.counters().get("program_hits", 0) - before
            out[f"{backend}-{out_sharded}"] = {
                "masks": len(masks),
                "chunked": any(m is not None and m.ndim == 2 for m in masks),
                "equal": [bool(np.array_equal(a, b))
                          for a, b in zip(kept, fresh)],
                "hits": hits}
            STAGED.clear()
    return out


@pytest.fixture(scope="module")
def cached_vs_fresh():
    import os
    import pathlib
    import subprocess
    import sys

    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    proc = subprocess.run(
        [sys.executable, "-c", _FOUR_DEVICES,
         str(pathlib.Path(__file__).resolve().parent)],
        env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-4000:]
    import json

    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("backend", ["dense_scan", "block_sparse"])
@pytest.mark.parametrize("out_sharded", [False, True])
def test_a_kept_program_equals_a_fresh_one_under_every_mask(
        cached_vs_fresh, backend, out_sharded):
    """One staged program serves None, every decodable single loss and a
    chunk-progress mask, bit for bit as a program staged afresh for each."""
    got = cached_vs_fresh[f"{backend}-{out_sharded}"]
    assert got["masks"] >= (2 if out_sharded else 3) and got["chunked"], got
    assert all(got["equal"]), got
    assert got["hits"] == got["masks"], got


def _hit_counts(monkeypatch, calls) -> list:
    """Each call's product: "hit" or "miss", from a fresh recorder."""
    from repro import obs

    monkeypatch.setattr(obs, "RECORDER", obs.Recorder())
    for call in calls:
        call().block_until_ready()
    return ["hit" if "program_hits" in s.counts else "miss"
            for s in obs.records() if s.name == obs.PRODUCT]


@pytest.mark.parametrize("change", ["b_width", "pack", "mesh", "layout",
                                    "out_dtype"])
def test_a_new_geometry_misses_once_then_hits(monkeypatch, change):
    from repro.runtime import pack_cache

    p = make_plan(1, 1, num_workers=1, max_degree=1, seed=3)
    rng = np.random.default_rng(4)
    A_np = rng.standard_normal((24, 16)).astype(np.float32)
    A = jnp.asarray(A_np)
    B = jnp.asarray(rng.standard_normal((24, 8)), jnp.float32)
    ell = dense_to_block_ell(A_np, block_size=8)
    mesh = jax.make_mesh((1,), ("model",), devices=jax.devices()[:1])
    op = from_plan(CodedMatmulConfig(backend="block_sparse"), p).bind(mesh)
    kw = kw2 = {"a_sparse": ell}
    B2, op2 = B, op
    if change == "b_width":
        B2 = jnp.concatenate([B, B], axis=1)
    elif change == "pack":
        kw2 = {"a_sparse": dense_to_block_ell(A_np, block_size=8)}
    elif change == "mesh":
        op2 = from_plan(CodedMatmulConfig(backend="block_sparse",
                                          axis_name="workers"), p).bind(
            jax.make_mesh((1,), ("workers",), devices=jax.devices()[:1]))
    else:
        setting = ({"out_sharded": True} if change == "layout"
                   else {"out_dtype": "bfloat16"})
        op2 = from_plan(CodedMatmulConfig(backend="block_sparse", **setting),
                        p).bind(mesh)
    pack_cache.clear()
    try:
        got = _hit_counts(monkeypatch, [lambda: op(A, B, **kw)] * 2
                          + [lambda: op2(A, B2, **kw2)] * 2
                          + [lambda: op(A, B, **kw)])
    finally:
        pack_cache.clear()
    assert got == ["miss", "hit", "miss", "hit", "hit"]


def test_the_pack_caches_clear_and_eviction_release_device_copies():
    """A pack's device copy goes with the pack: an evicted pack that no op
    holds frees it at once; a cleared cache leaves nothing staged."""
    from repro.core.coded_matmul import STAGED
    from repro.runtime import pack_cache

    p = make_plan(1, 1, num_workers=1, max_degree=1, seed=3)
    rng = np.random.default_rng(6)
    A_np = rng.standard_normal((24, 16)).astype(np.float32)
    A = jnp.asarray(A_np)
    B = jnp.asarray(rng.standard_normal((24, 8)), jnp.float32)
    op = from_plan(CodedMatmulConfig(backend="block_sparse"), p).bind(
        jax.make_mesh((1,), ("model",), devices=jax.devices()[:1]))
    pack_cache.clear()
    try:
        ells = [dense_to_block_ell(A_np, block_size=8)
                for _ in range(pack_cache.GLOBAL.max_entries + 1)]
        for ell in ells:
            op(A, B, a_sparse=ell).block_until_ready()
        assert pack_cache.cache_stats()["evictions"] == 1
        assert STAGED.stats() == {
            "programs": 1, "device_packs": pack_cache.GLOBAL.max_entries}
        pack_cache.clear()
        assert STAGED.stats() == {"programs": 0, "device_packs": 0}
    finally:
        pack_cache.clear()


def _packs(count: int, p) -> list:
    """``count`` tile packs of distinct 16 x 8 operands."""
    from repro.core.coded_matmul import pack_worker_tiles

    rng = np.random.default_rng(8)
    return [pack_worker_tiles(dense_to_block_ell(
        rng.standard_normal((16, 8)).astype(np.float32), block_size=8), p)
        for _ in range(count)]


def test_the_staging_caches_stay_bounded():
    from repro.core.coded_matmul import StagedPrograms

    p = make_plan(1, 1, num_workers=1, max_degree=1, seed=3)
    staged = StagedPrograms(max_entries=2)
    sharding = jax.sharding.SingleDeviceSharding(jax.devices()[0])
    packs = _packs(3, p)
    for k, pack in enumerate(packs):
        staged.program(("geometry", k), lambda x: x + 1)
        assert staged.device_pack(pack, sharding)[1] is True     # put
    assert staged.stats() == {"programs": 2, "device_packs": 2}
    assert staged.program(("geometry", 0), lambda x: x)[1] is False  # evicted
    assert staged.device_pack(packs[0], sharding)[1] is True         # evicted
    copies, fresh = staged.device_pack(packs[2], sharding)           # kept
    assert fresh is False
    np.testing.assert_array_equal(np.asarray(copies[0]), packs[2].vals)
    np.testing.assert_array_equal(np.asarray(copies[1]), packs[2].src)
    # a pack that dies takes its copy with it, at once
    del packs[2], pack
    assert staged.stats()["device_packs"] == 1


def test_the_staging_caches_hold_under_threads():
    """More threads than cores, switching often, over more keys and packs
    than the caches keep: each lookup returns what was kept for its own key
    and pack, and the bound holds."""
    import os
    import sys
    import threading

    from repro.core.coded_matmul import StagedPrograms

    p = make_plan(1, 1, num_workers=1, max_degree=1, seed=3)
    staged = StagedPrograms(max_entries=3)
    sharding = jax.sharding.SingleDeviceSharding(jax.devices()[0])
    packs = _packs(5, p)
    programs = [lambda x: x for _ in packs]
    wrong = []

    def work(t):
        for i in range(300):
            k = (t + i) % len(packs)
            try:
                jitted, _ = staged.program(("geometry", k), programs[k])
                copies, _ = staged.device_pack(packs[k], sharding)
            except Exception as e:      # a lost update, reported below
                wrong.append((t, i, repr(e)))
                continue
            if jitted.__wrapped__ is not programs[k] or not (
                    np.array_equal(np.asarray(copies[0]), packs[k].vals)
                    and np.array_equal(np.asarray(copies[1]), packs[k].src)):
                wrong.append((t, i))

    threads = [threading.Thread(target=work, args=(t,))
               for t in range(min((os.cpu_count() or 1) + 2, 16))]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert not wrong
    assert staged.stats() == {"programs": 3, "device_packs": 3}


@pytest.mark.parametrize("out_sharded", [False, True])
def test_under_an_outer_jit_nothing_is_kept(out_sharded):
    """A caller jitting around ``apply`` with ``pack=`` stages as before: the
    product is right, and neither cache holds anything, let alone a tracer."""
    from repro.core.coded_matmul import STAGED
    from repro.runtime import pack_cache

    p = make_plan(1, 1, num_workers=1, max_degree=1, seed=3)
    rng = np.random.default_rng(9)
    A_np = rng.standard_normal((24, 16)).astype(np.float32)
    B_np = rng.standard_normal((24, 8)).astype(np.float32)
    op = from_plan(CodedMatmulConfig(backend="block_sparse",
                                     out_sharded=out_sharded), p).bind(
        jax.make_mesh((1,), ("model",), devices=jax.devices()[:1]))
    pack = op.pack_for(dense_to_block_ell(A_np, block_size=8),
                       use_cache=False)
    pack_cache.clear()
    try:
        C = jax.jit(lambda a, b: op.apply(a, b, pack=pack))(
            jnp.asarray(A_np), jnp.asarray(B_np))
        np.testing.assert_allclose(np.asarray(C), A_np.T @ B_np,
                                   rtol=1e-5, atol=1e-5)
        assert STAGED.stats() == {"programs": 0, "device_packs": 0}
        # and a concrete call after it keeps device arrays, not tracers
        op.apply(jnp.asarray(A_np), jnp.asarray(B_np), pack=pack)
        _, copies = next(iter(STAGED._packs.values()))
        assert len(copies) == 2 and not any(
            isinstance(a, jax.core.Tracer) for a in copies)
    finally:
        pack_cache.clear()
