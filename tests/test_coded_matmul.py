import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.coded import CodedMatmulConfig, from_plan
from repro.core.coded_matmul import (
    BACKENDS,
    CodedMatmulPlan,
    make_plan,
    pack_worker_tiles,
    uncoded_matmul_reference,
)
from repro.core.decoder import DecodingError
from repro.sparse import dense_to_block_ell


def _bound_op(plan, mesh, **cfg_kw):
    return from_plan(CodedMatmulConfig(**cfg_kw), plan).bind(mesh)


def _mesh_1d(name="model"):
    devs = jax.devices()
    return jax.make_mesh((len(devs),), (name,))


def test_make_plan_full_rank_and_padded():
    plan = make_plan(2, 2, num_workers=8, seed=0)
    assert plan.cols.shape == plan.weights.shape == (8, plan.max_degree)
    M = np.zeros((8, 4))
    for k in range(8):
        for l in range(plan.max_degree):
            if plan.weights[k, l] != 0:
                M[k, plan.cols[k, l]] += plan.weights[k, l]
    assert np.linalg.matrix_rank(M) == 4
    # decode really is a left inverse
    np.testing.assert_allclose(plan.decode @ M, np.eye(4), atol=1e-4)


def test_coded_matmul_single_device_mn1():
    # on the single default device only mn=1 is codable (N=1 row spans 1 block)
    mesh = _mesh_1d()
    plan = make_plan(1, 1, num_workers=mesh.shape["model"], max_degree=1, seed=3)
    rng = np.random.default_rng(0)
    s, r, t = 24, 8, 12
    A = jnp.asarray(rng.standard_normal((s, r)), jnp.float32)
    B = jnp.asarray(rng.standard_normal((s, t)), jnp.float32)
    C = _bound_op(plan, mesh)(A, B)
    C_ref = uncoded_matmul_reference(A, B)
    np.testing.assert_allclose(np.asarray(C), np.asarray(C_ref), atol=1e-2, rtol=1e-3)


def test_coded_matmul_spmd_8dev_subprocess():
    """Full SPMD check on an 8-device mesh (subprocess so the main pytest
    process keeps the default single-device platform)."""
    import pathlib
    import subprocess
    import sys

    script = pathlib.Path(__file__).parent / "spmd_coded_matmul_check.py"
    env = dict(os.environ, PYTHONPATH=str(pathlib.Path(__file__).parents[1] / "src"))
    # the check grew the partial-chunk survivor axis (extra shard_map
    # compilations per plan), so give it headroom beyond the historical 600
    out = subprocess.run([sys.executable, str(script)], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "ALL-OK" in out.stdout


def test_coded_matmul_single_device_block_sparse():
    # the block_sparse backend must agree with dense_scan on the trivial
    # single-device code too (mn=1, one worker, bs=8 tiles)
    mesh = _mesh_1d()
    plan = make_plan(1, 1, num_workers=mesh.shape["model"], max_degree=1, seed=3)
    rng = np.random.default_rng(1)
    s, r, t = 24, 16, 12
    A_np = rng.standard_normal((s, r))
    A_np[:, 8:] = 0.0  # one dead column tile column: block sparsity is real
    A = jnp.asarray(A_np, jnp.float32)
    B = jnp.asarray(rng.standard_normal((s, t)), jnp.float32)
    C = _bound_op(plan, mesh, backend="block_sparse")(A, B)
    C_ref = uncoded_matmul_reference(A, B)
    np.testing.assert_allclose(np.asarray(C), np.asarray(C_ref), atol=1e-2, rtol=1e-3)


def test_coded_matmul_out_sharded_matches_replicated_single_device():
    # the scatter decode must agree with the replicated decode bit-for-bit
    # (the 8-device + dead-worker variants live in spmd_coded_matmul_check)
    mesh = _mesh_1d()
    plan = make_plan(1, 1, num_workers=mesh.shape["model"], max_degree=1, seed=3)
    rng = np.random.default_rng(2)
    s, r, t = 24, 16, 12
    A = jnp.asarray(rng.standard_normal((s, r)), jnp.float32)
    B = jnp.asarray(rng.standard_normal((s, t)), jnp.float32)
    for backend in BACKENDS:
        C_rep = _bound_op(plan, mesh, backend=backend)(A, B)
        C_sc = _bound_op(plan, mesh, backend=backend, out_sharded=True)(A, B)
        np.testing.assert_array_equal(np.asarray(C_sc), np.asarray(C_rep))


def test_coded_matmul_accepts_prebuilt_pack():
    # a pack built once (e.g. by the runtime LRU cache) short-circuits
    # re-packing and produces the same result as the a_sparse path
    mesh = _mesh_1d()
    plan = make_plan(1, 1, num_workers=mesh.shape["model"], max_degree=1, seed=3)
    rng = np.random.default_rng(4)
    s, r, t = 32, 16, 12
    A_np = rng.standard_normal((s, r)).astype(np.float32)
    A = jnp.asarray(A_np)
    B = jnp.asarray(rng.standard_normal((s, t)), jnp.float32)
    ell = dense_to_block_ell(A_np, block_size=8)
    pack = pack_worker_tiles(ell, plan)
    op = _bound_op(plan, mesh, backend="block_sparse")
    C_pack = op(A, B, pack=pack)
    C_ell = op(A, B, a_sparse=ell)
    np.testing.assert_array_equal(np.asarray(C_pack), np.asarray(C_ell))


def test_coded_matmul_rejects_stale_pack():
    # a pack built for a different A must be refused, not silently gathered
    # out of range (XLA clamps indices, which would corrupt the result)
    mesh = _mesh_1d()
    plan = make_plan(1, 1, num_workers=mesh.shape["model"], max_degree=1, seed=3)
    rng = np.random.default_rng(5)
    A_big = rng.standard_normal((64, 16)).astype(np.float32)
    pack = pack_worker_tiles(dense_to_block_ell(A_big, block_size=8), plan)
    A = jnp.asarray(rng.standard_normal((32, 16)), jnp.float32)   # shorter s
    B = jnp.asarray(rng.standard_normal((32, 12)), jnp.float32)
    op = _bound_op(plan, mesh, backend="block_sparse")
    with pytest.raises(ValueError, match="different A"):
        op(A, B, pack=pack)
    # wrong output tiling (r mismatch) is also refused
    A2 = jnp.asarray(rng.standard_normal((64, 32)), jnp.float32)
    B2 = jnp.asarray(rng.standard_normal((64, 12)), jnp.float32)
    with pytest.raises(ValueError, match="does not tile"):
        op(A2, B2, pack=pack)


def test_coded_matmul_rejects_unknown_backend():
    # the config is the validation point now: an unknown backend never
    # reaches staging (and the registry snapshot still lists the builtins)
    with pytest.raises(ValueError, match="backend"):
        CodedMatmulConfig(backend="nope")
    assert set(BACKENDS) == {"dense_scan", "block_sparse", "auto"}


def test_pack_worker_tiles_counts_live_tiles():
    # packing is nnz-proportional: an all-zero A packs zero live tiles, a
    # dense A packs (live blocks of A) x (slots with nonzero weight)
    plan = make_plan(2, 2, num_workers=8, seed=0)
    s, r = 16, 16
    ell0 = dense_to_block_ell(np.zeros((s, r)), block_size=8)
    p0 = pack_worker_tiles(ell0, plan)
    assert p0.live_tiles.sum() == 0
    ell1 = dense_to_block_ell(np.ones((s, r)), block_size=8)
    p1 = pack_worker_tiles(ell1, plan)
    live_slots = (plan.weights != 0).sum()
    # per live slot: one column group of A = (s/8) x (br/8) = 2 x 1 tiles
    assert p1.live_tiles.sum() == live_slots * 2
    assert p1.vals.shape[0] == plan.num_workers


def test_coded_matmul_survivor_refusal():
    plan = make_plan(2, 2, num_workers=6, seed=1)
    dead = np.zeros(6, dtype=bool)  # everyone dead
    with pytest.raises(ValueError):
        plan.with_survivors(dead)
    # the specific failure is a DecodingError (which IS a ValueError), with
    # the rank deficit spelled out
    with pytest.raises(DecodingError, match="rank"):
        plan.with_survivors(dead)
    # a wrong-length mask is a plain usage error
    with pytest.raises(ValueError, match="entries"):
        plan.with_survivors(np.ones(4, dtype=bool))


def _kill_k_keeping_rank(plan, k_dead, seed=0):
    """A survivor mask with k_dead dead workers that keeps M full rank."""
    M = plan.coefficient_matrix()
    d = plan.m * plan.n
    rng = np.random.default_rng(seed)
    for _ in range(200):
        surv = np.ones(plan.num_workers, dtype=bool)
        surv[rng.choice(plan.num_workers, size=k_dead, replace=False)] = False
        if np.linalg.matrix_rank(M * surv[:, None]) >= d:
            return surv
    pytest.skip(f"no full-rank mask with {k_dead} dead workers for this plan")


@pytest.mark.parametrize("k_dead", [1, 2])
def test_with_survivors_decodes_with_dead_workers(k_dead):
    # decode correctness with 1 and 2 dead workers: the re-derived decode
    # matrix must stay an exact left inverse of the masked coefficient rows
    plan = make_plan(2, 2, num_workers=12, seed=4)
    surv = _kill_k_keeping_rank(plan, k_dead)
    p2 = plan.with_survivors(surv)
    M_surv = plan.coefficient_matrix() * surv[:, None]
    np.testing.assert_allclose(p2.decode @ M_surv, np.eye(4), atol=1e-4)
    # dead workers' columns of the decode matrix are irrelevant: their
    # contributions are zeroed on device, so D[:, dead] @ anything must not
    # be needed -- verify decode applied to masked synthetic results is exact
    rng = np.random.default_rng(1)
    blocks = rng.standard_normal((4, 3, 5))
    results = np.einsum("kc,cij->kij", M_surv, blocks)
    np.testing.assert_allclose(
        np.einsum("ck,kij->cij", p2.decode, results), blocks, atol=1e-6)


def test_with_survivors_all_alive_is_identity_plan():
    plan = make_plan(2, 2, num_workers=8, seed=2)
    assert plan.with_survivors(np.ones(8, dtype=bool)) is plan


def test_with_survivors_still_decodes():
    # drop workers one at a time until rank breaks; every surviving plan must
    # still be an exact left-inverse
    plan = make_plan(2, 2, num_workers=8, seed=2)
    M = np.zeros((8, 4))
    for k in range(8):
        for l in range(plan.max_degree):
            if plan.weights[k, l] != 0:
                M[k, plan.cols[k, l]] += plan.weights[k, l]
    surv = np.ones(8, dtype=bool)
    rng = np.random.default_rng(0)
    for kill in rng.permutation(8)[:4]:
        surv2 = surv.copy()
        surv2[kill] = False
        if np.linalg.matrix_rank(M * surv2[:, None]) < 4:
            continue
        p2 = plan.with_survivors(surv2)
        np.testing.assert_allclose(p2.decode @ (M * surv2[:, None]), np.eye(4), atol=1e-4)
        surv = surv2
