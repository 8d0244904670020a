"""Pallas kernel validation: shape/dtype sweeps against the pure-jnp oracles,
plus hypothesis property sweeps.  Kernels run in interpret mode on CPU.

hypothesis is an optional test dependency (requirements-test.txt): without
it the property sweeps skip but collection -- and the deterministic sweeps
-- still run (so `pytest -x` never hard-fails on the import)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

try:
    from hypothesis import HealthCheck, given, settings, strategies as st
except ModuleNotFoundError:  # property sweeps skip; see module docstring
    given = settings = st = HealthCheck = None

from repro.kernels import ops
from repro.kernels.ref import coded_accum_ref, spmm_block_fused_ref, spmm_block_ref
from repro.sparse import BlockELL, block_ell_to_dense, dense_to_block_ell

if given is not None:
    SETTINGS = dict(max_examples=10, deadline=None,
                    suppress_health_check=[HealthCheck.too_slow])


# ----------------------------- coded_accum --------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("m,n,s,r,t,L", [
    (2, 2, 128, 16, 24, 3),
    (2, 2, 256, 32, 32, 5),
    (4, 2, 128, 32, 16, 7),
    (1, 4, 128, 8, 32, 2),
    (3, 3, 384, 24, 36, 4),
])
def test_coded_accum_sweep(dtype, m, n, s, r, t, L):
    rng = np.random.default_rng(hash((m, n, s, r, t, L)) % 2**31)
    A = jnp.asarray(rng.standard_normal((s, r)), dtype)
    B = jnp.asarray(rng.standard_normal((s, t)), dtype)
    cols = jnp.asarray(rng.integers(0, m * n, size=L), jnp.int32)
    w = rng.standard_normal(L).astype(np.float32)
    w[-1] = 0.0  # exercise padding semantics
    w = jnp.asarray(w)
    got = ops.coded_accum(A, B, cols, w, m=m, n=n, s_chunk=128)
    want = coded_accum_ref(A, B, cols, w, m=m, n=n)
    atol = 5e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=atol, rtol=1e-2)


if given is not None:
    @given(data=st.data())
    @settings(**SETTINGS)
    def test_coded_accum_property(data):
        m = data.draw(st.integers(1, 3))
        n = data.draw(st.integers(1, 3))
        L = data.draw(st.integers(1, 6))
        s = 128 * data.draw(st.integers(1, 2))
        br = 8 * data.draw(st.integers(1, 3))
        bt = 8 * data.draw(st.integers(1, 3))
        seed = data.draw(st.integers(0, 10_000))
        rng = np.random.default_rng(seed)
        A = jnp.asarray(rng.standard_normal((s, m * br)), jnp.float32)
        B = jnp.asarray(rng.standard_normal((s, n * bt)), jnp.float32)
        cols = jnp.asarray(rng.integers(0, m * n, size=L), jnp.int32)
        w = jnp.asarray(rng.standard_normal(L), jnp.float32)
        got = ops.coded_accum(A, B, cols, w, m=m, n=n)
        want = coded_accum_ref(A, B, cols, w, m=m, n=n)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-3, rtol=1e-3)


# ----------------------------- spmm_block ---------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("bs,RB,CB,t,density", [
    (8, 4, 4, 128, 0.3),
    (8, 8, 2, 256, 0.1),
    (16, 4, 4, 128, 0.5),
    (8, 2, 8, 128, 0.9),
])
def test_spmm_block_sweep(dtype, bs, RB, CB, t, density):
    rng = np.random.default_rng(hash((bs, RB, CB, t)) % 2**31)
    # build a block-sparse A directly
    mask = rng.random((RB, CB)) < density
    A = rng.standard_normal((RB * bs, CB * bs)) * np.kron(mask, np.ones((bs, bs)))
    ell = dense_to_block_ell(A, block_size=bs)
    B = jnp.asarray(rng.standard_normal((RB * bs, t)), dtype)
    vals = jnp.asarray(ell.vals, dtype)
    idx = jnp.asarray(ell.idx)
    got = ops.spmm_block(vals, idx, B, t_tile=128)
    want = spmm_block_ref(vals, idx, B, out_rows=CB * bs)
    atol = 2e-1 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=atol, rtol=1e-2)
    # and against the dense oracle via the format round-trip
    dense = block_ell_to_dense(ell)
    want_dense = dense.T @ np.asarray(B, np.float64)
    np.testing.assert_allclose(np.asarray(got), want_dense,
                               atol=atol * 10, rtol=5e-2)


if given is not None:
    @given(data=st.data())
    @settings(**SETTINGS)
    def test_spmm_block_property(data):
        bs = data.draw(st.sampled_from([8, 16]))
        RB = data.draw(st.integers(1, 4))
        CB = data.draw(st.integers(1, 4))
        t = 128
        density = data.draw(st.floats(0.0, 1.0))
        seed = data.draw(st.integers(0, 10_000))
        rng = np.random.default_rng(seed)
        mask = rng.random((RB, CB)) < density
        A = rng.standard_normal((RB * bs, CB * bs)) * np.kron(mask, np.ones((bs, bs)))
        ell = dense_to_block_ell(A, block_size=bs)
        B = jnp.asarray(rng.standard_normal((RB * bs, t)), jnp.float32)
        got = ops.spmm_block(jnp.asarray(ell.vals, jnp.float32), jnp.asarray(ell.idx), B)
        want = np.asarray(block_ell_to_dense(ell)).T @ np.asarray(B)
        np.testing.assert_allclose(np.asarray(got), want, atol=1e-3, rtol=1e-3)


def test_spmm_block_auto_interpret_matches_ref_on_cpu():
    """interpret=None auto-selects from the backend: off-TPU (this CPU
    container) the kernel must run interpreted and match the jnp oracle."""
    from repro.kernels.spmm_block import resolve_interpret

    assert jax.default_backend() != "tpu"
    assert resolve_interpret() is True
    assert resolve_interpret(False) is False  # explicit arg still wins
    rng = np.random.default_rng(7)
    bs, RB, CB, t = 8, 4, 3, 128
    mask = rng.random((RB, CB)) < 0.4
    A = rng.standard_normal((RB * bs, CB * bs)) * np.kron(mask, np.ones((bs, bs)))
    ell = dense_to_block_ell(A, block_size=bs)
    B = jnp.asarray(rng.standard_normal((RB * bs, t)), jnp.float32)
    vals = jnp.asarray(ell.vals, jnp.float32)
    idx = jnp.asarray(ell.idx)
    got = ops.spmm_block(vals, idx, B)          # interpret unspecified
    want = spmm_block_ref(vals, idx, B, out_rows=CB * bs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-4, rtol=1e-4)


# --------------------------- spmm_block_fused ------------------------------

def _random_fused_operands(rng, bs, CB, L, s, n, bt, zero_slots=1):
    vals = rng.standard_normal((CB, L, bs, bs)).astype(np.float32)
    src = np.stack([rng.integers(0, s // bs, (CB, L)),
                    rng.integers(0, n, (CB, L))], axis=-1).astype(np.int32)
    w = rng.standard_normal((CB, L)).astype(np.float32)
    if zero_slots:  # exercise padded-slot semantics: weight 0 kills the tile
        w[:, -zero_slots:] = 0.0
    B = rng.standard_normal((s, n * bt)).astype(np.float32)
    return vals, src, w, B


@pytest.mark.parametrize("bs", [8, 16])
@pytest.mark.parametrize("CB,L,s,n,bt", [
    (4, 3, 64, 2, 128),    # degree-ish L small, t_tile == bt
    (2, 7, 32, 3, 24),     # ragged bt (t_tile == 24), higher degree
    (3, 1, 48, 1, 32),     # single slot, single column group
])
def test_spmm_block_fused_sweep(bs, CB, L, s, n, bt):
    rng = np.random.default_rng(hash((bs, CB, L, s, n, bt)) % 2**31)
    vals, src, w, B = _random_fused_operands(rng, bs, CB, L, s, n, bt)
    want = spmm_block_fused_ref(jnp.asarray(vals), jnp.asarray(src),
                                jnp.asarray(w), jnp.asarray(B), bt)
    # dense einsum oracle: scatter the pack back to a dense stacked product
    dense_want = np.zeros((CB * bs, bt), np.float32)
    B4 = B.reshape(s // bs, bs, n, bt)
    for cb in range(CB):
        for l in range(L):
            brows = B4[src[cb, l, 0], :, src[cb, l, 1], :]
            dense_want[cb * bs:(cb + 1) * bs] += w[cb, l] * np.einsum(
                "io,it->ot", vals[cb, l], brows)
    np.testing.assert_allclose(np.asarray(want), dense_want, atol=1e-4, rtol=1e-3)
    # XLA gather path (the off-TPU default)
    got = ops.spmm_block_fused(jnp.asarray(vals), jnp.asarray(src),
                               jnp.asarray(w), jnp.asarray(B), bt=bt)
    np.testing.assert_allclose(np.asarray(got), dense_want, atol=1e-4, rtol=1e-3)
    # Pallas kernel body (interpreter), including the scalar-prefetched
    # weight and the two-level (row-block, column-group) index map
    got_pl = ops.spmm_block_fused(jnp.asarray(vals), jnp.asarray(src),
                                  jnp.asarray(w), jnp.asarray(B), bt=bt,
                                  t_tile=bt, interpret=True)
    np.testing.assert_allclose(np.asarray(got_pl), dense_want,
                               atol=1e-4, rtol=1e-3)


def test_spmm_block_fused_matches_packed_coded_product():
    """End-to-end over a real pack: the fused kernel on pack_worker_tiles
    output equals the worker's coded combination sum_l w_l A_{i_l}^T B_{j_l}
    computed densely, across every worker and degree the plan sampled."""
    from repro.core.coded_matmul import make_plan, pack_worker_tiles

    rng = np.random.default_rng(11)
    plan = make_plan(2, 2, num_workers=8, seed=1)
    s, r, t, bs = 32, 32, 24, 8
    m, n = 2, 2
    br, bt = r // m, t // n
    mask = rng.random((s // bs, r // bs)) < 0.6
    A = rng.standard_normal((s, r)) * np.kron(mask, np.ones((bs, bs)))
    B = rng.standard_normal((s, t)).astype(np.float32)
    ell = dense_to_block_ell(A.astype(np.float32), block_size=bs)
    pack = pack_worker_tiles(ell, plan)
    for k in range(plan.num_workers):
        got = ops.spmm_block_fused(
            jnp.asarray(pack.vals[k]), jnp.asarray(pack.src[k]),
            jnp.asarray(pack.wslot[k]), jnp.asarray(B), bt=bt)
        want = np.zeros((br, bt), np.float32)
        for l in range(plan.max_degree):
            wgt = plan.weights[k, l]
            if wgt == 0.0:
                continue
            i, j = divmod(int(plan.cols[k, l]), n)
            want += wgt * (A[:, i * br:(i + 1) * br].T
                           @ B[:, j * bt:(j + 1) * bt])
        np.testing.assert_allclose(np.asarray(got), want, atol=1e-3, rtol=1e-3)


# ----------------------- spmm_block_fused_decode ---------------------------
#
# The one-launch kernel: decode combine folded into the epilogue.  Parity is
# defined PER LANE -- the fused kernel must be bit-identical to the two-step
# composition (same-lane local product, then dvec[:, None, None] * C~[None])
# because both run the identical accumulation order; across lanes only
# allclose holds (einsum vs sequential slot accumulation reassociate).

LANES = ["xla", "tpu"]


def _fused_decode_case(seed=0, bs=8, CB=4, L=3, s=64, n=2, bt=128, mn=4):
    rng = np.random.default_rng(seed)
    vals, src, w, B = _random_fused_operands(rng, bs, CB, L, s, n, bt)
    dvec = rng.standard_normal(mn).astype(np.float32)
    return (jnp.asarray(vals), jnp.asarray(src), jnp.asarray(w),
            jnp.asarray(dvec), jnp.asarray(B))


@pytest.mark.parametrize("lane", LANES)
def test_fused_decode_bitwise_vs_two_step_per_lane(lane):
    vals, src, w, dvec, B = _fused_decode_case()
    # the tpu lane's two-step reference must run the SAME Pallas kernel
    # body (interpreted on this CPU box), not the XLA fallback the internal
    # policy would pick off-TPU -- bitwise parity is per accumulation order
    Ct = ops.spmm_block_fused(vals, src, w, B, bt=128, lane=lane,
                              interpret=True if lane == "tpu" else None)
    want = np.asarray(dvec)[:, None, None] * np.asarray(Ct)[None]
    got = ops.spmm_block_fused_decode(vals, src, w, dvec, B, bt=128, lane=lane)
    assert got.shape == (len(dvec), Ct.shape[0], Ct.shape[1])
    np.testing.assert_array_equal(np.asarray(got), want,
                                  err_msg=f"lane={lane} fused != two-step")


@pytest.mark.parametrize("lane", LANES)
def test_fused_decode_lanes_agree_allclose(lane):
    vals, src, w, dvec, B = _fused_decode_case(seed=5)
    ref = ops.spmm_block_fused_decode(vals, src, w, dvec, B, bt=128, lane="xla")
    got = ops.spmm_block_fused_decode(vals, src, w, dvec, B, bt=128, lane=lane)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("bt,t_tile", [(24, 24), (40, 8)])
def test_fused_decode_non_multiple_t_tile_shapes(bt, t_tile):
    # bt not a multiple of 128: the tpu-lane kernel body (interpreted here)
    # must still tile correctly
    vals, src, w, dvec, B = _fused_decode_case(seed=9, s=32, n=3, bt=bt)
    ref = ops.spmm_block_fused_decode(vals, src, w, dvec, B, bt=bt, lane="xla")
    got = ops.spmm_block_fused_decode(vals, src, w, dvec, B, bt=bt,
                                      t_tile=t_tile, lane="tpu")
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_decode_dtype_sweep(dtype):
    """bf16 tiles flow through every lane within bf16 tolerance of the f32
    result (tiles are upcast to f32 inside the kernels; the error budget is
    the bf16 storage rounding of vals, eps = 2**-8)."""
    vals, src, w, dvec, B = _fused_decode_case(seed=13)
    ref = np.asarray(ops.spmm_block_fused_decode(vals, src, w, dvec, B,
                                                 bt=128, lane="xla"))
    vq = vals.astype(dtype)
    scale = float(np.abs(ref).max())
    for lane in LANES:
        got = ops.spmm_block_fused_decode(vq, src, w, dvec, B, bt=128,
                                          lane=lane)
        atol = 1e-6 if dtype == jnp.float32 else 2 ** -8 * 4 * scale
        np.testing.assert_allclose(np.asarray(got), ref, atol=atol, rtol=2e-2)


def test_fused_decode_survivor_rebind_pack():
    """Over a real pack under a survivor rebind: the fused kernel fed the
    rebound plan's gathered weights and decode column equals the dense
    per-worker decode-weighted coded product."""
    from repro.core.coded_matmul import make_plan, pack_worker_tiles

    rng = np.random.default_rng(21)
    plan = make_plan(2, 2, num_workers=8, seed=4)
    surv = np.ones(8, dtype=bool)
    surv[3] = False
    rplan = plan.with_survivors(surv)
    s, r, t, bs = 32, 32, 24, 8
    m, n = 2, 2
    br, bt = r // m, t // n
    mask = rng.random((s // bs, r // bs)) < 0.6
    A = rng.standard_normal((s, r)) * np.kron(mask, np.ones((bs, bs)))
    B = rng.standard_normal((s, t)).astype(np.float32)
    ell = dense_to_block_ell(A.astype(np.float32), block_size=bs)
    pack = pack_worker_tiles(ell, plan)  # packs survive rebinds unchanged
    for k in range(rplan.num_workers):
        dcol = rplan.decode[:, k].astype(np.float32) * float(surv[k])
        got = ops.spmm_block_fused_decode(
            jnp.asarray(pack.vals[k]), jnp.asarray(pack.src[k]),
            jnp.asarray(pack.wslot[k]), jnp.asarray(dcol), jnp.asarray(B),
            bt=bt)
        Ct = np.zeros((br, bt), np.float32)
        for l in range(rplan.max_degree):
            wgt = rplan.weights[k, l]
            if wgt == 0.0:
                continue
            i, j = divmod(int(rplan.cols[k, l]), n)
            Ct += wgt * (A[:, i * br:(i + 1) * br].T
                         @ B[:, j * bt:(j + 1) * bt]).astype(np.float32)
        want = dcol[:, None, None] * Ct[None]
        np.testing.assert_allclose(np.asarray(got), want, atol=1e-3, rtol=1e-3)


def test_resolve_lane_precedence(monkeypatch):
    from repro.kernels.spmm_block import resolve_lane

    monkeypatch.delenv("REPRO_KERNEL_LANE", raising=False)
    monkeypatch.delenv("REPRO_PALLAS_INTERPRET", raising=False)
    assert jax.default_backend() not in ("tpu", "gpu")
    assert resolve_lane() == "xla"                 # backend default on CPU
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    assert resolve_lane() == "tpu"                 # interpret opt-in
    monkeypatch.setenv("REPRO_KERNEL_LANE", "xla")
    assert resolve_lane() == "xla"                 # env beats interpret
    assert resolve_lane("tpu") == "tpu"            # explicit arg beats env
    monkeypatch.setenv("REPRO_KERNEL_LANE", "cuda")
    with pytest.raises(ValueError, match="cuda"):
        resolve_lane()
    with pytest.raises(ValueError, match="not in"):
        resolve_lane("metal")


def test_lane_policy_raises_off_cpu_and_tpu(monkeypatch):
    """On a backend that is neither CPU nor TPU the lane and interpret
    policies raise instead of silently interpreting or taking the XLA lane."""
    from repro.kernels import spmm_block

    monkeypatch.delenv("REPRO_KERNEL_LANE", raising=False)
    monkeypatch.delenv("REPRO_PALLAS_INTERPRET", raising=False)
    monkeypatch.setattr(spmm_block.jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="gpu"):
        spmm_block.resolve_lane()
    with pytest.raises(RuntimeError, match="gpu"):
        spmm_block.resolve_interpret()
    monkeypatch.setattr(spmm_block.jax, "default_backend", lambda: "tpu")
    assert spmm_block.resolve_lane() == "tpu"
    assert spmm_block.resolve_interpret() is False


def test_oversized_slot_table_raises_before_compile():
    """bs = 8 at r = s = 16384 packs 2048 column blocks; at 80 slots each
    the flat slot tables need more SMEM than a v5e core has, so staging the
    TPU kernel raises (naming block_size) before anything compiles."""
    from repro.kernels.spmm_block import (
        SMEM_PREFETCH_BYTES, _spmm_block_fused_decode_pallas)

    CB, L, bs, s, bt = 2048, 80, 8, 16384, 128
    args = (jax.ShapeDtypeStruct((CB, L, bs, bs), jnp.float32),
            jax.ShapeDtypeStruct((CB, L, 2), jnp.int32),
            jax.ShapeDtypeStruct((CB, L), jnp.float32),
            jax.ShapeDtypeStruct((1,), jnp.float32),
            jax.ShapeDtypeStruct((s, bt), jnp.float32))
    with pytest.raises(ValueError, match=rf"block_size=8 .*{SMEM_PREFETCH_BYTES}"):
        jax.eval_shape(
            lambda *a: _spmm_block_fused_decode_pallas(*a, bt=bt), *args)
    # the smoke width (bs = 128, 128 x 26 slots) fits
    jax.eval_shape(lambda *a: _spmm_block_fused_decode_pallas(*a, bt=bt),
                   jax.ShapeDtypeStruct((128, 26, 128, 128), jnp.float32),
                   jax.ShapeDtypeStruct((128, 26, 2), jnp.int32),
                   jax.ShapeDtypeStruct((128, 26), jnp.float32),
                   jax.ShapeDtypeStruct((1,), jnp.float32),
                   jax.ShapeDtypeStruct((16384, bt), jnp.float32))


@pytest.mark.parametrize("bt,bs,mn,itemsize,want", [
    (128, 128, 1, 4, (128, 128)),      # aligned: no padding
    (384, 128, 1, 4, (384, 384)),      # the whole group fits in one tile
    (13, 128, 1, 4, (128, 128)),       # small prime
    (24, 128, 1, 4, (128, 128)),       # no 128-multiple divisor
    (251, 128, 1, 4, (256, 256)),      # prime > 128: 256 divides 256
    (2 * 127, 128, 1, 4, (256, 256)),
    (16384, 128, 1, 4, (4096, 16384)),  # the cell: one decode row, f32
    (16384, 128, 2, 4, (2048, 16384)),  # two decode rows: a larger output
    (16384, 128, 1, 2, (4096, 16384)),  # bf16 pack
    (16384, 128, 2, 2, (2048, 16384)),
], ids=["128", "384", "13", "24", "251", "254", "16384-mn1-f32",
        "16384-mn2-f32", "16384-mn1-bf16", "16384-mn2-bf16"])
def test_plan_t_tiling_prime_bt_pads(bt, bs, mn, itemsize, want):
    """The TPU lane pads a bt that is not a multiple of 128 (Mosaic's lane
    width) up to one, and tiles it with the widest 128-multiple that
    divides the padded width and whose VMEM footprint fits the budget: the
    next wider divisor does not fit.  The XLA lane does not tile or pad."""
    from repro.core.coded_matmul import _plan_t_tiling
    from repro.kernels.spmm_block import VMEM_TILE_BYTES, fused_vmem_bytes

    got = _plan_t_tiling(bt, "tpu", bs=bs, mn=mn, itemsize=itemsize)
    assert got == want
    t_tile, bt_pad = got
    assert t_tile % 128 == 0 and bt_pad % t_tile == 0
    assert fused_vmem_bytes(bs, mn, t_tile, itemsize) <= VMEM_TILE_BYTES
    wider = [t for t in range(t_tile + 128, bt_pad + 1, 128) if bt_pad % t == 0]
    assert all(fused_vmem_bytes(bs, mn, t, itemsize) > VMEM_TILE_BYTES
               for t in wider)
    assert _plan_t_tiling(bt, "xla", bs=bs, mn=mn, itemsize=itemsize) == (bt, bt)


def test_plan_t_tiling_keeps_128_when_nothing_wider_fits(monkeypatch):
    """A budget that no 256-wide tile fits leaves the 128-wide tile."""
    from repro.core.coded_matmul import _plan_t_tiling
    from repro.kernels import spmm_block

    monkeypatch.setattr(spmm_block, "VMEM_TILE_BYTES",
                        spmm_block.fused_vmem_bytes(128, 1, 256, 4) - 1)
    assert _plan_t_tiling(16384, "tpu", bs=128, mn=1, itemsize=4) == (
        128, 16384)


@pytest.mark.parametrize("mn", [1, 2])
def test_fused_decode_wide_tile_matches_xla_and_128(mn):
    """The interpreted kernel at t_tile 256 over a 512-wide group (two
    column tiles per group, two groups) agrees with the XLA lane within the
    lanes' tolerance, and bit for bit with the same kernel at t_tile 128:
    each output column's slots accumulate in the same order whatever the
    tile width."""
    vals, src, w, dvec, B = _fused_decode_case(seed=23, bt=512, mn=mn)
    ref = ops.spmm_block_fused_decode(vals, src, w, dvec, B, bt=512, lane="xla")
    wide = ops.spmm_block_fused_decode(vals, src, w, dvec, B, bt=512,
                                       t_tile=256, lane="tpu")
    narrow = ops.spmm_block_fused_decode(vals, src, w, dvec, B, bt=512,
                                         t_tile=128, lane="tpu")
    assert wide.shape == (mn, 4 * 8, 512)
    np.testing.assert_allclose(np.asarray(wide), np.asarray(ref),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_array_equal(np.asarray(wide), np.asarray(narrow))


def test_fused_decode_prime_bt_end_to_end(monkeypatch):
    """The padded-t staging path: a per-worker coded product with prime
    bt=251 on the TPU lane (interpreted here; the t axis genuinely pads to
    256) must match the dense reference after the pad+slice."""
    from repro.core.coded_matmul import (
        _make_block_sparse_fused_decode, make_plan, pack_worker_tiles)

    rng = np.random.default_rng(17)
    plan = make_plan(2, 2, num_workers=8, seed=4)
    s, r, bs = 32, 16, 8
    n, bt = 2, 251
    t = n * bt
    mask = rng.random((s // bs, r // bs)) < 0.7
    A = (rng.standard_normal((s, r)) * np.kron(mask, np.ones((bs, bs)))
         ).astype(np.float32)
    B = jnp.asarray(rng.standard_normal((s, t)), jnp.float32)
    ell = dense_to_block_ell(A, block_size=bs)
    pack = pack_worker_tiles(ell, plan)
    monkeypatch.setenv("REPRO_KERNEL_LANE", "tpu")
    arrays, fused = _make_block_sparse_fused_decode(plan, pack, bt)
    dvec = jnp.asarray(rng.standard_normal(4).astype(np.float32))
    for k in [0, 3]:
        got = np.asarray(fused(jnp.asarray(A), B, dvec,
                               *(jnp.asarray(a[k]) for a in arrays)))
        assert got.shape == (4, r // 2, bt)
        Ct = np.zeros((r // 2, bt), np.float32)
        for l in range(plan.max_degree):
            wgt = plan.weights[k, l]
            if wgt == 0.0:
                continue
            i, j = divmod(int(plan.cols[k, l]), n)
            Ct += wgt * (A[:, i * (r // 2):(i + 1) * (r // 2)].T
                         @ np.asarray(B)[:, j * bt:(j + 1) * bt])
        np.testing.assert_allclose(
            got, np.asarray(dvec)[:, None, None] * Ct[None],
            atol=1e-3, rtol=1e-3)


# ------------------------- format round-trips ------------------------------

if given is not None:
    @given(data=st.data())
    @settings(**SETTINGS)
    def test_block_ell_roundtrip(data):
        bs = data.draw(st.sampled_from([4, 8]))
        RB = data.draw(st.integers(1, 5))
        CB = data.draw(st.integers(1, 5))
        density = data.draw(st.floats(0.0, 1.0))
        seed = data.draw(st.integers(0, 10_000))
        rng = np.random.default_rng(seed)
        mask = rng.random((RB, CB)) < density
        A = rng.standard_normal((RB * bs, CB * bs)) * np.kron(mask, np.ones((bs, bs)))
        ell = dense_to_block_ell(A, block_size=bs)
        np.testing.assert_array_equal(block_ell_to_dense(ell), A)
else:
    @pytest.mark.skip(reason="hypothesis not installed (requirements-test.txt)")
    def test_property_sweeps_need_hypothesis():
        pass
