"""Tests of ``repro.obs``: the spans and counters one coded product records.

Each case runs a small block_sparse op on the CPU, one worker on one device
(m = n = 1) and, in a process of its own, m = 2 over N = 4 host devices.
"""

import json
import os
import pathlib
import subprocess
import sys
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import obs
from repro.coded import CodedMatmulConfig, from_plan
from repro.core.coded_matmul import build_coded_program, make_plan
from repro.runtime import pack_cache
from repro.sparse import dense_to_block_ell

HERE = pathlib.Path(__file__).resolve().parent
STAGING = {obs.PREPARE, obs.UPLOAD, obs.JIT}
FROM_JAX = {obs.LOWER, obs.COMPILE}

_FOUR_DEVICES = r"""
import json, os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, sys.argv[1])
import test_obs
print(json.dumps(test_obs.facts(m=2, num_workers=4)))
"""


def small_op(m: int, num_workers: int):
    """A bound block_sparse op over the first ``num_workers`` devices, and
    its operands: A (32, 16 m) at half its 8x8 tiles live, B (32, 16)."""
    p = make_plan(m, 1, num_workers=num_workers, seed=3)
    mesh = jax.make_mesh((num_workers,), ("model",),
                         devices=jax.devices()[:num_workers])
    rng = np.random.default_rng(5)
    A_np = rng.standard_normal((32, 16 * m)).astype(np.float32)
    A_np *= np.kron(rng.random((4, 2 * m)) < 0.5, np.ones((8, 8)))
    op = from_plan(CodedMatmulConfig(backend="block_sparse"), p).bind(mesh)
    return (op, jnp.asarray(A_np),
            jnp.asarray(rng.standard_normal((32, 16)), jnp.float32),
            dense_to_block_ell(A_np, block_size=8))


def _span(s) -> dict:
    return {"name": s.name, "start": s.start_ns, "end": s.end_ns,
            "id": s.span_id, "parent": s.parent, "product": s.product,
            "counts": s.counts}


def facts(m: int, num_workers: int) -> dict:
    """Two calls of one op, then one of its rebind, into a fresh recorder:
    what the recorder kept, and what it should have counted."""
    op, A, B, ell = small_op(m, num_workers)
    _, worker_arrays = build_coded_program(
        op.plan_, op.mesh, B.shape[1], **op._staging_kwargs(A, B, ell, None))
    pack_cache.clear()
    before = obs.RECORDER
    obs.RECORDER = obs.Recorder()
    try:
        misses = []
        for _ in range(2):
            op(A, B, a_sparse=ell).block_until_ready()
            misses.append(pack_cache.cache_stats()["misses"])
        rebound = op.with_survivors(np.ones(num_workers, bool))
        rebound(A, B, a_sparse=ell).block_until_ready()
        return {"spans": [_span(s) for s in obs.records()],
                "counters": obs.RECORDER.counters(),
                "snapshot": obs.snapshot(),
                "pack_cache": pack_cache.cache_stats(),
                "worker_nbytes": int(sum(a.nbytes for a in worker_arrays)),
                # what a call puts on the devices once the pack's tiles are
                # there: the decode columns and the slot weights
                "per_call_nbytes": int(worker_arrays[0].nbytes
                                       + worker_arrays[-1].nbytes),
                "misses": misses}
    finally:
        obs.RECORDER = before
        pack_cache.clear()


@pytest.fixture(scope="module", params=["m1", "m2-N4"])
def case(request):
    if request.param == "m1":
        return facts(m=1, num_workers=1)
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    proc = subprocess.run(
        [sys.executable, "-c", _FOUR_DEVICES, str(HERE)], env=env,
        capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def _products(case) -> list:
    return [s for s in case["spans"] if s["name"] == obs.PRODUCT]


def _children(case, product) -> list:
    return [s for s in case["spans"]
            if s["product"] == product["product"] and s is not product]


def test_each_call_is_one_product_with_its_staging_children(case):
    products = _products(case)
    assert len(products) == 3
    assert len({p["product"] for p in products}) == 3
    for p in products:
        assert p["parent"] is None
        kids = _children(case, p)
        by_id = {s["id"]: s for s in kids}
        names = [s["name"] for s in kids]
        assert STAGING <= set(names) <= STAGING | FROM_JAX
        assert names.count(obs.UPLOAD) == names.count(obs.JIT) == 1
        for s in kids:
            # the staging spans hang off the product, JAX's off the jit span
            if s["name"] in STAGING:
                assert s["parent"] == p["id"]
            else:
                assert by_id[s["parent"]]["name"] == obs.JIT
            assert p["start"] <= s["start"] <= s["end"] <= p["end"]
        up, = (s for s in kids if s["name"] == obs.UPLOAD)
        jit, = (s for s in kids if s["name"] == obs.JIT)
        assert up["end"] <= jit["start"]


def test_upload_bytes_is_the_worker_arrays_nbytes(case):
    """The first product puts every worker array on the devices, the pack's
    tiles among them; each later one, the rebound op's too, puts only its
    decode columns and slot weights."""
    first, *later = _products(case)
    assert first["counts"]["upload_bytes"] == case["worker_nbytes"]
    assert case["per_call_nbytes"] < case["worker_nbytes"]
    for p in later:
        assert p["counts"]["upload_bytes"] == case["per_call_nbytes"]
    assert case["counters"]["upload_bytes"] == (
        case["worker_nbytes"] + 2 * case["per_call_nbytes"])


def test_a_second_call_compiles_again_without_a_new_pack_miss(case):
    """The name is historical: the second call now neither lowers nor
    compiles; it reuses the staged program and the pack's device copy, and
    makes no new pack miss."""
    first, second, _ = _products(case)
    assert first["counts"]["program_misses"] == first["counts"]["compiles"] == 1
    names = {s["name"] for s in _children(case, second)}
    assert not names & FROM_JAX
    assert second["counts"]["program_hits"] == 1
    assert "compiles" not in second["counts"]
    assert "program_misses" not in second["counts"]
    assert case["misses"] == [1, 1]


def test_with_survivors_records_one_rebind(case):
    rebinds = [s for s in case["spans"] if s["name"] == obs.REBIND]
    assert len(rebinds) == 1 and rebinds[0]["product"] is None
    assert case["counters"]["rebinds"] == 1
    # the rebound op's product comes after it
    assert rebinds[0]["end"] <= _products(case)[2]["start"]


def test_snapshot_is_the_counters_and_the_pack_cache_as_it_reads(case):
    snap = dict(case["snapshot"])
    assert snap.pop("pack_cache") == case["pack_cache"]
    assert snap == case["counters"]
    assert snap["products"] == 3


def test_the_ring_stays_bounded(monkeypatch):
    op, A, B, ell = small_op(1, 1)
    monkeypatch.setattr(obs, "RECORDER", obs.Recorder(ring=16))
    for _ in range(4):
        op(A, B, a_sparse=ell).block_until_ready()
    kept = obs.records()
    assert len(kept) == 16
    assert obs.RECORDER.counters()["products"] == 4
    assert kept[-1].name == obs.PRODUCT and kept[-1].product == 4


def test_programs_outside_a_product_record_nothing(monkeypatch):
    monkeypatch.setattr(obs, "RECORDER", obs.Recorder())
    op, A, B, ell = small_op(1, 1)
    pack_cache.clear()         # nothing staged: the op's product compiles
    op(A, B, a_sparse=ell).block_until_ready()      # the listener is on
    n = len(obs.records())
    jax.jit(lambda x: x * 3.0 + 1.0)(jnp.arange(7.0)).block_until_ready()
    assert len(obs.records()) == n
    assert obs.RECORDER.counters()["compiles"] == 1


def test_two_threads_calling_one_op_keep_separate_parents(monkeypatch):
    op, A, B, ell = small_op(1, 1)
    monkeypatch.setattr(obs, "RECORDER", obs.Recorder())
    start, errors = threading.Barrier(2), []

    def caller():
        try:
            start.wait(timeout=60)
            for _ in range(3):
                op(A, B, a_sparse=ell).block_until_ready()
        except Exception as e:  # noqa: BLE001 -- reported by the test thread
            errors.append(e)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=caller) for _ in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=240)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads) and not errors
    spans = obs.records()
    by_id = {s.span_id: s for s in spans}
    products = [s for s in spans if s.name == obs.PRODUCT]
    assert len(products) == 6 and len({p.product for p in products}) == 6
    for s in spans:
        if s.name == obs.PRODUCT:
            continue
        parent = by_id[s.parent]
        # a span's parent belongs to the same product, and so to its thread
        assert parent.product == s.product
        assert parent.start_ns <= s.start_ns <= s.end_ns <= parent.end_ns
    for p in products:
        names = [s.name for s in spans if s.product == p.product]
        assert names.count(obs.UPLOAD) == names.count(obs.JIT) == 1


def test_the_xla_lane_counts_no_kernel_grid_steps(case):
    """The XLA lane runs no Pallas grid, so its products count no steps."""
    for p in _products(case):
        assert "kernel_grid_steps" not in p["counts"]
    assert "kernel_grid_steps" not in case["counters"]


def test_a_tpu_lane_product_counts_its_kernel_grid_steps(monkeypatch):
    """On the TPU lane (the kernel interpreted here) a product's root
    carries the launch's CB * (bt_pad / t_tile) * L grid steps.  A budget
    that fits half of the 512-wide column group makes two column tiles."""
    from repro.kernels import spmm_block

    monkeypatch.setenv("REPRO_KERNEL_LANE", "tpu")
    monkeypatch.setattr(spmm_block, "VMEM_TILE_BYTES",
                        spmm_block.fused_vmem_bytes(8, 1, 256, 4))
    monkeypatch.setattr(obs, "RECORDER", obs.Recorder())
    op, A, _, ell = small_op(1, 1)
    B = jnp.asarray(np.random.default_rng(6).standard_normal((32, 512)),
                    jnp.float32)
    C = op(A, B, a_sparse=ell)
    np.testing.assert_allclose(np.asarray(C), np.asarray(A).T @ np.asarray(B),
                               atol=1e-4, rtol=1e-4)
    _, CB, L = op.pack_for(ell).wslot.shape
    root, = (s for s in obs.records() if s.name == obs.PRODUCT)
    assert root.counts["kernel_grid_steps"] == CB * 2 * L
    assert obs.RECORDER.counters()["kernel_grid_steps"] == CB * 2 * L
