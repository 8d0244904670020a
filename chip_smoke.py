"""Run the coded block-sparse matmul on a TPU and check what comes out.

    python chip_smoke.py [--seed N]          # one chip: f32, bf16, dense_scan
    python chip_smoke.py --four-chips        # the 4-worker mesh path (2x2 host)

Drives the main path -- ``repro.coded``: ``plan`` -> ``bind`` -> ``op(A, B)``
(-> ``with_survivors``) -- on C = A^T B with A (16384, 16384) f32 at 10%
live 128x128 tiles and B dense (16384, 16384) f32, both made from ``--seed``.

One chip: one coded worker (m = n = 1, N = 1) runs (a) the f32 pack, (b)
the bf16 pack and (c) the dense_scan backend on the same operands.  The
block_sparse program must contain the compiled Pallas kernel
(``tpu_custom_call``).  Four chips: the sparse code with m=2, n=1 over N=4
workers, one per chip, healthy and with one dead worker, under both decode
collectives (psum, reduce-scatter), also compared with a single-device
uncoded product.

Every result is checked for finiteness and against a host float64 product
on 512 seeded output columns, within a per-entry bound derived from the
operand dtype and the contraction length s (``entry_tolerance``).  The
script runs in one process, starts none, and exits non-zero -- printing no
result -- when JAX finds no TPU, when a kernel override would route the
chip away from the compiled kernel, or when any check fails.  Its last line
is the JSON object ``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import argparse
import importlib.metadata
import json
import os
import pathlib
import sys
import time

import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

S = R = T = 16384          # A is (s, r), B is (s, t)
BS = 128                   # tile edge: one MXU pass deep
LIVE_FRACTION = 0.10       # live 128x128 tiles of A
REF_COLUMNS = 512          # output columns checked against float64
U32 = 2.0 ** -24           # unit roundoff of float32
U_TILE = {"float32": 0.0, "bfloat16": 2.0 ** -8}   # rounding of A's tiles


def log(msg: str) -> None:
    print(msg, flush=True)


def fail(msg: str) -> None:
    print(f"chip_smoke: FAILED: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def require_tpu_lane():
    """Refuse to start unless the compiled TPU kernel is what will run."""
    pallas = os.environ.get("REPRO_PALLAS_INTERPRET")
    if pallas not in (None, "0"):
        fail(f"REPRO_PALLAS_INTERPRET={pallas} would interpret the kernels")
    lane_env = os.environ.get("REPRO_KERNEL_LANE")
    if lane_env not in (None, "", "tpu"):
        fail(f"REPRO_KERNEL_LANE={lane_env} would route off the TPU kernel")

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        fail(f"found platform {dev.platform!r} ({dev.device_kind}), "
             "need a TPU")
    from repro.kernels.spmm_block import resolve_interpret, resolve_lane

    if resolve_lane() != "tpu" or resolve_interpret() is not False:
        fail(f"kernel lane {resolve_lane()!r}, interpret="
             f"{resolve_interpret()}: not the compiled TPU kernel")
    return jax


def make_operands(seed: int):
    """Host A (block-sparse), B (dense), and the checked column subset."""
    rng = np.random.default_rng(seed)
    rb, cb = S // BS, R // BS
    n_live = round(LIVE_FRACTION * rb * cb)
    live = rng.choice(rb * cb, size=n_live, replace=False)
    A = np.zeros((S, R), np.float32)
    tiles = A.reshape(rb, BS, cb, BS)
    vals = rng.standard_normal((n_live, BS, BS), dtype=np.float32)
    tiles[live // cb, :, live % cb, :] = vals
    B = rng.standard_normal((S, T), dtype=np.float32)
    cols = np.sort(rng.choice(T, size=REF_COLUMNS, replace=False))
    return A, B, cols


def float64_reference(A, B, cols):
    """A^T B and |A|^T |B| on the checked columns, in float64 on the host."""
    A64 = A.astype(np.float64)
    B64 = B[:, cols].astype(np.float64)
    return A64.T @ B64, np.abs(A64).T @ np.abs(B64)


def entry_tolerance(plan, alive, absab, u_tile: float, contraction: int):
    """Per-entry bound on |C - A^T B| for a coded result with n = 1.

    Each local product sums at most ``contraction`` (= s) products in f32
    (plus the slot weight, the decode weight and the psum over N workers):
    |error| <= gamma_k |A|^T|B| with gamma_k = k u / (1 - k u), k = s + 2 +
    N, u = 2**-24 (f32 accumulation, fp32 MXU contraction).  Tiles stored
    rounded to bf16 add u_tile = 2**-8 relative error per product.  The
    decode sums worker results with the f32 decode matrix D, so block row i
    of C carries sum_b (eps K[i, b] + |D M - I|[i, b]) |A_b|^T |B| over the
    block rows b, with K = |D| |M| and eps = u_tile + gamma_k; the second
    term is the f32 decode matrix's own residual.
    """
    if plan.n != 1:
        raise ValueError("the bound is written for one column group (n=1)")
    m, N = plan.m, plan.num_workers
    k = contraction + 2 + N
    eps = u_tile + k * U32 / (1 - k * U32)
    M = plan.coefficient_matrix() * np.asarray(alive, np.float64)[:, None]
    D = plan.decode.astype(np.float64)
    coef = eps * (np.abs(D) @ np.abs(M)) + np.abs(D @ M - np.eye(m))
    r, tc = absab.shape
    blocks = absab.reshape(m, r // m, tc)
    return np.einsum("ib,bpq->ipq", coef, blocks).reshape(r, tc)


def check(name, C, cols, ref, tol):
    """isfinite over all of C, then the bound on the checked columns."""
    import jax.numpy as jnp

    if not bool(jnp.isfinite(C).all()):
        fail(f"{name}: non-finite values in the output")
    within(name, np.asarray(C[:, cols], np.float64), ref, tol, "float64")


def within(name, got, want, tol, what):
    """Fail unless |got - want| <= tol everywhere (0 where tol is 0)."""
    err = np.abs(got - want)
    ratio = np.divide(err, tol, out=np.zeros_like(err), where=tol > 0)
    log(f"{name} vs {what}: max_abs_err={float(err.max()):.6e} "
        f"max_err_over_tolerance={float(ratio.max()):.6e} "
        f"max_tolerance={float(tol.max()):.6e}")
    if (err > tol).any():
        fail(f"{name}: {int((err > tol).sum())} entries differ from the "
             f"{what} result beyond their tolerance")


def timed(fn):
    """``fn()`` ready, its wall time, and the backend compiles and cache
    read-backs the program counted in its products (``repro.obs``)."""
    from repro import obs

    before = obs.snapshot()
    t0 = time.perf_counter()
    out = fn()
    out.block_until_ready()
    dt = time.perf_counter() - t0
    after = obs.snapshot()
    return out, dt, *(after.get(k, 0) - before.get(k, 0)
                      for k in ("compiles", "readbacks"))


def peak_bytes(dev) -> str:
    stats = dev.memory_stats() or {}
    return str(stats.get("peak_bytes_in_use", "not reported"))


def one_chip(jax, args):
    from repro import compat
    from repro.coded import CodedMatmulConfig, plan
    from repro.sparse import dense_to_block_ell

    dev = jax.devices()[0]
    mesh = compat.make_mesh((1,), ("model",), devices=[dev])
    t0 = time.perf_counter()
    A_np, B_np, cols = make_operands(args.seed)
    ref, absab = float64_reference(A_np, B_np, cols)
    ell = dense_to_block_ell(A_np, block_size=BS)
    A = jax.device_put(A_np, dev)
    B = jax.device_put(B_np, dev)
    del A_np, B_np
    log(f"setup: data + float64 reference + BlockELL in "
        f"{time.perf_counter() - t0:.3f}s, live tiles "
        f"{int(ell.nnzb.sum())}/{(S // BS) * (R // BS)}")
    phases = (("block_sparse_f32", "block_sparse", "float32"),
              ("block_sparse_bf16", "block_sparse", "bfloat16"),
              ("dense_scan_f32", "dense_scan", "float32"))
    for name, backend, dtype in phases:
        cfg = CodedMatmulConfig(backend=backend, block_size=BS,
                                compute_dtype=dtype)
        op = plan(cfg, m=1, n=1, num_workers=1, seed=args.seed).bind(mesh)
        kw = {"a_sparse": ell} if op.needs_pack else {}
        if name == "block_sparse_f32":
            t0 = time.perf_counter()
            compiled = op.lower(A, B, **kw).compile()
            log(f"{name}: compile_s={time.perf_counter() - t0:.3f}")
            if "tpu_custom_call" not in compiled.as_text():
                fail(f"{name}: no tpu_custom_call in the compiled program "
                     "-- the Pallas kernel is not in it")
            log(f"{name}: tpu_custom_call present in the compiled program")
            del compiled
        C, dt1, n1, h1 = timed(lambda: op(A, B, **kw))
        log(f"{name}: call1_s={dt1:.6f} compiles={n1} readbacks={h1}")
        C, dt2, n2, h2 = timed(lambda: op(A, B, **kw))
        log(f"{name}: call2_s={dt2:.6f} compiles={n2} readbacks={h2} "
            f"recompiled_on_second_call={'yes' if n2 else 'no'}")
        tol = entry_tolerance(op.plan_, np.ones(1), absab, U_TILE[dtype], S)
        check(name, C, cols, ref, tol)
        log(f"{name}: peak_bytes_in_use={peak_bytes(dev)}")
        del C


def four_chips(jax, args):
    import jax.numpy as jnp

    from repro import compat
    from repro.coded import CodedMatmulConfig, plan
    from repro.core.coded_matmul import uncoded_matmul_reference
    from repro.sparse import dense_to_block_ell

    devs = jax.devices()
    if len(devs) < 4:
        fail(f"--four-chips needs 4 devices, found {len(devs)}")
    mesh = compat.make_mesh((4,), ("model",), devices=devs[:4])
    m, n, N = 2, 1, 4
    t0 = time.perf_counter()
    A_np, B_np, cols = make_operands(args.seed)
    ref, absab = float64_reference(A_np, B_np, cols)
    ell = dense_to_block_ell(A_np, block_size=BS)
    A = jnp.asarray(A_np)
    B = jnp.asarray(B_np)
    del A_np, B_np
    log(f"setup: data + float64 reference + BlockELL in "
        f"{time.perf_counter() - t0:.3f}s")
    # the single-device uncoded product, at the same f32 contraction
    U, dt, _, _ = timed(lambda: uncoded_matmul_reference(
        jax.device_put(A, devs[0]), jax.device_put(B, devs[0])))
    U_cols = np.asarray(U[:, cols], np.float64)
    del U
    unc_tol = (S * U32 / (1 - S * U32)) * absab
    log(f"uncoded_1dev: wall_s={dt:.6f}")
    within("uncoded_1dev", U_cols, ref, unc_tol, "float64")

    base = plan(CodedMatmulConfig(backend="block_sparse", block_size=BS),
                m=m, n=n, num_workers=N, seed=args.seed)
    # a dead worker whose loss keeps the code decodable (rank >= mn)
    M = base.plan_.coefficient_matrix()
    for dead in range(N):
        alive = np.ones(N, bool)
        alive[dead] = False
        if np.linalg.matrix_rank(M * alive[:, None]) >= m * n:
            break
    else:
        fail("no single worker can die with the code still decodable")
    for out_sharded in (False, True):
        layout = "reduce_scatter" if out_sharded else "psum"
        cfg = CodedMatmulConfig(backend="block_sparse", block_size=BS,
                                out_sharded=out_sharded)
        healthy = plan(cfg, m=m, n=n, num_workers=N,
                       seed=args.seed).bind(mesh)
        for arm, op, mask in (("healthy", healthy, np.ones(N, bool)),
                              (f"dead_worker_{dead}",
                               healthy.with_survivors(alive), alive)):
            name = f"{layout}_{arm}"
            compiled = op.lower(A, B, a_sparse=ell).compile()
            text = compiled.as_text()
            if "tpu_custom_call" not in text:
                fail(f"{name}: no tpu_custom_call in the compiled program")
            # arguments 2.. are the per-worker operands (decode column, tiles,
            # slot addresses, slot weights): worker k's row on device k
            for idx, sh in enumerate(compiled.input_shardings[0][2:]):
                rows = sorted((d.id, ix[0].start)
                              for d, ix in sh.devices_indices_map((N,)).items())
                log(f"{name}: worker operand {idx} (device id, row): {rows}")
            del compiled, text
            C, dt, nc, hits = timed(lambda: op(A, B, a_sparse=ell))
            shards = sorted((s.device.id, tuple((i.start, i.stop)
                                                for i in s.index))
                            for s in C.addressable_shards)
            log(f"{name}: wall_s={dt:.6f} compiles={nc} readbacks={hits} "
                f"output shards (device id, index) {shards}")
            tol = entry_tolerance(op.plan_, mask, absab, U_TILE["float32"], S)
            check(name, C, cols, ref, tol)
            within(name, np.asarray(C[:, cols], np.float64), U_cols,
                   tol + unc_tol, "uncoded_1dev")
            del C
    for d in devs[:4]:
        log(f"device {d.id}: peak_bytes_in_use={peak_bytes(d)}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-worker mesh path (needs 4 chips)")
    args = ap.parse_args(argv)

    jax = require_tpu_lane()
    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    dev = jax.devices()[0]
    log(f"jax={jax.__version__} jaxlib={importlib.metadata.version('jaxlib')} "
        f"libtpu={importlib.metadata.version('libtpu')} "
        f"device_kind={dev.device_kind} devices={len(jax.devices())} "
        f"compile_cache={cache}")
    if args.four_chips:
        four_chips(jax, args)
    else:
        one_chip(jax, args)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
