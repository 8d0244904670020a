"""SPMD integration benchmark (no paper figure -- the framework's own table):
coded vs uncoded distributed matmul on a JAX mesh, across both local-compute
backends (dense_scan vs the fused-gather block-sparse path), swept over
block densities {2%, 10%, 30%}.  Driven through the ``repro.coded`` op API
(one bound ``CodedOp`` per backend x decode layout; straggler decode via
``with_survivors``).

Runs in a subprocess with 8 host devices (this process keeps the default
single-device platform).  Reports wall time per (density, backend), the
scatter-decode variant, the redundancy overhead of the coded path, and the
fault-tolerance outcome (decode with a killed worker).  The full result
dict is persisted to BENCH_coded_matmul.json at the repo root, seeding the
perf trajectory the CI artifact tracks."""

from __future__ import annotations

import json
import pathlib
import subprocess
import sys

from benchmarks.common import Row, merge_into_bench_json

DENSITIES = (0.02, 0.10, 0.30)

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "2")
import json, sys, time
import numpy as np
import jax, jax.numpy as jnp
from repro import compat
from repro.launch.compile_cache import enable_compile_cache
enable_compile_cache()
from repro.coded import CodedMatmulConfig, from_plan
from repro.core.coded_matmul import make_plan, uncoded_matmul_reference
from repro.sparse import dense_to_block_ell

FULL = bool(int(sys.argv[1])) if len(sys.argv) > 1 else False
DENSITIES = json.loads(sys.argv[2]) if len(sys.argv) > 2 else [0.02, 0.10, 0.30]

mesh = compat.make_mesh((8,), ("model",),
                        axis_types=compat.auto_axis_types(1))
m = n = 2
plan = make_plan(m, n, num_workers=8, seed=0)
s, r, t = (1024, 512, 512) if FULL else (512, 256, 256)
bs = 8
rng = np.random.default_rng(0)
B = jnp.asarray(rng.standard_normal((s, t)), jnp.float32)
unc = jax.jit(uncoded_matmul_reference)

# one bound CodedOp per (backend x decode layout); packs resolve through the
# op (and its pack cache) per operand below
OPS = {
    "dense_scan": from_plan(CodedMatmulConfig(
        backend="dense_scan"), plan).bind(mesh),
    "block_sparse": from_plan(CodedMatmulConfig(
        backend="block_sparse", block_size=bs), plan).bind(mesh),
    "block_sparse_scatter": from_plan(CodedMatmulConfig(
        backend="block_sparse", block_size=bs, out_sharded=True),
        plan).bind(mesh),
}

def bench(fn, *args):
    fn(*args).block_until_ready()
    ts = []
    for _ in range(5):
        t0 = time.perf_counter()
        fn(*args).block_until_ready()
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))

out = {"max_degree": plan.max_degree, "shape": {"s": s, "r": r, "t": t},
       "block_size": bs, "num_workers": 8, "densities": {}}

for density in DENSITIES:
    mask = rng.random((s // bs, r // bs)) < density
    A_np = rng.standard_normal((s, r)) * np.kron(mask, np.ones((bs, bs)))
    A = jnp.asarray(A_np, jnp.float32)
    # the tile pack is static metadata: build it on host, outside jit
    ell = dense_to_block_ell(np.asarray(A_np, np.float32), block_size=bs)
    fns = {}
    for name, op in OPS.items():
        kw = {"a_sparse": ell} if op.needs_pack else {}
        fns[name] = jax.jit(lambda a, b, op=op, kw=kw: op.apply(a, b, **kw))
    ref = unc(A, B)
    d = {"block_density": float(mask.mean()),
         "live_tile_fraction": float(ell.nnzb.sum()) / ((s // bs) * (r // bs))}
    for name, fn in fns.items():
        d[f"t_{name}"] = bench(fn, A, B)
        d[f"err_{name}"] = float(jnp.max(jnp.abs(fn(A, B) - ref)))
    d["t_uncoded"] = bench(unc, A, B)
    d["speedup_block_vs_dense"] = d["t_dense_scan"] / max(d["t_block_sparse"], 1e-12)
    out["densities"][f"{density:.2f}"] = d

# fault tolerance at the middle density: kill worker 3, rebind the op to the
# survivors (the pack is reused -- it depends only on the task table)
density = DENSITIES[len(DENSITIES) // 2]
mask = rng.random((s // bs, r // bs)) < density
A_np = rng.standard_normal((s, r)) * np.kron(mask, np.ones((bs, bs)))
A = jnp.asarray(A_np, jnp.float32)
ell = dense_to_block_ell(np.asarray(A_np, np.float32), block_size=bs)
ref = unc(A, B)
surv = np.ones(8, dtype=bool); surv[3] = False
for backend in ("dense_scan", "block_sparse"):
    kw = {"a_sparse": ell} if OPS[backend].needs_pack else {}
    try:
        # with_survivors raises DecodingError (a ValueError) EAGERLY on
        # rank loss, so the rebind must sit inside the recording try
        C2 = OPS[backend].with_survivors(surv).apply(A, B, **kw)
        out[f"ft_err_{backend}"] = float(jnp.max(jnp.abs(C2 - ref)))
    except ValueError:   # rank lost: record the outcome, don't crash the bench
        out[f"ft_err_{backend}"] = float("nan")

print(json.dumps(out))
"""


def run(quick: bool = True):
    root = pathlib.Path(__file__).parents[1]
    proc = subprocess.run(
        [sys.executable, "-c", _SCRIPT, "0" if quick else "1",
         json.dumps(list(DENSITIES))],
        env={"PYTHONPATH": str(root / "src"), "PATH": "/usr/bin:/bin",
             "HOME": "/root"},
        capture_output=True, text=True, timeout=900)
    rows = []
    if proc.returncode != 0:
        rows.append(Row("coded_matmul/ERROR", 0.0, proc.stderr[-200:]))
        return rows
    d = json.loads(proc.stdout.strip().splitlines()[-1])
    # merge: the completion suite persists its chunked sweep into the same
    # artifact, so preserve keys this suite does not own
    merge_into_bench_json(d)
    for key, dd in d["densities"].items():
        rows.append(Row(
            f"coded_matmul/dense_scan_8dev_d{key}", dd["t_dense_scan"] * 1e6,
            f"max_err={dd['err_dense_scan']:.2e} max_degree={d['max_degree']}"))
        rows.append(Row(
            f"coded_matmul/block_sparse_8dev_d{key}", dd["t_block_sparse"] * 1e6,
            f"max_err={dd['err_block_sparse']:.2e} "
            f"vs_dense={dd['speedup_block_vs_dense']:.2f}x"))
        rows.append(Row(
            f"coded_matmul/block_sparse_scatter_8dev_d{key}",
            dd["t_block_sparse_scatter"] * 1e6,
            f"max_err={dd['err_block_sparse_scatter']:.2e}"))
        rows.append(Row(
            f"coded_matmul/uncoded_8dev_d{key}", dd["t_uncoded"] * 1e6,
            f"overhead={dd['t_dense_scan'] / max(dd['t_uncoded'], 1e-12):.2f}x"))
    rows.append(Row(
        "coded_matmul/fault_tolerant_decode", 0.0,
        f"killed_worker_3_err dense={d['ft_err_dense_scan']:.2e} "
        f"block_sparse={d['ft_err_block_sparse']:.2e}"))
    return rows
