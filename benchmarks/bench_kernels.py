"""Kernel-lane roofline benchmark: fused decode epilogue vs two launches.

Benchmarks the coded local product at the KERNEL level (no mesh, no psum):
``spmm_block_fused_decode`` (one launch, decode combine in the epilogue)
against the historical two-step path (local product launch, then the
decode broadcast-multiply as a second launch), on whatever lane
``resolve_lane`` picks for this host -- XLA on CPU CI, compiled Pallas on
TPU.  Results are reported as FRACTION of a roofline
(``repro.launch.roofline.machine_peaks``): the chip's published peaks on a
TPU, in-place calibrated peaks on CPU -- a CPU fraction is not a device
number.  Quantized packs (bf16 / int8 tile values,
weights exact) ride along as a dtype sweep of the fused kernel.

Persists the ``kernel`` key of BENCH_coded_matmul.json.
"""

from __future__ import annotations

import json
import pathlib
import subprocess
import sys

from benchmarks.common import Row, merge_into_bench_json

_SCRIPT = r"""
import os
os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "2")
import json, sys, time
import numpy as np
import jax, jax.numpy as jnp

from repro.launch.compile_cache import enable_compile_cache
enable_compile_cache()
from repro.launch.roofline import machine_peaks, fused_kernel_cost, roofline_fraction
from repro.kernels import ops
from repro.kernels.spmm_block import resolve_lane

FULL = bool(int(sys.argv[1])) if len(sys.argv) > 1 else False

CB, L, bs, mn = (64, 32, 8, 4) if FULL else (32, 32, 8, 4)
bt = 256 if FULL else 128
s, t = 64 * bs, 2 * bt
br = CB * bs

rng = np.random.default_rng(0)
vals32 = rng.normal(size=(CB, L, bs, bs)).astype(np.float32)
src = np.stack([rng.integers(0, s // bs, (CB, L)),
                rng.integers(0, t // bt, (CB, L))], -1).astype(np.int32)
wslot = rng.normal(size=(CB, L)).astype(np.float32)
dvec = rng.normal(size=(mn,)).astype(np.float32)
B = jnp.asarray(rng.normal(size=(s, t)), jnp.float32)
src_j = jnp.asarray(src); w_j = jnp.asarray(wslot); d_j = jnp.asarray(dvec)

def bench(fn, *args, reps=20):
    fn(*args).block_until_ready()
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn(*args).block_until_ready()
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))

lane = resolve_lane()
peaks = machine_peaks(calibrate_cpu=jax.default_backend() == "cpu")

# two launches: the local product, then the decode combine as its own jit
# (a launch boundary, exactly what the staged program used to pay)
step1 = jax.jit(lambda v, s_, w, b: ops.spmm_block_fused(v, s_, w, b, bt=bt))
step2 = jax.jit(lambda d, c: d[:, None, None] * c[None])
def two_step(v, s_, w, d, b):
    return step2(d, step1(v, s_, w, b))
fused = jax.jit(lambda v, s_, w, d, b:
                ops.spmm_block_fused_decode(v, s_, w, d, b, bt=bt))

out = {"lane": lane, "peaks": peaks,
       "shape": {"CB": CB, "L": L, "bs": bs, "bt": bt, "mn": mn,
                 "s": s, "t": t}}

v32 = jnp.asarray(vals32)
ref = np.asarray(two_step(v32, src_j, w_j, d_j, B))
got = np.asarray(fused(v32, src_j, w_j, d_j, B))
out["max_err_fused_vs_two_step"] = float(np.abs(got - ref).max())

t_unfused = bench(two_step, v32, src_j, w_j, d_j, B)
t_fused = bench(fused, v32, src_j, w_j, d_j, B)
live = CB * L
cost = fused_kernel_cost(live_tiles=live, bs=bs, bt=bt, mn=mn, br=br,
                         fused=True)
out["t_unfused_s"] = t_unfused
out["t_fused_s"] = t_fused
out["roofline_fraction_fused"] = roofline_fraction(cost, t_fused, peaks)
out["roofline_fraction_unfused"] = roofline_fraction(cost, t_unfused, peaks)
out["fused_ge_unfused"] = bool(
    out["roofline_fraction_fused"] >= out["roofline_fraction_unfused"])
out["speedup_fused"] = t_unfused / max(t_fused, 1e-12)

# quantized tile sweep: same kernel, tiles stored bf16 / int8 (weights
# exact; int8 scale folded into the weights, as the pack layer does)
out["dtypes"] = {}
for name, itemsize in (("float32", 4), ("bfloat16", 2), ("int8", 1)):
    if name == "float32":
        v, w_eff = v32, w_j
    elif name == "bfloat16":
        v, w_eff = v32.astype(jnp.bfloat16), w_j
    else:
        amax = np.abs(vals32).max(axis=(-2, -1))
        scale = np.where(amax > 0, amax / 127.0, 1.0).astype(np.float32)
        v = jnp.asarray(np.rint(vals32 / scale[..., None, None]).astype(np.int8))
        # per-tile scale folds into the per-slot weight (CB, L)
        w_eff = w_j * jnp.asarray(scale)
    tq = bench(fused, v, src_j, w_eff, d_j, B)
    cq = fused_kernel_cost(live_tiles=live, bs=bs, bt=bt, mn=mn, br=br,
                           fused=True, tile_itemsize=itemsize)
    errq = float(np.abs(np.asarray(fused(v, src_j, w_eff, d_j, B)) - ref).max())
    out["dtypes"][name] = {
        "t_s": tq, "max_err": errq,
        "roofline_fraction": roofline_fraction(cq, tq, peaks)}

print(json.dumps(out))
"""


def run(quick: bool = True):
    root = pathlib.Path(__file__).parents[1]
    proc = subprocess.run(
        [sys.executable, "-c", _SCRIPT, "0" if quick else "1"],
        env={"PYTHONPATH": str(root / "src"), "PATH": "/usr/bin:/bin",
             "HOME": "/root", "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=900)
    rows = []
    if proc.returncode != 0:
        rows.append(Row("kernel/ERROR", 0.0, proc.stderr[-200:]))
        return rows
    d = json.loads(proc.stdout.strip().splitlines()[-1])
    merge_into_bench_json({"kernel": d})
    rows.append(Row(
        f"kernel/fused_decode_{d['lane']}", d["t_fused_s"] * 1e6,
        f"roofline={d['roofline_fraction_fused']:.3f} "
        f"err={d['max_err_fused_vs_two_step']:.2e}"))
    rows.append(Row(
        f"kernel/two_step_{d['lane']}", d["t_unfused_s"] * 1e6,
        f"roofline={d['roofline_fraction_unfused']:.3f} "
        f"fused_speedup={d['speedup_fused']:.2f}x "
        f"fused_ge_unfused={d['fused_ge_unfused']}"))
    for name, dd in d["dtypes"].items():
        rows.append(Row(
            f"kernel/fused_{name}", dd["t_s"] * 1e6,
            f"roofline={dd['roofline_fraction']:.3f} err={dd['max_err']:.2e}"))
    return rows
