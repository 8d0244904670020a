"""Roofline analysis from compiled dry-run artifacts (single-pod mesh).

HLO cost analysis counts scan/while bodies ONCE, so raw full-model numbers
undercount deep stacks.  We therefore compile *probe* variants -- the same
config at 1 and 2 layer groups, fully unrolled (and CE in 2 unrolled chunks)
-- and extrapolate:

    total(G) = probe(1) + (G - 1) * [probe(2) - probe(1)]

which is exact for flops/bytes/collectives because every group is
structurally identical.  Sequence-recurrence scans (rwkv / mamba time steps)
cannot be unrolled at 4k-500k steps; their per-step state-update flops are
added analytically (a few % of the matmul flops; see EXPERIMENTS.md).

Terms (per training/serving step, TPU v5e):
    compute_s    = HLO_flops_per_device / 197e12
    memory_s     = HLO_bytes_per_device / 819e9
    collective_s = collective_bytes_per_device (x2 for all-reduce) / 50e9
"""

import argparse
import dataclasses
import json
import os
import pathlib

import jax.numpy as jnp
import numpy as np

from repro.configs import ARCHS, get
from repro.launch.dryrun import SHAPES, cell_supported, run_cell
from repro.launch.mesh import HBM_BW, ICI_BW, PEAK_FLOPS_BF16


OUT_DIR = pathlib.Path(__file__).resolve().parents[3] / "experiments" / "roofline"
DRYRUN_DIR = pathlib.Path(__file__).resolve().parents[3] / "experiments" / "dryrun"


# ------------------------- kernel-level roofline -----------------------------
#
# The coded-matmul kernels (spmm_block_fused / spmm_block_fused_decode,
# DESIGN.md section 12) are priced against the published peaks of the chip
# they ran on, looked up by ``device_kind``.  A device that is not in the
# table is an error, never a default.

#: Published per-chip peaks, keyed by ``jax.Device.device_kind``.
DEVICE_PEAKS = {
    "TPU v5 lite": {
        "peak_flops": PEAK_FLOPS_BF16,   # bf16 FLOP/s
        "peak_int8_ops": 393e12,         # int8 OP/s
        "peak_bw": HBM_BW,               # HBM bytes/s (16 GB)
        "source": "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
                  "393 TOP/s int8, 819 GB/s HBM",
    },
}


def machine_peaks(device_kind: str | None = None, *,
                  calibrate_cpu: bool = False, reps: int = 5) -> dict:
    """{"peak_flops", "peak_bw", "source", "device_kind", ...} of a chip.

    ``device_kind`` defaults to that of ``jax.devices()[0]`` and must be a
    key of ``DEVICE_PEAKS``.  ``calibrate_cpu=True`` instead measures the
    CPU backend in place (a big square matmul, a pure streaming pass) for
    CPU-only smoke runs; those numbers are labelled "calibrated-cpu" and
    are never a device's peaks.
    """
    import time

    import jax
    import jax.numpy as jnp

    if calibrate_cpu:
        if jax.default_backend() != "cpu":
            raise ValueError(
                "calibrate_cpu measures the CPU backend; the default backend "
                f"is {jax.default_backend()!r} -- use its published peaks")
    else:
        kind = device_kind or jax.devices()[0].device_kind
        if kind not in DEVICE_PEAKS:
            raise ValueError(
                f"no published peaks for device kind {kind!r}; known: "
                f"{sorted(DEVICE_PEAKS)}")
        return dict(DEVICE_PEAKS[kind], device_kind=kind)

    def best_time(fn, *args):
        fn(*args).block_until_ready()
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            fn(*args).block_until_ready()
            ts.append(time.perf_counter() - t0)
        return min(ts)

    n = 1024
    x = jnp.ones((n, n), jnp.float32)
    t_mm = best_time(jax.jit(lambda a: a @ a), x)
    peak_flops = 2.0 * n ** 3 / t_mm

    big = jnp.ones((32 * 1024 * 1024 // 4,), jnp.float32)  # 32 MB stream
    t_bw = best_time(jax.jit(lambda a: a + 1.0), big)
    peak_bw = 2.0 * big.size * 4 / t_bw                    # read + write

    return {"peak_flops": float(peak_flops), "peak_bw": float(peak_bw),
            "source": "calibrated-cpu", "device_kind": "cpu"}


def fused_kernel_cost(*, live_tiles: int, bs: int, bt: int, mn: int, br: int,
                      fused: bool, tile_itemsize: int = 4) -> dict:
    """{"flops", "bytes"} of one worker's coded local product + decode.

    The USEFUL work is identical for both paths (same tiles, same decode
    combine); the unfused path additionally round-trips the (br, bt)
    accumulation C~ through HBM between its two launches, which is the
    whole point of the fused epilogue.  ``tile_itemsize`` prices quantized
    packs (4 f32, 2 bf16, 1 int8); B and the outputs are always f32.
    """
    flops = 2.0 * live_tiles * bs * bs * bt     # tile^T @ B-tile MACs
    flops += live_tiles * bs * bt               # per-slot weight scale
    flops += mn * br * bt                       # decode combine multiplies
    bytes_ = live_tiles * bs * bs * tile_itemsize   # packed tiles of A
    bytes_ += live_tiles * bs * bt * 4              # gathered B tiles
    bytes_ += mn * br * bt * 4                      # decode-stack write
    if not fused:
        bytes_ += 2.0 * br * bt * 4             # C~ HBM round-trip
    return {"flops": float(flops), "bytes": float(bytes_)}


def roofline_fraction(cost: dict, measured_s: float, peaks: dict) -> float:
    """Achieved fraction of the roofline of ``peaks`` for the given cost.

    ideal = max(compute-bound, memory-bound) time; fraction = ideal /
    measured.  Compare paths at the SAME cost (the useful work) so the
    fraction penalizes overhead instead of crediting it with extra bytes.
    """
    ideal = max(cost["flops"] / peaks["peak_flops"],
                cost["bytes"] / peaks["peak_bw"])
    return float(ideal / max(measured_s, 1e-12))


def _probe_cfg(cfg, groups: int, enc_layers: int | None = None):
    g = cfg.group_size
    kw = {"num_layers": g * groups, "name": f"{cfg.name}-probe{groups}"}
    if cfg.encoder_layers:
        kw["encoder_layers"] = enc_layers if enc_layers is not None else 1
    return dataclasses.replace(cfg, **kw)


def _extract(rec: dict) -> dict:
    ca = rec["cost_analysis"]
    coll = rec["collectives"]["bytes"]
    # per-device collective seconds: ring all-reduce moves ~2x the payload
    coll_bytes = (coll["all-gather"] + coll["reduce-scatter"]
                  + coll["all-to-all"] + coll["collective-permute"]
                  + 2 * coll["all-reduce"])
    return {
        "flops": ca["flops_per_device"],
        "bytes": ca["bytes_per_device"],
        "coll_bytes": float(coll_bytes),
    }


def _combine(p1: dict, p2: dict, reps: int) -> dict:
    """total = p1 + (reps-1) * (p2 - p1), clamped at >= p1."""
    out = {}
    for k in p1:
        marg = max(p2[k] - p1[k], 0.0)
        out[k] = p1[k] + (reps - 1) * marg
    return out


def analytic_memory_bytes(cfg, shape: str, chips: int = 256,
                          dp: int = 16, tp: int = 16) -> float:
    """Per-device HBM traffic model (the XLA CPU backend's 'bytes accessed'
    has no fusion modeling and overestimates ~10x; this coarse analytic model
    is the headline memory term, the raw HLO number is reported alongside).

    train  : AdamW state machine (24 B/param local) + C1 passes over local
             activations (fwd+bwd+remat) + attention score traffic.
    prefill: param reads + C2 activation passes + KV-cache writes.
    decode : params read once per token step + full KV-cache read.
    """
    info = SHAPES[shape]
    import jax
    from repro.models import build
    model = build(cfg)
    n_params = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(model.shapes()))
    d = cfg.d_model
    L = cfg.num_layers

    if info["kind"] == "train":
        toks_local = info["batch"] * info["seq"] // dp
        param_traffic = 24.0 * n_params / chips
        # residual-stream tensors are replicated across TP; inner (ff, heads)
        # tensors are /tp and roughly cancel the extra passes -> ~40 passes
        # of (tokens_local x d) per layer covers fwd+bwd+remat
        act = 40.0 * toks_local * d * 2.0 * L
        # attention scores fwd+bwd+remat (causal ~ S^2/2), sharded dp x tp
        if not cfg.rwkv and cfg.attn_every >= 1:
            attn_layers = sum(1 for mx, _ in cfg.layer_plan()
                              if mx in ("attn", "cross", "self_cross")) * cfg.num_groups
            act += 3.0 * info["batch"] * cfg.num_heads * info["seq"] ** 2 * 2.0 \
                * attn_layers / (2.0 * chips)
        return param_traffic + act
    if info["kind"] == "prefill":
        toks_local = info["batch"] * info["seq"] // dp
        act = 14.0 * toks_local * d * 2.0 * L
        attn_layers = sum(1 for mx, _ in cfg.layer_plan()
                          if mx in ("attn", "cross", "self_cross")) * cfg.num_groups
        if not cfg.rwkv:
            act += info["batch"] * cfg.num_heads * info["seq"] ** 2 * 2.0 \
                * attn_layers / (2.0 * chips)
        return 2.0 * n_params / chips + act
    # decode: one token against the cache
    cache_bytes = 0.0
    attn_layers = sum(1 for mx, _ in cfg.layer_plan()
                      if mx in ("attn", "self_cross")) * cfg.num_groups
    cache_bytes += (2.0 * info["batch"] * info["seq"] * cfg.num_kv_heads
                    * cfg.hd * 2.0 * attn_layers) / chips
    frac_active = cfg.active_params_count() / max(cfg.params_count(), 1)
    return 2.0 * n_params * min(frac_active, 1.0) / chips + cache_bytes


def _recurrence_flops(cfg, tokens: int) -> float:
    """Analytic per-step state-update flops hidden inside sequence scans."""
    per_tok_layer = 0.0
    if cfg.rwkv:
        hs = cfg.rwkv_head_size
        H = cfg.d_model // hs
        per_tok_layer += 6.0 * H * hs * hs
    if cfg.ssm is not None:
        di = cfg.ssm.expand * cfg.d_model
        frac = sum(1 for mx, _ in cfg.layer_plan() if mx == "mamba") / cfg.group_size
        per_tok_layer += 6.0 * di * cfg.ssm.d_state * frac
    return per_tok_layer * cfg.num_layers * tokens


def analyze_cell(arch: str, shape: str, *, chips: int = 256,
                 cfg_override=None, force: bool = False,
                 opts: tuple = ()) -> dict:
    cfg = cfg_override or get(arch)
    if opts:
        cfg = cfg.with_opts(opts)
    ok, why = cell_supported(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape, "status": "skipped", "reason": why}

    info = SHAPES[shape]
    tokens = info["batch"] * (info["seq"] if info["kind"] == "train" else
                              (info["seq"] if info["kind"] == "prefill" else 1))

    # probes: 1 and 2 layer groups, unrolled, CE in 2 big chunks
    ce = None
    if info["kind"] == "train":
        ce = (info["batch"] * info["seq"]) // 2
    probes = {}
    for gk in (1, 2):
        rec = run_cell(arch, shape, multi_pod=False, scan_unroll=True,
                       cfg_override=_probe_cfg(cfg, gk), ce_chunk=ce)
        if rec["status"] != "ok":
            return {"arch": arch, "shape": shape, "status": "error",
                    "error": rec.get("error", "probe failed")}
        probes[gk] = _extract(rec)
    total = _combine(probes[1], probes[2], cfg.num_groups)

    if cfg.encoder_layers:
        # encoder marginal: probe with 2 encoder layers at 1 group
        rec = run_cell(arch, shape, multi_pod=False, scan_unroll=True,
                       cfg_override=_probe_cfg(cfg, 1, enc_layers=2), ce_chunk=ce)
        if rec["status"] == "ok":
            enc2 = _extract(rec)
            for k in total:
                marg = max(enc2[k] - probes[1][k], 0.0)
                total[k] += (cfg.encoder_layers - 1) * marg

    # hidden recurrence flops (seq scans not unrollable)
    seq_tokens = info["batch"] * (info["seq"] if info["kind"] != "decode" else 1)
    total["flops"] += _recurrence_flops(cfg, seq_tokens) / chips

    mem_model = analytic_memory_bytes(cfg, shape, chips=chips)
    compute_s = total["flops"] / PEAK_FLOPS_BF16
    memory_s = mem_model / HBM_BW
    memory_s_hlo_raw = total["bytes"] / HBM_BW
    coll_s = total["coll_bytes"] / ICI_BW
    terms = {"compute_s": compute_s, "memory_s": memory_s,
             "collective_s": coll_s}
    dominant = max(terms, key=terms.get)

    # MODEL_FLOPS: 6*N_active*D train, 2*N_active*D inference
    import jax
    from repro.models import build
    model = build(cfg)
    shapes_tree = model.shapes()
    n_total = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes_tree))
    frac_active = cfg.active_params_count() / max(cfg.params_count(), 1)
    n_active = n_total * min(frac_active, 1.0)
    mult = 6.0 if info["kind"] == "train" else 2.0
    model_flops = mult * n_active * tokens
    hlo_total = total["flops"] * chips
    ratio = model_flops / max(hlo_total, 1.0)

    # step time bound & roofline fraction
    step_bound = max(terms.values())
    mfu_bound = (model_flops / chips / PEAK_FLOPS_BF16) / max(step_bound, 1e-12)

    return {
        "arch": arch, "shape": shape, "status": "ok", "chips": chips,
        "tokens_per_step": tokens,
        "per_device": total,
        "terms": terms,
        "memory_s_hlo_raw": memory_s_hlo_raw,
        "dominant": dominant,
        "model_flops": model_flops,
        "hlo_flops_total": hlo_total,
        "useful_ratio": ratio,
        "roofline_fraction_bound": mfu_bound,
        "n_params": n_total,
        "n_active": n_active,
    }


def main():
    # the production meshes are described on the host platform: pin its
    # device count before the first backend call (never at import)
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "2")
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all", choices=["all"] + list(SHAPES))
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--opt", default="",
                    help="comma list: fused_ce,moe_local_dispatch,onehot_cache"
                         " (writes <arch>__<shape>__<opts>.json)")
    args = ap.parse_args()
    opts = tuple(o for o in args.opt.split(",") if o)

    OUT_DIR.mkdir(parents=True, exist_ok=True)
    archs = sorted(ARCHS) if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    suffix = ("__" + "+".join(opts)) if opts else ""
    for arch in archs:
        for shape in shapes:
            path = OUT_DIR / f"{arch}__{shape}{suffix}.json"
            if path.exists() and not args.force:
                print(f"[roofline] {arch}/{shape}{suffix}: cached")
                continue
            try:
                rec = analyze_cell(arch, shape, opts=opts)
                rec["opts"] = list(opts)
            except Exception as e:  # noqa: BLE001
                rec = {"arch": arch, "shape": shape, "status": "error",
                       "error": f"{type(e).__name__}: {e}"}
            path.write_text(json.dumps(rec, indent=1))
            if rec["status"] == "ok":
                t = rec["terms"]
                print(f"[roofline] {arch}/{shape}: compute={t['compute_s']*1e3:.2f}ms "
                      f"memory={t['memory_s']*1e3:.2f}ms "
                      f"coll={t['collective_s']*1e3:.2f}ms "
                      f"dom={rec['dominant']} useful={rec['useful_ratio']:.2f} "
                      f"roofline<={rec['roofline_fraction_bound']:.2%}", flush=True)
            else:
                print(f"[roofline] {arch}/{shape}: {rec['status']} "
                      f"{rec.get('error', rec.get('reason', ''))[:120]}", flush=True)


if __name__ == "__main__":
    main()
