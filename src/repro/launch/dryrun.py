"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell we jit the production step function with explicit in/out
shardings on the production mesh, .lower().compile() it, and record
memory_analysis / cost_analysis / the collective mix parsed from the
compiled HLO.  Failures here are sharding bugs in the framework.

Roofline probes: scan bodies are counted ONCE by HLO cost analysis, so for
the roofline we also compile fully-unrolled shallow variants (1 and 2 layer
groups; encoder depths likewise for enc-dec) and extrapolate exact per-group
marginal costs.  Probes run on the single-pod mesh only (the roofline table
is single-pod per the assignment).
"""

import argparse
import dataclasses
import json
import os
import pathlib
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import compat
from repro.configs import ARCHS, get
from repro.launch import meshctx
from repro.launch.mesh import make_production_mesh
from repro.models import build
from repro.serving.serve_step import make_prefill_step
from repro.training.data import input_specs
from repro.training.optimizer import AdamW
from repro.training.train_step import make_train_step


SHAPES = {
    "train_4k": dict(seq=4096, batch=256, kind="train"),
    "prefill_32k": dict(seq=32_768, batch=32, kind="prefill"),
    "decode_32k": dict(seq=32_768, batch=128, kind="decode"),
    "long_500k": dict(seq=524_288, batch=1, kind="decode"),
}

OUT_DIR = pathlib.Path(__file__).resolve().parents[3] / "experiments" / "dryrun"

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")


def cell_supported(cfg, shape_name: str) -> tuple[bool, str]:
    if shape_name == "long_500k" and not cfg.sub_quadratic:
        return False, "long_500k needs sub-quadratic attention (skip per spec)"
    return True, ""


# ------------------------- collective byte parsing --------------------------

_SHAPE_RE = re.compile(r"(bf16|f16|f32|f64|s32|u32|s8|u8|pred|s64|u64)\[([0-9,]*)\]")
_BYTES = {"bf16": 2, "f16": 2, "f32": 4, "f64": 8, "s32": 4, "u32": 4,
          "s8": 1, "u8": 1, "pred": 1, "s64": 8, "u64": 8}


def _shape_bytes(text: str) -> int:
    """Sum byte sizes of all typed shapes in an HLO type string."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(text):
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict:
    """Per-collective-kind output bytes from compiled HLO text.

    Counts each instruction once (scan bodies are therefore single-counted --
    the roofline probes correct for that by extrapolating unrolled shallow
    models instead of trusting these raw numbers on deep scans).
    """
    out = {k: 0 for k in COLLECTIVES}
    counts = {k: 0 for k in COLLECTIVES}
    for line in hlo_text.splitlines():
        stripped = line.strip()
        # "%name = TYPE all-reduce(...)" -- take lhs type bytes
        m = re.match(r"%?[\w.\-]+\s*=\s*(\([^)]*\)|\S+)\s+([\w\-]+)", stripped)
        if not m:
            continue
        op = m.group(2)
        if op.endswith("-start"):
            op = op[:-6]
        if op in COLLECTIVES:
            out[op] += _shape_bytes(m.group(1))
            counts[op] += 1
    return {"bytes": out, "counts": counts,
            "total_bytes": int(sum(out.values()))}


# ------------------------------ cell builders --------------------------------

def _shardings_for(tree_specs, mesh):
    return jax.tree.map(
        lambda spec: NamedSharding(mesh, meshctx.spec(*spec) if isinstance(spec, tuple) else spec),
        tree_specs, is_leaf=lambda x: isinstance(x, tuple))


def _batch_shardings(batch_struct, mesh):
    def spec_for(path_leaf):
        if path_leaf.ndim == 2:
            return NamedSharding(mesh, meshctx.spec("dp", None))
        return NamedSharding(mesh, meshctx.spec("dp", None, None))
    return jax.tree.map(spec_for, batch_struct)


def _serving_layout(param_shardings, mesh):
    """Decode-time weight layout (opt_serving_layout).

    At one token per step there is no batch to amortize FSDP: GSPMD
    all-gathers every data-sharded weight each step (measured as the dominant
    long_500k/decode collective).  Re-lay the weights so the 'data' axis
    shards a *contraction* (or output) dimension instead: the per-token
    matmul then emits a tiny partial that one psum fixes, and no weight ever
    moves.  KV caches keep the 'model' axis (sequence-sharded flash-decode).
    """
    def rewrite(path, sh):
        names = [getattr(p, "key", None) for p in path]
        leaf = names[-1] if names else None
        def ns(*axes):
            return NamedSharding(mesh, meshctx.spec(*axes))
        if leaf in ("w_gate", "w_up"):
            if len(sh.spec) == 4:      # MoE experts (G, E, d, ff)
                return ns(None, "model", None, "data")
            return ns(None, None, "data")          # dense MLP (G, d, ff)
        if leaf == "w_down":
            if len(sh.spec) == 4:      # (G, E, ff, d)
                return ns(None, "model", "data", None)
            return ns(None, "data", None)          # (G, ff, d)
        if leaf in ("wq", "wk", "wv", "wr", "wg"):
            return ns(None, None, "data")          # out-dim over data
        if leaf == "wo":
            return ns(None, "data", None)          # in-dim over data -> psum
        if leaf in ("in_proj", "x_proj", "dt_proj", "out_proj"):
            # mamba: keep d_inner on 'model' (state layout), drop 'data'
            return NamedSharding(mesh, PSpecDrop(sh.spec, "data"))
        if leaf in ("embed", "head"):
            return sh                               # vocab stays model-sharded
        # everything else: drop 'data' (replicate small tensors)
        return NamedSharding(mesh, PSpecDrop(sh.spec, "data"))

    return jax.tree_util.tree_map_with_path(rewrite, param_shardings)


def PSpecDrop(spec, axis):
    out = []
    for entry in spec:
        if entry == axis:
            out.append(None)
        elif isinstance(entry, tuple):
            kept = tuple(a for a in entry if a != axis)
            out.append(kept if kept else None)
        else:
            out.append(entry)
    return P(*out)


def _sanitize(structs, shardings, mesh):
    """Explicit pjit in_shardings require exact divisibility (constraints
    would pad).  Replicate any dimension whose size does not divide its mesh
    axes -- the production choice for odd head counts / vocab sizes / short
    memory axes (waste surfaces in the roofline ratio)."""
    def fix(struct, sh):
        if not isinstance(sh, NamedSharding):
            return sh
        spec = sh.spec
        new = []
        for dim, axes in zip(struct.shape, tuple(spec) + (None,) * (len(struct.shape) - len(spec))):
            if axes is None:
                new.append(None)
                continue
            names = axes if isinstance(axes, tuple) else (axes,)
            total = 1
            for nm in names:
                total *= mesh.shape[nm]
            new.append(axes if dim % total == 0 else None)
        return NamedSharding(mesh, P(*new))

    return jax.tree.map(fix, structs, shardings)


def build_cell(cfg, shape_name: str, mesh, scan_unroll=False, ce_chunk=None):
    """Returns (fn, example_args, in_shardings, out_shardings)."""
    info = SHAPES[shape_name]
    model = build(cfg)
    model.scan_unroll = scan_unroll
    model.ce_chunk = ce_chunk
    param_structs = model.shapes(jnp.bfloat16)
    param_shardings = _sanitize(param_structs,
                                _shardings_for(model.specs(), mesh), mesh)

    batch_struct = input_specs(cfg, info["batch"], info["seq"], kind=info["kind"])
    batch_shardings = _sanitize(batch_struct,
                                _batch_shardings(batch_struct, mesh), mesh)

    if info["kind"] == "train":
        opt = AdamW(lr=1e-4, state_dtype=jnp.float32)
        opt_struct = jax.eval_shape(opt.init, param_structs)
        opt_shardings = {
            "m": param_shardings, "v": param_shardings,
            "count": NamedSharding(mesh, P()),
        }
        step = make_train_step(model, opt)
        args = (param_structs, opt_struct, batch_struct)
        in_sh = (param_shardings, opt_shardings, batch_shardings)
        out_sh = (param_shardings, opt_shardings,
                  jax.tree.map(lambda _: NamedSharding(mesh, P()),
                               {"loss": 0, "grad_norm": 0}))
        return step, args, in_sh, out_sh

    if info["kind"] == "prefill":
        step = make_prefill_step(model, max_seq=info["seq"])
        args = (param_structs, batch_struct)
        in_sh = (param_shardings, batch_shardings)
        return step, args, in_sh, None

    # decode: one token against a cache of length seq
    if getattr(cfg, "opt_serving_layout", False):
        param_shardings = _sanitize(
            param_structs, _serving_layout(param_shardings, mesh), mesh)
    cache_struct = jax.eval_shape(
        lambda: model.init_cache(info["batch"], info["seq"], jnp.bfloat16))
    cache_shardings = _sanitize(
        cache_struct,
        jax.tree.map(lambda sp: NamedSharding(mesh, sp),
                     model.cache_specs(cache_struct)),
        mesh)
    tok_struct = jax.ShapeDtypeStruct((info["batch"], 1), jnp.int32)
    tok_sharding = _sanitize(tok_struct,
                             NamedSharding(mesh, meshctx.spec("dp", None)), mesh)

    def decode_fn(params, cache, tokens):
        logits, cache = model.decode_step(params, cache, tokens)
        return jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32), cache

    args = (param_structs, cache_struct, tok_struct)
    in_sh = (param_shardings, cache_shardings, tok_sharding)
    out_sh = (_sanitize(jax.ShapeDtypeStruct((info["batch"],), jnp.int32),
                        NamedSharding(mesh, meshctx.spec("dp")), mesh),
              cache_shardings)
    return decode_fn, args, in_sh, out_sh


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             scan_unroll=False, cfg_override=None, ce_chunk=None,
             mesh=None) -> dict:
    cfg = cfg_override or get(arch)
    ok, why = cell_supported(cfg, shape_name)
    if not ok:
        return {"arch": arch, "shape": shape_name,
                "mesh": "multi" if multi_pod else "single",
                "status": "skipped", "reason": why}
    if mesh is None:
        mesh = make_production_mesh(multi_pod=multi_pod)
    record = {"arch": arch, "shape": shape_name,
              "mesh": "multi" if multi_pod else "single",
              "mesh_shape": dict(mesh.shape), "status": "ok",
              # the coded-matmul deployment this cell would run with
              # (registry-validated at ArchConfig construction)
              "coded": {"scheme": cfg.coded.scheme,
                        "backend": cfg.coded.backend,
                        "out_sharded": cfg.coded.out_sharded}}
    with meshctx.use_mesh(mesh):
        fn, args, in_sh, out_sh = build_cell(cfg, shape_name, mesh,
                                             scan_unroll=scan_unroll,
                                             ce_chunk=ce_chunk)
        t0 = time.time()
        jitted = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh)
        lowered = jitted.lower(*args)
        record["lower_s"] = round(time.time() - t0, 2)
        t0 = time.time()
        compiled = lowered.compile()
        record["compile_s"] = round(time.time() - t0, 2)

        ca = compat.cost_analysis(compiled)
        record["cost_analysis"] = {
            "flops_per_device": float(ca.get("flops", -1)),
            "bytes_per_device": float(ca.get("bytes accessed", -1)),
            "transcendentals": float(ca.get("transcendentals", 0)),
        }
        ma = compiled.memory_analysis()
        if ma is not None:
            record["memory_analysis"] = {
                "argument_bytes": int(ma.argument_size_in_bytes),
                "output_bytes": int(ma.output_size_in_bytes),
                "temp_bytes": int(ma.temp_size_in_bytes),
                "alias_bytes": int(ma.alias_size_in_bytes),
                "peak_bytes_est": int(ma.argument_size_in_bytes
                                      + ma.output_size_in_bytes
                                      + ma.temp_size_in_bytes
                                      - ma.alias_size_in_bytes),
            }
        txt = compiled.as_text()
        record["collectives"] = collective_bytes(txt)
        record["hlo_chars"] = len(txt)
    return record


def sweep_cell(arch: str, shape: str, multi_pod: bool, outdir: pathlib.Path,
               force: bool = False, mesh=None, cfg_override=None,
               verbose: bool = False) -> dict:
    """Run one cell and persist its record (ok, skipped, or error).

    A family that fails to lower/compile is surfaced as an ``error`` record
    carrying the exception string -- the report renders it as a table row
    instead of the family silently vanishing from the sweep.

    The on-disk cache is keyed by (arch, shape, mesh kind) only, so a
    ``mesh``/``cfg_override`` call is never served from (or mixed into a
    later read of) the cache under a key describing a different config: it
    always recomputes and overwrites.  Cache hits are marked ``cached``.
    """
    tag = f"{arch}__{shape}__{'multi' if multi_pod else 'single'}"
    path = pathlib.Path(outdir) / f"{tag}.json"
    ad_hoc = mesh is not None or cfg_override is not None
    if path.exists() and not force and not ad_hoc:
        return dict(json.loads(path.read_text()), cached=True)
    if verbose:
        print(f"[dryrun] {tag}: lowering...", flush=True)
    try:
        rec = run_cell(arch, shape, multi_pod, mesh=mesh,
                       cfg_override=cfg_override)
    except Exception as e:  # noqa: BLE001 -- report and continue sweep
        rec = {"arch": arch, "shape": shape,
               "mesh": "multi" if multi_pod else "single",
               "status": "error", "error": f"{type(e).__name__}: {e}"}
    path.write_text(json.dumps(rec, indent=1))
    return rec


def main():
    # the production meshes are described on the host platform: pin its
    # device count before the first backend call (never at import)
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "2")
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="all", help="arch id or 'all'")
    ap.add_argument("--shape", default="all", choices=["all"] + list(SHAPES))
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--out", default=str(OUT_DIR))
    ap.add_argument("--force", action="store_true", help="recompute existing")
    args = ap.parse_args()

    outdir = pathlib.Path(args.out)
    outdir.mkdir(parents=True, exist_ok=True)

    archs = sorted(ARCHS) if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    failures = 0
    for arch in archs:
        for shape in shapes:
            for multi in meshes:
                tag = f"{arch}__{shape}__{'multi' if multi else 'single'}"
                rec = sweep_cell(arch, shape, multi, outdir, force=args.force,
                                 verbose=True)
                if rec.get("cached"):
                    print(f"[dryrun] {tag}: cached")
                    continue
                status = rec["status"]
                extra = ""
                if status == "ok":
                    extra = (f" compile={rec['compile_s']}s "
                             f"flops/dev={rec['cost_analysis']['flops_per_device']:.3g} "
                             f"coll={rec['collectives']['total_bytes']:.3g}B")
                elif status == "error":
                    failures += 1
                print(f"[dryrun] {tag}: {status}{extra}", flush=True)
    print(f"[dryrun] done, {failures} failures")
    return 0 if failures == 0 else 1


if __name__ == "__main__":
    raise SystemExit(main())
