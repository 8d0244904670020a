import dataclasses
import os
import pathlib
import subprocess
import sys

import numpy as np
import pytest

import repro.configs as configs
from repro.configs.base import ArchConfig


def test_all_ten_archs_registered():
    expected = {
        "whisper-medium", "rwkv6-3b", "llama-3.2-vision-11b", "dbrx-132b",
        "qwen3-moe-30b-a3b", "internlm2-1.8b", "starcoder2-7b",
        "command-r-35b", "qwen2-7b", "jamba-1.5-large-398b",
    }
    assert expected <= set(configs.ARCHS)


def test_param_counts_match_public_scale():
    """Sanity: analytic parameter counts land near the published sizes."""
    expect = {
        "internlm2-1.8b": (1.5e9, 2.5e9),
        "qwen2-7b": (6e9, 9e9),
        "starcoder2-7b": (6e9, 9e9),
        "llama-3.2-vision-11b": (8e9, 13e9),
        "command-r-35b": (27e9, 40e9),  # 30.3B with the assigned ff/tied-embed
        "dbrx-132b": (110e9, 145e9),
        "qwen3-moe-30b-a3b": (25e9, 36e9),
        "jamba-1.5-large-398b": (330e9, 440e9),
        "rwkv6-3b": (2e9, 4e9),
        "whisper-medium": (0.5e9, 0.9e9),  # enc-dec with untied 51865 vocab
    }
    for name, (lo, hi) in expect.items():
        n = configs.get(name).params_count()
        assert lo < n < hi, f"{name}: {n:,} outside [{lo:,}, {hi:,}]"


def test_moe_active_params_below_total():
    for name in ("dbrx-132b", "qwen3-moe-30b-a3b", "jamba-1.5-large-398b"):
        cfg = configs.get(name)
        assert cfg.active_params_count() < 0.6 * cfg.params_count()


def test_layer_plans():
    jamba = configs.get("jamba-1.5-large-398b")
    plan = jamba.layer_plan()
    assert len(plan) == 8
    assert sum(1 for m, _ in plan if m == "attn") == 1
    assert plan[4][0] == "attn"  # attn_layer_offset = 4
    assert sum(1 for _, f in plan if f == "moe") == 4  # every other layer

    vlm = configs.get("llama-3.2-vision-11b")
    plan = vlm.layer_plan()
    assert sum(1 for m, _ in plan if m == "cross") == 1
    assert len(plan) == 5

    rwkv = configs.get("rwkv6-3b")
    assert all(m == "rwkv" for m, _ in rwkv.layer_plan())


def test_with_opts_validation():
    cfg = configs.get("internlm2-1.8b")
    c2 = cfg.with_opts(("fused_ce", "onehot_cache"))
    assert c2.opt_fused_ce and c2.opt_onehot_cache and not c2.opt_seq_parallel
    with pytest.raises(ValueError):
        cfg.with_opts(("not_a_real_opt",))


def test_reduced_configs_are_small():
    for name in configs.ARCHS:
        r = configs.get(name).reduced()
        assert r.params_count() < 5e7, name
        assert r.num_layers <= 16


def test_with_opts_rejects_bad_coded_backend():
    cfg = configs.get("internlm2-1.8b")
    assert cfg.coded_backend == "dense_scan"
    c2 = dataclasses.replace(cfg, coded_backend="block_sparse")
    assert c2.coded_backend == "block_sparse"
    with pytest.raises(ValueError, match="coded_backend"):
        dataclasses.replace(cfg, coded_backend="csr")


def test_coded_backend_validates_against_live_registry():
    """No hardcoded backend tuple: a backend registered AFTER configs were
    defined is immediately a legal coded_backend value."""
    from repro.core import coded_backends

    cfg = configs.get("internlm2-1.8b")
    name = "_test_backend"
    try:
        coded_backends.register_backend(name, doc="registry-desync probe")
        c2 = dataclasses.replace(cfg, coded_backend=name)
        assert c2.coded.backend == name
    finally:
        coded_backends._REGISTRY.pop(name, None)


def test_archconfig_embeds_coded_matmul_config():
    from repro.coded import CodedMatmulConfig

    cfg = configs.get("internlm2-1.8b")
    assert isinstance(cfg.coded, CodedMatmulConfig)
    # the alias mirrors the embedded config both ways
    c2 = dataclasses.replace(cfg, coded_backend="block_sparse")
    assert c2.coded.backend == "block_sparse"
    c3 = cfg.with_coded(backend="block_sparse", out_sharded=True)
    assert c3.coded_backend == "block_sparse" and c3.coded.out_sharded
    # a later replace of the alias keeps the other coded knobs
    c4 = dataclasses.replace(c3, coded_backend="dense_scan")
    assert c4.coded.backend == "dense_scan" and c4.coded.out_sharded


def test_archconfig_explicit_coded_not_clobbered_by_alias_default():
    # passing coded= alone must win: the alias default (None = follow
    # coded) may not silently reset an explicitly chosen backend
    from repro.coded import CodedMatmulConfig

    base = configs.get("internlm2-1.8b")
    cfg = dataclasses.replace(
        base, coded=CodedMatmulConfig(backend="block_sparse",
                                      out_sharded=True),
        coded_backend=None)
    assert cfg.coded.backend == "block_sparse" and cfg.coded.out_sharded
    assert cfg.coded_backend == "block_sparse"  # mirror follows coded
    direct = ArchConfig(
        name="t", family="dense", num_layers=2, d_model=64, num_heads=4,
        num_kv_heads=4, d_ff=128, vocab_size=512,
        coded=CodedMatmulConfig(backend="block_sparse"))
    assert direct.coded.backend == "block_sparse"
    assert direct.coded_backend == "block_sparse"


_DRYRUN_RECORDS_SCRIPT = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "2")
import dataclasses, pathlib
import repro.configs as configs
from repro import compat
from repro.launch import dryrun, meshctx

outdir = pathlib.Path(sys.argv[1])
mesh = compat.make_mesh((4, 2), ("data", "model"),
                        axis_types=compat.auto_axis_types(2))
cfg = dataclasses.replace(
    configs.get("internlm2-1.8b"), num_layers=1, d_model=64, num_heads=4,
    num_kv_heads=2, head_dim=16, d_ff=128, vocab_size=512, max_seq=64)
dryrun.SHAPES["tiny_train"] = dict(seq=32, batch=8, kind="train")
dryrun.SHAPES["tiny_decode"] = dict(seq=32, batch=8, kind="decode")
for shp in ("tiny_train", "tiny_decode"):
    rec = dryrun.sweep_cell("internlm2-1.8b", shp, False, outdir,
                            mesh=mesh, cfg_override=cfg)
    assert rec["status"] == "ok", rec
# a family that fails must surface its error string as a record, not vanish
rec2 = dryrun.sweep_cell("no-such-arch", "tiny_train", False, outdir, mesh=mesh)
assert rec2["status"] == "error" and "KeyError" in rec2["error"], rec2
print("RECORDS-OK")
"""


def test_report_tables_render(tmp_path):
    from repro.launch.report import dryrun_table, perf_table, roofline_table

    # an empty/missing records dir renders an explicit placeholder, never a
    # silently bare header
    empty = dryrun_table(root=tmp_path / "nothing-here")
    assert "no dryrun records" in empty

    # real records: one compiled tiny cell + one errored family, produced by
    # the dryrun sweep machinery in a subprocess (8-device mesh isolation)
    outdir = tmp_path / "dryrun"
    outdir.mkdir()
    env = dict(os.environ, PYTHONPATH=str(pathlib.Path(__file__).parents[1] / "src"))
    proc = subprocess.run(
        [sys.executable, "-c", _DRYRUN_RECORDS_SCRIPT, str(outdir)],
        env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-4000:]

    d = dryrun_table(root=outdir)
    assert d.count("|") > 50          # header + data rows
    assert "| ok |" in d              # the compiled family is a data row
    assert "error: KeyError" in d     # the failed family surfaces its error
    r = roofline_table()
    assert "dominant" in r or "arch" in r
    perf_table()  # renders without error even if variants are sparse


def test_machine_peaks_by_device_kind():
    """Peaks come from the published table by device kind; an unknown kind
    (the CPU backend here included) raises instead of defaulting, and CPU
    calibration is opt-in and labelled as such."""
    from repro.launch.roofline import DEVICE_PEAKS, machine_peaks

    v5e = machine_peaks("TPU v5 lite")
    assert (v5e["peak_flops"], v5e["peak_bw"]) == (197e12, 819e9)
    assert v5e["peak_int8_ops"] == 393e12 and "TPU v5e" in v5e["source"]
    assert set(DEVICE_PEAKS) == {"TPU v5 lite"}
    with pytest.raises(ValueError, match="no published peaks"):
        machine_peaks("TPU v9 imaginary")
    with pytest.raises(ValueError, match="no published peaks"):
        machine_peaks()                        # this CPU has none
    cpu = machine_peaks(calibrate_cpu=True, reps=1)
    assert cpu["source"] == "calibrated-cpu" and cpu["peak_flops"] > 0


def test_roofline_import_sets_no_xla_flags():
    """Importing the kernel-roofline helpers (and the dry-run module they
    pull in) must not touch the environment; only their main()s set the
    host device count."""
    code = ("import os; os.environ.pop('XLA_FLAGS', None); "
            "import repro.launch.roofline; "
            "print(os.environ.get('XLA_FLAGS'))")
    env = dict(os.environ, PYTHONPATH=str(pathlib.Path(__file__).parents[1] / "src"))
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.strip() == "None"


def test_compile_cache_dir_env_wins_else_fixed_checkout_path(monkeypatch):
    import jax

    from repro.launch import compile_cache

    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
        assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir == before  # untouched
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = compile_cache.enable_compile_cache()
        assert path == str(pathlib.Path(__file__).parents[1] / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
