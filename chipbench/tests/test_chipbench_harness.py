"""CPU tests of the harness: where it refuses to run, and that a run at a small
size decides ``correct`` by a comparison that its control and each fault of
the timed path fail."""

from __future__ import annotations

import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

from chipbench import cells

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = cells.load_benchmark()
#: the faults of the timed path each cell can have, and the control in the
#: program's place: the exchange between chips exists only where there is
#: more than one
FAULTS = {w["name"]: ("unchanged", "half", "altered", "control")
          + (("exchange",) if w["chips"] > 1 else ())
          for w in BENCH["workloads"]}


def _env(**extra):
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "REPRO_KERNEL_LANE", "REPRO_PALLAS_INTERPRET")}
    env.update(JAX_PLATFORMS="cpu", **extra)
    return env


def _bench(cwd, *args, **env):
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "coded16k-w1.iterative",
         "--seed", "5", "--seconds", "1", "--trace", "0", *args],
        cwd=cwd, env=_env(**env), capture_output=True, text=True, timeout=300)


def test_harness_exits_nonzero_without_a_tpu():
    proc = _bench(ROOT)
    assert proc.returncode != 0
    assert proc.stdout == ""
    assert "need a TPU" in proc.stderr


def test_harness_refuses_a_kernel_override():
    proc = _bench(ROOT, REPRO_KERNEL_LANE="xla")
    assert proc.returncode != 0 and proc.stdout == ""
    assert "REPRO_KERNEL_LANE" in proc.stderr


def test_harness_exits_nonzero_without_the_program(tmp_path):
    for path in BENCH["paths"]:
        shutil.copytree(ROOT / path, tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    proc = _bench(tmp_path)
    assert proc.returncode != 0
    assert proc.stdout == ""
    assert "repro" in proc.stderr


#: run_cell on a configuration given as a file, with the traffic given as
#: JSON, on one host device; the mesh run_cell builds is reported on stderr,
#: and a result line is printed only where the run ends
MANY_WORKERS_RUN = """
import json, sys
from unittest import mock
sys.path[:0] = ['.', 'src']
from chipbench import cells, run as harness
from repro import compat

with open(sys.argv[1]) as f:
    config = json.load(f)
traffic = json.loads(sys.argv[2])
cells.check_config(config, traffic, 1, sys.argv[1])
cell = cells.Cell(name='scratch', chips=1, config=config, traffic=traffic,
                  end_to_end=(), per_layer=())
make_mesh = compat.make_mesh

def spy(*args, **kw):
    mesh = make_mesh(*args, **kw)
    print('mesh', json.dumps(dict(mesh.shape)), file=sys.stderr, flush=True)
    return mesh

with mock.patch.object(compat, 'make_mesh', spy):
    result, _ = harness.run_cell(cell, 2 ** 31 + 5, 1.0, False)
print(json.dumps(result))
"""


def test_many_workers_on_one_chip_reach_the_program(tmp_path):
    """The m = n = 4 code on 20 workers, two stragglers a product, on one
    device: the harness takes the configuration and builds a one-device
    mesh.  A program that cannot place 20 workers on it refuses with its own
    error and the run prints no result; one that can, runs it correctly."""
    entry = next(c for c in BENCH["configs"] if c["name"] == "coded16k-w4")
    with open(ROOT / entry["file"]) as f:
        config = dict(json.load(f), m=4, n=4, num_workers=20, num_stragglers=2,
                      s=512, r=512, t=512, nnz_a=7, nnz_b=512 * 512,
                      check_columns=64)
    traffic = {"name": "scratch", "b_operands": 2, "warmup_products": 4,
               "membership": {"dead_workers": 2}}
    cells.check_config(config, traffic, 1, "scratch")
    path = tmp_path / "many-workers.json"
    path.write_text(json.dumps(config))
    proc = subprocess.run(
        [sys.executable, "-c", MANY_WORKERS_RUN, str(path), json.dumps(traffic)],
        cwd=ROOT, env=_env(), capture_output=True, text=True, timeout=600)
    meshes = [json.loads(line[len("mesh "):])
              for line in proc.stderr.splitlines() if line.startswith("mesh ")]
    assert meshes == [{"model": 1}], proc.stderr[-4000:]
    if proc.returncode == 0:
        assert json.loads(proc.stdout.strip().splitlines()[-1])["correct"]
        return
    assert proc.stdout == ""
    last = proc.stderr.strip().splitlines()
    frames = [line for line in last if line.lstrip().startswith("File ")]
    assert "src/repro/" in frames[-1] and "chipbench" not in frames[-1], frames
    assert last[-1].startswith("ValueError: ") and "workers 20" in last[-1]


@pytest.fixture(scope="module")
def fault_runs():
    """{workload: {fault: {"correct", "check"}}}, one process per cell."""
    out = {}
    for workload, faults in FAULTS.items():
        proc = subprocess.run(
            [sys.executable, "-m", "chipbench.tests.fault_run", str(cells.BENCHMARK),
             workload, "none", *faults],
            cwd=ROOT, env=_env(), capture_output=True, text=True, timeout=600)
        assert proc.returncode == 0, proc.stderr[-4000:]
        out[workload] = json.loads(proc.stdout.strip().splitlines()[-1])
    return out


@pytest.mark.parametrize("workload", sorted(FAULTS))
def test_a_sound_small_run_is_correct(fault_runs, workload):
    run = fault_runs[workload]["none"]
    assert run["correct"], run["check"]
    assert list(run["check"])[-1] == "masks_unchecked"


@pytest.mark.parametrize("workload,fault", [
    (w, f) for w, faults in sorted(FAULTS.items()) for f in faults])
def test_a_fault_of_the_timed_path_is_not_correct(fault_runs, workload, fault):
    run = fault_runs[workload][fault]
    assert not run["correct"], run["check"]
    assert any(c["value"] > c["limit"] for c in run["check"].values())


@pytest.mark.parametrize("workload", sorted(FAULTS))
def test_the_control_fails_a_limit(fault_runs, workload):
    """A run with the reference one precision step lower in the program's
    place comes out as not correct, on a gap and not on the masks."""
    run = fault_runs[workload]["control"]
    assert not run["correct"], run["check"]
    gaps = {k: c for k, c in run["check"].items() if k != "masks_unchecked"}
    assert any(c["value"] > c["limit"] for c in gaps.values()), run["check"]
    assert run["check"]["masks_unchecked"]["value"] == 0
