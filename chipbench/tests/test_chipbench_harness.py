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
