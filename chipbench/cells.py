"""Resolve a cell of ``BENCHMARK.json`` to its files, by name.

A workload names a configuration and a traffic mix; each metric is a name.
They are found so:

* configuration ``<c>``  -> the ``file`` its ``configs`` entry gives
  (``chipbench/configs/<c>.json``), whose keys are ``CONFIG_KEYS``: a key
  the harness does not read, or a value it does not implement, is refused;
* traffic ``<t>``        -> ``chipbench/traffic/<t>.json``;
* metric ``<m>``         -> ``chipbench/metrics/<m>.py``, whose ``read(run)``
  returns the metric's value or None when the run has nothing to read.

A new cell, traffic mix or metric is therefore new files and new entries in
``BENCHMARK.json``, and no edit here.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
BENCHMARK = ROOT / "BENCHMARK.json"


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: tuple   # the BENCHMARK.json entries this cell reports
    per_layer: tuple

    def metrics(self, trace: bool) -> tuple:
        """The metrics a run prints: per-layer ones when traced."""
        return self.per_layer if trace else self.end_to_end


def load_benchmark(path: pathlib.Path = BENCHMARK) -> dict:
    with open(path) as f:
        return json.load(f)


#: what a configuration file holds: the keys the harness reads, and the ones
#: that only document the deployment and its cuts (``paper``, ``reduced``,
#: ``assumed``, ``deployment``)
CONFIG_KEYS = {
    "name", "source", "deployment", "paper", "reduced", "assumed",
    "s", "r", "t", "nnz_a", "nnz_b", "block_size",
    "m", "n", "num_workers", "num_stragglers", "plan_seed",
    "scheme", "backend", "decode", "compute_dtype", "precision",
    "check_columns", "check_projections", "check_products", "limits",
}
#: the numbers ``run.check_kept`` compares, each with a limit
LIMITS = {"err_max", "err_fro", "proj_max"}


def check_config(config: dict, traffic: dict, chips: int, where: str):
    """Refuse a configuration whose keys or values the harness does not run
    as written."""
    keys = set(config)
    if keys != CONFIG_KEYS:
        raise ValueError(f"{where}: unknown keys {sorted(keys - CONFIG_KEYS)}, "
                         f"missing {sorted(CONFIG_KEYS - keys)}")
    if config["precision"] != "highest":
        raise ValueError(f"{where}: precision {config['precision']!r}; the "
                         "reference computes at 'highest'")
    if config["nnz_b"] != config["s"] * config["t"]:
        raise ValueError(f"{where}: nnz_b must be s * t: the device path takes "
                         "B dense")
    if config["decode"] not in ("psum", "reduce_scatter"):
        raise ValueError(f"{where}: decode {config['decode']!r}")
    bs = config["block_size"]
    if config["s"] % bs or config["r"] % bs:
        raise ValueError(f"{where}: s and r must be multiples of block_size")
    workers = config["num_workers"]
    if workers < chips or workers % chips:
        raise ValueError(f"{where}: {workers} workers on {chips} chips; the "
                         "mesh axis of one device per chip splits the workers "
                         "into equal contiguous runs, so their number must be "
                         "a whole multiple of the chips")
    if set(config["limits"]) != LIMITS:
        raise ValueError(f"{where}: limits must name exactly {sorted(LIMITS)}")
    dead = (traffic.get("membership") or {}).get("dead_workers", 0)
    if dead > config["num_stragglers"]:
        raise ValueError(f"{where}: the traffic kills {dead} workers, the code "
                         f"is held to {config['num_stragglers']}")


def _named(entries, name: str, what: str) -> dict:
    found = [e for e in entries if e["name"] == name]
    if len(found) != 1:
        raise KeyError(f"{what} {name!r}: {len(found)} entries in BENCHMARK.json")
    return found[0]


def _reports(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def config_path(entry: dict) -> pathlib.Path:
    return ROOT / entry["file"]


def traffic_path(name: str) -> pathlib.Path:
    return HERE / "traffic" / f"{name}.json"


def metric_path(name: str) -> pathlib.Path:
    return HERE / "metrics" / f"{name}.py"


def resolve(bench: dict, workload: str) -> Cell:
    """The cell named ``workload``, with its configuration and traffic read."""
    from chipbench import generator

    w = _named(bench["workloads"], workload, "workload")
    entry = _named(bench["configs"], w["config"], "configuration")
    with open(config_path(entry)) as f:
        config = json.load(f)
    if config.get("name") != entry["name"]:
        raise ValueError(f"{entry['file']} names {config.get('name')!r}, "
                         f"not {entry['name']!r}")
    traffic = generator.load(traffic_path(w["traffic"]))
    check_config(config, traffic, int(w["chips"]), entry["file"])
    e2e = tuple(m for m in bench["end_to_end"] if _reports(m, workload))
    layer = tuple(m for m in bench["per_layer"] if _reports(m, workload))
    for m in e2e + layer:
        if not metric_path(m["name"]).is_file():
            raise FileNotFoundError(f"metric {m['name']!r} has no reader "
                                    f"{metric_path(m['name'])}")
    return Cell(name=workload, chips=int(w["chips"]), config=config,
                traffic=traffic, end_to_end=e2e, per_layer=layer)


_READERS: dict = {}


def reader(name: str):
    """The ``read(run)`` function of metric ``name``."""
    if name not in _READERS:
        path = metric_path(name)
        spec = importlib.util.spec_from_file_location(
            f"chipbench_metric_{name}", path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        _READERS[name] = module.read
    return _READERS[name]
