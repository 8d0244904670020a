"""CPU tests of the benchmark's data side: BENCHMARK.json, the files each
cell resolves to by name, and the traffic generator."""

from __future__ import annotations

import json
import re

import numpy as np
import pytest

from chipbench import cells, generator

BENCH = cells.load_benchmark()
WORKLOADS = [w["name"] for w in BENCH["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SEEDS = [0, 7, 2 ** 31 + 11, 3_000_000_001]


@pytest.mark.parametrize("workload", WORKLOADS)
def test_workload_resolves_to_its_files_by_name(workload):
    cell = cells.resolve(BENCH, workload)
    w = next(x for x in BENCH["workloads"] if x["name"] == workload)
    entry = next(c for c in BENCH["configs"] if c["name"] == w["config"])
    assert cells.config_path(entry).is_file()
    assert cells.config_path(entry).is_relative_to(cells.HERE)
    assert cells.traffic_path(w["traffic"]).is_file()
    assert cell.config["name"] == w["config"]
    assert cell.chips == w["chips"]
    assert cell.config["num_workers"] % cell.chips == 0
    names = [m["name"] for m in cell.end_to_end]
    assert "setup_s" in names and len(names) >= 2
    assert cell.per_layer
    for m in cell.end_to_end + cell.per_layer:
        assert callable(cells.reader(m["name"]))


def test_benchmark_names_units_and_bounds():
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in BENCH[key]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names)
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"] + BENCH["end_to_end"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        for w in m.get("workloads", ()):
            assert w in WORKLOADS
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        # each cell that reports the metric reports what it moves
        moved = next(x for x in BENCH["end_to_end"] if x["name"] == m["moves"])
        for w in m.get("workloads", WORKLOADS):
            assert w in moved.get("workloads", WORKLOADS)
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(
        1, len(BENCH["workloads"]) // 2)
    assert len(json.dumps(BENCH)) < 64 * 1024


def _named_files() -> dict:
    """{directory under chipbench/: the files BENCHMARK.json names there}."""
    return {
        "configs": {cells.config_path(c) for c in BENCH["configs"]},
        "traffic": {cells.traffic_path(w["traffic"]) for w in BENCH["workloads"]},
        "metrics": {cells.metric_path(m["name"])
                    for m in BENCH["end_to_end"] + BENCH["per_layer"]},
    }


@pytest.mark.parametrize("directory", ["configs", "traffic", "metrics"])
def test_every_file_of_a_cell_is_named_by_the_benchmark(directory):
    """No configuration, traffic mix or metric sits in the harness without
    the BENCHMARK.json entry that runs it: a cell is added whole or not at
    all."""
    here = {p for p in (cells.HERE / directory).iterdir()
            if p.suffix in (".json", ".py") and p.name != "__init__.py"}
    assert here and here == _named_files()[directory]


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda e: e["name"])
def test_config_reduced_keys_differ_from_the_paper(entry):
    with open(cells.config_path(entry)) as f:
        config = json.load(f)
    assert config["reduced"] == entry["reduced"]
    assert config["source"] == entry["source"]
    for key in entry["reduced"]:
        assert key in config
        assert config[key] != config["paper"].get(key)
    for name, limit in config["limits"].items():
        assert limit is not None and limit > 0, name


def test_tile_pattern_is_seeded_and_every_seed_has_the_same_shape():
    shapes = set()
    for seed in SEEDS:
        a = generator.tile_pattern(generator.streams(seed)["tiles"], row_blocks=128,
                                 col_blocks=128, live_fraction=0.3)
        b = generator.tile_pattern(generator.streams(seed)["tiles"], row_blocks=128,
                                 col_blocks=128, live_fraction=0.3)
        np.testing.assert_array_equal(a, b)
        assert all(len(set(row)) == len(row) for row in a.tolist())
        assert (np.diff(a, axis=1) > 0).all() and a.min() >= 0 and a.max() < 128
        shapes.add(a.shape)
    assert shapes == {(128, 38)}
    first, second = (generator.tile_pattern(generator.streams(s)["tiles"],
                                          row_blocks=128, col_blocks=128,
                                          live_fraction=0.02)
                     for s in SEEDS[:2])
    assert first.shape == (128, 3) and not np.array_equal(first, second)


def _churn_code():
    """A 4-worker, m=2 code as the cell's plan draws it: worker 0 alone holds
    block 0, so its loss cannot be decoded and the other three can."""
    return np.array([[4.0, 0.0], [0.0, 4.0], [0.0, 3.0], [0.0, 3.0]])


def _churn_masks(seed, products):
    churn = generator.load(cells.traffic_path("churn"))
    sched = generator.Schedule(churn, generator.streams(seed)["membership"],
                               _churn_code())
    return sched, [sched.mask(i) for i in range(products)]


@pytest.mark.parametrize("seed", SEEDS)
def test_churn_schedule_names_only_decodable_masks_and_repeats(seed):
    code = _churn_code()
    dead = generator.load(cells.traffic_path("churn"))["membership"]["dead_workers"]
    sched, got = _churn_masks(seed, 64)
    _, again = _churn_masks(seed, 64)
    for mask, other in zip(got, again):
        assert (mask is None) == (other is None)
        if mask is None:
            continue
        np.testing.assert_array_equal(mask, other)
        assert (~mask).sum() == dead
        assert np.linalg.matrix_rank(code * mask[:, None]) == code.shape[1]
    used = {None if m is None else tuple(m) for m in got}
    # every product draws anew: each decodable loss and the undecodable one
    # (decoded from every worker) all come up in 64 products
    assert used == {None} | {tuple(m) for m in sched.masks}
    assert [sched.b_index(i) for i in range(4)] == [0, 1, 0, 1]
    other_seed = [None if m is None else tuple(m)
                  for m in _churn_masks(seed + 1, 64)[1]]
    assert other_seed != [None if m is None else tuple(m) for m in got]


def test_churn_stragglers_are_drawn_uniformly_over_every_worker():
    """Each worker straggles in a quarter of the products, worker 0 among
    them, whose products are decoded from every worker; consecutive products
    share a straggler a quarter of the time."""
    _, got = _churn_masks(SEEDS[0], 8000)
    keys = [None if m is None else tuple(m) for m in got]
    for key in set(keys):
        assert keys.count(key) / len(keys) == pytest.approx(0.25, abs=0.02)
    same = sum(a == b for a, b in zip(keys, keys[1:])) / (len(keys) - 1)
    assert same == pytest.approx(0.25, abs=0.02)


def test_decodable_masks_leave_out_a_loss_that_breaks_rank():
    code = np.array([[1.0, 0.0], [0.0, 1.0], [1.0, 0.0], [2.0, 0.0]])
    masks = generator.decodable_masks(code, 1)
    # losing worker 1, the only one that sees block 1, cannot be decoded
    assert [tuple(m) for m in masks] == [(False, True, True, True),
                                         (True, True, False, True),
                                         (True, True, True, False)]


def test_live_tile_fraction_is_the_papers_density_at_the_tile_size():
    # 600000 uniform nonzeros over 150000^2 put 0.4369 into each 128^2 tile
    got = generator.live_tile_fraction(600_000, 150_000, 150_000, 128)
    assert got == pytest.approx(1 - np.exp(-600_000 * 128 ** 2 / 150_000 ** 2))
    assert got == pytest.approx(0.35396, abs=1e-5)
    # the same density at the run's size gives the same share
    assert generator.live_tile_fraction(7158, 16384, 16384, 128) == pytest.approx(
        got, abs=1e-4)
    assert generator.live_tile_fraction(10 ** 9, 256, 256, 128) == 1.0


def _write(tmp_path, obj):
    path = tmp_path / "x.json"
    path.write_text(json.dumps(obj))
    return path


@pytest.mark.parametrize("extra", [{"clients": 4}, {"loop": "open"},
                                   {"membership": {"healthy_products": 2}}])
def test_a_traffic_key_the_generator_does_not_read_is_refused(tmp_path, extra):
    with pytest.raises(ValueError):
        generator.load(_write(tmp_path, dict({"name": "x", "b_operands": 2}, **extra)))


#: (the chips of the cell whose configuration is changed, the change)
REFUSED = [(1, {"precision": "high"}), (1, {"distribution": "robust_soliton"}),
           (1, {"nnz_b": 7}), (1, {"decode": "gather"}),
           (4, {"num_workers": 6}), (1, {"limits": {"err_max": 1.0}})]


@pytest.mark.parametrize("chips,change", REFUSED,
                         ids=[f"change{j}" for j in range(len(REFUSED))])
def test_a_config_value_the_harness_does_not_run_is_refused(chips, change):
    w = next(x for x in BENCH["workloads"] if x["chips"] == chips)
    entry = next(c for c in BENCH["configs"] if c["name"] == w["config"])
    with open(cells.config_path(entry)) as f:
        config = dict(json.load(f), **change)
    traffic = generator.load(cells.traffic_path(w["traffic"]))
    with pytest.raises(ValueError):
        cells.check_config(config, traffic, w["chips"], entry["file"])


def test_a_traffic_that_kills_more_than_the_code_holds_is_refused():
    w = next(x for x in BENCH["workloads"] if x["chips"] == 1)
    entry = next(c for c in BENCH["configs"] if c["name"] == w["config"])
    with open(cells.config_path(entry)) as f:
        config = json.load(f)
    churn = generator.load(cells.traffic_path("churn"))
    with pytest.raises(ValueError, match="held to 0"):
        cells.check_config(config, churn, 1, entry["file"])


@pytest.mark.parametrize("chips", [1, 4])
def test_workers_a_whole_multiple_of_the_chips_are_accepted(chips):
    """20 workers, the m = n = 4 code with two stragglers, fit one chip and a
    2x2 host; no count that is not a whole multiple of the chips is taken."""
    w = next(x for x in BENCH["workloads"] if x["chips"] == 4)
    entry = next(c for c in BENCH["configs"] if c["name"] == w["config"])
    with open(cells.config_path(entry)) as f:
        config = dict(json.load(f), m=4, n=4, num_stragglers=2)
    traffic = generator.load(cells.traffic_path(w["traffic"]))
    accepted = []
    for workers in range(25):
        try:
            cells.check_config(dict(config, num_workers=workers), traffic,
                               chips, entry["file"])
        except ValueError as e:
            assert "whole multiple of the chips" in str(e)
        else:
            accepted.append(workers)
    assert 20 in accepted
    assert accepted == list(range(chips, 25, chips))
