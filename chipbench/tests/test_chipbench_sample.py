"""CPU tests of what set-up warms up and what the check keeps, with no device
work: the cells of BENCHMARK.json keep the sequences they kept before the
bound on many masks, and a code of many workers and masks is bounded."""

from __future__ import annotations

import json

import numpy as np
import pytest

from chipbench import cells, generator

BENCH = cells.load_benchmark()
WORKLOADS = [w["name"] for w in BENCH["workloads"]]
SEEDS = [0, 2 ** 31 + 11, 3_000_000_001]
PRODUCTS = 300          # about a window of either cell


def _code(config):
    from repro.coded import CodedMatmulConfig, plan

    ccfg = CodedMatmulConfig(scheme=config["scheme"], backend=config["backend"],
                             block_size=config["block_size"])
    return plan(ccfg, m=config["m"], n=config["n"],
                num_workers=config["num_workers"],
                seed=config["plan_seed"]).plan_.coefficient_matrix()


def _key(mask):
    return None if mask is None else tuple(bool(x) for x in mask)


def _old_warmup(schedule, products):
    """Set-up's pairs as the harness chose them before many masks were
    bounded: every pair, then again in turn."""
    pairs = [(b, None) for b in range(schedule.b_operands)]
    pairs += [(0, m) for m in schedule.masks]
    return [pairs[j % len(pairs)] for j in range(max(products, len(pairs)))]


def _old_kept(schedule, draws, max_kept):
    """The window's kept products as the harness chose them before: each
    new pair, and a 0.2 sample while fewer than ``max_kept`` are kept."""
    kept, seen = [], set()
    for i, draw in enumerate(draws):
        b, key = schedule.b_index(i), _key(schedule.mask(i))
        if (b, key) not in seen or (len(kept) < max_kept and draw < 0.2):
            seen.add((b, key))
            kept.append((i, b, key))
    return kept


def _takes(schedule, draws, sample):
    return [(i, schedule.b_index(i), _key(schedule.mask(i)),
             sample.take((schedule.b_index(i), _key(schedule.mask(i))), draw))
            for i, draw in enumerate(draws)]


def _schedule(config, traffic, seed):
    rng = generator.streams(seed)
    code = _code(config) if traffic.get("membership") else None
    return generator.Schedule(traffic, rng["membership"], code), rng


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("workload", WORKLOADS)
def test_the_cells_warm_up_and_keep_what_they_kept_before(workload, seed):
    cell = cells.resolve(BENCH, workload)
    limit = int(cell.config["check_products"])
    products = int(cell.traffic["warmup_products"])
    sched, rng = _schedule(cell.config, cell.traffic, seed)
    old, _ = _schedule(cell.config, cell.traffic, seed)
    assert len(sched.pairs) <= limit
    got = sched.warmup(products, limit, rng["warmup"])
    want = _old_warmup(old, products)
    assert [(b, _key(m)) for b, m in got] == [(b, _key(m)) for b, m in want]
    draws = rng["check"].random(PRODUCTS)
    takes = _takes(sched, draws,
                   generator.CheckSample(limit, len(sched.pairs)))
    kept = [(i, b, key) for i, b, key, take in takes if take is not None]
    assert kept == _old_kept(old, draws, limit)
    assert {take for *_, take in takes} <= {"columns", None}


def _many_workers(seed):
    """The m = n = 4 code on 20 workers with two stragglers a product, over
    the four-chip cell's configuration: 187 decodable masks."""
    w = next(x for x in BENCH["workloads"] if x["chips"] == 4)
    entry = next(c for c in BENCH["configs"] if c["name"] == w["config"])
    with open(cells.config_path(entry)) as f:
        config = dict(json.load(f), m=4, n=4, num_workers=20, num_stragglers=2)
    traffic = {"name": "x", "b_operands": 2, "warmup_products": 4,
               "membership": {"dead_workers": 2}}
    return config, traffic, _schedule(config, traffic, seed)


@pytest.mark.parametrize("seed", SEEDS)
def test_many_masks_warm_up_a_seeded_sample_and_check_every_mask(seed):
    config, traffic, (sched, rng) = _many_workers(seed)
    limit = int(config["check_products"])
    assert len(sched.pairs) > limit
    warm = sched.warmup(traffic["warmup_products"], limit, rng["warmup"])
    assert len(warm) == traffic["warmup_products"]
    assert [(b, m) for b, m in warm[:2]] == [(0, None), (1, None)]
    drawn = [_key(m) for _, m in warm[2:]]
    assert len(set(drawn)) == len(drawn)
    assert set(drawn) <= {_key(m) for m in sched.masks}
    _, _, (again, rng2) = _many_workers(seed)
    assert drawn == [_key(m) for _, m in again.warmup(
        traffic["warmup_products"], limit, rng2["warmup"])[2:]]

    takes = _takes(sched, rng["check"].random(200),
                   generator.CheckSample(limit, len(sched.pairs)))
    assert sum(take == "columns" for *_, take in takes) == limit
    assert any(take == "projection" for *_, take in takes)
    used = {key for _, _, key, _ in takes}
    checked = {key for _, _, key, take in takes if take is not None}
    assert len(used) > limit and used <= checked
    # every pair is compared once it is new, and never again past the columns
    pairs = [(b, key) for _, b, key, take in takes if take == "projection"]
    assert len(pairs) == len(set(pairs))


def test_a_seed_draws_its_own_warm_up_masks():
    draws = set()
    for seed in SEEDS:
        config, traffic, (sched, rng) = _many_workers(seed)
        warm = sched.warmup(traffic["warmup_products"],
                            int(config["check_products"]), rng["warmup"])
        draws.add(tuple(_key(m) for _, m in warm[2:]))
    assert len(draws) == len(SEEDS)


def test_the_warm_up_stream_moves_no_other_draw():
    """Adding the ``warmup`` stream left the streams before it as they were."""
    for seed in SEEDS:
        children = np.random.SeedSequence(seed).spawn(len(generator.STREAMS) - 1)
        rng = generator.streams(seed)
        for name, child in zip(generator.STREAMS, children):
            assert rng[name].random() == np.random.default_rng(child).random()


@pytest.mark.parametrize("pairs,late", [(2, "columns"), (3, "projection")])
def test_a_late_new_pair_keeps_columns_only_where_the_pairs_are_few(pairs, late):
    """Two sampled products fill the columns; a pair first seen after them
    keeps its columns as before where set-up's pairs number at most the
    check's products, and its projection alone where they number more."""
    sample = generator.CheckSample(2, pairs)
    first = [sample.take("a", 0.1), sample.take("a", 0.1), sample.take("a", 0.1)]
    assert first == ["columns", "columns", None]
    assert sample.take("b", 0.9) == late
    assert sample.take("b", 0.1) is None
