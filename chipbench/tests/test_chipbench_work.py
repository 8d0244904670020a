"""CPU tests of the roofline's yardstick: the work count and the peak table."""

from __future__ import annotations

import dataclasses

import numpy as np
import pytest

from chipbench import peaks, work, xplane

BS = 2


def _tiny():
    """m = 2, n = 1 over s = r = 8 (4 x 4 tiles of 2 x 2), t = 6.

    A's live tiles by column block: cb 0 -> rows {0, 2}, cb 1 -> {3},
    cb 2 -> {}, cb 3 -> {1}.  Column group 0 is cb 0-1 (3 tiles, B rows
    {0, 2, 3}); column group 1 is cb 2-3 (1 tile, B row {1}).
    """
    tile_rows = [[0, 2], [3], [], [1]]
    cols = np.array([[0, 1], [1, 0], [0, 0]], np.int32)
    weights = np.array([[1.0, 0.5], [2.0, 0.0], [0.0, 0.0]], np.float32)
    return cols, weights, tile_rows


def test_work_matches_a_hand_count():
    cols, weights, tile_rows = _tiny()
    got = work.worker_work(cols, weights, 2, 1, tile_rows, s=8, r=8, t=6,
                           block_size=BS)
    bt, br, mn = 6, 4, 2
    # worker 0: slots on groups 0 and 1 -> 3 + 1 tiles, every tile read once,
    # B rows {0, 2, 3} and {1} of the one column group
    assert got[0]["tiles"] == 4
    assert got[0]["flops"] == 2 * BS * BS * bt * 4 + mn * br * bt
    assert got[0]["bytes"] == 4 * BS * BS * 4 + 4 * BS * bt * 4 + br * bt * 4
    # worker 1: one live slot on group 1 (the padded slot adds nothing)
    assert got[1]["tiles"] == 1
    assert got[1]["flops"] == 2 * BS * BS * bt * 1 + mn * br * bt
    assert got[1]["bytes"] == 1 * BS * BS * 4 + 1 * BS * bt * 4 + br * bt * 4
    # worker 2: no live slot, the decode combine and its result alone
    assert got[2]["tiles"] == 0
    assert got[2]["bytes"] == br * bt * 4
    # one worker a chip: the work of a chip is the mean over the workers
    per_chip = work.chip_work(got, 3)
    for key in ("flops", "bytes"):
        assert per_chip[key] == float(np.mean([w[key] for w in got]))
    assert per_chip["flops"] == pytest.approx(sum(w["flops"] for w in got) / 3)


def test_work_prices_the_stored_itemsize():
    cols, weights, tile_rows = _tiny()
    f32, bf16 = (work.worker_work(cols, weights, 2, 1, tile_rows, s=8, r=8,
                                  t=6, block_size=BS, tile_itemsize=size)[0]
                 for size in (4, 2))
    assert f32["flops"] == bf16["flops"]
    assert f32["bytes"] - bf16["bytes"] == 4 * BS * BS * 2


def test_work_ignores_slot_and_tile_order():
    cols, weights, tile_rows = _tiny()
    base = work.worker_work(cols, weights, 2, 1, tile_rows, s=8, r=8, t=6,
                            block_size=BS)
    perm = work.worker_work(cols[:, ::-1], weights[:, ::-1], 2, 1,
                            [rows[::-1] for rows in tile_rows], s=8, r=8, t=6,
                            block_size=BS)
    padded = work.worker_work(np.pad(cols, ((0, 0), (0, 2))),
                              np.pad(weights, ((0, 0), (0, 2))), 2, 1,
                              tile_rows, s=8, r=8, t=6, block_size=BS)
    assert base == perm == padded


def _permuted_plan(plan, order):
    return dataclasses.replace(plan, cols=plan.cols[:, order],
                               weights=plan.weights[:, order])


def test_work_is_unchanged_when_the_plan_is_packed_in_another_slot_order():
    from repro.coded import CodedMatmulConfig, plan
    from repro.core.coded_matmul import pack_worker_tiles
    from repro.sparse import dense_to_block_ell

    rng = np.random.default_rng(3)
    s = r = 64
    t, bs = 32, 8
    A = rng.standard_normal((s, r)).astype(np.float32)
    live = rng.random((s // bs, r // bs)) < 0.3
    A *= np.kron(live, np.ones((bs, bs), np.float32))
    ell = dense_to_block_ell(A, block_size=bs)
    p = plan(CodedMatmulConfig(block_size=bs), m=2, n=2, num_workers=8,
             seed=1).plan_
    q = _permuted_plan(p, np.arange(p.cols.shape[1])[::-1])
    pack_p, pack_q = pack_worker_tiles(ell, p), pack_worker_tiles(ell, q)
    assert not np.array_equal(pack_p.src, pack_q.src)   # another slot order
    tile_rows = [ell.idx[cb, :ell.nnzb[cb]] for cb in range(r // bs)]
    got_p, got_q = (work.worker_work(x.cols, x.weights, 2, 2, tile_rows, s=s,
                                     r=r, t=t, block_size=bs) for x in (p, q))
    assert got_p == got_q
    # the packs hold exactly the tiles the count prices, slot by slot
    assert [w["tiles"] for w in got_p] == pack_p.live_tiles.tolist()
    assert [w["tiles"] for w in got_q] == pack_q.live_tiles.tolist()


def test_chip_work_sums_each_chips_contiguous_workers():
    """8 workers on 2 chips: chip 0 holds workers 0-3, chip 1 workers 4-7,
    as ``P("model")`` splits the leading worker axis."""
    per_worker = [{"flops": 10.0 ** k, "bytes": 3.0 * k, "tiles": k}
                  for k in range(8)]
    got = work.chip_work(per_worker, 2)
    assert got["flops"] == (1111.0 + 11110000.0) / 2
    assert got["bytes"] == (3.0 * (0 + 1 + 2 + 3) + 3.0 * (4 + 5 + 6 + 7)) / 2
    with pytest.raises(ValueError):
        work.chip_work(per_worker, 3)


def _traced(work_, kernel_s_per_chip, chips):
    """A run of 10 products whose trace holds the kernel for the given
    seconds on each chip."""
    from chipbench.record import Run

    kernel = "spmm_block_fused_decode.1"
    trace = xplane.TraceSummary(
        window_s=1.0, chips=chips, busy_s=kernel_s_per_chip,
        op_s=[{kernel: kernel_s_per_chip} for _ in range(chips)],
        op_count=[{kernel: 10} for _ in range(chips)], idle_by_span={})
    return Run(chips=chips, setup_s=1.0, window_s=1.0, product_s=[0.1] * 10,
               stage_s=[0.05] * 10, recover_s=[], rebind_s=[], compiles=10,
               work=work_, peaks=peaks.lookup("TPU v5 lite"), trace=trace)


@pytest.mark.parametrize("per_chip", [2, 4])
def test_kernel_roofline_reads_the_work_of_every_worker_a_chip_holds(per_chip):
    """At the same kernel time, a chip that holds k workers does k times the
    work of one, so its roofline share reads k times as high."""
    cols, weights, tile_rows = _tiny()
    one = work.worker_work(cols, weights, 2, 1, tile_rows, s=8, r=8, t=6,
                           block_size=BS)[0]
    single = _traced(work.chip_work([one] * 2, 2), 0.5, 2)
    many = _traced(work.chip_work([one] * (2 * per_chip), 2), 0.5, 2)
    assert single.read("kernel_ms") == many.read("kernel_ms") == 50.0
    assert many.read("kernel_roofline") == pytest.approx(
        per_chip * single.read("kernel_roofline"), rel=1e-12)


def test_peaks_are_looked_up_by_device_kind_and_unknown_kinds_fail():
    v5e = peaks.lookup("TPU v5 lite")
    assert v5e["peak_flops"] == 197e12 and v5e["peak_bw"] == 819e9
    assert "TPU v5e" in v5e["source"]
    for kind in ("cpu", "TPU v4", ""):
        with pytest.raises(KeyError):
            peaks.lookup(kind)


def test_roofline_takes_the_bound_met_first():
    p = {"peak_flops": 100.0, "peak_bw": 10.0}
    assert xplane.bound({"flops": 1000.0, "bytes": 50.0}, p) == "compute"
    assert xplane.roofline_s({"flops": 1000.0, "bytes": 50.0}, p) == 10.0
    assert xplane.bound({"flops": 100.0, "bytes": 50.0}, p) == "memory"
    assert xplane.roofline_s({"flops": 100.0, "bytes": 50.0}, p) == 5.0
