"""CPU tests of ``program_hits_per_product``, the staging cache's counter
over the window's products, and of the span ring holding a window fast
enough that every program-span metric still finds all its products."""

from __future__ import annotations

import pytest

from chipbench import cells
from chipbench.tests.test_chipbench_program_spans import (
    BENCH,
    READERS,
    _run,
    _small_op,
)

HITS = "program_hits_per_product"


def _product_counts(monkeypatch, counts: list) -> None:
    """Made-up records: one product root per dict of ``counts``, in order."""
    from repro import obs

    spans = [obs.Span(obs.PRODUCT, 10 * i, 10 * i + 5, i + 1, None, i + 1, c)
             for i, c in enumerate(counts)]
    monkeypatch.setattr(obs, "records", lambda: list(spans))


@pytest.mark.parametrize("window,want", [
    # every window product reused the staged program: the warm-up's misses
    # before the window are not read
    ([{"program_hits": 1}] * 3, 1.0),
    # one product of four built a program
    ([{"program_hits": 1}] * 3 + [{"program_misses": 1}], 0.75),
    # a program without the counter, as before it was counted
    ([{"upload_bytes": 1, "compiles": 1}] * 3, 0.0),
])
def test_hits_reads_the_windows_hits_per_product(monkeypatch, window, want):
    warm = [{"program_misses": 1, "compiles": 1}] * 2
    _product_counts(monkeypatch, warm + window)
    assert cells.reader(HITS)(_run(len(window))) == pytest.approx(want)


@pytest.mark.parametrize("products", [6, 0])
def test_hits_gives_nothing_without_the_windows_products(monkeypatch,
                                                         products):
    _product_counts(monkeypatch, [{"program_hits": 1}] * 3)
    assert cells.reader(HITS)(_run(products)) is None


def test_hits_gives_nothing_without_repro_obs(monkeypatch):
    import sys

    import repro

    monkeypatch.delattr(repro, "obs")
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    assert cells.reader(HITS)(_run(3)) is None


def test_hits_is_a_staging_counter_that_moves_throughput():
    entry = next(m for m in BENCH["per_layer"] if m["name"] == HITS)
    assert entry == {"name": HITS, "unit": "hits/product", "better": "higher",
                     "source": "program_counter", "layer": "staging",
                     "moves": "products_per_s"}


def test_a_small_op_hits_from_its_second_product(monkeypatch):
    """On the CPU: two warm-up products then a window of three, all of one
    op; the window reads 1.0, and 0.8 with the first product in it."""
    from repro import obs

    monkeypatch.setattr(obs, "RECORDER", obs.Recorder())
    op, A, B, ell = _small_op()
    _cold()
    try:
        for _ in range(5):
            op(A, B, a_sparse=ell).block_until_ready()
        assert cells.reader(HITS)(_run(3)) == 1.0
        assert cells.reader(HITS)(_run(5)) == pytest.approx(0.8)
    finally:
        _cold()


def _cold() -> None:
    """Nothing staged and no pack cached: the next product builds both."""
    from repro.runtime import pack_cache

    pack_cache.clear()


#: the readers of the program's spans and counters, each read in every cell
SPAN_READERS = (*READERS, "kernel_steps_per_product", HITS)


@pytest.mark.parametrize("products,rebinds", [
    # coded16k-w4.churn at its fastest, the kernel and psum alone (74.3 ms
    # a product): 690 products in a 51 s window, a straggler change on 3 of 4
    (690, 3),
    # what obs.RING is sized for: 2700 products, a rebind before each
    (2700, 4),
])
def test_a_fast_window_keeps_every_program_span_metric(monkeypatch, products,
                                                       rebinds):
    """A hit product's own spans, recorded on the CPU, replayed for a whole
    window after a cold first product: every reader finds the window."""
    from repro import obs

    monkeypatch.setattr(obs, "RECORDER", obs.Recorder())
    op, A, B, ell = _small_op()
    _cold()
    try:
        for _ in range(2):
            op(A, B, a_sparse=ell).block_until_ready()
    finally:
        _cold()
    hit = [s for s in obs.records() if s.name == obs.PRODUCT][-1]
    names = [s.name for s in obs.records()
             if s.product == hit.product and s.name != obs.PRODUCT]
    assert obs.JIT in names and obs.LOWER not in names
    # the TPU lane's grid steps, which the CPU's XLA lane does not count
    counts = dict(hit.counts, kernel_grid_steps=23040)
    for i in range(products):
        if i % 4 < rebinds:
            with obs.span(obs.REBIND):
                pass
        with obs.product():
            for name in names:
                with obs.span(name):
                    pass
            for name, n in counts.items():
                obs.count(name, n)
    got = {name: cells.reader(name)(_run(products)) for name in SPAN_READERS}
    assert None not in got.values(), got
    assert got[HITS] == 1.0
    assert got["kernel_steps_per_product"] == 23040
    assert got["lower_ms_p50"] == got["compile_ms_p50"] == 0
