"""CPU tests of ``kernel_steps_per_product``: it reads the program's
``kernel_grid_steps`` counter off the window's product spans, and gives
None where the program counts none."""

from __future__ import annotations

import json

import pytest

from chipbench import cells
from chipbench.record import Run

READ = cells.reader("kernel_steps_per_product")


def _run(products: int) -> Run:
    return Run(chips=1, setup_s=1.0, window_s=1.0, product_s=[0.5] * products,
               stage_s=[0.1] * products, recover_s=[], rebind_s=[], compiles=0)


def _records(monkeypatch, counts: list):
    """Product roots carrying ``counts``, oldest first."""
    from repro import obs

    spans = [obs.Span(obs.PRODUCT, 10 * i, 10 * i + 5, i + 1, None, i + 1, c)
             for i, c in enumerate(counts)]
    monkeypatch.setattr(obs, "records", lambda: list(spans))


def test_reads_the_windows_steps_per_product(monkeypatch):
    """Two warm-up products at another width, then a window of three."""
    warm = {"upload_bytes": 1, "kernel_grid_steps": 737_280}
    steps = {"upload_bytes": 1, "kernel_grid_steps": 23_040}
    _records(monkeypatch, [warm, warm, steps, steps, steps])
    assert READ(_run(3)) == 23_040
    assert READ(_run(5)) == pytest.approx((2 * 737_280 + 3 * 23_040) / 5)


@pytest.mark.parametrize("products", [6, 0])
def test_nothing_without_the_windows_products(monkeypatch, products):
    _records(monkeypatch, [{"kernel_grid_steps": 5}] * 5)
    assert READ(_run(products)) is None


def test_nothing_from_a_program_that_counts_no_steps(monkeypatch):
    """As on a program before the counter, or on the XLA lane."""
    _records(monkeypatch, [{"upload_bytes": 1, "compiles": 1}] * 3)
    assert READ(_run(3)) is None


def test_nothing_without_repro_obs(monkeypatch):
    import sys

    import repro

    monkeypatch.delattr(repro, "obs")
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    assert READ(_run(3)) is None


def test_the_metric_is_a_program_counter_of_the_kernel_layer():
    entry, = (m for m in cells.load_benchmark()["per_layer"]
              if m["name"] == "kernel_steps_per_product")
    assert json.dumps(entry, sort_keys=True) == json.dumps({
        "name": "kernel_steps_per_product", "unit": "steps/product",
        "better": "lower", "source": "program_counter", "layer": "kernel",
        "moves": "products_per_s",
        "workloads": ["coded16k-w1.iterative", "coded16k-w4.churn"],
    }, sort_keys=True)
