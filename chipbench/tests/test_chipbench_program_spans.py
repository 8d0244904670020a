"""CPU tests of the staging metrics that read the program's own spans
(``repro.obs``): which records they take, where they give nothing, and the
spans' place in a trace recorded on the chip."""

from __future__ import annotations

import gzip
import pathlib

import numpy as np
import pytest
from jax.profiler import ProfileData

from chipbench import cells, xplane
from chipbench.record import Run

HERE = pathlib.Path(__file__).resolve().parent
#: a ``--seconds 3 --trace 1`` run of ``coded16k-w1.iterative`` on one TPU v5e
#: (7 products, the kernel named ``spmm_block_fused_decode``), gzipped
RECORDED = HERE / "data" / "w1-program-spans.xplane.pb.gz"
BENCH = cells.load_benchmark()
READERS = ("prep_ms_p50", "upload_ms_p50", "upload_mb_per_product",
           "lower_ms_p50", "compile_ms_p50", "readbacks_per_product")
MS = 1_000_000


def _run(products: int) -> Run:
    return Run(chips=1, setup_s=1.0, window_s=1.0, product_s=[0.5] * products,
               stage_s=[0.1] * products, recover_s=[], rebind_s=[], compiles=0)


class _Spans:
    """Made-up records: products of known staging times, in the order the
    program closes them (children first)."""

    def __init__(self):
        from repro import obs

        self.obs, self.spans, self.t = obs, [], 0
        self.ids = iter(range(1, 10 ** 6))

    def add(self, name, start, end, parent=None, product=None, counts=None,
            span_id=None):
        self.spans.append(self.obs.Span(name, start, end,
                                        span_id or next(self.ids), parent,
                                        product, counts))

    def product(self, pid, prep, upload, lower, compile_, counts):
        """``lower`` holds (start, end) offsets in ms from the jit call's
        start, nested ones included; the others are durations in ms."""
        obs = self.obs
        root, jit = next(self.ids), next(self.ids)
        t0 = t = self.t
        for d in prep:
            self.add(obs.PREPARE, t, t + d * MS, root, pid)
            t += d * MS
        self.add(obs.UPLOAD, t, t + upload * MS, root, pid)
        jit0 = t = t + upload * MS
        for a, b in lower:
            self.add(obs.LOWER, jit0 + a * MS, jit0 + b * MS, jit, pid)
        t = jit0 + max(b for _, b in lower) * MS
        self.add(obs.COMPILE, t, t + compile_ * MS, jit, pid)
        t += (compile_ + 1) * MS
        self.add(obs.JIT, jit0, t, root, pid, span_id=jit)
        self.add(obs.PRODUCT, t0, t, None, pid, counts, span_id=root)
        self.t = t + 10 * MS


@pytest.fixture
def made_up(monkeypatch):
    """Two warm-up products, a rebind and a lowering outside any product,
    then a window of three products."""
    spans = _Spans()
    big = {"upload_bytes": 10_000_000, "compiles": 1}
    for pid in (1, 2):
        spans.product(pid, [90, 90], 100, [(0, 100)], 500, big)
    spans.add(spans.obs.REBIND, spans.t, spans.t + 3 * MS)
    spans.add(spans.obs.PREPARE, spans.t, spans.t + 50 * MS)
    spans.t += 60 * MS
    counts = {"upload_bytes": 377_600_000, "compiles": 1, "readbacks": 1}
    # prepare 1 + 2 ms; lower: 10 ms holding a nested 1 ms, then 2 ms
    for pid, upload in ((3, 4), (4, 6), (5, 5)):
        spans.product(pid, [1, 2], upload, [(2, 3), (0, 10), (11, 13)], 7,
                      counts)
    monkeypatch.setattr(spans.obs, "records", lambda: list(spans.spans))
    return spans


def test_each_reader_picks_the_windows_records(made_up):
    run = _run(3)
    got = {name: cells.reader(name)(run) for name in READERS}
    assert got == {
        "prep_ms_p50": pytest.approx(3.0),
        "upload_ms_p50": pytest.approx(5.0),
        "upload_mb_per_product": pytest.approx(377.6),
        "lower_ms_p50": pytest.approx(12.0),
        "compile_ms_p50": pytest.approx(7.0),
        "readbacks_per_product": 1.0,
    }


@pytest.mark.parametrize("products", [6, 0])
def test_readers_give_nothing_without_the_windows_products(made_up, products):
    """More products than the program kept spans of, or none at all."""
    for name in READERS:
        assert cells.reader(name)(_run(products)) is None, name


def test_readers_give_nothing_without_repro_obs(made_up, monkeypatch):
    """As on a program that has no ``repro.obs``."""
    import sys

    import repro

    monkeypatch.delattr(repro, "obs")
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    for name in READERS:
        assert cells.reader(name)(_run(3)) is None, name


def _small_op():
    import jax
    import jax.numpy as jnp

    from repro.coded import CodedMatmulConfig, plan
    from repro.sparse import dense_to_block_ell

    rng = np.random.default_rng(1)
    A_np = rng.standard_normal((32, 16)).astype(np.float32)
    mesh = jax.make_mesh((1,), ("model",), devices=jax.devices()[:1])
    op = plan(CodedMatmulConfig(backend="block_sparse"), m=1, n=1,
              num_workers=1, max_degree=1).bind(mesh)
    return (op, jnp.asarray(A_np),
            jnp.asarray(rng.standard_normal((32, 16)), jnp.float32),
            dense_to_block_ell(A_np, block_size=8))


def _window_readings(monkeypatch, patch, window: int) -> dict:
    """Two warm-up products, then a window of ``window``, with the op's call
    replaced by ``patch``; the readers' values."""
    from repro import obs

    monkeypatch.setattr(obs, "RECORDER", obs.Recorder())
    op, A, B, ell = _small_op()
    with patch:
        for _ in range(2 + window):
            op(A, B, a_sparse=ell).block_until_ready()
    return {name: cells.reader(name)(_run(window)) for name in READERS}


def test_readers_under_the_unchanged_fault(monkeypatch):
    """The harness's ``unchanged`` fault hands back its first answer, but
    ``dict.setdefault`` evaluates the call it wraps every time: every
    product still runs ``CodedOp.apply``, so the readers read those calls."""
    from chipbench.tests import fault_run

    got = _window_readings(monkeypatch, fault_run.fault("unchanged"), 3)
    assert all(v is not None for v in got.values()), got
    assert got["upload_mb_per_product"] > 0


def test_readers_give_nothing_when_the_window_skips_the_program(monkeypatch):
    """A stale answer that never calls the program leaves the window's
    products without spans: every reader gives None."""
    from unittest import mock

    from repro.coded import op as op_mod

    call, first = op_mod.CodedOp.__call__, {}

    def stale(self, A, B, **kw):
        if "C" not in first:
            first["C"] = call(self, A, B, **kw)
        return first["C"]

    got = _window_readings(
        monkeypatch, mock.patch.object(op_mod.CodedOp, "__call__", stale), 3)
    assert got == dict.fromkeys(READERS), got


def test_every_metric_names_where_its_number_comes_from():
    sources = {"host_clock", "program_counter", "program_span", "device_trace"}
    for m in BENCH["per_layer"] + BENCH["end_to_end"]:
        assert m["source"] in sources, m["name"]
    for name in READERS:
        entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
        assert entry["layer"] == "staging" and "workloads" not in entry
        assert entry["source"] == ("program_counter" if "per_product" in name
                                   else "program_span")


def _host_spans(data) -> dict:
    spans: dict = {}
    for plane in data.planes:
        if plane.name == xplane.HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    spans.setdefault(ev.name, []).append((ev.start_ns, ev.end_ns))
    return {k: sorted(v) for k, v in spans.items()}


def _kernel_events(data) -> list:
    out = []
    for plane in data.planes:
        if xplane.DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == xplane.OPS_LINE:
                    out += [(ev.start_ns, ev.end_ns) for ev in line.events
                            if "fused_decode" in xplane.op_name(ev.name)]
    return sorted(out)


def _inside(outer, inner) -> bool:
    return outer[0] <= inner[0] <= inner[1] <= outer[1]


def test_recorded_trace_holds_the_program_spans_in_each_stage():
    data = ProfileData.from_serialized_xspace(
        gzip.decompress(RECORDED.read_bytes()))
    spans = _host_spans(data)
    stages = spans["chipbench.stage"]
    assert len(stages) >= 3
    for stage in stages:
        for name in ("repro.stage.prepare", "repro.stage.upload",
                     "repro.stage.jit", "repro.product"):
            assert any(_inside(stage, s) for s in spans[name]), name
    # one clock: product k's kernel starts after its jit call started and
    # ends before the wait for it ended
    jits = [s for s in spans["repro.stage.jit"]
            if any(_inside(st, s) for st in stages)]
    waits = spans["chipbench.wait"]
    kernels = _kernel_events(data)
    assert len(kernels) == len(jits) == len(waits) == len(stages)
    for jit, kernel, wait in zip(jits, kernels, waits):
        assert jit[0] < kernel[0] and kernel[1] <= wait[1]
    # the kernel's stable name: ``kernel_ms`` finds one event per product
    summary = xplane.reduce_data(data)
    assert summary.op_events(r"fused_decode") == [len(stages)]
    run = Run(chips=1, setup_s=1.0, window_s=summary.window_s,
              product_s=[0.5] * len(stages), stage_s=[0.1] * len(stages),
              recover_s=[], rebind_s=[], compiles=0, trace=summary)
    assert 380 < cells.reader("kernel_ms")(run) < 400
