"""CPU tests of the churn cell's own metrics: the survivor rebind, the
recovery after a membership change and the decode collective, on made-up
runs and on a trace of the cell recorded on a 2x2 TPU v5e."""

from __future__ import annotations

import gzip
import pathlib

import pytest
from jax.profiler import ProfileData

from chipbench import cells, xplane
from chipbench.record import Run
from chipbench.tests.test_chipbench_xplane import _event, _meta

#: a ``--seconds 2 --trace 1`` run of ``coded16k-w4.churn`` on a 2x2 TPU v5e
#: (12 products, 5 membership changes), gzipped
RECORDED = pathlib.Path(__file__).resolve().parent / "data" / "w4-churn.xplane.pb.gz"


def _run(trace=None, **kw) -> Run:
    fields = dict(chips=2, setup_s=1.0, window_s=1.0, product_s=[0.2] * 4,
                  stage_s=[0.1] * 4, recover_s=[], rebind_s=[], compiles=4,
                  trace=trace)
    fields.update(kw)
    return Run(**fields)


def _trace(chip_ops) -> xplane.TraceSummary:
    """A 1000 ns window on one chip per entry of ``chip_ops``, each a list of
    (operation name, start ns, duration ns)."""
    host = ('planes { id: 1 name: "/host:CPU" lines { id: 1 name: "python" '
            f'timestamp_ns: 0 {_event(1, 0, 1000)} }} '
            f'{_meta(["chipbench.window"])} }}')
    planes = [host]
    for chip, ops in enumerate(chip_ops):
        names = sorted({name for name, _, _ in ops})
        events = " ".join(_event(names.index(n) + 1, a, d) for n, a, d in ops)
        planes.append(f'planes {{ id: {chip + 2} name: "/device:TPU:{chip}" '
                      f'lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0 {events} }} '
                      f'{_meta(names)} }}')
    return xplane.reduce_data(ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(" ".join(planes))))


@pytest.mark.parametrize("metric", ["rebind_ms_p50", "recover_ms_p50"])
def test_no_membership_change_reads_none(metric):
    assert cells.reader(metric)(_run()) is None


def test_rebind_and_recovery_are_medians_in_ms():
    run = _run(recover_s=[0.30, 0.21, 0.25], rebind_s=[0.004, 0.001, 0.003, 0.002])
    assert cells.reader("recover_ms_p50")(run) == pytest.approx(250.0)
    assert cells.reader("rebind_ms_p50")(run) == pytest.approx(2.5)


def test_no_collective_in_the_trace_reads_none():
    kernel_only = _trace([[("spmm_block_fused_decode.1", 100, 300)]] * 2)
    assert cells.reader("collective_ms")(_run(trace=kernel_only)) is None
    assert cells.reader("collective_ms")(_run()) is None


@pytest.mark.parametrize("name", ["psum.7", "all-reduce.1", "reduce-scatter.2",
                                  "reduce_scatter.2", "all_gather.3", "ppermute.1"])
def test_collective_ms_is_the_mean_over_chips_per_product(name):
    """A TPU trace names the decode's all-reduce after JAX's primitive
    (``psum.7``), and the other collectives after theirs (``reduce_scatter``,
    ``all_gather``, ``ppermute``); XLA's opcode names count too."""
    # chip 0: two collectives of 30 and 10 ns; chip 1: one of 20 ns; 2 products
    trace = _trace([
        [("spmm_block_fused_decode.1", 0, 100), (name, 100, 30), (name, 500, 10)],
        [("spmm_block_fused_decode.1", 0, 120), (name, 120, 20)]])
    run = _run(trace=trace, product_s=[0.2, 0.2])
    assert cells.reader("collective_ms")(run) == pytest.approx(
        (40e-9 + 20e-9) / 2 / 2 * 1e3)


def test_the_recorded_psum_is_read_on_every_chip():
    """On the chip the decode is one synchronous all-reduce a product, which
    the trace names ``psum.7``: every chip runs it once a product after its
    kernel, and ``collective_ms`` counts it."""
    s = xplane.reduce_data(ProfileData.from_serialized_xspace(
        gzip.decompress(RECORDED.read_bytes())))
    assert s.chips == 4
    assert s.op_events("^psum") == [12] * 4
    assert s.op_events("fused_decode") == [12] * 4
    assert s.op_events("all-reduce") == [0] * 4     # the opcode is not the name
    run = _run(trace=s, chips=4, product_s=[0.17] * 12)
    collective = cells.reader("collective_ms")(run)
    assert collective == pytest.approx(
        sum(s.op_seconds("^psum")) / 4 / 12 * 1e3)
    assert 10 < collective < 40
    assert 0 < cells.reader("kernel_ms")(run) < 0.17e3
