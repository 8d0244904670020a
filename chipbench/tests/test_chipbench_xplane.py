"""CPU tests of the trace reduction, on a made-up trace and on one recorded
on a TPU v5e."""

from __future__ import annotations

import gzip
import pathlib

import pytest
from jax.profiler import ProfileData

from chipbench import cells, xplane
from chipbench.record import Run

HERE = pathlib.Path(__file__).resolve().parent
#: 4 s of a run at 2% live tiles (s = r = t = 16384) on one TPU v5e (23
#: products), gzipped
RECORDED = HERE / "data" / "w1-2pct-live.xplane.pb.gz"


def _event(meta: int, start_ns: int, dur_ns: int) -> str:
    return (f"events {{ metadata_id: {meta} offset_ps: {start_ns * 1000} "
            f"duration_ps: {dur_ns * 1000} }}")


def _meta(names) -> str:
    return " ".join(f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
                    for i, n in enumerate(names, 1))


def _made_up() -> ProfileData:
    """A 1000 ns window on two chips.  The host stages 0-300 and 500-800,
    waits 300-500 and 800-1000.  Chip 0 runs the kernel 300-450 and
    600-700 and an all-reduce 440-480 (overlapping the kernel); chip 1 runs
    the kernel 320-420 and an op outside the window."""
    host = ("planes { id: 1 name: \"/host:CPU\" lines { id: 1 name: \"python\" "
            "timestamp_ns: 0 "
            + " ".join([_event(1, 0, 1000), _event(2, 0, 300), _event(3, 300, 200),
                        _event(2, 500, 300), _event(3, 800, 200)])
            + " } " + _meta(["chipbench.window", "chipbench.stage",
                             "chipbench.wait"]) + " }")
    chip0 = ("planes { id: 2 name: \"/device:TPU:0\" lines { id: 1 name: \"XLA Ops\" "
             "timestamp_ns: 0 "
             + " ".join([_event(1, 300, 150), _event(1, 600, 100),
                         _event(2, 440, 40)])
             + " } lines { id: 2 name: \"XLA Modules\" timestamp_ns: 0 "
             + _event(3, 0, 1000) + " } "
             + _meta(["_fused_decode_kernel", "all-reduce.1", "jit_program"]) + " }")
    chip1 = ("planes { id: 3 name: \"/device:TPU:1\" lines { id: 1 name: \"XLA Ops\" "
             "timestamp_ns: 0 " + _event(1, 320, 100) + " " + _event(2, 1200, 50)
             + " } " + _meta(["_fused_decode_kernel", "fusion.9"]) + " }")
    return ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(host + chip0 + chip1))


def test_made_up_trace_reduces_to_hand_counted_numbers():
    s = xplane.reduce_data(_made_up())
    assert s.chips == 2
    assert s.window_s == pytest.approx(1000e-9)
    # chip 0 busy 300-480 and 600-700 (overlap counted once), chip 1 320-420
    assert s.busy_s == pytest.approx((280e-9 + 100e-9) / 2)
    assert s.op_seconds("fused_decode") == pytest.approx([250e-9, 100e-9])
    assert s.op_events("all-reduce") == [1, 0]
    assert "jit_program" not in s.op_s[0]            # modules are not ops
    assert "fusion.9" not in s.op_s[1]               # outside the window
    # idle: chip 0 0-300 stage, 480-500 wait, 500-600 stage, 700-800 stage,
    # 800-1000 wait; chip 1 0-300 stage, 300-320 wait, 420-500 wait,
    # 500-800 stage, 800-1000 wait
    assert s.idle_by_span["stage"] == pytest.approx((500e-9 + 600e-9) / 2)
    assert s.idle_by_span["wait"] == pytest.approx((220e-9 + 300e-9) / 2)
    assert s.idle_by_span["loop"] == pytest.approx(0.0)
    assert sum(s.idle_by_span.values()) == pytest.approx(s.window_s - s.busy_s)
    b = xplane.breakdown(s)
    assert b["device_ops"][0] == ["_fused_decode_kernel", pytest.approx(175e-9)]
    assert [k for k, _ in b["idle_gaps"]] == ["stage", "wait"]


def test_made_up_trace_feeds_the_metric_readers():
    run = Run(chips=2, setup_s=1.0, window_s=1e-6, product_s=[5e-7, 5e-7],
              stage_s=[3e-7, 3e-7], recover_s=[], rebind_s=[], compiles=2,
              work={"flops": 197e12 * 50e-9, "bytes": 1.0},
              peaks={"peak_flops": 197e12, "peak_bw": 819e9},
              trace=xplane.reduce_data(_made_up()))
    assert cells.reader("kernel_ms")(run) == pytest.approx(175e-9 / 2 * 1e3)
    assert cells.reader("kernel_roofline")(run) == pytest.approx(
        100 * 50e-9 / (175e-9 / 2))
    assert cells.reader("collective_ms")(run) == pytest.approx(20e-9 / 2 * 1e3)
    assert cells.reader("device_idle")(run) == pytest.approx(
        100 * (1 - 190e-9 / 1000e-9))
    assert cells.reader("compiles_per_product")(run) == 1.0


def test_a_trace_without_its_window_span_is_refused():
    data = ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(
            'planes { id: 1 name: "/device:TPU:0" }'))
    with pytest.raises(ValueError, match="window"):
        xplane.reduce_data(data)


def test_recorded_chip_trace_reduces():
    s = xplane.reduce_data(ProfileData.from_serialized_xspace(
        gzip.decompress(RECORDED.read_bytes())))
    assert s.chips == 1
    assert 0 < s.busy_s < s.window_s
    # 23 products: one kernel event each, about 29 ms
    assert s.op_events(cells_pattern("kernel_ms", "KERNEL")) == [23]
    kernel = s.op_seconds(cells_pattern("kernel_ms", "KERNEL"))[0]
    assert 0.02 * 23 < kernel < 0.04 * 23
    assert s.op_events(cells_pattern("collective_ms", "COLLECTIVE")) == [0]
    assert sum(s.idle_by_span.values()) == pytest.approx(s.window_s - s.busy_s)
    b = xplane.breakdown(s)
    assert b["device_ops"][0][0] == "_spmm_block_fused_decode_pallas.1"
    assert 0 < len(b["device_ops"]) <= 10
    assert b["idle_gaps"][0][0] == "stage"


def cells_pattern(metric: str, attr: str) -> str:
    import importlib.util

    spec = importlib.util.spec_from_file_location(metric, cells.metric_path(metric))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return getattr(module, attr)
