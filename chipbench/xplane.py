"""Reduce a profiler trace (``.xplane.pb``) to the per-layer numbers.

The trace holds the benchmark's host spans (``jax.profiler.TraceAnnotation``
named ``chipbench.<span>``) on the host plane and each chip's operations on
its device plane, both on one clock.  Within the window span this module
takes, per chip:

* every device operation's time, by the operation's name;
* busy time, the union of the operations' intervals (so overlapping
  operations count once), and idle time, the rest of the window;
* each idle gap split by the host span it fell in (``stage``, ``wait``,
  ``rebind``, ``check``; ``loop`` where the host was between spans).

Everything is then averaged over the chips.  Only JAX is needed to read the
file (``jax.profiler.ProfileData``).
"""

from __future__ import annotations

import dataclasses
import pathlib
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
#: the device plane's line that holds one event per executed operation
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "chipbench."
OUTSIDE_SPANS = "loop"


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    chips: int
    busy_s: float                       # mean over chips
    op_s: list                          # per chip: {op name: seconds}
    op_count: list                      # per chip: {op name: events}
    idle_by_span: dict                  # {span: idle seconds}, mean over chips

    def op_seconds(self, pattern: str) -> list:
        """Per chip, the seconds of the operations whose name matches."""
        rx = re.compile(pattern)
        return [sum(v for k, v in ops.items() if rx.search(k))
                for ops in self.op_s]

    def op_events(self, pattern: str) -> list:
        rx = re.compile(pattern)
        return [sum(v for k, v in ops.items() if rx.search(k))
                for ops in self.op_count]


def op_name(event_name: str) -> str:
    """An operation's name without its HLO text: a TPU trace names each op
    event by its whole instruction (``%name = f32[...] custom-call(...)``)."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def find_xspace(logdir) -> pathlib.Path:
    """The one ``*.xplane.pb`` a ``jax.profiler`` trace wrote under ``logdir``."""
    found = sorted(pathlib.Path(logdir).rglob("*.xplane.pb"))
    if len(found) != 1:
        raise FileNotFoundError(f"{len(found)} .xplane.pb files under {logdir}")
    return found[0]


def _union(intervals):
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _overlap(a0, a1, intervals) -> float:
    return sum(max(0, min(a1, b1) - max(a0, b0)) for b0, b1 in intervals)


def reduce(path, window_span: str = "chipbench.window",
           spans=("stage", "wait", "rebind", "check")) -> TraceSummary:
    """The summary of the trace at ``path`` inside ``window_span``."""
    from jax.profiler import ProfileData

    return reduce_data(ProfileData.from_file(str(path)), window_span, spans)


def reduce_data(data, window_span: str = "chipbench.window",
                spans=("stage", "wait", "rebind", "check")) -> TraceSummary:
    """``reduce`` of a trace already read (``jax.profiler.ProfileData``)."""
    spans = tuple(spans)
    host_spans: dict = {}
    device_planes = []
    for plane in data.planes:
        if plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        host_spans.setdefault(ev.name[len(SPAN_PREFIX):], []).append(
                            (ev.start_ns, ev.end_ns))
        elif DEVICE_PLANE.match(plane.name):
            device_planes.append(plane)
    window_name = window_span[len(SPAN_PREFIX):]
    if len(host_spans.get(window_name, ())) != 1:
        raise ValueError(f"the trace holds {len(host_spans.get(window_name, ()))} "
                         f"{window_span!r} spans, expected 1")
    if not device_planes:
        raise ValueError("the trace holds no TPU device plane")
    w0, w1 = host_spans[window_name][0]
    span_iv = {name: sorted(host_spans.get(name, ())) for name in spans}
    busy, op_s, op_count = [], [], []
    idle_by_span = {name: 0.0 for name in spans + (OUTSIDE_SPANS,)}
    for plane in sorted(device_planes, key=lambda p: p.name):
        ops, counts, intervals = {}, {}, []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                a, b = max(ev.start_ns, w0), min(ev.end_ns, w1)
                if b <= a:
                    continue
                intervals.append((a, b))
                name = op_name(ev.name)
                ops[name] = ops.get(name, 0.0) + (b - a) * 1e-9
                counts[name] = counts.get(name, 0) + 1
        merged = _union(intervals)
        busy.append(sum(b - a for a, b in merged) * 1e-9)
        op_s.append(ops)
        op_count.append(counts)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for g0, g1 in zip(edges[::2], edges[1::2]):
            if g1 <= g0:
                continue
            covered = 0.0
            for name in spans:
                o = _overlap(g0, g1, span_iv[name])
                idle_by_span[name] += o * 1e-9
                covered += o
            idle_by_span[OUTSIDE_SPANS] += (g1 - g0 - covered) * 1e-9
    chips = len(device_planes)
    return TraceSummary(
        window_s=(w1 - w0) * 1e-9, chips=chips, busy_s=sum(busy) / chips,
        op_s=op_s, op_count=op_count,
        idle_by_span={k: v / chips for k, v in idle_by_span.items()})


def breakdown(summary: TraceSummary, top: int = 10) -> dict:
    """The device operations that took most time (seconds, mean over chips)
    and the idle time by the host span it fell in, each longest first."""
    total: dict = {}
    for ops in summary.op_s:
        for name, sec in ops.items():
            total[name] = total.get(name, 0.0) + sec / summary.chips
    device_ops = sorted(total.items(), key=lambda kv: -kv[1])[:top]
    idle = sorted(((k, v) for k, v in summary.idle_by_span.items() if v > 0),
                  key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in device_ops],
            "idle_gaps": [[k, v] for k, v in idle]}


def bound(work_: dict, peaks_: dict) -> str:
    """Which roofline bound the algorithm's work meets first."""
    compute = work_["flops"] / peaks_["peak_flops"]
    memory = work_["bytes"] / peaks_["peak_bw"]
    return "compute" if compute >= memory else "memory"


def roofline_s(work_: dict, peaks_: dict) -> float:
    """The least time the chip could take for ``work_``."""
    return max(work_["flops"] / peaks_["peak_flops"],
               work_["bytes"] / peaks_["peak_bw"])
