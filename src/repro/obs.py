"""Spans and counters of the coded product's device path.

One product -- ``CodedOp.apply``, i.e. ``op(A, B)`` -- records a tree of
spans, each in the module where its work happens:

=======================  ==================================================
``repro.product``        the whole call (``CodedOp.apply``); its root
``repro.stage.prepare``  operand checks, the pack-cache lookup,
                         ``resolve_pack`` (``CodedOp._staging_kwargs``), and
                         the per-call weight gather, decode columns and
                         ``shard_map`` (``build_coded_program``)
``repro.stage.upload``   the ``device_put`` of the worker arrays not yet on
                         the devices (``stage_coded_matmul``)
``repro.stage.jit``      the jitted call until it returns; its self time is
                         the enqueue
``repro.stage.lower``    JAX's trace and lowering to MLIR, child of the jit
                         span (from ``jax.monitoring``)
``repro.stage.compile``  the backend compile, or the executable read back
                         from the persistent cache, child of the jit span
                         (from ``jax.monitoring``)
``repro.rebind``         ``CodedOp.with_survivors``: the decode re-derivation
=======================  ==================================================

Counters are process-wide integers, incremented at the same boundaries:
``products``, ``upload_bytes`` (the ``nbytes`` of the worker arrays put on
the devices in the call: a pack's tiles on its first call, then only the
per-call decode columns and weights), ``program_hits`` (the call reused a
staged program and the pack's device copy) and ``program_misses`` (it built
either), ``kernel_grid_steps`` (the grid steps of one worker's block-sparse
kernel launch, on the TPU lane only), ``compiles`` (backend compiles inside
a product, read-backs included), ``readbacks`` (executables read back from
the persistent cache inside a product) and ``rebinds``.  A
product's span also carries the counts that moved while it was open, so a
window of products can be read without a counter snapshot at its start.

Every span enters ``jax.profiler.TraceAnnotation`` of its name (the root a
``StepTraceAnnotation`` numbered by its product id), so a profiler trace
shows it on the host plane, on the device planes' clock.  The ``lower`` and
``compile`` spans come from ``jax.monitoring`` after the fact and reach only
this module's records, not the profiler's.

Spans are kept in memory, in a bounded ring (``records()``); ``snapshot()``
is the counters and the pack cache's own ``cache_stats()``.  The parent of
a span is kept per thread, so an op shared by threads does not mix their
products.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import itertools
import threading
import time

import jax

#: spans kept: a window of 2800 products of 5 spans and a rebind each, a
#: 51 s run at 18 ms a product (a first call's lower and compile add 2)
RING = 16384

PRODUCT = "repro.product"
PREPARE = "repro.stage.prepare"
UPLOAD = "repro.stage.upload"
JIT = "repro.stage.jit"
LOWER = "repro.stage.lower"
COMPILE = "repro.stage.compile"
REBIND = "repro.rebind"

#: jax.monitoring duration events -> the child span of ``JIT`` they become
_DURATION_SPANS = {
    "/jax/core/compile/jaxpr_trace_duration": LOWER,
    "/jax/core/compile/jaxpr_to_mlir_module_duration": LOWER,
    "/jax/core/compile/backend_compile_duration": COMPILE,
}
_READBACK_EVENT = "/jax/compilation_cache/cache_hits"


@dataclasses.dataclass(frozen=True)
class Span:
    """A closed span.  Times are ``time.perf_counter_ns()``; ``product`` is
    the id shared by every span of one product (None outside a product);
    ``counts`` is set on a product's root: the counters it moved."""

    name: str
    start_ns: int
    end_ns: int
    span_id: int
    parent: int | None
    product: int | None
    counts: dict | None = None


class _Open:
    __slots__ = ("name", "span_id", "parent", "product", "root", "counts",
                 "start_ns")

    def __init__(self, name, span_id, top, product_id):
        """A child of ``top``, or, given a ``product_id``, a product's root."""
        self.name, self.span_id = name, span_id
        self.parent = top.span_id if top else None
        self.start_ns = 0
        if product_id is not None:
            self.product, self.root = product_id, self
            self.counts = collections.Counter()
        else:
            self.product = top.product if top else None
            self.root = top.root if top else None
            self.counts = None


class Recorder:
    """The ring of closed spans, the counters, and each thread's open spans."""

    def __init__(self, ring: int = RING):
        self._ring: collections.deque = collections.deque(maxlen=ring)
        self._counters: collections.Counter = collections.Counter()
        self._lock = threading.Lock()
        self._local = threading.local()
        self._span_ids = itertools.count(1)
        self._product_ids = itertools.count(1)

    def _stack(self) -> list:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    @contextlib.contextmanager
    def span(self, name: str, *, new_product: bool = False):
        """A child of this thread's open span (a root if none is open); with
        ``new_product`` the root of a product with a new id."""
        stack = self._stack()
        frame = _Open(name, next(self._span_ids), stack[-1] if stack else None,
                      next(self._product_ids) if new_product else None)
        annotation = (
            jax.profiler.StepTraceAnnotation(name, step_num=frame.product)
            if new_product else jax.profiler.TraceAnnotation(name))
        stack.append(frame)
        with annotation:
            frame.start_ns = time.perf_counter_ns()
            try:
                yield
            finally:
                end_ns = time.perf_counter_ns()
                stack.pop()
                self._ring.append(Span(
                    name, frame.start_ns, end_ns, frame.span_id, frame.parent,
                    frame.product,
                    None if frame.counts is None else dict(frame.counts)))

    def count(self, name: str, n: int = 1) -> None:
        """Add ``n`` to counter ``name``, and to the open product's counts."""
        with self._lock:
            self._counters[name] += n
        stack = self._stack()
        if stack and stack[-1].root is not None:
            stack[-1].root.counts[name] += n

    def open_jit(self) -> _Open | None:
        """This thread's innermost open span, if it is a product's jit span."""
        stack = self._stack()
        if stack and stack[-1].name == JIT and stack[-1].product is not None:
            return stack[-1]
        return None

    def child_ending_now(self, name: str, parent: _Open, seconds: float):
        """Record a closed child of ``parent`` that ends now and lasted
        ``seconds``."""
        end_ns = time.perf_counter_ns()
        self._ring.append(Span(name, end_ns - int(seconds * 1e9), end_ns,
                               next(self._span_ids), parent.span_id,
                               parent.product))

    def records(self) -> list:
        """The closed spans kept, oldest first."""
        return list(self._ring)

    def counters(self) -> dict:
        with self._lock:
            return dict(self._counters)


#: the process-wide recorder every product records into
RECORDER = Recorder()


@contextlib.contextmanager
def span(name: str):
    """Record span ``name`` (a context manager, or a decorator)."""
    with RECORDER.span(name):
        yield


@contextlib.contextmanager
def product():
    """Record the root span of one product, under a new product id."""
    _listen()
    RECORDER.count("products")
    with RECORDER.span(PRODUCT, new_product=True):
        yield


def count(name: str, n: int = 1) -> None:
    RECORDER.count(name, n)


def records() -> list:
    return RECORDER.records()


def snapshot() -> dict:
    """The counters, and the pack cache's ``cache_stats()`` as it reads."""
    # imported here: the pack cache imports the staging code, which imports this
    from repro.runtime import pack_cache

    return dict(RECORDER.counters(), pack_cache=pack_cache.cache_stats())


def _on_duration(event: str, duration: float, **_) -> None:
    name = _DURATION_SPANS.get(event)
    if name is None:
        return
    jit = RECORDER.open_jit()
    if jit is None:        # not inside a product: the caller's own programs
        return
    RECORDER.child_ending_now(name, jit, duration)
    if name == COMPILE:
        RECORDER.count("compiles")


def _on_event(event: str, **_) -> None:
    if event == _READBACK_EVENT and RECORDER.open_jit() is not None:
        RECORDER.count("readbacks")


_LISTENING = False
_LISTEN_LOCK = threading.Lock()


def _listen() -> None:
    """Register the ``jax.monitoring`` listeners, once per process (JAX
    cannot remove one)."""
    global _LISTENING
    if _LISTENING:
        return
    with _LISTEN_LOCK:
        if _LISTENING:
            return
        from jax import monitoring

        monitoring.register_event_duration_secs_listener(_on_duration)
        monitoring.register_event_listener(_on_event)
        _LISTENING = True
