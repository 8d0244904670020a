"""Run one cell of BENCHMARK.json once, on the chips of this machine.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The run builds its operands from ``--seed`` (B on the device, A's tiles on
the host as a BlockELL and A dense on the device from them), plans the
configuration's ``num_workers`` workers and binds them to a mesh of one
device per chip of the cell, packs once through ``repro.coded``, warms up the
(B operand, liveness mask) pairs its traffic uses (``generator.Schedule``),
and then runs a closed loop for ``--seconds``: one caller, each product timed
from the call to ``block_until_ready`` of the decoded C.  Once the window has
closed it compares a seeded sample of the window's products, every mask it
used among them (``generator.CheckSample``), with the plain reference, and
prints the result as the last line of standard output.  ``--trace 1``
records a profiler trace of the window and prints the per-layer metrics in
place of the end-to-end ones.

It exits non-zero, printing no result, where JAX finds no TPU or fewer chips
than the cell asks for, and where a kernel override would route the product
off the compiled TPU kernel.
"""

from __future__ import annotations

import time

_T_IMPORT = time.perf_counter()

import argparse
import contextlib
import json
import os
import pathlib
import shutil
import sys
import tempfile

import numpy as np

HERE = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parent), str(HERE.parent / "src")]

from chipbench import cells, generator, peaks, work  # noqa: E402
from chipbench.record import Run  # noqa: E402

SPANS = ("stage", "wait", "rebind", "check")
WINDOW_SPAN = "chipbench.window"


def fail(msg: str):
    print(f"chipbench: {msg}", file=sys.stderr, flush=True)
    sys.exit(2)


def process_age_s() -> float:
    """Seconds since this process started, from /proc (else since import)."""
    try:
        with open("/proc/self/stat") as f:
            fields = f.read().rsplit(")", 1)[1].split()
        started = int(fields[19]) / os.sysconf("SC_CLK_TCK")
        age = time.clock_gettime(time.CLOCK_BOOTTIME) - started
        if 0.0 <= age < 3600.0:
            return age
    except (OSError, ValueError, IndexError, AttributeError):
        pass
    return time.perf_counter() - _T_IMPORT


def require_chip(chips: int):
    """Refuse to run anywhere but on ``chips`` TPUs with the compiled kernel."""
    pallas = os.environ.get("REPRO_PALLAS_INTERPRET")
    if pallas not in (None, "0"):
        fail(f"REPRO_PALLAS_INTERPRET={pallas} would interpret the kernels")
    lane = os.environ.get("REPRO_KERNEL_LANE")
    if lane not in (None, "", "tpu"):
        fail(f"REPRO_KERNEL_LANE={lane} would route off the TPU kernel")
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        fail(f"found platform {devices[0].platform!r}, need a TPU")
    if len(devices) < chips:
        fail(f"the cell needs {chips} chips, found {len(devices)}")
    from repro.kernels.spmm_block import resolve_interpret, resolve_lane

    if resolve_lane() != "tpu" or resolve_interpret() is not False:
        fail("the kernel lane is not the compiled TPU kernel")


class CompileCounter:
    """Counts XLA backend compiles through ``jax.monitoring``."""

    def __init__(self):
        from jax import monitoring

        self.compiles = 0
        monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1


#: one per process: jax.monitoring cannot remove a listener, so a counter per
#: run would count every compile once per run made in the process
_COUNTER = None


def compile_counter() -> CompileCounter:
    global _COUNTER
    if _COUNTER is None:
        _COUNTER = CompileCounter()
    return _COUNTER


def cache_every_program():
    """Let JAX's persistent cache keep every program, the product's too, so
    that a cell's first run in a checkout compiles and every later run reads
    its programs back.  JAX writes only compiles slower than a threshold (1 s
    by default); the program compiles its product afresh on every call in
    about that time, so under the default a run read it back only once some
    earlier compile had happened to pass 1 s, and runs fell into two regimes.
    Called before the program's ``enable_compile_cache()``: a threshold that
    the program sets there is the one its compiles meet."""
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


@contextlib.contextmanager
def cache_every_compile():
    """Keep the benchmark's own programs (operand generation, the check) in
    the persistent cache whatever threshold the program set, and put the
    program's threshold back after them."""
    import jax

    before = jax.config.jax_persistent_cache_min_compile_time_secs
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    try:
        yield
    finally:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", before)


def span(name: str):
    import jax

    return jax.profiler.TraceAnnotation(f"chipbench.{name}")


class Operands:
    """A's tiles (host BlockELL) and A dense, the B operands, the checked
    columns, all from the seed."""

    def __init__(self, config: dict, traffic_: dict, rng: dict, mesh):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec

        from chipbench import reference
        from repro.sparse.blocksparse import BlockELL

        s, r, t = config["s"], config["r"], config["t"]
        bs = config["block_size"]
        self.idx = generator.tile_pattern(
            rng["tiles"], row_blocks=s // bs, col_blocks=r // bs,
            live_fraction=generator.live_tile_fraction(config["nnz_a"], s, r, bs))
        vals = generator.tile_values(rng["tiles"], self.idx.shape + (bs, bs))
        self.ell = BlockELL(vals=vals, idx=self.idx,
                            nnzb=np.full(r // bs, self.idx.shape[1], np.int32),
                            shape=(s, r), block_size=bs)
        replicated = NamedSharding(mesh, PartitionSpec())
        with cache_every_compile():
            self.A = jax.jit(reference.dense_a, static_argnames=("s", "r"),
                             out_shardings=replicated)(vals, self.idx, s=s, r=r)
            key = jax.random.key(generator.jax_seed(rng["b"]))
            make_b = jax.jit(
                lambda k: jax.random.normal(k, (s, t), jax.numpy.float32),
                out_shardings=replicated)
            self.B = [make_b(jax.random.fold_in(key, j))
                      for j in range(int(traffic_.get("b_operands", 1)))]
            jax.block_until_ready((self.A, self.B))
        ncols = min(int(config["check_columns"]), t)
        self.cols = np.sort(rng["check"].choice(t, size=ncols, replace=False))
        self.V = rng["check"].standard_normal(
            (t, int(config["check_projections"])), dtype=np.float32)


def _keep(C, cols, V):
    """What the check keeps of a product, and of the reference's: the
    checked columns, and the projection on V, which an altered entry
    anywhere in C moves.  Both sides are projected by this one function, so
    the projection's own rounding largely cancels in their gap."""
    import jax

    return C[:, cols], jax.numpy.einsum(
        "rt,tp->rp", C, V, precision=jax.lax.Precision.HIGHEST)


def _mask_key(mask) -> tuple | None:
    return None if mask is None else tuple(bool(x) for x in mask)


def check_kept(kept, refs, device, limits) -> tuple[dict, int]:
    """The worst gap of every compared number over the kept products, on
    every chip's copy, and how many products failed a limit: ``proj_max``
    on every kept product, ``err_max`` and ``err_fro`` on those that kept
    their columns (``cut`` not None)."""
    import jax

    from chipbench import reference

    worst = {name: 0.0 for name in limits}
    failed = 0
    for b, _, cut, proj in kept:
        bad = False
        for j, p_shard in enumerate(proj.addressable_shards):
            g = {"proj_max": reference.gaps(jax.device_put(p_shard.data, device),
                                            refs[b][1])["err_max"]}
            if cut is not None:
                g.update(reference.gaps(
                    jax.device_put(cut.addressable_shards[j].data, device),
                    refs[b][0]))
            for name, value in g.items():
                limit = limits[name]
                worst[name] = max(worst[name], value)
                bad |= limit is None or not value <= limit
        failed += bad
    return worst, failed


def run_cell(cell: cells.Cell, seed: int, seconds: float, trace: bool,
             setup_t0: float | None = None) -> tuple[dict, list]:
    """Run ``cell`` once; return (the result line's object, check lines).

    ``setup_t0`` is the host-clock time that set-up is counted from (the
    process's start where the caller knows it).
    """
    import jax

    from chipbench import reference, xplane
    from repro import compat
    from repro.coded import CodedMatmulConfig, plan
    from repro.launch.compile_cache import enable_compile_cache

    if setup_t0 is None:
        setup_t0 = time.perf_counter()
    config, traffic_ = cell.config, cell.traffic
    devices = jax.devices()[:cell.chips]
    chip_peaks = peaks.lookup(devices[0].device_kind) if trace else None
    cache_every_program()
    enable_compile_cache()
    counter = compile_counter()
    N, m, n = config["num_workers"], config["m"], config["n"]
    # One device per chip, N / chips workers to each: a program that cannot
    # place them refuses at bind, and the run ends on its error.
    mesh = compat.make_mesh((cell.chips,), ("model",), devices=devices)
    rng = generator.streams(seed)

    # -- set-up: operands, plan, bind, pack, warm-up -------------------------
    phases = {"start": time.perf_counter() - setup_t0}
    ops_ = Operands(config, traffic_, rng, mesh)
    phases["operands"] = time.perf_counter() - setup_t0 - sum(phases.values())
    ccfg = CodedMatmulConfig(
        scheme=config["scheme"], backend=config["backend"],
        block_size=config["block_size"],
        compute_dtype=config["compute_dtype"],
        out_sharded=config["decode"] == "reduce_scatter")
    base = plan(ccfg, m=m, n=n, num_workers=N,
                seed=config["plan_seed"]).bind(mesh)
    base.pack_for(ops_.ell)
    phases["plan_pack"] = time.perf_counter() - setup_t0 - sum(phases.values())
    schedule = generator.Schedule(traffic_, rng["membership"],
                                base.plan_.coefficient_matrix())
    with cache_every_compile():
        keep = jax.jit(_keep)
        cols_dev, V_dev = jax.device_put((ops_.cols, ops_.V),
                                         ops_.A.sharding)
    max_kept = int(config["check_products"])
    warm_ms = []
    for b, mask in schedule.warmup(int(traffic_.get("warmup_products", 0)),
                                   max_kept, rng["warmup"]):
        t0 = time.perf_counter()
        C = base.with_survivors(mask)(ops_.A, ops_.B[b], a_sparse=ops_.ell)
        warm_ms.append((time.perf_counter() - t0) * 1e3)
        with cache_every_compile():
            jax.block_until_ready(keep(C, cols_dev, V_dev))
        del C

    # -- the window ---------------------------------------------------------
    tracedir = None
    if trace:
        tracedir = tempfile.mkdtemp(prefix="chipbench-trace-")
        options = jax.profiler.ProfileOptions()
        options.host_tracer_level = 1
        options.python_tracer_level = 0
        jax.profiler.start_trace(tracedir, profiler_options=options)
    check_rng = rng["check"]
    sample = generator.CheckSample(max_kept, len(schedule.pairs))
    kept = []
    product_s, stage_s, recover_s, rebind_s = [], [], [], []
    op, cur = base, None
    compiles0 = counter.compiles
    t_win0 = time.perf_counter()
    setup_s = t_win0 - setup_t0
    phases["warmup"] = setup_s - sum(phases.values())
    deadline = t_win0 + seconds
    i = 0
    with span("window"):
        while time.perf_counter() < deadline:
            mask = schedule.mask(i)
            key = _mask_key(mask)
            changed = key != cur
            if changed:
                t_r = time.perf_counter()
                with span("rebind"):
                    op = base.with_survivors(mask)
                rebind_s.append(time.perf_counter() - t_r)
                cur = key
            b = schedule.b_index(i)
            t0 = time.perf_counter()
            with span("stage"):
                C = op(ops_.A, ops_.B[b], a_sparse=ops_.ell)
            t1 = time.perf_counter()
            with span("wait"):
                C.block_until_ready()
            t2 = time.perf_counter()
            product_s.append(t2 - t0)
            stage_s.append(t1 - t0)
            if changed:
                recover_s.append(t2 - t_r)
            take = sample.take((b, key), check_rng.random())
            if take is not None:
                # One program keeps both parts, so a projection kept alone
                # is the one a product with columns keeps.
                with span("check"):
                    cut, proj = keep(C, cols_dev, V_dev)
                    kept.append((b, key, cut if take == "columns" else None,
                                 proj))
                    del cut
            del C
            i += 1
    t_win1 = time.perf_counter()
    compiles = counter.compiles - compiles0
    if trace:
        jax.profiler.stop_trace()
    memory_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                      for d in devices)

    # -- the check, after the window ---------------------------------------
    on_first = devices[0]

    def first(x):
        return next(sh.data for sh in x.addressable_shards
                    if sh.device == on_first)

    A_first = first(ops_.A)
    cols_first, V_first = jax.device_put((ops_.cols, ops_.V), on_first)
    with cache_every_compile():
        refs = {}
        for b in sorted({k[0] for k in kept}):
            refs[b] = keep(reference.product(A_first, first(ops_.B[b])),
                           cols_first, V_first)
        del ops_.B
        worst, failed = check_kept(kept, refs, on_first, config["limits"])
    masks_used = {_mask_key(schedule.mask(j)) for j in range(i)}
    masks_checked = {k[1] for k in kept}
    correct = failed == 0 and bool(kept) and masks_used <= masks_checked

    run = Run(chips=cell.chips, setup_s=setup_s, window_s=t_win1 - t_win0,
              product_s=product_s, stage_s=stage_s, recover_s=recover_s,
              rebind_s=rebind_s, compiles=compiles, peaks=chip_peaks)
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": int(memory_peak)}
    result = {"correct": correct, "attempted": i, "failed": int(failed)}
    extra = {}
    if trace:
        per_worker = work.worker_work(
            base.base_plan.cols, base.base_plan.weights, m, n,
            list(ops_.idx), s=config["s"], r=config["r"],
            t=config["t"], block_size=config["block_size"],
            tile_itemsize=np.dtype(config["compute_dtype"]).itemsize)
        run.work = work.chip_work(per_worker, cell.chips)
        summary = xplane.reduce(xplane.find_xspace(tracedir), WINDOW_SPAN,
                                spans=SPANS)
        shutil.rmtree(tracedir, ignore_errors=True)
        run.trace = summary
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        extra["kernel_work"] = dict(
            run.work, bound=xplane.bound(run.work, chip_peaks),
            peaks_source=chip_peaks["source"])
        extra["breakdown"] = xplane.breakdown(summary)
    result["metrics"] = {}
    for metric in cell.metrics(trace):
        value = run.read(metric["name"])
        if value is not None:
            result["metrics"][metric["name"]] = {"value": value,
                                                 "unit": metric["unit"]}
    result["device"] = device
    result.update(extra)
    result["counts"] = {"products": i, "recoveries": len(recover_s),
                        "checked": len(kept), "compiles": compiles}
    check = {name: {"value": worst[name], "limit": limit}
             for name, limit in config["limits"].items()}
    check["masks_unchecked"] = {"value": len(masks_used - masks_checked),
                                "limit": 0}
    result["check"] = check
    slow = sorted(range(i), key=lambda j: -product_s[j])[:8]
    lines = ["setup " + " ".join(f"{k} {v:.3f}s" for k, v in phases.items()),
             "warm-up products' stage ms: " + " ".join(f"{x:.0f}" for x in warm_ms),
             "slowest products (index stage+wait ms): " + " ".join(
                 f"{j}:{stage_s[j] * 1e3:.0f}+{(product_s[j] - stage_s[j]) * 1e3:.0f}"
                 for j in slow)]
    lines += [f"check {name}: {c['value']!r} limit {c['limit']!r}"
              for name, c in check.items()]
    return result, lines


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        fail(f"--seed {args.seed} is negative")
    setup_t0 = time.perf_counter() - process_age_s()
    cell = cells.resolve(cells.load_benchmark(), args.workload)
    import repro.coded  # noqa: F401  (the program under test, from the checkout)

    require_chip(cell.chips)
    result, lines = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                             setup_t0=setup_t0)
    counts = result["counts"]
    print(f"chipbench {cell.name} seed {args.seed}: products "
          f"{counts['products']} recoveries {counts['recoveries']} checked "
          f"{counts['checked']} compiles {counts['compiles']}", flush=True)
    for line in lines:
        print(line, file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
