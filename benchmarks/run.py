"""Benchmark harness: one module per paper table/figure.

  python -m benchmarks.run [--quick | --full] [--only NAME]

Prints ``name,us_per_call,derived`` CSV rows.  --full uses the larger
configurations (slower, closer to the paper's dimensions); --quick is the
default small configuration, spelled out for CI invocations.
"""

from __future__ import annotations

import argparse
import sys
import time

from repro.launch.compile_cache import enable_compile_cache

from benchmarks import (
    bench_chaos,
    bench_completion,
    bench_components,
    bench_coded_matmul,
    bench_decode,
    bench_density,
    bench_kernels,
    bench_recovery,
    bench_serving,
)

SUITES = {
    "density": bench_density,        # Fig 1(b)
    "recovery": bench_recovery,      # Fig 4 / Table IV
    "completion": bench_completion,  # Fig 5 / Table III
    "components": bench_components,  # Fig 6
    "decode": bench_decode,          # Theorem 1
    "coded_matmul": bench_coded_matmul,  # SPMD integration
    "kernel": bench_kernels,         # one-launch fused decode vs roofline
    "chaos": bench_chaos,            # process runtime vs simulator twin
    "serving": bench_serving,        # multi-tenant coded serving SLOs
}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    size = ap.add_mutually_exclusive_group()
    size.add_argument("--full", action="store_true")
    size.add_argument("--quick", action="store_true",
                      help="small configurations (the default, made explicit)")
    ap.add_argument("--only", default=None, choices=list(SUITES))
    args = ap.parse_args(argv)
    enable_compile_cache()

    names = [args.only] if args.only else list(SUITES)
    failed = []
    print("name,us_per_call,derived")
    for name in names:
        t0 = time.time()
        try:
            rows = SUITES[name].run(quick=not args.full)
        except Exception as e:  # noqa: BLE001 -- keep the suite going
            print(f"{name}/SUITE_ERROR,0.0,{type(e).__name__}: {e}")
            failed.append(name)
            continue
        for row in rows:
            print(row)
            if "/ERROR" in str(row).split(",", 1)[0]:
                failed.append(name)
        print(f"# {name} finished in {time.time() - t0:.1f}s", file=sys.stderr)
    if failed:
        # exit nonzero so CI goes red on the bench step itself, not on a
        # downstream missing-artifact message
        print(f"# FAILED suites: {sorted(set(failed))}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
