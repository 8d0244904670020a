"""Read the numbers that set a cell's limits: the program's and the control's.

    python3 chipbench/control.py --workload <name> --seeds 11,12,... \
        --control-seeds 11,12,13 --seconds 10

For every seed of ``--seeds`` it runs the cell as a benchmark run does (set-up,
a short window at the cell's own load, the comparison with the reference) and
prints the compared numbers: the lower readings.  For every seed of
``--control-seeds`` it runs the cell the same way with the control in the
program's place (``control_in_place``) and prints the same numbers and what
the run decided: the upper readings, from runs that have to come out as not
correct.  The limits in the configuration file lie between the largest lower
reading and the smallest upper one.  Every seed runs in this one process,
which needs the chips of the cell; the benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import pathlib
import sys
from unittest import mock

HERE = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parent), str(HERE.parent / "src")]

from chipbench import cells, run as harness  # noqa: E402


@contextlib.contextmanager
def control_in_place():
    """Every product of the run is the control: the reference computed one
    precision step lower (``reference.product_high``), from the same A and B."""
    from chipbench import reference
    from repro.coded import op as op_mod

    def control(self, A, B, **_):
        return reference.product_high(A, B)

    with mock.patch.object(op_mod.CodedOp, "__call__", control):
        yield


def readings(cell: cells.Cell, seed: int, seconds: float, control: bool) -> dict:
    """What one run of ``cell`` decided and the numbers it compared."""
    from repro.runtime import pack_cache

    with control_in_place() if control else contextlib.nullcontext():
        result, _ = harness.run_cell(cell, seed, seconds, False)
    pack_cache.clear()
    return {"control" if control else "program": seed,
            "correct": result["correct"],
            "checked": result["counts"]["checked"],
            "numbers": {k: v["value"] for k, v in result["check"].items()}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    cell = cells.resolve(cells.load_benchmark(), args.workload)
    harness.require_chip(cell.chips)

    lower, upper = {}, {}
    for control, seeds, fold in ((False, args.seeds, max),
                                 (True, args.control_seeds, min)):
        into = upper if control else lower
        for seed in (int(s) for s in seeds.split(",")):
            got = readings(cell, seed, args.seconds, control)
            print(json.dumps(got), flush=True)
            for k, v in got["numbers"].items():
                into[k] = fold(into[k], v) if k in into else v
    print(json.dumps({"workload": cell.name, "lower": lower, "upper": upper}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
