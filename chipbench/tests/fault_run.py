"""Drive a whole run of a cell at a small size on the CPU, optionally with the
timed path broken underneath, and print what the run decided.

    python -m chipbench.tests.fault_run <benchmark.json> <workload> <fault> [<fault> ...]

Run from the root of the checkout, in a process of its own: it gives JAX as
many host devices as the cell has chips before JAX starts.  It skips the
harness's look for a chip and nothing else.  ``none`` runs the cell as it is;
the faults are

* ``unchanged`` -- every product returns the first answer it ever gave;
* ``half``      -- half of A's packed tiles are left out, the rest doubled;
* ``exchange``  -- the psum between the workers is left out;
* ``altered``   -- one entry of each answer is changed where it is produced;
* ``control``   -- every product is the control, the reference one precision
  step lower (``chipbench.control.control_in_place``).

The last line of standard output is a JSON object {fault: {"correct", "check"}}.
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
import os
import sys
from unittest import mock

SIZE = 512
SECONDS = 1.5
SEED = 2 ** 31 + 77


def small(cell):
    """The cell at s = r = t = SIZE, with A's density kept and B dense."""
    c = cell.config
    nnz_a = round(c["nnz_a"] * SIZE / c["s"] * SIZE / c["r"])
    config = dict(c, s=SIZE, r=SIZE, t=SIZE, nnz_a=nnz_a, nnz_b=SIZE * SIZE,
                  check_columns=64)
    return dataclasses.replace(cell, config=config)


def fault(name: str):
    import jax
    import numpy as np

    from repro.coded import op as op_mod

    if name == "none":
        return contextlib.nullcontext()
    if name == "unchanged":
        call, first = op_mod.CodedOp.__call__, {}

        def stale(self, A, B, **kw):
            return first.setdefault("C", call(self, A, B, **kw))

        return mock.patch.object(op_mod.CodedOp, "__call__", stale)
    if name == "half":
        pack_for = op_mod.CodedOp.pack_for

        def halved(self, a_sparse, **kw):
            pack = pack_for(self, a_sparse, **kw)
            _, cbl, lw = pack.wslot.shape
            drop = (np.arange(cbl)[:, None] + np.arange(lw)[None, :]) % 2 == 1
            return dataclasses.replace(
                pack, wslot=np.where(drop, 0.0, pack.wslot).astype(np.float32),
                vals=np.where(drop[..., None, None], 0.0,
                              2.0 * pack.vals).astype(pack.vals.dtype))

        return mock.patch.object(op_mod.CodedOp, "pack_for", halved)
    if name == "exchange":
        return mock.patch.object(jax.lax, "psum", lambda x, axis_name, **_: x)
    if name == "altered":
        call = op_mod.CodedOp.__call__

        def altered(self, A, B, **kw):
            C = call(self, A, B, **kw)
            return C.at[C.shape[0] // 3, C.shape[1] // 2 + 1].add(1.0)

        return mock.patch.object(op_mod.CodedOp, "__call__", altered)
    if name == "control":
        from chipbench.control import control_in_place

        return control_in_place()
    raise ValueError(f"unknown fault {name!r}")


def main(argv) -> int:
    bench, workload, faults = argv[0], argv[1], argv[2:]
    sys.path[:0] = [os.getcwd(), os.path.join(os.getcwd(), "src")]
    from chipbench import cells

    cell = small(cells.resolve(cells.load_benchmark(bench), workload))
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               f" --xla_force_host_platform_device_count={cell.chips}")
    from chipbench import run as harness
    from repro.runtime import pack_cache

    out = {}
    for name in faults:
        with fault(name):
            result, _ = harness.run_cell(cell, SEED, SECONDS, False)
        pack_cache.clear()
        out[name] = {"correct": result["correct"], "check": result["check"]}
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
