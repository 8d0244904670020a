"""End-to-end training driver example.

Default: a ~115M-parameter dense LM (same code path as the 10 assigned
archs) for a few hundred steps -- the assignment's "train a ~100M model"
driver.  On this CPU container that is hours; pass --tiny for a 2-minute
demonstration of the identical pipeline (synthetic corpus -> pjit train step
-> async checkpoints -> resume).

  PYTHONPATH=src python examples/train_lm.py --tiny --steps 30

Fault-tolerance demo: run with --simulate-failure N, then re-run the same
command -- training resumes from the last checkpoint.
"""

import argparse
import sys

sys.path.insert(0, "src")

from repro.configs.base import ArchConfig, register
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.train import main as train_main

LM_100M = register(ArchConfig(
    name="lm-100m",
    family="dense",
    num_layers=12,
    d_model=768,
    num_heads=12,
    num_kv_heads=4,
    d_ff=3072,
    vocab_size=32_000,
    source="example driver (~115M params)",
))


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--simulate-failure", type=int, default=0)
    args, rest = ap.parse_known_args()

    argv = ["--arch", "lm-100m", "--steps", str(args.steps),
            "--batch", "4", "--seq", "256", "--ckpt-every", "20",
            "--coded-ckpt"]
    if args.tiny:
        argv += ["--reduced"]
    if args.simulate_failure:
        argv += ["--simulate-failure", str(args.simulate_failure)]
    return train_main(argv + rest)


if __name__ == "__main__":
    raise SystemExit(main())
