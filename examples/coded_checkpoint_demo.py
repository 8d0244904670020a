"""Coded checkpointing demo: train, erasure-code the checkpoint with the
(P,S)-sparse code across 12 storage targets, destroy a third of them, and
restore exactly -- the paper's any-K-of-N decodability as fault tolerance.

  PYTHONPATH=src python examples/coded_checkpoint_demo.py
"""

import pathlib
import shutil
import tempfile

import jax
import jax.numpy as jnp
import numpy as np

import repro.configs as configs
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build
from repro.training import checkpoint as ckpt_lib
from repro.training.data import SyntheticCorpus
from repro.training.optimizer import AdamW
from repro.training.train_step import make_train_step


def main():
    enable_compile_cache()
    cfg = configs.get("internlm2-1.8b").reduced()
    model = build(cfg)
    params = model.init(jax.random.key(0), jnp.float32)
    opt = AdamW(lr=1e-3)
    opt_state = opt.init(params)
    step_fn = jax.jit(make_train_step(model, opt))
    corpus = SyntheticCorpus(cfg, 2, 32, seed=0)

    for step in range(5):
        batch = {k: jnp.asarray(v) for k, v in corpus.make_batch(step).items()}
        params, opt_state, metrics = step_fn(params, opt_state, batch)
    print(f"trained 5 steps, loss={float(metrics['loss']):.4f}")

    tmp = pathlib.Path(tempfile.mkdtemp(prefix="coded_ckpt_"))
    try:
        manifest = ckpt_lib.save_coded_checkpoint(tmp, 5, params, m=3, n=3,
                                                  num_targets=14)
        print(f"wrote {manifest['num_targets']} coded shards "
              f"(mn={manifest['m']*manifest['n']} data chunks)")

        # destroy 4 of 14 storage targets (10 >= mn = 9 survive)
        for k in (1, 4, 7, 10):
            (tmp / "coded_00000005" / f"target_{k:03d}.npz").unlink()
        survivors = [0, 2, 3, 5, 6, 8, 9, 11, 12, 13]
        print(f"destroyed shards [1, 4, 7, 10]; restoring from {survivors}")

        restored, stats = ckpt_lib.restore_coded_checkpoint(
            tmp, 5, params, available=survivors)
        print(f"decode: {stats.peels} peels, {stats.roots} roots")
        err = max(float(jnp.max(jnp.abs(a.astype(jnp.float32) -
                                        b.astype(jnp.float32))))
                  for a, b in zip(jax.tree.leaves(params),
                                  jax.tree.leaves(restored)))
        print(f"max restore error: {err:.2e}")
        assert err < 1e-4
        print("OK: checkpoint survived losing 4/12 storage targets")
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    main()
