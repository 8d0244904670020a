"""Quickstart: the coded-matmul API end to end in 60 seconds.

  PYTHONPATH=src python examples/quickstart.py

One scheme registry entry drives BOTH execution paths from the same code
design (``repro.coded``, DESIGN.md section 7):

1. pick the paper's (P, S)-sparse code by name -- ``get_scheme("sparse_code")``;
2. host path: ``scheme.instance(...)`` -> master/worker protocol with two
   declared stragglers, hybrid peeling + rooting decode (Algorithm 1);
3. device path: ``plan(config, ...)`` -> a ``CodedOp`` bound to a mesh of
   the visible devices (m=n=2 over 8 workers where 8 devices exist, e.g.
   the 8 host devices set below; m=n=1 on one worker otherwise, e.g. one
   TPU chip), applied, then rebound to survivors with ``with_survivors``;
4. checks both against the direct product.
"""

import os

# 8 host devices for the SPMD op on CPU (must be set before jax initializes;
# it does not change how many accelerator devices there are)
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "2")

import numpy as np
import scipy.sparse as sp

from repro.coded import CodedMatmulConfig, get_scheme, plan, scheme_names
from repro.core.encoder import split_blocks, make_tasks, encode_blocks
from repro.launch.compile_cache import enable_compile_cache


def host_path():
    """The paper's protocol: code across 12 workers, never wait for two."""
    m, n, N = 2, 3, 12
    s, r, t = 4000, 1800, 2400
    A = sp.random(s, r, density=0.01, format="csc",
                  random_state=np.random.RandomState(0))
    B = sp.random(s, t, density=0.01, format="csc",
                  random_state=np.random.RandomState(1))
    print(f"A: {A.shape} nnz={A.nnz}   B: {B.shape} nnz={B.nnz}")

    scheme = get_scheme("sparse_code")     # any name in scheme_names()
    code = scheme.instance(m, n, N, seed=0, distribution="wave_soliton")
    print(f"scheme {code.name}: avg degree {code.M.nnz / N:.2f} "
          f"(Theta(ln mn) -- the paper's overhead)")

    A_blocks, B_blocks = split_blocks(A, m), split_blocks(B, n)
    results = [encode_blocks(t_, A_blocks, B_blocks, n)
               for t_ in make_tasks(code.M)]

    stragglers = {3, 7}
    finished = [k for k in range(N) if k not in stragglers]
    print(f"workers {sorted(stragglers)} are stragglers -> decoding from "
          f"{len(finished)} results")
    blocks = code.decode(finished, dict(enumerate(results)))

    C = (A.T @ B).toarray()
    br, bt = r // m, t // n
    err = max(
        abs(blocks[i * n + j] - C[i*br:(i+1)*br, j*bt:(j+1)*bt]).max()
        for i in range(m) for j in range(n)
    )
    print(f"host path max abs error vs direct product: {err:.2e}")
    assert err < 1e-8


def device_path():
    """The same design as an SPMD op: plan -> bind -> apply (-> rebind)."""
    import jax
    import jax.numpy as jnp

    from repro import compat
    from repro.core.coded_matmul import uncoded_matmul_reference

    # one worker per device: the coded 8-worker design needs 8 devices
    m, n, N = (2, 2, 8) if len(jax.devices()) >= 8 else (1, 1, 1)
    cfg = CodedMatmulConfig(scheme="sparse_code", backend="dense_scan")
    mesh = compat.make_mesh((N,), ("model",), devices=jax.devices()[:N])
    op = plan(cfg, m=m, n=n, num_workers=N, seed=5).bind(mesh)
    print(f"device path: {op}")

    rng = np.random.default_rng(0)
    s, r, t = 64, 16, 24
    A = jnp.asarray(rng.standard_normal((s, r)), jnp.float32)
    B = jnp.asarray(rng.standard_normal((s, t)), jnp.float32)
    C_ref = np.asarray(uncoded_matmul_reference(A, B))

    C = np.asarray(op(A, B))
    err = np.abs(C - C_ref).max()
    print(f"all-alive max abs error: {err:.2e}")
    assert err < 1e-2
    if N == 1:
        return  # one worker carries no redundancy to lose

    # kill a worker whose loss keeps the code decodable, rebind, re-apply
    M = op.plan_.coefficient_matrix()
    for kill in range(op.num_workers):
        surv = np.ones(op.num_workers, dtype=bool)
        surv[kill] = False
        if np.linalg.matrix_rank(M * surv[:, None]) >= m * n:
            break
    C2 = np.asarray(op.with_survivors(surv)(A, B))
    err2 = np.abs(C2 - C_ref).max()
    print(f"killed worker {kill}: max abs error {err2:.2e} "
          "(decoded from survivors, no recompute)")
    assert err2 < 1e-2


def main():
    enable_compile_cache()
    print(f"registered schemes: {', '.join(scheme_names())}")
    host_path()
    device_path()
    print("OK")


if __name__ == "__main__":
    main()
