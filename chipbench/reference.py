"""The plain reference of C = A^T B, its lower-precision control, and the gaps.

Imports nothing of the program under test.  A is built here from the seeded
tiles; the reference is A^T B in float32 at ``Precision.HIGHEST``, the
precision the configuration states.  The control is the same contraction one
step lower, at the ``high`` precision (three bf16 passes), written out so
that it computes the same on every platform (XLA on a CPU ignores the
precision setting, and on a TPU may fold a written-out split away).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


@functools.partial(jax.jit, static_argnames=("s", "r"))
def dense_a(vals, idx, *, s: int, r: int):
    """The dense (s, r) A whose column block ``cb`` holds ``vals[cb, l]`` at
    row block ``idx[cb, l]`` and zeros elsewhere."""
    cb_n, _, bs, _ = vals.shape
    A = jnp.zeros((s // bs, bs, cb_n, bs), jnp.float32)
    cb = jnp.broadcast_to(jnp.arange(cb_n)[:, None], idx.shape)
    return A.at[idx, :, cb, :].set(vals.astype(jnp.float32)).reshape(s, r)


@jax.jit
def product(A, B):
    """A^T B in float32 at ``Precision.HIGHEST``."""
    return jnp.einsum("sr,st->rt", A, B,
                      precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


def _split(x):
    """x = hi + lo + O(2^-16 |x|): hi rounded to bf16 (to nearest, ties to
    even, in integer arithmetic so that no compiler pass can fold it away),
    lo the rest rounded to bf16."""
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    bits = (bits + jnp.uint32(0x7FFF) + ((bits >> 16) & jnp.uint32(1))) & jnp.uint32(0xFFFF0000)
    hi = jax.lax.bitcast_convert_type(bits, jnp.float32)
    return hi.astype(jnp.bfloat16), (x - hi).astype(jnp.bfloat16)


@jax.jit
def product_high(A, B):
    """The control: A^T B at the ``high`` precision, three bf16 passes
    (hi*hi + hi*lo + lo*hi) accumulated in float32, as one contraction over
    the three stacked so that no pass can be merged into another."""
    a_hi, a_lo = _split(A)
    b_hi, b_lo = _split(B)
    return jnp.einsum("sr,st->rt", jnp.concatenate([a_hi, a_hi, a_lo]),
                      jnp.concatenate([b_hi, b_lo, b_hi]),
                      preferred_element_type=jnp.float32)


@jax.jit
def _gaps(got, want):
    d = got.astype(jnp.float32) - want
    finite = jnp.all(jnp.isfinite(got))
    rms = jnp.sqrt(jnp.mean(want * want))
    err_max = jnp.max(jnp.abs(d)) / rms
    err_fro = jnp.linalg.norm(d) / jnp.linalg.norm(want)
    return finite, err_max, err_fro


def gaps(got, want) -> dict:
    """{"err_max", "err_fro"} of ``got`` against the reference ``want``.

    ``err_max`` is the widest entry gap over the reference's root mean
    square, ``err_fro`` the Frobenius norm of the gap over the reference's.
    A non-finite value anywhere in ``got`` reads as infinity on both.
    """
    finite, err_max, err_fro = (np.asarray(x) for x in _gaps(got, want))
    if not bool(finite):
        return {"err_max": float("inf"), "err_fro": float("inf")}
    return {"err_max": float(err_max), "err_fro": float(err_fro)}
