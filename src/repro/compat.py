"""JAX API boundary (DESIGN.md section 4).

Policy: every JAX API whose location or signature has moved between JAX
releases is reached ONLY through this module.  Call sites never touch
``jax.shard_map`` / ``jax.sharding.AxisType`` / ``jax.make_mesh`` /
``jax.lax.psum_scatter`` directly (the ``compat-boundary`` lint enforces
it), so the next upgrade that moves one of them is a one-file change.

The tree supports exactly the JAX pinned in ``requirements.txt`` (0.9.0);
there are no branches for older releases.
"""

from __future__ import annotations

import jax

AxisType = jax.sharding.AxisType


def shard_map(f, *, mesh, in_specs, out_specs, check_vma: bool = True,
              **kwargs):
    """``jax.shard_map`` (``check_vma`` is its replication check)."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                         check_vma=check_vma, **kwargs)


def psum_scatter(x, axis_name, *, scatter_dimension: int = 0, tiled: bool = True):
    """``lax.psum_scatter`` (reduce-scatter over a mesh axis).

    ``tiled=True`` splits ``scatter_dimension`` (which must divide by the
    axis size) instead of adding a leading axis.
    """
    return jax.lax.psum_scatter(
        x, axis_name, scatter_dimension=scatter_dimension, tiled=tiled)


def cost_analysis(compiled) -> dict:
    """``Compiled.cost_analysis()`` as a dict (empty if the backend has none)."""
    return compiled.cost_analysis() or {}


def auto_axis_types(n: int) -> tuple:
    """``(AxisType.Auto,) * n`` -- the annotation every current mesh uses."""
    return (AxisType.Auto,) * n


def make_mesh(axis_shapes, axis_names, *, axis_types=None, devices=None):
    """``jax.make_mesh`` over ``devices`` (default: every visible device).

    Axes default to ``AxisType.Auto``: ``jax.make_mesh`` itself defaults to
    Explicit axes, under which the eager block->C assembly after a
    reduce-scatter decode must spell out every output sharding.
    """
    if axis_types is None:
        axis_types = auto_axis_types(len(axis_names))
    return jax.make_mesh(tuple(axis_shapes), tuple(axis_names),
                         axis_types=axis_types, devices=devices)
