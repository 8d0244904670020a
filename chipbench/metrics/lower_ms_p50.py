"""Median over the window's products of the program's ``repro.stage.lower``
spans, summed per product (JAX's nested trace events counted once): the
trace and the lowering to MLIR, from ``jax.monitoring``."""

from chipbench import program_spans


def read(run):
    return program_spans.median_ms(run, "repro.stage.lower")
