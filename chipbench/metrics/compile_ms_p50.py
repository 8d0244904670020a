"""Median over the window's products of the program's ``repro.stage.compile``
span: the backend compile, or the executable read back from the persistent
cache, from ``jax.monitoring``."""

from chipbench import program_spans


def read(run):
    return program_spans.median_ms(run, "repro.stage.compile")
