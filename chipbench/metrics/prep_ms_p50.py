"""Median over the window's products of the program's ``repro.stage.prepare``
spans: operand checks, the pack-cache lookup and ``resolve_pack``, the
per-call weight gather, decode columns and ``shard_map``."""

from chipbench import program_spans


def read(run):
    return program_spans.median_ms(run, "repro.stage.prepare")
