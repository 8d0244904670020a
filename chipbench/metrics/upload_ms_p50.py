"""Median over the window's products of the program's ``repro.stage.upload``
span: the ``device_put`` of the worker arrays, to its return."""

from chipbench import program_spans


def read(run):
    return program_spans.median_ms(run, "repro.stage.upload")
