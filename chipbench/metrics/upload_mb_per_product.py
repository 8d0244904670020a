"""The program's ``upload_bytes`` counter over the window's products, per
product, in MB (1e6 bytes): the worker arrays put on the devices."""

from chipbench import program_spans


def read(run):
    per = program_spans.per_product(run, "upload_bytes")
    return None if per is None else per / 1e6
