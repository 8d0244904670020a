"""The program's ``readbacks`` counter over the window's products, per
product: executables read back from JAX's persistent cache."""

from chipbench import program_spans


def read(run):
    return program_spans.per_product(run, "readbacks")
