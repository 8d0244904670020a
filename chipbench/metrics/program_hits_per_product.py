"""The program's ``program_hits`` counter over the window's products, per
product: the products that reused a staged program and the tile pack's
device copy, and so neither lowered nor uploaded the pack.  0 on a program
that counts no hits; None where it records no spans."""

from chipbench import program_spans


def read(run):
    return program_spans.per_product(run, "program_hits")
