"""The program's ``kernel_grid_steps`` counter over the window's products,
per product: the grid steps of the block-sparse kernel's launches, CB *
(bt_pad / t_tile) * L per worker.  None where no product of the window
counted any, as on a program that does not count them."""

from chipbench import program_spans

COUNTER = "kernel_grid_steps"


def read(run):
    found = program_spans.window(run)
    if found is None or not any(COUNTER in root.counts for root in found[0]):
        return None
    return program_spans.per_product(run, COUNTER)
