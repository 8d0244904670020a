"""The kernel's share of its roofline, in %: the least time the chip could
take for the algorithm's work of one chip (``chipbench.work.chip_work``: the
live tiles the coded tasks of the workers it holds need, summed over those
workers and averaged over the chips, priced against the published peaks of
the chip's ``device_kind``) over the kernel's device time per product, which
is also per chip."""

from chipbench import xplane


def read(run):
    kernel_ms = run.read("kernel_ms")
    if kernel_ms is None or run.work is None:
        return None
    return 100.0 * xplane.roofline_s(run.work, run.peaks) / (kernel_ms * 1e-3)
