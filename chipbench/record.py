"""What one run recorded, as the metric readers see it."""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass
class Run:
    """One run of a cell.  Times are seconds on the host's clock.

    ``product_s`` holds every product of the window, call to decoded C
    ready; ``stage_s`` the part of it before the call returned;
    ``recover_s`` for each membership change the time from the rebind call
    to the first product under the new mask being ready; ``rebind_s`` the
    rebind call alone.  ``trace`` is the reduced profiler trace of a
    ``--trace 1`` run (``chipbench.xplane.TraceSummary``), ``work`` the
    algorithm's work per chip, the sum over the workers a chip holds, mean
    over the chips (``chipbench.work.chip_work``), and ``peaks`` the chip's
    published peaks (``chipbench.peaks``).
    """

    chips: int
    setup_s: float
    window_s: float
    product_s: list
    stage_s: list
    recover_s: list
    rebind_s: list
    compiles: int
    work: dict | None = None
    peaks: dict | None = None
    trace: object | None = None

    @property
    def products(self) -> int:
        return len(self.product_s)

    def read(self, metric: str):
        """The value of another metric of this run (None if it has none)."""
        from chipbench import cells

        return cells.reader(metric)(self)
