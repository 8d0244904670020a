"""Median over membership changes: rebind call to the first product under
the new mask ready."""

import numpy as np


def read(run):
    if not run.recover_s:
        return None
    return float(np.median(run.recover_s)) * 1e3
