"""Median host time of the survivor rebind call (``CodedOp.with_survivors``)."""

import numpy as np


def read(run):
    if not run.rebind_s:
        return None
    return float(np.median(run.rebind_s)) * 1e3
