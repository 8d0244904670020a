"""Median host time from the product call to its return, before the wait:
trace, compile, enqueue and the upload of the worker pack."""

import numpy as np


def read(run):
    if not run.stage_s:
        return None
    return float(np.median(run.stage_s)) * 1e3
