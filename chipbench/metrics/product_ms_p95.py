"""95th percentile over every product of the window, call to decoded C ready."""

import numpy as np


def read(run):
    if not run.products:
        return None
    return float(np.percentile(run.product_s, 95)) * 1e3
