"""The share of the traced window, in %, in which no operation ran on a
chip: 1 - (union of device-operation intervals) / window, mean over chips."""


def read(run):
    if run.trace is None:
        return None
    return 100.0 * (1.0 - run.trace.busy_s / run.trace.window_s)
