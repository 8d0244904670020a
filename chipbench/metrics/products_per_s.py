"""Decoded products completed in the window over the window's length."""


def read(run):
    if not run.products:
        return None
    return run.products / run.window_s
