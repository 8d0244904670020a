"""Process start to the first timed product: loading, data, packing, warm-up."""


def read(run):
    return run.setup_s
