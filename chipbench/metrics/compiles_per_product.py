"""Backend compiles (jax.monitoring events) inside the window per product."""


def read(run):
    if not run.products:
        return None
    return run.compiles / run.products
