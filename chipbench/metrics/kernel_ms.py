"""Device time of the fused-decode Pallas kernel per product, mean over chips.

The kernel's operations are found in the trace by name.  None where the
trace holds none of them.
"""

#: the name the trace gives the kernel's operations
KERNEL = r"fused_decode"


def read(run):
    if run.trace is None or not run.products:
        return None
    per_chip = run.trace.op_seconds(KERNEL)
    if not any(run.trace.op_events(KERNEL)):
        return None
    return sum(per_chip) / len(per_chip) / run.products * 1e3
