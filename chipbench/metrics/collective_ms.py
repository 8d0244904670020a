"""Device time of the decode collective (all-reduce, or reduce-scatter) per
product, mean over chips.  None where the trace holds no collective, as on
one chip, where the psum over one worker is compiled away."""

#: the names the trace gives collective operations
COLLECTIVE = r"all-reduce|reduce-scatter|all-gather|collective-permute"


def read(run):
    if run.trace is None or not run.products:
        return None
    if not any(run.trace.op_events(COLLECTIVE)):
        return None
    per_chip = run.trace.op_seconds(COLLECTIVE)
    return sum(per_chip) / len(per_chip) / run.products * 1e3
