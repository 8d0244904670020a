"""Device time of the decode collective per product, mean over chips.  None
where the trace holds no collective, as on one chip, where the psum over
one worker is compiled away.

A TPU trace names an operation by its HLO instruction's name, and
``xplane.op_name`` keeps only that name, not the opcode.  JAX names the
instruction after its primitive: the decode's all-reduce is ``psum.<k>`` (a
v5e 2x2 compile of the churn cell's program), a reduce-scatter
``reduce_scatter.<k>``, an all-gather ``all_gather.<k>``, a permute
``ppermute.<k>``.  Only names that hold one of the words in ``COLLECTIVE``,
spelt with ``_`` or ``-`` (XLA's own names), are counted; a collective named
otherwise reads as none.
"""

#: the words the names of collective operations hold
COLLECTIVE = (r"psum|all[-_]reduce|reduce[-_]scatter|all[-_]gather"
              r"|all[-_]to[-_]all|collective[-_]permute|ppermute")


def read(run):
    if run.trace is None or not run.products:
        return None
    if not any(run.trace.op_events(COLLECTIVE)):
        return None
    per_chip = run.trace.op_seconds(COLLECTIVE)
    return sum(per_chip) / len(per_chip) / run.products * 1e3
