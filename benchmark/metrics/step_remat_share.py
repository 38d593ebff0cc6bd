"""Share of the step program's device time spent making the forward pass
again: the time of its leaf instructions' events whose row in the
program's compiled-step ledger is ``recomputed`` (JAX writes
``rematted_computation`` into the `op_name` of every op that a
`jax.checkpoint` region runs a second time for its backward pass), over the
time of all its leaf instructions' events, in whole runs of the step
program. What a block keeps (`ops.REMAT_KEEP`: a recurrence's result, the
flash forward's output and log-sum-exp, the experts' result) is not made
again and does not count. ``None`` where the program keeps no such rows."""
from benchmark.lib import op_table


def read(ctx):
    got = op_table.share(ctx, lambda row: row["recomputed"])
    return None if got is None else got[0]
