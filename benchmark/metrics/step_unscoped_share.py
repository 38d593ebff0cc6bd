"""Share of the step program's device time under no part scope: the time
of its leaf instructions' events whose row in the program's compiled-step
ledger has no ``part`` (`deeplearning4j_tpu/monitor/scopes.py`: the op
carries no `jax.named_scope` that says what it does inside its layer, or
no `op_name` at all), over the time of all its leaf instructions' events,
in whole runs of the step program; an instruction that only holds others
(`while`, `conditional`, `call`) is counted by its children alone. Its
line also gives the share under no ``layer``. XLA's grouped-product
kernels, which lose their scope, count as ``moe/experts`` by their name.
This reader also prints, once a traced run, the tables of the step by
part, by layer and by instruction (`benchmark/lib/op_table.py`). ``None``
where the program keeps no such rows."""
from benchmark.lib import op_table


def read(ctx):
    op_table.print_tables(ctx)
    got = op_table.share(ctx, lambda row: op_table.part_of(row) is None)
    if got is None:
        return None
    no_layer = op_table.share(ctx, lambda row: row["layer"] is None)
    print(f"[step_unscoped_share] no part {got[0]:.3f} % ({got[1]:.4f} of "
          f"{got[2]:.4f} s of leaf events); no layer {no_layer[0]:.3f} %",
          flush=True)
    return got[0]
