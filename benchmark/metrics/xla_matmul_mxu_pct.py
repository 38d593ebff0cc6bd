"""How near XLA's own matmul fusions, AS COMPILED, run to the MXU: the
FLOPs of the dots and convolutions held by the step program's instructions
(``dot_flops`` of the program's compiled-step ledger: 2 x multiply-adds
from the compiled shapes; custom calls, which are the Pallas kernels and
XLA's grouped products, hold none and have rooflines of their own) summed
over those instructions' trace EVENTS, over the published peak times the
summed duration of the same events, in whole runs of the step program.
Work made twice (the rematerialised forward) counts twice in both sums: this
reads the fusions, not the policy. It cannot pass 100 %: an instruction
whose own events read above 100 is printed by name (its FLOPs are counted
too high, or its time leaves out part of the work), and the value is
returned as found. ``None`` where the program keeps no such rows."""
from benchmark.lib import op_table


def read(ctx):
    got = op_table.matmul(ctx)
    if got is None or ctx["peaks"] is None or not got[1]:
        return None
    flops, took, by = got
    peak = ctx["peaks"]["flops_bf16"]
    for name, (f, s) in sorted(by.items()):
        if s and f / (peak * s) > 1.0:
            print(f"[xla_matmul_mxu_pct] OVER THE PEAK: {name} reads "
                  f"{100.0 * f / (peak * s):.2f} % ({f:.4e} FLOPs in "
                  f"{s:.4e} s of events)", flush=True)
    print(f"[xla_matmul_mxu_pct] {len(by)} instructions hold a dot or a "
          f"convolution: {flops:.4e} FLOPs in {took:.4f} s of events",
          flush=True)
    return 100.0 * flops / (peak * took)
