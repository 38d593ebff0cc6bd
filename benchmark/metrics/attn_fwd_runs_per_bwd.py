"""Runs of the flash attention's forward kernel a run of its backward, in
the step program: compiled instructions named after the kernel ``flash_fwd``
over those named after ``flash_bwd_dq``, from the instruction names of the
program's ledger (the adapter's ``op_scopes()``; the program publishes the
same counts as ``xla_program_kernel_calls{program,kernel}``). 2 where every
attention sits in a rematerialised block whose backward pass runs the
forward kernel again; 1 where the block keeps the kernel's output and
log-sum-exp. ``None`` where the adapter has no map or the program no flash
kernel."""


def read(ctx):
    names = getattr(ctx["system"], "op_scopes", lambda: None)()
    if not names:
        return None
    calls = lambda kernel: sum(n.split(".", 1)[0] == kernel for n in names)
    backward = calls("flash_bwd_dq")
    if not backward:
        return None
    return calls("flash_fwd") / backward
