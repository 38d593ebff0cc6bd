"""Device time of one run of the prefill-chunk program (the program under
the ``serving/prefill_chunk`` span), mean over the traced window."""


def read(ctx):
    tr = ctx["trace"]
    runs = tr.program_runs(ctx["system"].CHUNK_PROGRAM) if tr else []
    return 1e3 * sum(runs) / len(runs) if runs else None
