"""Device time of one run of the decode-step program (the program under
the ``serving/decode_step`` span), mean over the traced window."""


def read(ctx):
    tr = ctx["trace"]
    runs = tr.program_runs(ctx["system"].DECODE_PROGRAM) if tr else []
    return 1e3 * sum(runs) / len(runs) if runs else None
