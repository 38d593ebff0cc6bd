"""Device time by the program's scopes, for the per-layer readers that
have to tell XLA's fusions apart by what they compute.

A trace names an op by its compiled instruction (``fusion.812``,
``flash_fwd.80``, ``ragged-dot-none.3``); the program's ledger keeps, for
the step program, each instruction's ``op_name``: the ``jax.named_scope``
path of the op, or of a fusion's root (``.../jvp(moe/experts)/...``). The
system adapter hands that map over as ``op_scopes()``. A program or an
adapter without it gives every function here nothing to return, and the
readers ``None``."""
from __future__ import annotations

import statistics

#: what the per-scope table of a traced run lists, in this order; an op
#: goes to the first marker its scope holds
MARKERS = ("kda/proj", "kda/scan", "kda/out", "mla/proj", "mla/attn",
           "moe/route", "moe/dispatch", "moe/experts", "moe/shared",
           "moe/combine", "mlp/gated", "head/loss", "opt/update")


def step_runs(ctx):
    """Whole runs of the step program in the traced stretch, or None. The
    run in flight when the profiler starts is cut, and its first recorded
    op may fall after the window's opening mark, so that
    ``Trace.program_spans`` takes it for a whole one (PERF.md section 7):
    a run shorter than nine tenths of the median run is left out here."""
    tr = ctx["trace"]
    if tr is None:
        return None
    runs = tr.program_spans(ctx["system"].STEP_PROGRAM)
    if not runs:
        return None
    median = statistics.median(e - s for _, s, e in runs)
    return [r for r in runs if r[2] - r[1] >= 0.9 * median] or None


def seconds(ctx, match):
    """(device seconds of the ops ``match(name, scope)`` accepts inside
    the whole runs of the step program, seconds of those runs, optimizer
    steps they hold), or None where there is nothing to read."""
    runs = step_runs(ctx)
    scopes = getattr(ctx["system"], "op_scopes", lambda: None)()
    if not runs or not scopes:
        return None
    tr = ctx["trace"]
    took = sum(tr.op_seconds(
        lambda n, kind: match(n, scopes.get(n, "")), inside=runs).values())
    chips = ctx["cell"].chips
    whole = sum(e - s for _, s, e in runs) / chips
    return took, whole, len(runs) / chips * ctx["steps_per_call"]


def share(ctx, *markers):
    """Percent of the step program's device time under any of the scopes
    ``markers``."""
    got = seconds(ctx, lambda n, scope: any(m in scope for m in markers))
    if got is None or not got[1]:
        return None
    return 100.0 * got[0] / got[1]


def table(ctx):
    """Device milliseconds of one optimizer step by scope, printed once a
    traced run as a ``[scopes]`` line (for PERF.md section 5); the
    grouped-product kernels, which keep no scope, count as
    ``moe/experts``."""
    runs = step_runs(ctx)
    scopes = getattr(ctx["system"], "op_scopes", lambda: None)()
    if not runs or not scopes:
        return None

    def of(name):
        if name.startswith("ragged-dot"):
            return "moe/experts"
        scope = scopes.get(name, "")
        return next((m for m in MARKERS if m in scope), "other")

    steps = len(runs) / ctx["cell"].chips * ctx["steps_per_call"]
    out = {}
    for name, took in ctx["trace"].op_seconds(inside=runs).items():
        out[of(name)] = out.get(of(name), 0.0) + took
    row = {k: round(1e3 * v / steps, 3) for k, v in sorted(out.items())}
    print(f"[scopes] device ms a step over {len(runs)} whole runs of "
          f"{ctx['steps_per_call']} steps: {row}", flush=True)
    return row
