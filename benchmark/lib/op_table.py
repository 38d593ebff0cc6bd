"""The step by instruction: every trace event of the step program joined
with the row that the program's compiled-step ledger keeps for its
instruction.

The program's ledger (``deeplearning4j_tpu.monitor.xla``) keeps, for a
captured program, one row an instruction of the compiled module: opcode,
the computation it lives in and the instruction that calls that
computation, the ``jax.named_scope`` path split into ``layer`` and
``part``, whether the op is the forward made again under gradient
checkpointing (``recomputed``), the FLOPs of the dots and convolutions it
holds (``dot_flops``) and the bytes of its result and operands as the
shapes say. A trace names an event by its instruction, so each EVENT takes
its instruction's row: a ``while`` body's trips and a conditional's taken
branch count themselves, a branch not taken counts nothing. Only whole
runs of the step program count (``scopes.step_runs``, less any run
shorter than 99 % of the median run), and an instruction
that only holds others (``while``, ``conditional``, ``call``, by OPCODE:
``lax.cond``'s instruction is called ``cond.N``) is counted by its
children alone.

A program whose record has no such rows (a parent of the PR that brought
them, an adapter that never switches the ledger on) gives every function
here nothing to return, and the readers ``None``.

``bytes_in`` is an UPPER bound wherever an op reads a slice of an operand
(``dynamic-slice``, ``gather``, the stacked operands of a scan): it is
printed, marked so, and read by no metric.
"""
from __future__ import annotations

import statistics

from benchmark.lib import scopes, xplane

#: XLA's grouped-product kernels lose the inner end of their scope (what
#: is left names the scan around them, if anything); they are the expert
#: layer's products (as `scopes.table` files them)
_BY_NAME = (("ragged-dot", "moe/experts"),)


def _records():
    from deeplearning4j_tpu.monitor import xla
    return [r for r in xla.records() if getattr(r, "ops", None)]


def events(ctx):
    """``(rows, steps, run_seconds)``: one ``(row, seconds)`` a leaf event
    inside the whole runs of the step program, the optimizer steps those
    runs hold and their summed length, all over all chips (the caller
    divides by ``steps``), or None.
    ``row`` is the instruction's row, or a stand-in with no scope for an
    event whose instruction the table does not have. Of the records that
    keep rows, the one whose instructions cover most of the events' time
    is the step program's."""
    if "op_table_events" in ctx:         # the three readers share one join
        return ctx["op_table_events"]
    runs = scopes.step_runs(ctx)
    if runs:
        # `step_runs` lets through a last run that the profiler's end cut
        # by less than a tenth; its last ops have no event, so here a run
        # shorter than 99 % of the median run is left out too
        median = statistics.median(e - s for _, s, e in runs)
        runs = [r for r in runs if r[2] - r[1] >= 0.99 * median]
    tables = [{r["name"]: r for r in rec.ops} for rec in _records()]
    got = None
    if runs and tables:
        inside = []
        for i, dev in enumerate(ctx["trace"].devices):
            spans = [(s, e) for chip, s, e in runs if chip == i]
            inside += [(n, e - s) for n, s, e, _ in dev["ops"]
                       if any(s >= lo and e <= hi for lo, hi in spans)]
        table = max(tables, key=lambda t: sum(
            took for n, took in inside if n in t))
        if any(n in table for n, _ in inside):
            out = []
            for name, took in inside:
                row = table.get(name)
                if row is None:
                    if xplane._is(name, xplane._CONTAINERS):
                        continue
                    row = _stand_in(name)
                elif row["opcode"] in ("while", "conditional", "call"):
                    continue
                out.append((row, took))
            got = (out, len(runs) * ctx["steps_per_call"],
                   sum(e - s for _, s, e in runs))
    ctx["op_table_events"] = got
    return got


def _stand_in(name):
    return {"name": name, "opcode": "?", "layer": None, "part": None,
            "recomputed": False, "dot_flops": 0, "bytes_in": 0,
            "bytes_out": 0}


def part_of(row):
    """The part its instruction's name stands for, else the row's."""
    for prefix, part in _BY_NAME:
        if row["name"].startswith(prefix):
            return part
    return row["part"]


def share(ctx, accept):
    """Percent of the leaf events' device time in the events whose row
    ``accept(row)`` takes, with both sums in seconds: ``(share, took,
    whole)``; None without the table."""
    got = events(ctx)
    if not got or not got[0]:
        return None
    whole = sum(took for _, took in got[0])
    took = sum(took for row, took in got[0] if accept(row))
    return (100.0 * took / whole, took, whole) if whole else None


def matmul(ctx):
    """``(flops, seconds, by_instruction)`` over the events of the
    instructions that hold a dot or a convolution: ``by_instruction`` maps
    a name to its own (flops, seconds). Work made twice counts twice in
    both sums."""
    got = events(ctx)
    if not got:
        return None
    by = {}
    for row, took in got[0]:
        if row["dot_flops"]:
            f, s = by.get(row["name"], (0, 0.0))
            by[row["name"]] = (f + row["dot_flops"], s + took)
    if not by:
        return None
    return (sum(f for f, _ in by.values()), sum(s for _, s in by.values()),
            by)


def _group(ctx, key):
    rows, steps, _ = events(ctx)
    out = {}
    for row, took in rows:
        g = out.setdefault(key(row) or "-", {
            "ms": 0.0, "dot_flops": 0, "dot_ms": 0.0, "remat_ms": 0.0,
            "remat_flops": 0})
        g["ms"] += 1e3 * took / steps
        if row["dot_flops"]:
            g["dot_flops"] += row["dot_flops"] / steps
            g["dot_ms"] += 1e3 * took / steps
        if row["recomputed"]:
            g["remat_ms"] += 1e3 * took / steps
            g["remat_flops"] += row["dot_flops"] / steps
    return out


def print_tables(ctx, top=20):
    """Once a traced run, what PERF.md section 5 is written from, every
    event counted once: ``[ops_by_part]`` and ``[ops_by_layer]`` (device
    ms a step, the dots' and convolutions' TFLOP a step, the share of the
    MXU's peak at which the instructions that hold them ran, the ms of
    recomputed ops), ``[ops]`` (the instructions with the most time over
    the larger of ``dot_flops`` / peak and bytes / bandwidth) and the
    step's ``dot_flops`` as compiled beside the reference's count."""
    got = events(ctx)
    if not got or not got[0] or ctx["peaks"] is None:
        return
    rows, steps, run_seconds = got
    peak, bw = ctx["peaks"]["flops_bf16"], ctx["peaks"]["hbm_bytes_per_s"]
    # the part of the runs in which no leaf instruction's event lies: the
    # loops' and conditionals' own bookkeeping, waits between ops
    between = 1e3 * (run_seconds - sum(took for _, took in rows)) / steps
    for tag, key in (("ops_by_part", part_of),
                     ("ops_by_layer", lambda r: r["layer"])):
        groups = _group(ctx, key)
        line = {k: {"ms": round(g["ms"], 3),
                    "dot_tflop": round(g["dot_flops"] / 1e12, 4),
                    "mxu_pct": round(100.0 * g["dot_flops"]
                                     / (peak * g["dot_ms"] * 1e-3), 2)
                    if g["dot_ms"] else None,
                    "remat_ms": round(g["remat_ms"], 3),
                    "remat_tflop": round(g["remat_flops"] / 1e12, 4)}
                for k, g in sorted(groups.items(),
                                   key=lambda kv: -kv[1]["ms"])}
        print(f"[{tag}] device ms a step over "
              f"{steps // ctx['steps_per_call']} whole runs, every leaf "
              f"event once; '-' = none; sum "
              f"{sum(g['ms'] for g in groups.values()):.3f} ms + "
              f"{between:.3f} between ops = "
              f"{1e3 * run_seconds / steps:.3f} ms a step: {line}",
              flush=True)
    by = {}
    for row, took in rows:
        acc = by.setdefault(row["name"], [row, 0.0, 0])
        acc[1] += took
        acc[2] += 1
    lost = []
    for row, took, n in by.values():
        flops_s = row["dot_flops"] / peak
        bytes_s = (row["bytes_in"] + row["bytes_out"]) / bw
        lost.append((took - n * max(flops_s, bytes_s), row, took, n,
                     "flops" if flops_s >= bytes_s else "bytes<="))
    lost.sort(key=lambda t: -t[0])
    for over, row, took, n, bound in lost[:top]:
        print(f"[ops] {row['name']} {row['opcode']} layer={row['layer']} "
              f"part={part_of(row)} {1e3 * took / steps:.3f} ms a step in "
              f"{n / steps:g} events, {1e3 * over / steps:.3f} over its "
              f"bound ({bound}: bytes as the shapes say, an upper bound "
              f"where the op reads a slice)", flush=True)
    flops_of = getattr(ctx["reference"], "train_flops_per_example", None)
    compiled = sum(row["dot_flops"] for row, _ in rows) / steps
    again = sum(row["dot_flops"] for row, _ in rows
                if row["recomputed"]) / steps
    said = flops_of(ctx["cell"].config) * ctx["batch"] if flops_of else None
    print(f"[ops] dot_flops a step as compiled (XLA's dots and convolutions "
          f"as they ran, recomputed ones twice; custom calls 0): "
          f"{compiled:.4e}, of which recomputed {again:.4e}; the "
          f"reference's train_flops_per_example x "
          f"batch: {said if said is None else format(said, '.4e')}",
          flush=True)
