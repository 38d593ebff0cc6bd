"""Roofline share of the decode step: the least time a step can take on
this chip for the sequences and cached tokens that were live while the
trace ran (all weights once in bf16 plus the live keys and values over the
memory bandwidth, or the FLOPs over the peak) over the step's device
time."""


def read(ctx):
    tr = ctx["trace"]
    runs = tr.program_runs(ctx["system"].DECODE_PROGRAM) if tr else []
    if not runs or ctx["peaks"] is None:
        return None
    load = ctx["load"]
    least = ctx["reference"].decode_step_min_seconds(
        ctx["cell"].config, ctx["peaks"], load["live_slots"],
        load["live_tokens"])
    step = sum(runs) / len(runs)
    bound = "bytes" if least["bytes_s"] >= least["flops_s"] else "FLOPs"
    print(f"[decode_step_roofline] live {load['live_slots']:.2f} sequences, "
          f"{load['live_tokens']:.0f} cached tokens: least "
          f"{least['least_s'] * 1e3:.3f} ms (bound by {bound}), step "
          f"{step * 1e3:.3f} ms", flush=True)
    return 100.0 * least["least_s"] / step
