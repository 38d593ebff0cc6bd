"""Roofline share of the convolutions: the least time the step's
convolutions can take on this chip (per convolution and product the larger
of FLOPs/peak and bytes/peak, from shapes) over the device time of the ops
that hold one: on the TPU a convolution (or a dot) is the root of a
``kind=kOutput`` fusion, whatever the fusion is called; a bare
``convolution`` op counts too. The dense output layer's dot is among them
(0.02 % of the step's FLOPs)."""


def read(ctx):
    tr = ctx["trace"]
    if tr is None or ctx["peaks"] is None:
        return None
    # only whole runs of the step program: a run the traced stretch cuts
    # would add convolution time and no steps
    runs = tr.program_spans(ctx["system"].STEP_PROGRAM)
    conv_s = sum(tr.op_seconds(
        lambda n, kind: kind == "kOutput" or "convolution" in n,
        inside=runs).values())
    if not conv_s or not runs:
        return None
    chips = ctx["cell"].chips
    steps = len(runs) / chips * ctx["steps_per_call"]
    per_chip = ctx["batch"] // chips
    least = ctx["reference"].conv_min_seconds_per_example(
        ctx["cell"].config, ctx["peaks"], per_chip)
    print(f"[conv_roofline] per example least {least['least_s']:.3e} s "
          f"(by FLOPs {least['flops_s']:.3e}, by bytes "
          f"{least['bytes_s']:.3e}); convolution ops took "
          f"{conv_s / steps / per_chip:.3e} s", flush=True)
    return 100.0 * least["least_s"] * per_chip * steps / conv_s
