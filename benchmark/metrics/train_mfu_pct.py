"""Model FLOP/s utilization: the FLOPs the forward and backward passes
need per example (counted from shapes by the configuration's reference
file) times the whole-window rate (in a traced run: of the part before the
profiler is switched on), over chips times the published peak."""


def read(ctx):
    if ctx["peaks"] is None:
        return None
    flops = ctx["reference"].train_flops_per_example(ctx["cell"].config)
    rate = ctx["window_rate"]
    return 100.0 * flops * rate / (ctx["cell"].chips
                                   * ctx["peaks"]["flops_bf16"])
