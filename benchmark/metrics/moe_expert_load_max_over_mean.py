"""The program's gauge ``moe_expert_load_max_over_mean{layer}``: tokens of
the busiest of the experts routed over, over the mean of all of them, in
the last step the fit loop saw; the layer where it is largest."""


def read(ctx):
    return getattr(ctx["system"], "expert_load_max_over_mean",
                   lambda: None)()
