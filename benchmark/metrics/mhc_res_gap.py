"""The program's gauge ``mhc_res_gap{layer}``: the largest ``|row sum -
1|`` or ``|column sum - 1|`` of H_res, the stream-to-stream mapping of a
multi-stream residual path, after its Sinkhorn steps, over the tokens and
the two sub-layers of a layer in the last step the fit loop saw; the layer
where it is largest. How far the mapping is from the doubly stochastic
matrices it is meant to lie on. None where the program has no such
gauge."""


def read(ctx):
    return getattr(ctx["system"], "mhc_res_gap", lambda: None)()
