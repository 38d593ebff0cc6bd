"""Share of the step program's device time under the gated short
convolution's scopes: ``sconv/proj`` (the projection to [B; C; u] and the
output projection) and ``sconv/mix`` (gates and taps), forward,
rematerialised forward and backward, in whole runs of the step program;
also printed as milliseconds a step beside the grouped-query attention's
four scopes, since the ``[scopes]`` table files all of them under
``other``."""
from benchmark.lib import scopes

_PRINTED = ("sconv/proj", "sconv/mix", "mha/proj", "mha/norm", "mha/rope",
            "mha/attn")


def read(ctx):
    parts = {m: scopes.seconds(ctx, lambda n, scope, m=m: m in scope)
             for m in _PRINTED}
    if None in parts.values() or not parts["sconv/proj"][1]:
        return None
    ms = {m: round(1e3 * took / steps, 3)
          for m, (took, _, steps) in parts.items()}
    print(f"[shortconv_share] device ms a step {ms}", flush=True)
    # no op is under both scopes: the share of either is their sum's
    took = parts["sconv/proj"][0] + parts["sconv/mix"][0]
    return 100.0 * took / parts["sconv/proj"][1] if took else None
