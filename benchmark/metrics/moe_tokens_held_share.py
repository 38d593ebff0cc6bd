"""Share of the tokens routed that have at least one of their experts held
on this chip, all expert layers together, since the process began: the
program's counter ``moe_tokens_with_held_pair_total{layer}`` over the
tokens behind ``moe_tokens_routed_total``. With no shared expert every
other token gets exactly zero from the layer (57.8 % of them where 8 of 64
experts are held and 4 chosen, balanced: 42.2 % here), which is what
compacting a dispatch by token would act on."""


def read(ctx):
    share = getattr(ctx["system"], "tokens_with_held_pair_share",
                    lambda: None)()
    return None if share is None else 100.0 * share
