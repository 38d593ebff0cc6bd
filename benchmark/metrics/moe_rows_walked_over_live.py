"""Rows the expert layers' dispatches walked over the (token, expert)
pairs that were live in them (held here): the program's counters
``moe_rows_walked_total{layer}`` over ``moe_tokens_routed_total{layer,
held="yes"}``, all layers together, since the process began. 1 where no
row is wasted; about 2 where every dispatch takes the small tier (twice
the balanced load of the share held); 8 where a layer that holds an
eighth of its experts walks the whole."""


def read(ctx):
    return getattr(ctx["system"], "expert_rows_walked_over_live",
                   lambda: None)()
