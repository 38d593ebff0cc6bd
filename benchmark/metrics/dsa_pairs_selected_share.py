"""Share of the causal (query, key) pairs that the sparse attentions'
selections kept, all layers together since the process began: the
program's counters ``dsa_pairs_selected_total{layer}`` over
``dsa_pairs_causal_total{layer}``, counted where the attention applies the
selection. An exact top-2,048 over sequences of 32,768 keeps 65,012,736 of
536,887,296 pairs, 12.1092 %; a selection that lets a tie in or drops a
key shows in the digits."""


def read(ctx):
    pairs = getattr(ctx["system"], "sparse_pairs", lambda: None)()
    if not pairs:
        return None
    kept, causal = pairs
    print(f"[dsa_pairs_selected_share] {kept:.0f} of {causal:.0f} pairs",
          flush=True)
    return 100.0 * kept / causal
