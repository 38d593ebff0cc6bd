"""Share of the token positions that the program's denoising pre-processor
replaced by the mask id, since the process began: its counters
``denoise_masked_total`` over ``denoise_positions_total`` (the adapter's
``denoise_counts()``). Every block draws its level t ~ U(1e-3, 1) and masks
each of its tokens with probability t: 50.05 % expected; the traffic is
what the cell says when this reads about 50. ``None`` without the
counters."""


def read(ctx):
    counts = getattr(ctx["system"], "denoise_counts", lambda: None)()
    if not counts:
        return None
    masked, seen = counts
    print(f"[denoise_masked_share] {masked:.0f} of {seen:.0f} positions",
          flush=True)
    return 100.0 * masked / seen
