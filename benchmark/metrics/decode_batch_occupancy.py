"""Live slots over slots, mean over the window's decode steps: the
``active`` count the program writes on each ``serving/decode_step`` span."""


def read(ctx):
    w0, w1 = ctx["window"]
    active = [e["args"]["active"] for e in ctx["spans"]
              if e["name"] == "serving/decode_step"
              and "active" in e.get("args", {})
              and w0 <= e["ts"] * 1e-6 < w1]
    if not active:
        return None
    return 100.0 * sum(active) / len(active) / ctx["slots"]
