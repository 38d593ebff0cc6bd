"""Seconds JAX spent tracing, lowering and compiling before the window's
``fit()``: the union of the ``xla/trace``, ``xla/lower`` and
``xla/backend_compile`` spans (traces nest, so a plain sum would count an
inner function twice; a compile-cache read is a short
``xla/backend_compile``). A ``[compile]`` line gives the three parts, the
three longest events by name, and the count of such events inside the
window, 0 in a sound run."""
from benchmark.lib import spans, xplane


def read(ctx):
    every = spans.program_spans()
    compiles = spans.named(every, *spans.COMPILE)
    epochs = spans.named(every, "train/epoch")
    if not compiles or not epochs:
        return None
    opened, closed = epochs[-1].t0, epochs[-1].t1
    before = [s for s in compiles if s.t1 <= opened]
    inside = [s for s in compiles if opened < s.t1 <= closed]
    union = lambda ss: xplane.measure(xplane.union(
        (s.t0, s.t1) for s in ss))
    parts = {n: union(spans.named(before, n)) for n in spans.COMPILE}
    longest = sorted(before, key=lambda s: s.t0 - s.t1)[:3]
    print(f"[compile] before the window's fit(): "
          + ", ".join(f"{n} {v:.3f} s in {len(spans.named(before, n))}"
                      for n, v in parts.items())
          + "; longest: " + ", ".join(
              f"{s.name} {s.args.get('fun_name')} {s.t1 - s.t0:.3f} s"
              for s in longest)
          + f"; inside the window's fit(): {len(inside)} events "
          f"{sorted({str(s.args.get('fun_name')) for s in inside})}",
          flush=True)
    return union(before)
