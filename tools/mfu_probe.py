"""MFU calibration probe: what fraction of the chip's peak is reachable,
and where the ResNet-50 step time actually goes.

Two question the bench sweep can't answer:

1. Is the ~197 TFLOP/s bf16 "peak" even reachable through this stack on
   this chip?  A plain large bf16 matmul is the upper bound any real
   model can hit; measuring it separates "the framework is slow" from
   "the ceiling is lower than the spec sheet".
2. Which segment of the training step eats the time?  Times forward-only,
   forward+loss+backward, and the full step (backward + optimizer) at the
   headline config, so the gap localizes to fwd / bwd / update.

Prints one JSON line per measurement with a platform stamp (`on_tpu`), so
a CPU run can never be mistaken for hardware numbers. Needs a TPU;
`JAX_PLATFORMS=cpu` asks for a tiny CPU smoke of the script itself.
"""
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import optax

from deeplearning4j_tpu.monitor.xla import device_peak_flops
from deeplearning4j_tpu.util.env import env_int
from deeplearning4j_tpu.util.platform import enable_compile_cache

enable_compile_cache()

DEV = jax.devices()[0]
ON_TPU = DEV.platform == "tpu"
#: the ONE peak table (monitor/xla.py); None on the CPU smoke
PEAK_FLOPS = device_peak_flops()
BEST_OF = env_int("DL4J_TPU_PROBE_BEST_OF", 3)


def pct_of_peak(tflops):
    return None if not PEAK_FLOPS \
        else round(100 * tflops * 1e12 / PEAK_FLOPS, 1)


def emit(row):
    row.update({"device_kind": DEV.device_kind, "on_tpu": ON_TPU})
    # mirror every numeric measurement into the telemetry registry so the
    # final metrics-summary line (and any /metrics scrape of a harness
    # embedding this probe) carries the same numbers as the log
    from deeplearning4j_tpu import monitor
    probe = str(row.get("segment") or row.get("kind") or "probe")
    for k, v in row.items():
        if isinstance(v, (int, float)) and not isinstance(v, bool):
            monitor.gauge(f"mfu_probe_{k}", "mfu_probe measurement",
                          labels=("probe",)).set(v, probe=probe)
    print(json.dumps(row), flush=True)


def timed_best(run):
    best = None
    for _ in range(BEST_OF):
        t = run()
        best = t if best is None else min(best, t)
    return best


def matmul_peak(n=8192):
    """Large square bf16 matmul chain — the practical compute ceiling.
    8 chained matmuls per call amortize dispatch."""
    rs = np.random.RandomState(0)
    a = jnp.asarray(rs.rand(n, n), jnp.bfloat16)
    b = jnp.asarray(rs.rand(n, n), jnp.bfloat16)
    chain = 8

    @jax.jit
    def mm(a, b):
        x = a
        for _ in range(chain):
            x = jnp.dot(x, b, preferred_element_type=jnp.bfloat16)
        return x

    x = mm(a, b)
    float(x[0, 0].astype(jnp.float32))  # host fetch = reliable barrier

    def run():
        t0 = time.perf_counter()
        y = mm(a, b)
        float(y[0, 0].astype(jnp.float32))
        return time.perf_counter() - t0

    t = timed_best(run)
    tflops = chain * 2 * n ** 3 / t / 1e12
    emit({"kind": "matmul-peak", "n": n, "chain": chain,
          "tflops": round(tflops, 1),
          "pct_of_peak": pct_of_peak(tflops),
          "wall_s": round(t, 3)})
    return tflops


def conv_micro(batch=128):
    """A single mid-network ResNet conv (3x3, 256->256 at 14x14... use the
    28x28x128 block: representative MXU-bound conv) chained 16x — conv MFU
    in isolation. If this is high while the full net is low, the gap is
    inter-op (BN/elementwise/memory), not the convs."""
    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.rand(batch, 28, 28, 128), jnp.bfloat16)
    w = jnp.asarray(rs.rand(3, 3, 128, 128) * 0.1, jnp.bfloat16)
    chain = 16

    @jax.jit
    def convs(x, w):
        for _ in range(chain):
            x = jax.lax.conv_general_dilated(
                x, w, (1, 1), "SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
                preferred_element_type=jnp.bfloat16)
        return x

    y = convs(x, w)
    float(y[0, 0, 0, 0].astype(jnp.float32))

    def run():
        t0 = time.perf_counter()
        y = convs(x, w)
        float(y[0, 0, 0, 0].astype(jnp.float32))
        return time.perf_counter() - t0

    t = timed_best(run)
    flops = chain * 2 * batch * 28 * 28 * 128 * 128 * 9
    tflops = flops / t / 1e12
    emit({"kind": "conv-micro", "batch": batch, "chain": chain,
          "tflops": round(tflops, 1),
          "pct_of_peak": pct_of_peak(tflops),
          "wall_s": round(t, 3)})


def resnet_segments(batch=128, hw=224):
    """Forward / forward+backward / full-step wall times at the headline
    bench config — same net construction as bench.py's resnet runner."""
    import dataclasses

    from deeplearning4j_tpu.models import ResNet50
    from deeplearning4j_tpu.nn.graph import ComputationGraph

    model = ResNet50(num_classes=1000, input_shape=(hw, hw, 3))
    conf = dataclasses.replace(model.conf(), compute_dtype="bfloat16")
    net = ComputationGraph(conf).init()
    tx = net._tx

    rs = np.random.RandomState(0)
    X = jnp.asarray(rs.rand(batch, hw, hw, 3).astype("float32"))
    Y = jnp.asarray(np.eye(1000, dtype="float32")[
        rs.randint(0, 1000, batch)])
    rng = jax.random.PRNGKey(0)

    def loss_fn(p, state):
        loss, (new_state, _) = net._score_fn(
            p, state, (X,), (Y,), None, None, True, rng)
        return loss, new_state

    fwd = jax.jit(lambda p, s: loss_fn(p, s)[0])

    @jax.jit
    def fwd_bwd(p, s):
        (loss, _), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(p, s)
        # fold grads so the backward can't be DCE'd, fetch one scalar
        return loss + sum(jnp.sum(g) for g in jax.tree_util.tree_leaves(
            grads)) * 0.0

    def full(p, o, s):
        (loss, new_state), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(p, s)
        updates, new_o = tx.update(grads, o, p)
        return optax.apply_updates(p, updates), new_o, new_state, loss

    # graftlint: disable=donated-aliasing -- p/o/s come from net.init() on-device; probes measure the raw step and an own_tree copy would distort the matmul-ceiling comparison
    jfull = jax.jit(full, donate_argnums=(0, 1, 2))

    p, o, s = net.params, net.opt_state, net.state
    reps = 5
    segs = {}
    for name, runner in (
        ("fwd", lambda: fwd(p, s)),
        ("fwd+bwd", lambda: fwd_bwd(p, s)),
    ):
        float(runner())   # compile + warm

        def run(runner=runner):
            t0 = time.perf_counter()
            for _ in range(reps):
                x = runner()
            float(x)
            return (time.perf_counter() - t0) / reps

        segs[name] = timed_best(run)

    p, o, s, loss = jfull(p, o, s)   # compile + warm
    float(loss)

    def run_full():
        nonlocal p, o, s
        t0 = time.perf_counter()
        for _ in range(reps):
            p, o, s, loss = jfull(p, o, s)
        float(loss)
        return (time.perf_counter() - t0) / reps

    segs["full-step"] = timed_best(run_full)

    gflops_img = 22.49   # XLA cost model, bench.py headline
    for name, t in segs.items():
        row = {"kind": "resnet-segment", "segment": name, "batch": batch,
               "ms": round(t * 1e3, 2)}
        if name == "full-step":
            row["imgs_sec"] = round(batch / t, 1)
            row["mfu_pct"] = pct_of_peak(batch * gflops_img / 1e3 / t)
        emit(row)
    return segs


if __name__ == "__main__":
    if not ON_TPU and os.environ.get("JAX_PLATFORMS") != "cpu":
        print("need TPU (set JAX_PLATFORMS=cpu for a tiny CPU smoke)",
              file=sys.stderr)
        sys.exit(2)
    if ON_TPU:
        matmul_peak()
        conv_micro()
        resnet_segments()
    else:
        matmul_peak(n=512)
        conv_micro(batch=2)
        resnet_segments(batch=2, hw=64)
    from deeplearning4j_tpu import monitor
    print(json.dumps({"kind": "metrics-summary",
                      "metrics": monitor.summary()}), flush=True)
