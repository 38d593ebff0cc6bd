"""Capture a jax.profiler trace of the ResNet-50 training step on TPU.

Runs a handful of warm per-call steps, then traces ~10 steps plus one
scan-of-10 invocation. The trace directory (/tmp/dl4jtpu_trace by
default) can be inspected with tensorboard or xprof; a one-line summary
of wall-per-step goes to stdout so PERF.md can quote it even if the
trace artifact is never pulled.
"""
import os
import sys
import time

import numpy as np

import jax
import jax.numpy as jnp
import optax
from jax import lax

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from deeplearning4j_tpu.util.env import env_int, env_str
from deeplearning4j_tpu.util.platform import enable_compile_cache

enable_compile_cache()
# a CPU run (JAX_PLATFORMS=cpu) only smoke-tests the script itself
if jax.devices()[0].platform != "tpu" \
        and os.environ.get("JAX_PLATFORMS") != "cpu":
    raise SystemExit("need TPU (set JAX_PLATFORMS=cpu for a tiny CPU smoke)")

import dataclasses

from deeplearning4j_tpu.models import ResNet50
from deeplearning4j_tpu.nn.graph import ComputationGraph

TRACE_DIR = env_str("DL4J_TPU_TRACE_DIR", "/tmp/dl4jtpu_trace")
BATCH = env_int("DL4J_TPU_TRACE_BATCH", 128)
# input size knob so the CPU smoke can shrink the model (a 224x224
# ResNet-50 compile on CPU runs minutes; 64x64 is seconds)
HW = env_int("DL4J_TPU_TRACE_HW", 224)

model = ResNet50(num_classes=1000, input_shape=(HW, HW, 3))
conf = dataclasses.replace(model.conf(), compute_dtype="bfloat16")
net = ComputationGraph(conf).init()
tx = net._tx

rs = np.random.RandomState(0)
X = jnp.asarray(rs.rand(BATCH, HW, HW, 3).astype("float32"))
Y = jnp.asarray(np.eye(1000, dtype="float32")[rs.randint(0, 1000, BATCH)])


def raw_step(params, opt_state, state, rng):
    def loss_fn(p):
        loss, (new_state, _) = net._score_fn(
            p, state, (X,), (Y,), None, None, True, rng)
        return loss, new_state
    (loss, new_state), grads = jax.value_and_grad(
        loss_fn, has_aux=True)(params)
    updates, new_opt = tx.update(grads, opt_state, params)
    return optax.apply_updates(params, updates), new_opt, new_state, loss


# graftlint: disable=donated-aliasing -- params come from ComputationGraph.init() on-device in this process; nothing deserialized/numpy-backed reaches the donated args
jstep = jax.jit(raw_step, donate_argnums=(0, 1, 2))


@jax.jit
def scan10(p, o, s, rng):
    def body(carry, _):
        cp, co, cs, cr = carry
        cr, sub = jax.random.split(cr)
        cp, co, cs, loss = raw_step(cp, co, cs, sub)
        return (cp, co, cs, cr), loss
    (p, o, s, rng), losses = lax.scan(body, (p, o, s, rng), jnp.arange(10))
    return p, o, s, losses[-1]


p, o, s = net.params, net.opt_state, net.state
rng = jax.random.PRNGKey(0)

# warm both programs (compile outside the trace window)
p, o, s, loss = jstep(p, o, s, rng)
float(loss)
p, o, s, loss = scan10(p, o, s, rng)
float(loss)
print("warm done", flush=True)

t0 = time.perf_counter()
with jax.profiler.trace(TRACE_DIR):
    for i in range(10):
        p, o, s, loss = jstep(p, o, s, jax.random.fold_in(rng, i))
    float(loss)
    t_per_call = time.perf_counter() - t0
    t1 = time.perf_counter()
    p, o, s, loss = scan10(p, o, s, rng)
    float(loss)
    t_scan = time.perf_counter() - t1

print(f"trace saved to {TRACE_DIR}", flush=True)
# platform stamp on the throughput line, and no "imgs/s" text at all on a
# CPU run: a smoke run must never look like a hardware measurement
# (mirrors bench.py's per-row on_tpu guard)
_plat = jax.devices()[0].device_kind
if jax.devices()[0].platform == "cpu":
    print(f"[{_plat}] CPU smoke only — throughput suppressed "
          f"(per-call {t_per_call * 100:.1f} ms/step, "
          f"scan10 {t_scan * 100:.1f} ms/step)", flush=True)
else:
    print(f"[{_plat}] per-call: {10 * BATCH / t_per_call:.1f} imgs/s "
          f"({t_per_call * 100:.1f} ms/step); "
          f"scan10: {10 * BATCH / t_scan:.1f} imgs/s "
          f"({t_scan * 100:.1f} ms/step)", flush=True)
