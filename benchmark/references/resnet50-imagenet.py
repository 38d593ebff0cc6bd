"""Plain reference for ``resnet50-imagenet``: ResNet-50 (He et al. 2015,
the 50-layer column of Table 1; stride on a stage's first 1x1 as in the
original model and the DL4J zoo), its loss, gradients and Nesterov step in
straightforward float32 ``jax.numpy``/``lax`` at ``highest`` matmul
precision. It imports nothing of the program and takes nothing the program
made: weights and batches come from the benchmark's seed.

Training semantics followed (DL4J's, which the program states): the loss is
the mean multi-class cross-entropy plus 0.5*l2*sum(w^2) over every
parameter whose name does not start with ``b`` (convolution and dense
kernels and batch-norm gammas); batch norm uses the batch's statistics
(biased variance); the updater is SGD with Nesterov momentum,
``t = g + mu*t; w -= lr*(g + mu*t)``.

``precision="fp8"`` is the control, not a reference: the same mathematics
with the operands of every convolution and matmul rounded to float8
(e4m3, one scale per tensor), the step below the bf16 the configuration
states.

Stages and, inside them, bottlenecks are rematerialised in the backward
pass so that a batch of 512 in float32 fits on one chip.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST


# ------------------------------------------------------------------ shapes
def layers(cfg):
    """Every parameterised layer in forward order:
    ``(name, kind, kernel, c_in, c_out, stride, in_hw)``; ``kind`` is
    ``conv`` or ``dense``. Names are the DL4J zoo's."""
    hw = cfg["image_size"]
    out = []
    k = cfg["stem_kernel"]
    out.append(("stem", "conv", k, cfg["channels"], cfg["stem_filters"], 2,
                hw + 2 * (k // 2)))
    hw = hw // 2          # stem conv (pad k//2, stride 2)
    hw = -(-hw // 2)      # 3x3/2 max pool, SAME
    c = cfg["stem_filters"]
    for si, (f1, f2, f3, blocks, stride) in enumerate(cfg["stages"]):
        for b in range(blocks):
            name = f"res{si + 2}{chr(97 + b)}"
            s = stride if b == 0 else 1
            out.append((f"{name}_a", "conv", 1, c, f1, s, hw))
            mid = -(-hw // s)
            out.append((f"{name}_b", "conv", 3, f1, f2, 1, mid))
            out.append((f"{name}_c", "conv", 1, f2, f3, 1, mid))
            if b == 0:
                out.append((f"{name}_sc", "conv", 1, c, f3, s, hw))
            c, hw = f3, mid
    out.append(("output", "dense", 1, c, cfg["num_classes"], 1, 1))
    return out


def train_flops_per_example(cfg) -> float:
    """FLOPs one example needs in a training step: 2 per multiply-add in
    every convolution and the dense layer, forward, and twice that again
    for the two backward products (input and weight gradients). The first
    convolution needs no input gradient. Element-wise work, batch norm and
    the optimizer are left out, as MFU's convention has it."""
    total = 0.0
    for i, (_, kind, k, cin, cout, s, hw) in enumerate(layers(cfg)):
        o = 1 if kind == "dense" else \
            ((hw - k) // s + 1 if i == 0 else -(-hw // s))
        macs = (o * o) * k * k * cin * cout
        total += 2.0 * macs * (2 if i == 0 else 3)
    return total


def conv_min_seconds_per_example(cfg, peaks, batch) -> dict:
    """The least time the convolutions of one training step can take per
    example: for each convolution and each of its three products the
    larger of FLOPs/peak and bytes/peak, with bf16 operands read once and
    the result written once (weights amortised over the batch)."""
    by_flops = by_bytes = least = 0.0
    for i, (_, kind, k, cin, cout, s, hw) in enumerate(layers(cfg)):
        if kind != "conv":
            continue
        o = (hw - k) // s + 1 if i == 0 else -(-hw // s)
        flops = 2.0 * o * o * k * k * cin * cout
        x, y, w = hw * hw * cin * 2, o * o * cout * 2, \
            k * k * cin * cout * 2 / batch
        for n in range(2 if i == 0 else 3):
            tf = flops / peaks["flops_bf16"]
            tb = (x + y + w) / peaks["hbm_bytes_per_s"]
            by_flops += tf
            by_bytes += tb
            least += max(tf, tb)
    return {"least_s": least, "flops_s": by_flops, "bytes_s": by_bytes}


def stage_of(cfg, leaf: str) -> str:
    """The stage a parameter leaf (by its path, ``['res3b_a_bn']['beta']``)
    belongs to: ``stem``, ``res2`` .. ``res5``, or ``head`` for the output
    layer. Batch norm at a random init makes gradients grow layer by layer
    on the way back, and in bf16 the single leaves of the early stages
    (a batch norm's 64 gammas) read 10-37 % off the float32 reference,
    for the program, for a bf16 copy of the reference and for the float8
    control alike (PERF.md section 2). So only the head is judged leaf by
    leaf; a stage's leaves together are steady (0.2-1.7 %) and are judged
    as one norm."""
    name = leaf.split("'")[1]
    if name.startswith("res"):
        return name[:4]
    return "head" if name == "output" else "stem"


# ----------------------------------------------------------------- weights
@functools.partial(jax.jit, static_argnums=(0,))
def _make_params(layer_spec, key):
    params = {}
    for i, (name, kind, k, cin, cout, _, _) in enumerate(layer_spec):
        sub = jax.random.fold_in(key, i)
        fan_in = k * k * cin
        a = (6.0 / fan_in) ** 0.5
        if kind == "conv":
            params[f"{name}_conv"] = {"W": jax.random.uniform(
                sub, (k, k, cin, cout), jnp.float32, -a, a)}
            params[f"{name}_bn"] = {"gamma": jnp.ones((cout,), jnp.float32),
                                    "beta": jnp.zeros((cout,), jnp.float32)}
        else:
            params[name] = {"W": jax.random.uniform(
                sub, (cin, cout), jnp.float32, -a, a),
                "b": jnp.zeros((cout,), jnp.float32)}
    return params


def make_params(cfg, seed: int):
    """Seeded float32 weights, made on the device in one jitted call."""
    return _make_params(tuple(layers(cfg)), jax.random.PRNGKey(seed))


# ----------------------------------------------------------------- forward
def _fp8(x):
    """Round to float8 e4m3 with one scale per tensor; gradients pass
    straight through."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    q = (x / s).astype(jnp.float8_e4m3fn).astype(x.dtype) * s
    return x + lax.stop_gradient(q - x)


def _conv(x, w, stride, padding, precision):
    if precision == "fp8":
        x, w = _fp8(x), _fp8(w)
    return lax.conv_general_dilated(
        x, w, (stride, stride), padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HIGHEST)


def _bn(x, p, eps):
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
    return (x - mean) * lax.rsqrt(var + eps) * p["gamma"] + p["beta"]


def _conv_bn(params, name, x, stride, eps, precision, relu=True):
    y = _bn(_conv(x, params[f"{name}_conv"]["W"], stride, "SAME", precision),
            params[f"{name}_bn"], eps)
    return jnp.maximum(y, 0.0) if relu else y


def _bottleneck(params, name, x, stride, project, eps, precision):
    y = _conv_bn(params, f"{name}_a", x, stride, eps, precision)
    y = _conv_bn(params, f"{name}_b", y, 1, eps, precision)
    y = _conv_bn(params, f"{name}_c", y, 1, eps, precision, relu=False)
    sc = _conv_bn(params, f"{name}_sc", x, stride, eps, precision,
                  relu=False) if project else x
    return jnp.maximum(y + sc, 0.0)


def logits(cfg, params, pixels, precision="highest"):
    """uint8 pixels (B, H, W, C) -> pre-softmax logits (B, classes)."""
    eps = cfg["bn_epsilon"]
    x = pixels.astype(jnp.float32) * jnp.float32(1.0 / 255.0)
    p = cfg["stem_kernel"] // 2
    x = jnp.pad(x, ((0, 0), (p, p), (p, p), (0, 0)))
    x = _conv(x, params["stem_conv"]["W"], 2, "VALID", precision)
    x = jnp.maximum(_bn(x, params["stem_bn"], eps), 0.0)
    x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
                          "SAME")
    for si, (_, _, _, blocks, stride) in enumerate(cfg["stages"]):
        def stage(params, x, si=si, blocks=blocks, stride=stride):
            for b in range(blocks):
                block = jax.checkpoint(functools.partial(
                    _bottleneck, name=f"res{si + 2}{chr(97 + b)}",
                    stride=stride if b == 0 else 1, project=b == 0, eps=eps,
                    precision=precision))
                x = block(params, x=x)
            return x
        # a stage is rematerialised as a whole and its blocks again inside
        # it: the backward pass keeps four stage inputs and one stage's
        # block inputs, not all sixteen
        x = jax.checkpoint(stage)(params, x)
    x = jnp.mean(x, axis=(1, 2))
    w = params["output"]["W"]
    if precision == "fp8":
        x, w = _fp8(x), _fp8(w)
    return jnp.dot(x, w, precision=HIGHEST) + params["output"]["b"]


def loss_fn(cfg, params, pixels, onehot, precision="highest"):
    z = logits(cfg, params, pixels, precision)
    ce = -jnp.mean(jnp.sum(onehot * jax.nn.log_softmax(z, axis=-1), axis=-1))
    reg = 0.0
    for sub in params.values():
        for k, v in sub.items():
            if not k.startswith("b"):
                reg = reg + jnp.sum(jnp.square(v))
    return ce + 0.5 * cfg["l2"] * reg


def train_steps(cfg, params, batches, precision="highest", devices=None):
    """Follow the optimizer through ``batches`` (a list of (uint8 pixels,
    one-hot labels)). Returns (losses, momentum trace, final params), all
    float32. With several ``devices`` each batch is split over them and the
    parameters copied to each; the mathematics stays one global batch."""
    lr, mu = cfg["learning_rate"], cfg["momentum"]

    @jax.jit
    def step(params, trace, pixels, onehot):
        loss, g = jax.value_and_grad(
            lambda p: loss_fn(cfg, p, pixels, onehot, precision))(params)
        trace = jax.tree_util.tree_map(lambda t, gi: gi + mu * t, trace, g)
        params = jax.tree_util.tree_map(
            lambda w, gi, t: w - lr * (gi + mu * t), params, g, trace)
        return params, trace, loss

    put = jnp.asarray
    if devices is not None and len(devices) > 1:
        from jax.sharding import Mesh, NamedSharding, PartitionSpec
        mesh = Mesh(devices, ("d",))
        rows = NamedSharding(mesh, PartitionSpec("d"))
        put = lambda a: jax.device_put(a, rows)
        params = jax.device_put(params, NamedSharding(mesh, PartitionSpec()))
    trace = jax.tree_util.tree_map(jnp.zeros_like, params)
    losses = []
    for pixels, onehot in batches:
        params, trace, loss = step(params, trace, put(pixels), put(onehot))
        losses.append(float(loss))
    return losses, trace, params
