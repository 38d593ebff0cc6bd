"""BatchNormalization's training statistics: one read of the activation.

The layer takes `sum(x - k)` and `sum((x - k)**2)` from one traversal and
leaves the backward to autodiff. Held here against a plain two-pass
reference (mean, then the mean of squared deviations): the values, the
data with |mean| >> std that the shift `k` exists for, and the structure
that is the point of the formulation: no reduction over the whole
activation waits on another of its direction.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend import core as jex_core

from deeplearning4j_tpu.nn.conf.base import InputType
from deeplearning4j_tpu.nn.layers import BatchNormalization

SHAPES = {"4d": (4, 6, 5, 8), "3d": (5, 7, 8), "2d": (16, 8)}


def _two_pass(layer, params, state, x):
    """The textbook formulation, kept as the reference."""
    axes = tuple(range(x.ndim - 1))
    xs = x.astype(jnp.promote_types(jnp.float32, x.dtype))
    mean = jnp.mean(xs, axis=axes)
    var = jnp.mean(jnp.square(xs - mean), axis=axes)
    new_state = {
        "mean": layer.decay * state["mean"] + (1.0 - layer.decay) * mean,
        "var": layer.decay * state["var"] + (1.0 - layer.decay) * var}
    inv = jax.lax.rsqrt(var + layer.epsilon)
    y = (x - mean.astype(x.dtype)) * inv.astype(x.dtype)
    if layer.lock_gamma_beta:
        return y * layer.gamma_init + layer.beta_init, new_state
    return y * params["gamma"] + params["beta"], new_state


def _setup(shape, dtype, lock, seed=0):
    layer = BatchNormalization(lock_gamma_beta=lock, gamma_init=1.5,
                               beta_init=0.25)
    c = shape[-1]
    params, state = layer.init(None, InputType.feed_forward(c), dtype)
    rs = np.random.RandomState(seed)
    if not lock:
        params = {"gamma": jnp.asarray(rs.uniform(0.5, 2.0, c), dtype),
                  "beta": jnp.asarray(rs.randn(c), dtype)}
    x = jnp.asarray(rs.randn(*shape) * rs.uniform(0.5, 3.0, c)
                    + rs.randn(c), dtype)
    w = jnp.asarray(rs.randn(*shape), dtype)      # the cotangent of y
    return layer, params, state, x, w


@pytest.mark.parametrize("lock", [False, True], ids=["gamma_beta", "locked"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("rank", sorted(SHAPES))
def test_matches_two_pass_reference(rank, dtype, lock):
    layer, params, state, x, w = _setup(SHAPES[rank], dtype, lock)

    def run(fn):
        def f(params, x):
            y, new_state = fn(params, state, x)
            return jnp.sum(y.astype(jnp.float32) * w.astype(jnp.float32)), (
                y, new_state)
        (_, (y, st)), (gp, gx) = jax.value_and_grad(
            f, argnums=(0, 1), has_aux=True)(params, x)
        return y, st, gp, gx

    y, st, gp, gx = run(lambda p, s, x: layer.apply(p, s, x, train=True))
    y0, st0, gp0, gx0 = run(lambda p, s, x: _two_pass(layer, p, s, x))
    # bfloat16: the statistics are float32 either way, but a last-bit
    # difference in them moves a bf16 product by one step of 2**-8
    tol = dict(rtol=2e-5, atol=2e-5) if dtype == jnp.float32 else dict(
        rtol=2e-2, atol=2e-2)
    f32 = lambda a: np.asarray(a, np.float32)
    assert y.dtype == x.dtype and gx.dtype == x.dtype
    np.testing.assert_allclose(f32(y), f32(y0), **tol)
    np.testing.assert_allclose(f32(gx), f32(gx0), **tol)
    for name in ("mean", "var"):
        assert st[name].dtype == jnp.float32
        np.testing.assert_allclose(f32(st[name]), f32(st0[name]),
                                   rtol=1e-5, atol=1e-6)
    assert sorted(gp) == sorted(gp0) == ([] if lock else ["beta", "gamma"])
    n = math.prod(SHAPES[rank][:-1])
    for name in gp:
        # a sum of n products, each off by the chain's rounding
        np.testing.assert_allclose(
            f32(gp[name]), f32(gp0[name]), rtol=tol["rtol"],
            atol=tol["atol"] * math.sqrt(n))


@pytest.mark.parametrize("offset", [1e3, 1e4], ids=["1e3_std", "1e4_std"])
@pytest.mark.parametrize("rank", ["4d", "2d"])
def test_offset_data_keeps_the_two_pass_variance(rank, offset):
    # un-centred features (a dense batch norm over raw inputs): E[x*x] -
    # E[x]**2 in float32 loses every digit of the variance here
    shape = SHAPES[rank][:-1] + (16,)
    rs = np.random.RandomState(3)
    std = rs.uniform(0.1, 10.0, shape[-1])
    x64 = rs.randn(*shape) * std + offset * std * rs.choice([-1, 1],
                                                            shape[-1])
    x = jnp.asarray(x64, jnp.float32)
    layer = BatchNormalization(decay=0.0)      # the new state IS the batch's
    params, state = layer.init(None, InputType.feed_forward(shape[-1]))
    y, st = layer.apply(params, state, x, train=True)
    axes = tuple(range(x.ndim - 1))
    var0 = np.var(np.asarray(x, np.float64), axis=axes)   # of the f32 data
    var = np.asarray(st["var"], np.float64)
    assert (var >= 0).all()
    np.testing.assert_allclose(var, var0, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(st["mean"], np.float64),
                               np.asarray(x, np.float64).mean(axes),
                               rtol=1e-6)
    assert np.isfinite(np.asarray(y)).all()


@pytest.mark.parametrize("value", [0.0, 3.25, -1e4])
def test_constant_input_is_finite(value):
    layer = BatchNormalization()
    params, state = layer.init(None, InputType.feed_forward(8))
    x = jnp.full((4, 3, 3, 8), value, jnp.float32)

    def f(params, x):
        y, st = layer.apply(params, state, x, train=True)
        return jnp.sum(y * y) + jnp.sum(y), st

    (_, st), (gp, gx) = jax.value_and_grad(f, argnums=(0, 1),
                                           has_aux=True)(params, x)
    np.testing.assert_array_equal(np.asarray(st["var"]),
                                  0.9 * np.ones(8, np.float32))
    for leaf in jax.tree_util.tree_leaves((gp, gx)):
        assert np.isfinite(np.asarray(leaf)).all()


# ---------------------------------------------------------------- structure
def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        if isinstance(v, jex_core.ClosedJaxpr):
            yield v.jaxpr
        elif isinstance(v, jex_core.Jaxpr):
            yield v


def _reduction_levels(jaxpr, in_levels, full_size):
    """For each outvar of `jaxpr`, how many reductions over an operand of
    `full_size` elements lie one behind another on the way to it; and the
    deepest such chain anywhere in the jaxpr."""
    level = dict(zip(jaxpr.invars, in_levels))
    deepest = 0

    def of(v):
        return 0 if isinstance(v, jex_core.Literal) else level.get(v, 0)

    for eqn in jaxpr.eqns:
        ins = [of(v) for v in eqn.invars]
        subs = [j for j in _sub_jaxprs(eqn)
                if len(j.invars) == len(eqn.invars)]
        if subs:
            outs, d = _reduction_levels(subs[0], ins, full_size)
            deepest = max(deepest, d)
        else:
            lv = max(ins, default=0)
            if (eqn.primitive.name.startswith("reduce_")
                    and eqn.invars[0].aval.size == full_size):
                lv += 1
            outs = [lv] * len(eqn.outvars)
        for v, lv in zip(eqn.outvars, outs):
            level[v] = lv
            deepest = max(deepest, lv)
    return [of(v) for v in jaxpr.outvars], deepest


def _grad_levels(fn, params, state, x, w):
    def loss(params, x):
        y, _ = fn(params, state, x)
        return jnp.sum(y.astype(jnp.float32) * w.astype(jnp.float32))

    closed = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(params, x)
    _, deepest = _reduction_levels(
        closed.jaxpr, [0] * len(closed.jaxpr.invars), x.size)
    return deepest


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("rank", sorted(SHAPES))
def test_no_full_reduction_waits_on_another_of_its_direction(rank, dtype):
    # forward one level (the two sums are siblings), backward one level
    # (every sum over dy and x needs only the forward's vectors): 2 in
    # all. Two-pass is deeper in both directions: var waits on mean, and
    # the term that flows through var back into the mean waits on the
    # backward's own sum of dy*(x - mean). By data dependence alone that
    # is 3 (that sum needs the mean and not inv); a compiler that puts the
    # backward's sums into one fusion runs it as 4.
    layer, params, state, x, w = _setup(SHAPES[rank], dtype, lock=False)
    assert _grad_levels(
        lambda p, s, x: layer.apply(p, s, x, train=True),
        params, state, x, w) == 2
    # the counter sees a chain where there is one
    assert _grad_levels(
        lambda p, s, x: _two_pass(layer, p, s, x),
        params, state, x, w) == 3
