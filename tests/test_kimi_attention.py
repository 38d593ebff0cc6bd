"""The attention layers of the hybrid LM and their kernels (KDA, MLA, the
flash kernel at two head sizes); see `_kimi_common.py`."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nn.conf.base import InputType
from deeplearning4j_tpu.nn.layers import (
    KimiDeltaAttention, MultiHeadLatentAttention,
)
from deeplearning4j_tpu.nn.layers.attention import dot_product_attention
from deeplearning4j_tpu.nn.layers.linear_attention import kda_chunked

from _kimi_common import CFG, REF, T, _layer_params
from _lm_common import (  # noqa: F401 (the autouse fixture)
    _budgets_at_the_tests_sizes, _close, jit_unoptimised as _jit,
    with_gradients,
)


# ------------------------------------------------------------------------ KDA
def _kda_inputs(t, seed=0, b=2, h=3, dk=8, dv=8, decay=1.0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)
    return (unit(jax.random.normal(ks[0], (b, t, h, dk))),
            unit(jax.random.normal(ks[1], (b, t, h, dk))),
            jax.random.normal(ks[2], (b, t, h, dv)),
            -decay * jnp.exp(jax.random.normal(ks[3], (b, t, h, dk)) - 1),
            jax.nn.sigmoid(jax.random.normal(ks[4], (b, t, h))))


@pytest.mark.parametrize("t,chunk", [(64, 32), (50, 32), (130, 64),
                                     (16, 16), (96, 8)])
def test_kda_chunked_is_the_token_recurrence(t, chunk, monkeypatch):
    """Output, final state and the gradient of every input, at sequence
    lengths that are and are not a multiple of the chunk."""
    args = _kda_inputs(t)
    w = jax.random.normal(jax.random.PRNGKey(7), args[2].shape)

    def with_gradients(fn):
        """(o, s) and the gradient of a weighting of both in every input:
        one forward and one backward, one compiled program."""
        def loss(*a):
            o, s = fn(*a)
            return jnp.sum(o * w) + jnp.sum(s * s), (o, s)
        (_, out), grads = _jit(jax.value_and_grad(
            loss, (0, 1, 2, 3, 4), has_aux=True))(*args)
        return out, grads

    chunked = lambda *a: kda_chunked(*a, chunk=chunk)
    (o, s), got = with_gradients(chunked)
    (o_ref, s_ref), want = with_gradients(
        lambda *a: REF.kda_recurrence(*a, segment=16))
    _close(o, o_ref, 2e-5)
    _close(s, s_ref, 2e-5)
    for a, b in zip(got, want):
        _close(a, b, 5e-5)
    # the (sequence, head) pairs in 3 groups, one after another: the same
    # (a function of its own: jit keeps its traces by function, and the
    # budget is read when the layer is traced)
    from deeplearning4j_tpu.nn.layers import linear_attention
    monkeypatch.setattr(linear_attention, "_SCAN_LIVE_BYTES",
                        2 * 40 * t * 8 * 4)
    for got, want in zip(_jit(lambda *a: chunked(*a))(*args), (o, s)):
        _close(got, want, 1e-6)


def test_kda_chunked_takes_no_positive_exponent():
    """A decay of e^-300 a step: every exponent the chunked form takes is
    <= 0, so nothing overflows and the numbers are the recurrence's."""
    args = _kda_inputs(64, decay=300.0)
    assert float(args[3].min()) < -200
    (_, (o, s)), g = _jit(jax.value_and_grad(
        lambda *a: (lambda o, s: (jnp.sum(o), (o, s)))(
            *kda_chunked(*a, chunk=32)),
        (0, 1, 2, 3, 4), has_aux=True))(*args)
    o_ref, s_ref = jax.jit(functools.partial(REF.kda_recurrence,
                                             segment=16))(*args)
    assert np.isfinite(np.asarray(o)).all()
    _close(o, o_ref, 2e-5)
    _close(s, s_ref, 2e-5)
    assert all(np.isfinite(np.asarray(x)).all() for x in g)


def test_kda_chunked_hands_a_state_over():
    """Two calls, the second given the first's state, are one call."""
    args = _kda_inputs(96)
    chunked = _jit(functools.partial(kda_chunked, chunk=32))
    o, s = chunked(*args)
    head = [a[:, :40] for a in args]
    tail = [a[:, 40:] for a in args]
    o1, s1 = chunked(*head)
    o2, s2 = chunked(*tail, initial_state=s1)
    _close(jnp.concatenate([o1, o2], axis=1), o, 2e-5)
    _close(s2, s, 2e-5)


@pytest.mark.parametrize("t", [128, 77])
def test_kda_layer_and_every_parameters_gradient(t):
    """The layer (projections, convolutions, gates, chunked recurrence,
    gated norm, output) against the reference's, with the gradient of
    every parameter and of the input."""
    p = _layer_params(2)["attn"]
    kda = KimiDeltaAttention(n_out=32, n_heads=4, head_dim=16, low_rank=8,
                             chunk=32)
    p0, _ = kda.init(jax.random.PRNGKey(0), InputType.recurrent(32, t))
    assert {k: v.shape for k, v in p0.items()} == \
        {k: v.shape for k, v in p.items()}
    x = jax.random.normal(jax.random.PRNGKey(1), (2, t, 32))
    w = jax.random.normal(jax.random.PRNGKey(2), (2, t, 32))
    y, got = with_gradients(lambda p, x: kda.apply(p, {}, x)[0], w, (p, x),
                            _jit)
    y_ref, want = with_gradients(
        lambda p, x: REF._kda(CFG, p, x, "highest"), w, (p, x), _jit)
    _close(y, y_ref, 2e-5)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                            jax.tree_util.tree_leaves(want)):
        assert np.abs(np.asarray(a - b)).max() <= 1e-4 * max(
            np.abs(np.asarray(b)).max(), 1e-30), path


def test_kda_init_follows_the_familys_convention():
    kda = KimiDeltaAttention(n_out=32, n_heads=4, head_dim=16)
    p, _ = kda.init(jax.random.PRNGKey(0), InputType.recurrent(32, 8))
    assert p["Wa_up"].shape == (16, 64)          # rank = head_dim
    a = np.exp(np.asarray(p["A_log"]))
    assert (a >= 1).all() and (a <= 16).all()
    dt = np.asarray(jax.nn.softplus(p["dt_bias"]))
    assert (dt >= 0.99e-3).all() and (dt <= 0.101).all()
    assert np.abs(np.asarray(p["conv_q"])).max() <= 0.5
    with pytest.raises(NotImplementedError, match="whole sequences"):
        kda.apply(p, {}, jnp.zeros((1, 8, 32)), mask=jnp.ones((1, 8)))
    with pytest.raises(ValueError, match="power of two"):
        kda_chunked(*_kda_inputs(8), chunk=24)


# ------------------------------------------------------------------------ MLA
def test_mla_layer_and_every_parameters_gradient():
    p = _layer_params(4)["attn"]
    mla = MultiHeadLatentAttention(n_out=32, n_heads=4, nope_dim=16,
                                   rope_dim=8, v_dim=16, kv_rank=24)
    p0, _ = mla.init(jax.random.PRNGKey(0), InputType.recurrent(32, T))
    assert {k: v.shape for k, v in p0.items()} == \
        {k: v.shape for k, v in p.items()}
    x = jax.random.normal(jax.random.PRNGKey(1), (2, T, 32))
    w = jax.random.normal(jax.random.PRNGKey(2), (2, T, 32))
    y, got = with_gradients(lambda p, x: mla.apply(p, {}, x)[0], w, (p, x))
    y_ref, want = with_gradients(
        lambda p, x: REF._mla(CFG, p, x, "highest"), w, (p, x))
    _close(y, y_ref, 2e-5)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        _close(a, b, 1e-4)


@pytest.mark.parametrize("t,block", [(256, 64), (200, 64)])
def test_flash_kernel_at_two_head_sizes_forward_and_backward(t, block):
    """q and k 192 wide a head, v 128 (the latent attention's sizes), the
    Pallas kernels in interpret mode against `dot_product_attention`."""
    from deeplearning4j_tpu.ops import flash_attention
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (1, t, 2, 192))
    k = jax.random.normal(ks[1], (1, t, 2, 192))
    v = jax.random.normal(ks[2], (1, t, 2, 128))
    w = jax.random.normal(ks[3], (1, t, 2, 128))
    flash = lambda q, k, v: flash_attention(
        q, k, v, causal=True, block_q=block, block_k=block, interpret=True)
    dense = lambda q, k, v: dot_product_attention(q, k, v, causal=True)
    out, got = with_gradients(flash, w, (q, k, v))
    ref, want = with_gradients(dense, w, (q, k, v))
    assert out.shape == (1, t, 2, 128)
    np.testing.assert_allclose(out, ref, atol=2e-5)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=1e-4)
    with pytest.raises(ValueError, match="head width"):
        flash_attention(q, k[..., :128], v, causal=True, interpret=True)
