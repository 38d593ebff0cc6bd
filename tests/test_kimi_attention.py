"""The attention layers of the hybrid LM and their kernels (KDA, MLA, the
flash kernel at two head sizes); see `_kimi_common.py`."""
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

from _kimi_common import (  # noqa: F401 (the autouse fixture)
    CFG, REF, T, _budgets_at_the_tests_sizes, _close, _layer_params,
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
    o, s = kda_chunked(*args, chunk=chunk)
    o_ref, s_ref = REF.kda_recurrence(*args, segment=16)
    _close(o, o_ref, 2e-5)
    _close(s, s_ref, 2e-5)
    # the (sequence, head) pairs in 3 groups, one after another: the same
    from deeplearning4j_tpu.nn.layers import linear_attention
    monkeypatch.setattr(linear_attention, "_SCAN_LIVE_BYTES",
                        2 * 40 * t * 8 * 4)
    for got, want in zip(kda_chunked(*args, chunk=chunk), (o, s)):
        _close(got, want, 1e-6)
    w = jax.random.normal(jax.random.PRNGKey(7), o.shape)
    loss = lambda fn: lambda *a: (lambda o, s: jnp.sum(o * w)
                                  + jnp.sum(s * s))(*fn(*a))
    got = jax.grad(loss(lambda *a: kda_chunked(*a, chunk=chunk)),
                   (0, 1, 2, 3, 4))(*args)
    want = jax.grad(loss(lambda *a: REF.kda_recurrence(*a, segment=16)),
                    (0, 1, 2, 3, 4))(*args)
    for a, b in zip(got, want):
        _close(a, b, 5e-5)


def test_kda_chunked_takes_no_positive_exponent():
    """A decay of e^-300 a step: every exponent the chunked form takes is
    <= 0, so nothing overflows and the numbers are the recurrence's."""
    args = _kda_inputs(64, decay=300.0)
    assert float(args[3].min()) < -200
    o, s = kda_chunked(*args, chunk=32)
    o_ref, s_ref = REF.kda_recurrence(*args, segment=16)
    assert np.isfinite(np.asarray(o)).all()
    _close(o, o_ref, 2e-5)
    _close(s, s_ref, 2e-5)
    g = jax.grad(lambda *a: jnp.sum(kda_chunked(*a, chunk=32)[0]),
                 (0, 1, 2, 3, 4))(*args)
    assert all(np.isfinite(np.asarray(x)).all() for x in g)


def test_kda_chunked_hands_a_state_over():
    """Two calls, the second given the first's state, are one call."""
    args = _kda_inputs(96)
    o, s = kda_chunked(*args, chunk=32)
    head = [a[:, :40] for a in args]
    tail = [a[:, 40:] for a in args]
    o1, s1 = kda_chunked(*head, chunk=32)
    o2, s2 = kda_chunked(*tail, chunk=32, initial_state=s1)
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
    prog = lambda p, x: jnp.sum(kda.apply(p, {}, x)[0] * w)
    ref = lambda p, x: jnp.sum(REF._kda(CFG, p, x, "highest") * w)
    _close(kda.apply(p, {}, x)[0], REF._kda(CFG, p, x, "highest"), 2e-5)
    got, want = jax.grad(prog, (0, 1))(p, x), jax.grad(ref, (0, 1))(p, x)
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
    _close(mla.apply(p, {}, x)[0], REF._mla(CFG, p, x, "highest"), 2e-5)
    got = jax.grad(lambda p, x: jnp.sum(mla.apply(p, {}, x)[0] * w),
                   (0, 1))(p, x)
    want = jax.grad(lambda p, x: jnp.sum(REF._mla(CFG, p, x, "highest") * w),
                    (0, 1))(p, x)
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
    assert flash(q, k, v).shape == (1, t, 2, 128)
    np.testing.assert_allclose(flash(q, k, v), dense(q, k, v), atol=2e-5)
    got = jax.grad(lambda *a: jnp.sum(flash(*a) * w), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(dense(*a) * w), (0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=1e-4)
    with pytest.raises(ValueError, match="head width"):
        flash_attention(q, k[..., :128], v, causal=True, interpret=True)
