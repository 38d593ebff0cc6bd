"""The rotated latent attention with a low-rank query and the flash kernel
at its head sizes (256-wide q.k and v); see `_glm_common.py`."""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nn.conf.base import InputType
from deeplearning4j_tpu.nn.layers import MultiHeadLatentAttention
from deeplearning4j_tpu.nn.layers.attention import dot_product_attention
from deeplearning4j_tpu.nn.layers.linear_attention import _rms, rope_pairs

from _glm_common import CFG, REF, T
from _lm_common import (  # noqa: F401 (the autouse fixture)
    _budgets_at_the_tests_sizes, _close, with_gradients,
)


def _layer(**over):
    return MultiHeadLatentAttention(**{**dict(
        n_out=32, n_heads=4, nope_dim=12, rope_dim=4, v_dim=16, kv_rank=16,
        q_rank=12, rotate=True, rope_theta=100.0), **over})


def test_rotation_is_the_references_up_to_one_permutation_of_the_dims():
    """`rope_pairs` turns the pairs (2j, 2j+1) as the reference's `rotate`
    does and lays the results out [first members | second members]: the
    reference's dims 0, 2, 4, .. then 1, 3, 5, ..; every q . k is the
    reference's."""
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 40, 3, 8))
    y = jax.random.normal(jax.random.PRNGKey(1), (2, 40, 1, 8))
    got, want = rope_pairs(x, jnp.arange(40), 100.0), REF.rotate(x, 100.0)
    _close(got, jnp.concatenate([want[..., 0::2], want[..., 1::2]], -1),
           2e-6)
    scores = lambda q, k: jnp.einsum("bqhd,bkhd->bhqk", q,
                                     jnp.broadcast_to(k, q.shape))
    _close(scores(got, rope_pairs(y, jnp.arange(40), 100.0)),
           scores(want, REF.rotate(y, 100.0)), 5e-6)
    # position 0 is left as it is (up to the permutation); the norm stays
    np.testing.assert_allclose(jnp.linalg.norm(got, axis=-1),
                               jnp.linalg.norm(x, axis=-1), rtol=1e-5)
    assert float(jnp.abs(got[:, 5] - got[:, 0]).max()) > 0.1


def test_mla_with_a_low_rank_query_and_rotation_is_the_references():
    """The layer and every parameter's gradient against the reference's
    attention at the test widths (12 + 4 / 16, query rank 12)."""
    p = REF.make_params(CFG)["layer1"]["attn"]
    mla = _layer()
    p0, state = mla.init(jax.random.PRNGKey(0), InputType.recurrent(32, T))
    assert {k: v.shape for k, v in p0.items()} \
        == {k: v.shape for k, v in p.items()}
    assert set(p0) == {"Wqa", "q_norm", "Wqb", "Wkva", "kv_norm", "Wkvb",
                       "Wo"}
    x = jax.random.normal(jax.random.PRNGKey(1), (2, T, 32))
    w = jax.random.normal(jax.random.PRNGKey(2), (2, T, 32))
    run = lambda p, x: mla.apply(p, state, x)[0]
    ref = lambda p, x: REF._attention(CFG, p, x, "highest")
    y, got = with_gradients(run, w, (p, x))
    y_ref, want = with_gradients(ref, w, (p, x))
    _close(y, y_ref, 3e-5)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                            jax.tree_util.tree_leaves(want)):
        assert np.abs(np.asarray(a - b)).max() <= 1e-4 * max(
            np.abs(np.asarray(b)).max(), 1e-7), jax.tree_util.keystr(path)
    # without the rotation it is another function: the fault the
    # benchmark plants
    off = dataclasses.replace(mla, rotate=False).apply(p, state, x)[0]
    _close(off, REF._attention(CFG, p, x, "highest", fault="no_rope"), 3e-5)
    assert float(jnp.abs(off - y).max()) > 1e-3


def _todays_layer(self, params, x):
    """`MultiHeadLatentAttention.apply` of the parent of the PR that
    brought the low-rank query and the rotation, statement for statement
    (no mask, off the TPU)."""
    b, t, _ = x.shape
    h = self.n_heads
    q = (x @ params["Wq"]).reshape(b, t, h, -1)
    ckr = x @ params["Wkva"]
    c = _rms(ckr[..., :self.kv_rank], params["kv_norm"],
             self.norm_epsilon).astype(x.dtype)
    kv = (c @ params["Wkvb"]).reshape(b, t, h, -1)
    k_r = jnp.broadcast_to(ckr[:, :, None, self.kv_rank:],
                           (b, t, h, self.rope_dim))
    k = jnp.concatenate([kv[..., :self.nope_dim], k_r], axis=-1)
    v = kv[..., self.nope_dim:]
    out = dot_product_attention(q, k, v, mask=None, causal=True)
    return out.reshape(b, t, h * self.v_dim) @ params["Wo"]


def test_with_both_off_the_layer_is_todays_bit_for_bit():
    """`q_rank` None and `rotate` False (the defaults, what `KimiLinearLM`
    builds): the parent's parameters from the same key, the parent's
    result and gradients bit for bit, and the parent's lowered program."""
    mla = MultiHeadLatentAttention(n_out=32, n_heads=4, nope_dim=16,
                                   rope_dim=8, v_dim=16, kv_rank=24)
    assert mla.q_rank is None and not mla.rotate
    key = jax.random.PRNGKey(3)
    p, state = mla.init(key, InputType.recurrent(32, 24))
    assert set(p) == {"Wq", "Wkva", "kv_norm", "Wkvb", "Wo"}
    from deeplearning4j_tpu.nn.initializers import get_initializer
    ks = jax.random.split(key, 4)
    np.testing.assert_array_equal(p["Wq"], get_initializer("xavier")(
        ks[0], (32, 96), 32, 96, jnp.float32))
    np.testing.assert_array_equal(p["Wo"], get_initializer("xavier")(
        ks[3], (64, 32), 64, 32, jnp.float32))
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 24, 32))
    def new(p, x):
        return jnp.sum(jnp.sin(mla.apply(p, state, x)[0]))

    def old(p, x):
        return jnp.sum(jnp.sin(_todays_layer(mla, p, x)))

    for a, b in zip(
            jax.tree_util.tree_leaves(jax.value_and_grad(new, (0, 1))(p, x)),
            jax.tree_util.tree_leaves(jax.value_and_grad(old, (0, 1))(p, x))):
        np.testing.assert_array_equal(a, b)
    text = lambda f: re.sub(r"loc\([^)]*\)", "", jax.jit(
        jax.grad(f, (0, 1))).lower(p, x).as_text())
    assert text(new).replace("jit_new", "") \
        == text(old).replace("jit_old", "")


@pytest.mark.parametrize("t,block", [(256, 128), (200, 64)])
def test_flash_kernel_at_256_wide_heads_forward_and_backward(t, block):
    """The Pallas kernels (interpreted here) at the rotated latent
    attention's head sizes, 256-wide q.k and 256-wide v, at a length that
    is and one that is not a multiple of the block: the dense path's
    result and the gradients of q, k and v."""
    from deeplearning4j_tpu.ops.flash_attention import flash_attention
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (1, t, 2, 256)) * 0.5
    k = jax.random.normal(ks[1], (1, t, 2, 256)) * 0.5
    v = jax.random.normal(ks[2], (1, t, 2, 256))
    w = jax.random.normal(ks[3], (1, t, 2, 256))
    flash = lambda q, k, v: flash_attention(
        q, k, v, causal=True, block_q=block, block_k=block, interpret=True)
    dense = lambda q, k, v: dot_product_attention(q, k, v, causal=True)
    out, got = with_gradients(flash, w, (q, k, v))
    ref, want = with_gradients(dense, w, (q, k, v))
    _close(out, ref, 2e-5)
    for a, b in zip(got, want):
        _close(a, b, 5e-5)
