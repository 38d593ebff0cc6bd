"""Pallas flash-attention kernel vs the XLA reference implementation
(interpret mode on CPU; the same kernel compiles for TPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nn.layers.attention import dot_product_attention
from deeplearning4j_tpu.ops import flash_attention


def _qkv(b=2, t=48, h=4, d=16, seed=0, dtype="float32"):
    rs = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rs.randn(b, t, h, d).astype(dtype))
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_dense(causal):
    q, k, v = _qkv()
    ref = dot_product_attention(q, k, v, causal=causal)
    out = flash_attention(q, k, v, causal=causal, block_q=16, block_k=16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_flash_key_mask_and_fully_masked_rows():
    q, k, v = _qkv(seed=1)
    mask = np.ones((2, 48), np.float32)
    mask[0, 20:] = 0.0
    mask[1, :] = 0.0                     # batch 1 fully masked -> zeros
    ref = dot_product_attention(q, k, v, mask=jnp.asarray(mask))
    out = flash_attention(q, k, v, mask=jnp.asarray(mask),
                          block_q=16, block_k=16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    assert np.abs(np.asarray(out)[1]).max() == 0.0


def test_flash_ragged_length_padding():
    q, k, v = _qkv(t=50, seed=2)         # 50 % 16 != 0 -> internal pad
    ref = dot_product_attention(q, k, v, causal=True)
    out = flash_attention(q, k, v, causal=True, block_q=16, block_k=16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_flash_bf16_accumulates_in_f32():
    q, k, v = _qkv(seed=3, dtype="float32")
    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))
    ref = dot_product_attention(qb, kb, vb, causal=True)
    out = flash_attention(qb, kb, vb, causal=True, block_q=16, block_k=16)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=3e-2, rtol=3e-2)


def test_flash_gradients_match_dense():
    q, k, v = _qkv(t=32, seed=4)
    mask = jnp.asarray((np.random.RandomState(5).rand(2, 32) > 0.2)
                       .astype("float32"))

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, mask=mask, causal=True,
                                       block_q=16, block_k=16) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(dot_product_attention(q, k, v, mask=mask,
                                             causal=True) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=5e-4)


def test_mha_flash_impl_matches_dense_and_trains():
    """MultiHeadAttention(attention_impl='flash') end-to-end parity + a
    training step through the custom VJP."""
    from deeplearning4j_tpu.nn.conf.base import InputType
    from deeplearning4j_tpu.nn.layers.attention import MultiHeadAttention
    rs = np.random.RandomState(6)
    x = jnp.asarray(rs.randn(2, 24, 32).astype("float32"))
    mask = jnp.asarray((rs.rand(2, 24) > 0.2).astype("float32"))
    dense = MultiHeadAttention(n_out=32, n_heads=4, causal=True)
    flash = MultiHeadAttention(n_out=32, n_heads=4, causal=True,
                               attention_impl="flash", block_size=8)
    params, state = dense.init(jax.random.PRNGKey(0),
                               InputType.recurrent(32, 24))
    yd, _ = dense.apply(params, state, x, mask=mask)
    yf, _ = flash.apply(params, state, x, mask=mask)
    np.testing.assert_allclose(np.asarray(yf), np.asarray(yd),
                               atol=3e-5, rtol=3e-5)

    def loss(p, layer):
        y, _ = layer.apply(p, state, x, mask=mask)
        return jnp.sum(y ** 2)

    gd = jax.grad(loss)(params, dense)
    gf = jax.grad(loss)(params, flash)
    for key in params:
        np.testing.assert_allclose(np.asarray(gf[key]), np.asarray(gd[key]),
                                   atol=2e-4, rtol=2e-4, err_msg=key)


def test_flash_cross_attention_gradients():
    """tq != tk (cross-attention): the Pallas backward has no square
    assumption — gradients must match the dense reference."""
    rs = np.random.RandomState(8)
    q = jnp.asarray(rs.randn(2, 24, 4, 16).astype("float32"))
    k = jnp.asarray(rs.randn(2, 40, 4, 16).astype("float32"))
    v = jnp.asarray(rs.randn(2, 40, 4, 16).astype("float32"))
    mask = jnp.asarray((rs.rand(2, 40) > 0.2).astype("float32"))

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, mask=mask,
                                       block_q=8, block_k=8) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(dot_product_attention(q, k, v, mask=mask) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=5e-4)
    # unequal q/k block sizes are legal (no square assumption anywhere)
    out_uneq = flash_attention(q, k, v, mask=mask, block_q=8, block_k=20)
    ref = dot_product_attention(q, k, v, mask=mask)
    np.testing.assert_allclose(np.asarray(out_uneq), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_flash_bf16_gradients_finite_and_close():
    rs = np.random.RandomState(9)
    mk = lambda: jnp.asarray(rs.randn(2, 16, 2, 8), jnp.bfloat16)
    q, k, v = mk(), mk(), mk()

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True, block_q=8,
                                       block_k=8).astype(jnp.float32) ** 2)

    g = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    for a in g:
        assert a.dtype == jnp.bfloat16
        assert np.isfinite(np.asarray(a, np.float32)).all()


def test_flash_lse_shard_merge_identity():
    """return_lse enables exact cross-shard composition: flash over two
    key shards merged via the LSE rule == flash over the full keys —
    the building block ring/context parallelism uses across chips."""
    rs = np.random.RandomState(10)
    q = jnp.asarray(rs.randn(2, 16, 2, 8).astype("float32"))
    k = jnp.asarray(rs.randn(2, 32, 2, 8).astype("float32"))
    v = jnp.asarray(rs.randn(2, 32, 2, 8).astype("float32"))
    full = flash_attention(q, k, v, block_q=8, block_k=8)

    o1, l1 = flash_attention(q, k[:, :16], v[:, :16], block_q=8,
                             block_k=8, return_lse=True)
    o2, l2 = flash_attention(q, k[:, 16:], v[:, 16:], block_q=8,
                             block_k=8, return_lse=True)
    m = jnp.maximum(l1, l2)
    w1 = jnp.exp(l1 - m)[..., None]
    w2 = jnp.exp(l2 - m)[..., None]
    merged = (w1 * o1 + w2 * o2) / (w1 + w2)
    np.testing.assert_allclose(np.asarray(merged), np.asarray(full),
                               atol=2e-5, rtol=2e-5)


def test_flash_lse_merge_trains_correctly():
    """Gradients THROUGH the two-shard LSE merge must equal gradients of
    full attention — the property that makes a flash-per-shard ring
    trainable with plain autodiff."""
    rs = np.random.RandomState(11)
    q = jnp.asarray(rs.randn(2, 8, 2, 8).astype("float32"))
    k = jnp.asarray(rs.randn(2, 16, 2, 8).astype("float32"))
    v = jnp.asarray(rs.randn(2, 16, 2, 8).astype("float32"))

    def loss_merged(q, k, v):
        o1, l1 = flash_attention(q, k[:, :8], v[:, :8], block_q=8,
                                 block_k=8, return_lse=True)
        o2, l2 = flash_attention(q, k[:, 8:], v[:, 8:], block_q=8,
                                 block_k=8, return_lse=True)
        m = jnp.maximum(l1, l2)
        w1 = jnp.exp(l1 - m)[..., None]
        w2 = jnp.exp(l2 - m)[..., None]
        return jnp.sum(((w1 * o1 + w2 * o2) / (w1 + w2)) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(dot_product_attention(q, k, v) ** 2)

    gm = jax.grad(loss_merged, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gm, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=5e-4)


@pytest.mark.slow
def test_ring_flash_matches_ring_online():
    """ring_flash_self_attention (fused kernel per shard + LSE merge)
    must match the lax online-softmax ring bit-for-tolerance on the
    8-device CPU mesh, causal and masked."""
    from deeplearning4j_tpu.parallel.mesh import MeshConfig, build_mesh
    from deeplearning4j_tpu.parallel.ring import (
        ring_flash_self_attention, ring_self_attention,
    )
    from jax.sharding import PartitionSpec as P

    mesh = build_mesh(MeshConfig(data=2, seq=4))
    rs = np.random.RandomState(12)
    T = 32                                   # 8 per shard over seq=4
    q = jnp.asarray(rs.randn(2, T, 2, 8).astype("float32"))
    k = jnp.asarray(rs.randn(2, T, 2, 8).astype("float32"))
    v = jnp.asarray(rs.randn(2, T, 2, 8).astype("float32"))
    mask = jnp.asarray((rs.rand(2, T) > 0.2).astype("float32"))
    spec = P(None, "seq", None, None)
    mspec = P(None, "seq")
    sm = dict(mesh=mesh, in_specs=(spec, spec, spec, mspec), out_specs=spec,
              check_vma=False)

    for causal in (True, False):
        ref_f = jax.shard_map(
            lambda q, k, v, m, c=causal: ring_self_attention(
                q, k, v, axis_name="seq", causal=c, mask=m),
            **sm)
        new_f = jax.shard_map(
            lambda q, k, v, m, c=causal: ring_flash_self_attention(
                q, k, v, axis_name="seq", causal=c, mask=m,
                block_q=8, block_k=8),
            **sm)
        ref = np.asarray(ref_f(q, k, v, mask))
        new = np.asarray(new_f(q, k, v, mask))
        np.testing.assert_allclose(new, ref, atol=3e-5, rtol=3e-5,
                                   err_msg=f"causal={causal}")

    # gradients through the sharded flash ring match the online ring
    def loss(fn):
        def go(q, k, v):
            return jnp.sum(fn(q, k, v, mask) ** 2)
        return go

    ref_f = jax.shard_map(
        lambda q, k, v, m: ring_self_attention(
            q, k, v, axis_name="seq", causal=True, mask=m),
        **sm)
    new_f = jax.shard_map(
        lambda q, k, v, m: ring_flash_self_attention(
            q, k, v, axis_name="seq", causal=True, mask=m,
            block_q=8, block_k=8),
        **sm)
    gr = jax.grad(loss(ref_f), argnums=(0, 1, 2))(q, k, v)
    gn = jax.grad(loss(new_f), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gn, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=5e-4)


@pytest.mark.parametrize("shape,bq,bk", [
    ((1, 96, 2, 128), 32, 64),      # head_dim 128, uneven T vs blocks
    ((2, 40, 1, 256), 16, 16),      # head_dim 256 (VMEM-heavy on TPU)
    ((1, 130, 2, 64), 128, 128),    # T barely over one block
    ((1, 8, 1, 32), 128, 128),      # T far below the block size
])
def test_flash_block_size_shape_matrix(shape, bq, bk):
    """The kernel must be exact across the block-size x head-dim x
    ragged-T matrix that real models hit."""
    from deeplearning4j_tpu.nn.layers.attention import dot_product_attention
    from deeplearning4j_tpu.ops import flash_attention

    rs = np.random.RandomState(42)
    b, t, h, d = shape
    q, k, v = [jnp.asarray(rs.randn(b, t, h, d).astype("float32") * 0.3)
               for _ in range(3)]
    for causal in (False, True):
        out = flash_attention(q, k, v, causal=causal, block_q=bq,
                              block_k=bk, interpret=True)
        ref = dot_product_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)
