"""Pallas flash-attention kernel vs the XLA reference implementation
(interpret mode on CPU; the same kernel compiles for TPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nn.layers.attention import dot_product_attention
from deeplearning4j_tpu.ops import REMAT_KEEP, flash_attention

from _lm_common import with_gradients as _with_gradients


def _grads(loss, args):
    """The gradient of ``loss`` in q, k and v, compiled as one program
    (the dense path takes three times as long op by op)."""
    return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(*args)


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

    gf = _grads(loss_flash, (q, k, v))
    gd = _grads(loss_dense, (q, k, v))
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

    gd = jax.jit(jax.grad(loss), static_argnums=1)(params, dense)
    gf = jax.jit(jax.grad(loss), static_argnums=1)(params, flash)
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

    gf = _grads(loss_flash, (q, k, v))
    gd = _grads(loss_dense, (q, k, v))
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

    gm = _grads(loss_merged, (q, k, v))
    gd = _grads(loss_dense, (q, k, v))
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


# ---- a rematerialised block keeps the kernel's output and log-sum-exp


def _two_layers(causal, with_mask, t, checkpointed, return_lse=False):
    """loss(q, k, v, mask) of two stacked "layers" around flash_attention,
    each under the containers' checkpoint (`nn/multilayer.py::_layer_call`)
    or bare. With return_lse the log-sum-exp carries a cotangent too."""
    def layer(x, k, v, mask):
        got = flash_attention(jnp.tanh(x), k, v, causal=causal,
                              mask=mask if with_mask else None,
                              block_q=16, block_k=16, return_lse=return_lse)
        if return_lse:
            out, lse = got
            return out * jnp.cos(lse)[..., None]
        return got

    if checkpointed:
        layer = jax.checkpoint(
            layer, policy=jax.checkpoint_policies.save_only_these_names(
                REMAT_KEEP))

    def loss(q, k, v, mask):
        return jnp.sum(layer(layer(q, k, v, mask), k, v, mask) ** 2)

    q, k, v = _qkv(t=t, seed=20)
    mask = np.ones((2, t), np.float32)
    mask[1, t - 7:] = 0.0
    return loss, (q, k, v, jnp.asarray(mask))


def _backward_kernels(form, calls):
    """{kernel name: calls} of ``calls`` backward passes in ``form``."""
    kernels = {"flash_bwd_dq": calls}
    if form == "pair":
        kernels["flash_bwd_dkv"] = calls
    return kernels


def _walk(jaxpr):
    """Every equation of a jaxpr, those of its sub-jaxprs too (each use of
    a shared one: the printed text shows it only once)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _walk(sub)


def _eqns(fn, args):
    """Every equation of the traced fn."""
    return _walk(jax.make_jaxpr(fn)(*args).jaxpr)


def _pallas_calls(eqns):
    """{kernel name: Pallas calls} among ``eqns``."""
    calls = {}
    for eqn in eqns:
        if eqn.primitive.name == "pallas_call":
            name = eqn.params["name"]
            calls[name] = calls.get(name, 0) + 1
    return calls


def _kernel_calls(fn, args):
    """{kernel name: Pallas calls} in the traced fn."""
    return _pallas_calls(_eqns(fn, args))


def _run_and_count(fn, args):
    """(fn(*args) compiled, its kernel calls) from ONE trace of fn."""
    traced = jax.jit(fn).trace(*args)
    return (traced.lower().compile()(*args),
            _pallas_calls(_walk(traced.jaxpr.jaxpr)))


@pytest.mark.parametrize("return_lse", [False, True])
@pytest.mark.parametrize("causal,with_mask,t", [
    (True, False, 48), (False, True, 48), (True, True, 40),
    (False, False, 40)])   # 40: padded to the block of 16
def test_checkpointed_block_runs_the_forward_kernel_once(causal, with_mask,
                                                         t, return_lse,
                                                         flash_backward):
    """Under the containers' policy the second forward of a block holds no
    `flash_fwd`: as many forward calls as layers, as many backward (one
    fused kernel each, or the pair); the gradients are bit for bit those
    of the bare function."""
    kept, args = _two_layers(causal, with_mask, t, True, return_lse)
    bare, _ = _two_layers(causal, with_mask, t, False, return_lse)
    grad = lambda f: jax.grad(f, argnums=(0, 1, 2))
    once = {"flash_fwd": 2, **_backward_kernels(flash_backward, 2)}
    (got, got_calls), (want, want_calls) = (
        _run_and_count(grad(f), args) for f in (kept, bare))
    assert got_calls == once and want_calls == once
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_a_checkpoint_that_keeps_nothing_runs_the_forward_twice(
        flash_backward):
    """What the name spares: under a checkpoint with no policy it is the
    identity and each block's backward pass runs `flash_fwd` again."""
    bare, args = _two_layers(True, False, 48, False)
    assert _kernel_calls(jax.grad(jax.checkpoint(bare)), args) == {
        "flash_fwd": 4, **_backward_kernels(flash_backward, 2)}


@pytest.mark.parametrize("return_lse", [False, True])
def test_without_a_checkpoint_the_name_adds_no_op(return_lse, monkeypatch):
    """Outside `jax.checkpoint` the lowered text is what it was before the
    residuals carried a name: the same text with the name taken away (but
    for the counter MLIR appends to a private function's symbol)."""
    import re
    import sys
    module = sys.modules["deeplearning4j_tpu.ops.flash_attention"]
    grad = lambda f: jax.grad(f, argnums=(0, 1, 2))

    def text():
        loss, args = _two_layers(True, True, 40, False, return_lse)
        return re.sub(r"@(\w+?)_\d+\b", r"@\1",
                      jax.jit(grad(loss)).lower(*args).as_text())

    named = text()
    calls = []
    monkeypatch.setattr(module, "checkpoint_name",
                        lambda x, name: calls.append(name) or x)
    assert text() == named
    assert calls == [REMAT_KEEP] * 4        # out and lse, two layers


# ---- grouped key/value heads: query head h reads key head h // group


@pytest.mark.parametrize("group", [1, 4, 8])
@pytest.mark.parametrize("d", [64, 128])
@pytest.mark.parametrize("causal,with_mask", [(True, False), (False, True)])
def test_grouped_kv_heads_forward_and_the_three_gradients(group, d, causal,
                                                          with_mask):
    """The kernels (interpreted) with 8 query heads on 8 // group
    key/value heads, against the dense path with k and v repeated to
    every query head: the output and dq, dk, dv (a key head's gradient
    sums over its group inside the backward kernel), causal and key-masked,
    at a length that is no multiple of the block."""
    h, hk, t = 8, 8 // group, 80
    ks = jax.random.split(jax.random.PRNGKey(group), 4)
    q = jax.random.normal(ks[0], (2, t, h, d)) * 0.5
    k = jax.random.normal(ks[1], (2, t, hk, d)) * 0.5
    v = jax.random.normal(ks[2], (2, t, hk, d))
    w = jax.random.normal(ks[3], (2, t, h, d))
    mask = None
    if with_mask:
        mask = np.ones((2, t), np.float32)
        mask[0, 50:] = 0.0
        mask[1, ::7] = 0.0
        mask = jnp.asarray(mask)
    flash = lambda q, k, v: flash_attention(
        q, k, v, causal=causal, mask=mask, block_q=32, block_k=32,
        interpret=True)
    dense = lambda q, k, v: dot_product_attention(
        q, jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2),
        causal=causal, mask=mask)
    out, got = _with_gradients(flash, w, (q, k, v))
    ref, want = _with_gradients(dense, w, (q, k, v))
    np.testing.assert_allclose(out, ref, atol=3e-6)
    assert got[1].shape == k.shape and got[2].shape == v.shape
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=2e-5)


# ---- the backward in one walk: the fused kernel against the pair of passes


def _both_backwards(monkeypatch, loss, args):
    """(gradients, kernel calls) of ``loss`` with the fused backward and
    with the pair a call keeps past the byte budget."""
    import sys
    module = sys.modules["deeplearning4j_tpu.ops.flash_attention"]
    got = {}
    for form, budget in (("fused", module._RESIDENT_SUM_BYTES), ("pair", 0)):
        monkeypatch.setattr(module, "_RESIDENT_SUM_BYTES", budget)
        # a function of its own a form: jit keeps its traces by function
        got[form] = _run_and_count(
            jax.grad(lambda *a: loss(*a), (0, 1, 2)), args)
    return got


@pytest.mark.parametrize("group", [1, 4, 8])
@pytest.mark.parametrize("d,dv", [(64, 64), (192, 128)])
@pytest.mark.parametrize("rule,keys", [
    ("none", "whole"), ("none", "masked"), ("none", "padded"),
    ("causal", "whole"), ("causal", "masked"), ("causal", "padded"),
    ("block_diffusion", "whole")])
def test_the_fused_backward_equals_the_pair_bit_for_bit(rule, keys, group, d,
                                                        dv, monkeypatch):
    """ONE kernel that makes a tile's scores, probabilities, dp and ds once
    gives the dq, dk and dv of the two passes that each make them again,
    EXACTLY: dq sums over k tiles in the same order, and a k block's dk
    and dv over (query head of the group, q block ascending), the k-major
    pass' order, in float32. Interpreted, in bfloat16 (a cast a sum) and
    with a cotangent on the log-sum-exp, which reaches both through
    ``delta``; under every rule, with a caller's key mask, with the
    wrapper's own for padded keys (and padded query rows), at one, four and
    eight query heads a key head and at a v narrower than q and k. (The
    rule of block diffusion takes no key mask and no padding.)"""
    h, hk = 8, 8 // group
    t = {"whole": 48, "masked": 48, "padded": 40}[keys]
    if rule == "block_diffusion":
        t = 64                      # twice a whole number of q blocks
    ks = jax.random.split(jax.random.PRNGKey(group + d), 4)
    bf = lambda x: x.astype(jnp.bfloat16)
    q = bf(jax.random.normal(ks[0], (2, t, h, d)) * 0.5)
    k = bf(jax.random.normal(ks[1], (2, t, hk, d)) * 0.5)
    v = bf(jax.random.normal(ks[2], (2, t, hk, dv)))
    w = bf(jax.random.normal(ks[3], (2, t, h, dv)))
    mask = None
    if keys == "masked":
        mask = np.ones((2, t), np.float32)
        mask[0, 30:] = 0.0
        mask[1, ::5] = 0.0
        mask = jnp.asarray(mask)
    how = dict(causal=rule == "causal", mask=mask, block_q=16, block_k=16)
    if rule == "block_diffusion":
        how = dict(block_diffusion=8, block_q=16, block_k=8)

    def loss(q, k, v):
        out, lse = flash_attention(q, k, v, return_lse=True, **how)
        return jnp.sum((out * w).astype(jnp.float32)) + jnp.sum(jnp.sin(lse))

    got = _both_backwards(monkeypatch, loss, (q, k, v))
    assert got["fused"][1] == {"flash_fwd": 1, "flash_bwd_dq": 1}
    assert got["pair"][1] == {"flash_fwd": 1, "flash_bwd_dq": 1,
                              "flash_bwd_dkv": 1}
    for a, b in zip(got["fused"][0], got["pair"][0]):
        assert a.dtype == jnp.bfloat16 and a.shape == b.shape
        assert float(jnp.abs(a.astype(jnp.float32)).max()) > 0
        np.testing.assert_array_equal(np.asarray(a.astype(jnp.float32)),
                                      np.asarray(b.astype(jnp.float32)))


@pytest.mark.parametrize("tk,d,dv,dtype,fused", [
    (16384, 128, 128, "bfloat16", True),     # the SDAR cell's stream
    (8192, 256, 256, "bfloat16", True),      # GLM
    (8192, 192, 128, "bfloat16", True),      # Kimi
    (8192, 64, 64, "bfloat16", True),        # LFM2: a lane tile a width
    (32768, 128, 128, "bfloat16", True),     # the largest at 128 + 128
    (32768 + 512, 128, 128, "bfloat16", False),
    (16384, 256, 256, "bfloat16", True),
    (16384 + 512, 256, 256, "bfloat16", False),
    (32768, 128, 128, "float32", False),     # 12 bytes a value, not 8
    (16384, 128, 128, "float32", True)])
def test_the_backward_is_fused_where_a_key_heads_sums_fit_the_budget(
        tk, d, dv, dtype, fused):
    """Which backward a call takes follows from its shapes alone: the
    float32 sums of one key head's dk and dv and their double-buffered
    output blocks, the widths in whole lanes, against 64 MiB."""
    import sys
    module = sys.modules["deeplearning4j_tpu.ops.flash_attention"]
    assert module._RESIDENT_SUM_BYTES == 64 * 2 ** 20 < module._VMEM_BYTES
    assert module._backward_is_fused(tk, d, dv, jnp.dtype(dtype)) is fused


def test_a_call_past_the_byte_budget_takes_the_pair_and_the_gauge_says_so(
        monkeypatch):
    """A call whose key-side sums pass the budget (cut here to the bytes of
    48 keys at a lane tile a width, in float32: 48 * 256 * 12) keeps the
    two passes; one key more in the budget and it takes the fused kernel.
    The gauge `flash_bwd_kernels` reads 2 and 1 where the calls are
    traced."""
    import sys
    from deeplearning4j_tpu import monitor
    module = sys.modules["deeplearning4j_tpu.ops.flash_attention"]
    monkeypatch.setattr(module, "_RESIDENT_SUM_BYTES", 48 * 256 * 12)
    for t, form, kernels in ((64, "pair", 2), (48, "fused", 1)):
        q, k, v = _qkv(t=t, seed=41)
        grad = jax.grad(lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, causal=True, block_q=16, block_k=16) ** 2), (0, 1, 2))
        assert _kernel_calls(grad, (q, k, v)) == {
            "flash_fwd": 1, **_backward_kernels(form, 1)}
        series = monitor.dump()["flash_bwd_kernels"]["series"]
        assert [s["value"] for s in series] == [kernels]


def test_grouped_kv_heads_must_divide_the_query_heads():
    q, _, _ = _qkv(h=4)
    k, v, _ = _qkv(h=3)
    with pytest.raises(ValueError, match="divides"):
        flash_attention(q, k, v)
    k, v, _ = _qkv(h=2, d=8)
    with pytest.raises(ValueError, match="head width"):
        flash_attention(q, k, v)


def _without_locations(text):
    """Lowered StableHLO with its locations stripped and every Mosaic
    kernel body (serialized MLIR bytecode, which carries the Python call
    stack of each op) decoded and printed without debug info."""
    import base64
    import json
    import re
    from jax._src.interpreters import mlir as jax_mlir
    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir
    text = re.sub(r"loc\([^)]*\)", "", text)
    text = re.sub(r"#loc.*\n", "", text)

    def decoded(match):
        raw = re.sub(r"\\([0-9A-Fa-f]{2})",
                     lambda h: chr(int(h.group(1), 16)), match.group(1))
        config = json.loads(raw)
        body = config.get("custom_call_config", {}).get("body")
        if body is None:
            return match.group(0)
        ctx = jax_mlir.make_ir_context()
        tpu.register_dialect(ctx)
        ctx.allow_unregistered_dialects = True
        with ctx:
            module = ir.Module.parse(base64.b64decode(body))
            config["custom_call_config"]["body"] = \
                module.operation.get_asm(enable_debug_info=False)
        return "backend_config = " + json.dumps(config, sort_keys=True)

    return re.sub(r'backend_config = "([^"]*)"', decoded, text)


def test_as_many_key_heads_as_query_heads_lowers_as_before_the_groups(
        monkeypatch, flash_backward):
    """With ``group`` 1 the grouping hands every grid index back as it
    came (no op traced), so an equal-heads call lowers, for the TPU, to
    the text of the ungrouped index maps: `_Group` replaced by the
    parent's expressions (``bh``, ``bh``, ``st``) gives the same text,
    with the fused backward (whose axis of a group's heads is 1 long) and
    with the pair. (Checked against the parent commit itself, kernel
    bodies decoded and locations stripped, in PERF.md section 6, PR 34.)"""
    import re
    import sys
    from jax import export
    module = sys.modules["deeplearning4j_tpu.ops.flash_attention"]
    grp = module._Group(1, 4)
    marks = [object() for _ in range(2)]
    assert grp.kv_row(marks[0]) is marks[0]
    assert grp.q_row(marks[0], marks[1]) is marks[0]
    assert grp.head_row(marks[0], marks[1]) is marks[0]
    assert grp.step(marks[1]) is marks[1]

    def loss(q, k, v, mask):
        out, lse = flash_attention(q, k, v, mask=mask, causal=True,
                                   block_q=128, block_k=128,
                                   interpret=False, return_lse=True)
        return jnp.sum(out ** 2) + jnp.sum(lse)

    q, k, v = _qkv(t=200, d=64)
    mask = jnp.ones((2, 200), jnp.float32)

    def text(k=k, v=v):
        exported = export.export(jax.jit(jax.grad(loss, (0, 1, 2))),
                                 platforms=["tpu"])(q, k, v, mask)
        return _without_locations(exported.mlir_module())

    grouped = text()
    assert grouped.count("tpu_custom_call") >= (
        2 if flash_backward == "fused" else 3)

    class Ungrouped:
        n = 1

        def __init__(self, group, nq):
            assert group == 1

        kv_row = staticmethod(lambda row: row)
        q_row = staticmethod(lambda row, st: row)
        head_row = staticmethod(lambda row, head: row)
        step = staticmethod(lambda st: st)

    with monkeypatch.context() as patch:
        patch.setattr(module, "_Group", Ungrouped)
        assert text() == grouped
    # and a grouped call does trace the grouping
    k2, v2, _ = _qkv(t=200, h=2, d=64)
    assert text(k2, v2) != grouped


# ---- a tile pays for what it needs: interior / diagonal / key-masked bodies


def _kernel_operands(fn, args):
    """{kernel name: number of operands of its Pallas call} in the traced
    fn, and the shapes of every array the trace builds from nothing (a
    broadcast of a scalar)."""
    operands, built = {}, []
    for eqn in _eqns(fn, args):
        if eqn.primitive.name == "pallas_call":
            operands[eqn.params["name"]] = len(eqn.invars)
        if eqn.primitive.name == "broadcast_in_dim" and not any(
                getattr(v.aval, "shape", ()) for v in eqn.invars):
            built.append(eqn.outvars[0].aval.shape)
    return operands, built


def _tile_case(tq, tk, h, hk, d=16, dv=16, seed=30):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (2, tq, h, d)) * 0.5
    k = jax.random.normal(ks[1], (2, tk, hk, d)) * 0.5
    v = jax.random.normal(ks[2], (2, tk, hk, dv))
    w = jax.random.normal(ks[3], (2, tq, h, dv))
    return q, k, v, w


def _key_mask(kind, tk):
    """None; ones; a caller's mask whose batch row 1 has no valid key."""
    if kind is None:
        return None
    mask = np.ones((2, tk), np.float32)
    if kind == "given":
        mask[0, tk // 2:] = 0.0
        mask[0, 3] = 0.0
        mask[1, :] = 0.0
    return jnp.asarray(mask)


# (causal, tq, tk, block_q, block_k, key mask, query heads, key heads):
# which body each kernel runs on the live tiles
_TILE_CASES = {
    "interior-only": (False, 64, 64, 16, 16, None, 4, 4),
    "interior-only-cross": (False, 32, 64, 16, 32, None, 4, 4),
    "diagonal-square-blocks": (True, 64, 64, 16, 16, None, 4, 4),
    "diagonal-wide-q-block": (True, 64, 64, 32, 16, None, 4, 4),
    "diagonal-wide-k-block": (True, 64, 64, 16, 32, None, 4, 4),
    "diagonal-more-keys": (True, 32, 64, 16, 16, None, 4, 4),
    "diagonal-more-queries": (True, 64, 32, 16, 32, None, 4, 4),
    "key-mask-given-causal": (True, 64, 64, 16, 16, "given", 4, 4),
    "key-mask-given": (False, 64, 64, 16, 32, "given", 4, 4),
    "padding-mask-causal": (True, 56, 56, 16, 16, None, 4, 4),
    "padding-mask-cross": (False, 40, 50, 16, 16, None, 4, 4),
    "queries-padded-keys-not": (True, 56, 64, 16, 16, None, 4, 4),
    "grouped-32-on-8": (True, 64, 64, 16, 16, None, 32, 8),
    "grouped-32-on-8-key-mask": (True, 64, 64, 32, 16, "given", 32, 8),
}


@pytest.mark.parametrize("case", sorted(_TILE_CASES))
def test_each_tile_body_forward_and_the_three_gradients(case):
    """Output, log-sum-exp and dq, dk, dv against the dense path for every
    body the kernels have: interior tiles only (not causal), the diagonal's
    (causal, at equal and unequal blocks and lengths), a caller's key mask
    with a fully masked row (zero output, the `lse` sentinel, zero
    gradients), the wrapper's own padding mask, grouped heads."""
    causal, tq, tk, bq, bk, kind, h, hk = _TILE_CASES[case]
    q, k, v, w = _tile_case(tq, tk, h, hk)
    mask = _key_mask(kind, tk)
    flash = lambda q, k, v: flash_attention(
        q, k, v, causal=causal, mask=mask, block_q=bq, block_k=bk,
        return_lse=True)
    dense = lambda q, k, v: dot_product_attention(
        q, jnp.repeat(k, h // hk, axis=2), jnp.repeat(v, h // hk, axis=2),
        causal=causal, mask=mask)
    (out, lse), got = _with_gradients(flash, w, (q, k, v))
    ref, want = _with_gradients(dense, w, (q, k, v))
    np.testing.assert_allclose(out, ref, atol=3e-6)
    # the log-sum-exp against the scores written out
    s = jnp.einsum("bqhd,bkhd->bqhk", q, jnp.repeat(k, h // hk, axis=2)) \
        / np.sqrt(q.shape[-1])
    seen = jnp.ones((2, tq, 1, tk), bool)
    if causal:
        seen = seen & (jnp.arange(tq)[:, None] >= jnp.arange(tk))[
            None, :, None, :]
    if mask is not None:
        seen = seen & (mask > 0)[:, None, None, :]
    want_lse = jax.nn.logsumexp(jnp.where(seen, s, -jnp.inf), axis=-1)
    want_lse = jnp.where(jnp.isfinite(want_lse), want_lse, -1e30)
    np.testing.assert_allclose(lse, want_lse, atol=1e-5, rtol=1e-6)
    if kind == "given":             # batch row 1: no valid key
        assert np.abs(np.asarray(out)[1]).max() == 0.0
        assert (np.asarray(lse)[1] == np.float32(-1e30)).all()
    for a, b in zip(got, want):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, atol=2e-5)


@pytest.mark.parametrize("causal,tq,tk,bq,bk,h,hk", [
    (True, 64, 64, 16, 16, 4, 4), (True, 64, 64, 32, 16, 4, 4),
    (True, 64, 64, 16, 32, 4, 4), (True, 32, 64, 16, 16, 4, 4),
    (True, 64, 32, 16, 32, 4, 4), (False, 32, 64, 16, 32, 4, 4),
    (True, 64, 64, 16, 16, 8, 2)])
def test_a_key_mask_of_ones_is_no_mask_to_the_last_bit(causal, tq, tk, bq,
                                                       bk, h, hk):
    """Without a key mask the kernels select nothing: every row has seen
    key 0 by the end of its first tile, so its running max is finite from
    then on and exp(NEG - m) is 0 by itself. Output, log-sum-exp and the
    three gradients equal those of a mask of ones (today's body on every
    tile) bit for bit, in float32 in the interpreter, at unequal blocks and
    unequal lengths too."""
    q, k, v, w = _tile_case(tq, tk, h, hk, seed=31)
    ones = jnp.ones((2, tk), jnp.float32)

    def run(mask):
        def loss(q, k, v):
            out, lse = flash_attention(q, k, v, causal=causal, mask=mask,
                                       block_q=bq, block_k=bk,
                                       return_lse=True)
            return jnp.sum(out * w) + jnp.sum(jnp.sin(lse)), (out, lse)
        grads, (out, lse) = jax.jit(
            jax.grad(loss, (0, 1, 2), has_aux=True))(q, k, v)
        return (out, lse) + tuple(grads)

    for a, b in zip(run(None), run(ones)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("causal", [True, False])
def test_without_a_key_mask_the_kernels_take_no_mask_operand(
        causal, flash_backward):
    """`mask=None` lowers `flash_fwd` on q, k, v and the backward's kernel
    (fused) or kernels (the pair) on q, k, v, dO, lse, delta: no mask
    operand, and no (B, T) array of ones is built for one. A caller's mask
    is one operand more."""
    q, k, v, w = _tile_case(64, 64, 4, 4)
    loss = lambda mask: lambda q, k, v: jnp.sum(flash_attention(
        q, k, v, causal=causal, mask=mask, block_q=16, block_k=16) * w)
    grad = lambda mask: jax.grad(loss(mask), (0, 1, 2))
    operands, built = _kernel_operands(grad(None), (q, k, v))
    assert operands == {"flash_fwd": 3, **_backward_kernels(flash_backward,
                                                           6)}
    assert not [shape for shape in built if shape in ((2, 64), (2, 1, 64))]
    operands, _ = _kernel_operands(grad(jnp.ones((2, 64))), (q, k, v))
    assert operands == {"flash_fwd": 4, **_backward_kernels(flash_backward,
                                                           7)}


@pytest.mark.parametrize("causal,tq,tk,bq,bk", [
    (True, 8192, 8192, 512, 512), (True, 1024, 1024, 256, 128),
    (True, 1024, 1024, 128, 256), (True, 512, 1024, 128, 128),
    (True, 1024, 512, 128, 128), (False, 512, 1024, 128, 256)])
def test_tile_shares_follow_the_geometry(causal, tq, tk, bq, bk):
    """`_Geometry.tile_counts` (Python integers) counts what the kernels'
    own predicates say tile by tile, and `flash_attention` publishes the
    shares as `flash_tile_share{kind}` where it is traced: 120 interior and
    16 diagonal of 136 live tiles at 8,192 positions in blocks of 512;
    every live tile `key_masked` for a caller with a key mask."""
    import sys
    from deeplearning4j_tpu import monitor
    module = sys.modules["deeplearning4j_tpu.ops.flash_attention"]
    geom = module._Geometry(causal, bq, bk, tq // bq, tk // bk)
    interior = diagonal = 0
    for qi in range(geom.nq):
        for kj in range(int(geom.k_hi(np.int32(qi))) + 1):
            assert qi >= int(geom.q_lo(np.int32(kj)))
            if not causal or geom.interior(qi, kj):
                interior += 1
            else:
                diagonal += 1
    assert geom.tile_counts() == (interior, diagonal)
    if (tq, bq, bk) == (8192, 512, 512):
        assert (interior, diagonal) == (120, 16)

    def shares(mask):
        shape = lambda t, *more: jax.ShapeDtypeStruct((1, t, 2, 8) + more,
                                                      jnp.float32)
        jax.eval_shape(lambda q, k, v: flash_attention(
            q, k, v, causal=causal, mask=mask, block_q=bq, block_k=bk,
            interpret=True), shape(tq), shape(tk), shape(tk))
        series = monitor.dump()["flash_tile_share"]["series"]
        return {s["labels"]["kind"]: s["value"] for s in series}

    live = interior + diagonal
    assert shares(None) == {"interior": 100.0 * interior / live,
                            "diagonal": 100.0 * diagonal / live,
                            "key_masked": 0.0}
    assert shares(jnp.ones((1, tk))) == {"interior": 0.0, "diagonal": 0.0,
                                         "key_masked": 100.0}


def test_the_benchmarks_reader_takes_the_interior_share_from_the_dump(
        monkeypatch):
    """`benchmark/metrics/flash_interior_tile_share.py` reads the gauge
    from `monitor.dump()` itself: the interior share where a flash call was
    traced, None where the program has no such gauge (a parent commit; a
    run that took the XLA path)."""
    import importlib.util
    import os
    from deeplearning4j_tpu import monitor
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "metrics",
        "flash_interior_tile_share.py")
    spec = importlib.util.spec_from_file_location("_flash_share", path)
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    shape = jax.ShapeDtypeStruct((1, 8192, 1, 8), jnp.float32)
    jax.eval_shape(lambda q, k, v: flash_attention(
        q, k, v, causal=True, block_q=512, block_k=512, interpret=True),
        shape, shape, shape)
    assert reader.read({}) == pytest.approx(100.0 * 120 / 136)
    dump = monitor.dump()
    dump.pop("flash_tile_share")
    monkeypatch.setattr(monitor, "dump", lambda: dump)
    assert reader.read({}) is None
