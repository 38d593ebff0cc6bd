"""The pieces of the learned sparse attention (`ops/dsa_attention.py`,
`MultiHeadAttention(indexer=)`): the exact selection against `lax.top_k`,
the attention over the kept keys against a dense softmax under the mask of
the selected ids, the indexer's loss and which weights it reaches, the
Pallas kernels (interpreted) against the XLA path, the three-row rotation
and the head width; see `_keye_common.py`."""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nn.conf.base import InputType
from deeplearning4j_tpu.nn.layers import (
    LightningIndexer, MultiHeadAttention, attach_auxiliary_loss,
)
from deeplearning4j_tpu.nn.layers.attention import (
    _add_u64, _merge_heads, _split_heads, dot_product_attention, rms_norm,
    rope,
)
from deeplearning4j_tpu.ops import dsa_attention as D


def _inputs(t=128, h=4, hk=2, d=16, hi=3, di=8, seed=0, b=2):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    n = jax.random.normal
    return (n(ks[0], (b, t, h, d)), n(ks[1], (b, t, hk, d)),
            n(ks[2], (b, t, hk, d)), n(ks[3], (b, t, hi, di)),
            n(ks[4], (b, t, di)), 0.3 * n(ks[5], (b, t, hi)))


def _topk_mask(scores, topk):
    """The dense mask of `lax.top_k`'s ids among the keys s <= t."""
    t = scores.shape[-1]
    seen = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    ids = jax.lax.top_k(jnp.where(seen, scores, -jnp.inf), min(topk, t))[1]
    rows = jnp.arange(t)[:, None]
    return jnp.zeros((t, t), bool).at[rows, ids].set(True) & seen


# ------------------------------------------------------------ the selection
@pytest.mark.parametrize("ties", ["none", "planted", "all_equal"])
def test_the_selection_is_lax_top_ks_set(ties):
    """(tau, cut) name exactly the keys `lax.top_k` picks among s <= t:
    rows shorter than ``topk`` keep all they see, ties at the edge go to
    the lower position, and the pairs kept are sum_t min(t + 1, topk)."""
    t, topk = 96, 16
    scores = jax.random.normal(jax.random.PRNGKey(1), (t, t))
    if ties == "planted":               # many equal values at the edge
        # (+ 0.0: no -0.0, which `lax.top_k` orders below +0.0 and the
        # indexer's scores, summed from +0.0, never hold)
        scores = jnp.round(scores * 2) / 2 + 0.0
    if ties == "all_equal":
        scores = jnp.zeros((t, t))
    tau, cut = D.select(scores[None], 0, topk)
    pos = jnp.arange(t)
    kept = D.keep_mask(scores, tau[0], cut[0], pos[:, None], pos[None, :])
    np.testing.assert_array_equal(kept, _topk_mask(scores, topk))
    np.testing.assert_array_equal(kept.sum(-1), np.minimum(pos + 1, topk))
    assert int(kept.sum()) == D.pairs_selected(t, topk) == 1416
    assert D.pairs_causal(t) == 4656


def test_the_counts_carry_over_two_words():
    """A layer's pair counts are two uint32 words: the sum of a step's
    sequences is added with its carries."""
    total = jnp.asarray([2 ** 32 - 5, 7], jnp.uint32)
    counts = jnp.full((9,), 536887296, jnp.uint32)
    lo, hi = (int(w) for w in _add_u64(total, counts))
    assert lo + (hi << 32) == (2 ** 32 - 5) + (7 << 32) + 9 * 536887296


# ------------------------------------------- attention over the kept keys
@pytest.mark.parametrize("group", [1, 8])
def test_sparse_attention_is_a_dense_softmax_under_the_selected_mask(group):
    """Forward, dq, dk and dv of the XLA path against a plain softmax over
    all keys under the mask scattered from `lax.top_k`'s ids, with one
    key head a query head and with one for eight."""
    q, k, v, qi, ki, wi = _inputs(h=8, hk=8 // group, b=1)
    topk = 24
    mask = _topk_mask(D.index_scores(qi, ki, wi)[0], topk)

    def dense(q, k, v):
        kf, vf = (jnp.repeat(a, group, axis=2) for a in (k, v))
        s = jnp.einsum("bqhd,bkhd->bhqk", q, kf) / 4.0
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, vf)

    sparse = lambda q, k, v: D.sparse_attention(
        q, k, v, qi, ki, wi, topk=topk, block_k=32)[0]
    np.testing.assert_allclose(sparse(q, k, v), dense(q, k, v), atol=2e-6)
    w = jnp.cos(jnp.arange(q.size, dtype=jnp.float32)).reshape(q.shape)
    grads = lambda f: jax.grad(lambda *a: jnp.sum(f(*a) * w), (0, 1, 2))(
        q, k, v)
    for got, want in zip(grads(sparse), grads(dense)):
        np.testing.assert_allclose(got, want, atol=2e-5)


def test_the_indexers_loss_and_its_gradient_are_the_plain_formulas():
    """kl[t] = sum_{s kept} p (log p - log softmax_kept(I)) with p the
    probabilities averaged over the heads, DETACHED: autodiff of that
    formula gives the indexer's gradients; q, k and v get NOTHING from
    the loss and the indexer's inputs nothing from the output."""
    q, k, v, qi, ki, wi = _inputs(b=1)
    topk = 24
    mask = _topk_mask(D.index_scores(qi, ki, wi)[0], topk)

    def plain(qi, ki, wi):
        kf = jnp.repeat(k, 2, axis=2)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, kf) / 4.0
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        target = jnp.mean(p, axis=1)
        log_pi = jax.nn.log_softmax(
            jnp.where(mask, D.index_scores(qi, ki, wi), -jnp.inf), axis=-1)
        return jnp.sum(jnp.where(
            mask, target * (jnp.log(jnp.maximum(target, 1e-37)) - log_pi),
            0.0), axis=-1)

    call = lambda *a: D.sparse_attention(*a, topk=topk, block_k=32)
    np.testing.assert_allclose(call(q, k, v, qi, ki, wi)[1],
                               plain(qi, ki, wi), atol=2e-6)
    w = jnp.sin(jnp.arange(128, dtype=jnp.float32))
    got = jax.grad(lambda *a: jnp.sum(call(*a)[1] * w), range(6))(
        q, k, v, qi, ki, wi)
    want = jax.grad(lambda *a: jnp.sum(plain(*a) * w), range(3))(qi, ki, wi)
    for g in got[:3]:
        assert not np.any(np.asarray(g))
    for g, r in zip(got[3:], want):
        np.testing.assert_allclose(g, r, atol=2e-6)
        assert np.abs(np.asarray(r)).max() > 1e-4
    from_out = jax.grad(lambda *a: jnp.sum(jnp.sin(call(*a)[0])), (3, 4, 5))(
        q, k, v, qi, ki, wi)
    for g in from_out:
        assert not np.any(np.asarray(g))


def _with_gradients(call, w, args):
    """(output, loss a query, pairs kept a query) of ``call`` and the six
    gradients of a weighting of the first two: one forward and one
    backward, one compiled program."""
    def loss(*a):
        r = call(*a)
        return jnp.sum(r[0] * w) + 3.0 * jnp.mean(r[1]), r
    (_, out), grads = jax.jit(jax.value_and_grad(
        loss, range(6), has_aux=True))(*args)
    return out, grads


@pytest.mark.parametrize("ties", [False, True])
def test_the_kernels_are_the_xla_path(ties):
    """The five Pallas kernels, interpreted, against the XLA path at 128-
    wide heads in a group of 4: output, the loss a query, the pairs kept a
    query (planted ties included: every indexer weight alike and the
    products rounded coarse), and all six gradients."""
    q, k, v, qi, ki, wi = _inputs(t=256, h=4, hk=1, d=128, hi=2, di=64, b=1,
                                  seed=3)
    if ties:
        qi, ki = jnp.round(qi), jnp.round(ki)
        wi = jnp.full_like(wi, 0.25)
    call = lambda kernels: lambda *a: D.sparse_attention(
        *a, topk=32, block_k=128, kernels=kernels, interpret=True)
    w = jnp.cos(jnp.arange(q.size, dtype=jnp.float32)).reshape(q.shape)
    args = (q, k, v, qi, ki, wi)
    got, got_g = _with_gradients(call(True), w, args)
    want, want_g = _with_gradients(call(False), w, args)
    np.testing.assert_allclose(got[0], want[0], atol=2e-6)
    np.testing.assert_allclose(got[1], want[1], atol=5e-6)
    np.testing.assert_array_equal(got[2], want[2])
    assert int(got[2].sum()) == D.pairs_selected(256, 32)
    if ties:
        scores = D.index_scores(qi, ki, wi)[0]
        edge = jnp.sort(jnp.where(_topk_mask(scores, 32), scores, jnp.inf),
                        axis=-1)[:, :1]
        assert int(((scores == edge) & ~_topk_mask(scores, 32)
                    & (jnp.arange(256)[None] <= jnp.arange(256)[:, None])
                    ).sum()) > 50       # ties the selection had to break
    for g, r in zip(got_g, want_g):
        scale = max(float(jnp.abs(r).max()), 1e-6)
        assert float(jnp.abs(g - r).max()) <= 2e-5 * scale


@pytest.mark.parametrize("t,b,bq,ties", [
    (512, 2, 128, False), (640, 1, 128, False), (512, 1, 64, False),
    (640, 1, 128, True)],
    ids=["four_q_blocks", "five_q_blocks", "two_q_blocks_a_k_block",
         "planted_ties"])
def test_the_one_backward_kernel_is_the_xla_path(t, b, bq, ties,
                                                 monkeypatch):
    """`dsa_attn_bwd`, interpreted, at four and at five q blocks with bq =
    bk = 128 (q block 0 has one live tile, so k block 0 is written at grid
    step (0, 0) and read at the very next, (1, 0); an even and an odd
    count of tiles in a row's last slot), with two q blocks a k block (a
    VMEM budget that holds 64 rows: a tile of 128 keys down and 64
    queries along, which a swapped axis does not survive), and at a group
    of 8 query heads on one key head: all six gradients against the XLA
    path. Once more over five q blocks with PLANTED TIES at the
    threshold (the key-major tiles of the backward and of the loss must
    keep the very keys the q-major selection kept): there the pairs kept
    and `kl` a query from `dsa_kl_fwd` too."""
    args = _inputs(t=t, h=8, hk=1, d=128, hi=2, di=64, b=b, seed=5)
    if ties:
        args = (*args[:3], jnp.round(args[3]), jnp.round(args[4]),
                jnp.full_like(args[5], 0.25))
    monkeypatch.setattr(D, "_Q_BLOCK_BYTES", bq * 8 * 128 * 16)
    assert D.kernel_blocks(t, 128, 8, 128) == (bq, 128)
    call = lambda kernels: lambda *a: D.sparse_attention(
        *a, topk=48, block_k=128, kernels=kernels, interpret=True)
    w = jnp.cos(jnp.arange(args[0].size, dtype=jnp.float32)).reshape(
        args[0].shape)
    got, got_g = _with_gradients(call(True), w, args)
    want, want_g = _with_gradients(call(False), w, args)
    if ties:
        scores = D.index_scores(*args[3:])[0]
        kept = _topk_mask(scores, 48)
        edge = jnp.sort(jnp.where(kept, scores, jnp.inf), axis=-1)[:, :1]
        seen = jnp.arange(t)[None] <= jnp.arange(t)[:, None]
        assert int(((scores == edge) & ~kept & seen).sum()) > 50
        np.testing.assert_array_equal(got[2], want[2])
        np.testing.assert_allclose(got[1], want[1], atol=5e-6)
        assert float(jnp.abs(want[1]).max()) > 1e-2
    for g, r in zip(got_g, want_g):
        scale = float(jnp.abs(r).max())
        assert scale > 1e-4
        assert float(jnp.abs(g - r).max()) <= 2e-5 * scale


def _sub_jaxprs(eqn):
    for value in eqn.params.values():
        for sub in value if isinstance(value, (list, tuple)) else [value]:
            sub = getattr(sub, "jaxpr", sub)
            if hasattr(sub, "eqns"):
                yield sub


def _equations(jaxpr):
    """Every equation of ``jaxpr``, those of its nested ones included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn):
            yield from _equations(sub)


def test_the_key_major_kernels_head_loops_turn_nothing_they_need_not():
    """What `dsa_attn_bwd` and `dsa_kl_fwd` do a QUERY HEAD and tile,
    read off their jaxprs (no chip): the tile is (keys, queries), NO
    product of the head loop contracts axis 0 of its left operand (the
    key side's two sums are plain, and dq gathers transposed from a `k^T`
    made once a tile; a q-major tile had two such products a head), and
    no vector that lies along the lanes (a saved statistic: `lse`,
    `delta`) is turned down the sublanes (the `[:, None]` of a q-major
    tile)."""
    heads, bq, bk = 8, 64, 256
    q, k, v, qi, ki, wi = _inputs(t=512, h=heads, hk=2, d=128, hi=2, di=64,
                                  b=1)
    heads_first = [D._heads_first(a) for a in (q, k, v, qi)]
    fwd = lambda *a: D._forward_kernels(*a, 32, bq, bk, True)
    out, lse, tau, cut, lsei, kl, _ = jax.eval_shape(fwd, *heads_first, ki,
                                                     wi)
    res = (*heads_first, ki, wi, out, lse, tau, cut, lsei)
    bwd = lambda res, g, gk: D._backward_kernels(res, g, gk, bq, bk, True)
    kernels = {
        eqn.params["name"]: eqn.params["jaxpr"]
        for traced in (jax.make_jaxpr(fwd)(*heads_first, ki, wi),
                       jax.make_jaxpr(bwd)(res, out, kl))
        for eqn in _equations(traced.jaxpr)
        if eqn.primitive.name == "pallas_call"}
    assert set(kernels) == {"dsa_index", "dsa_select", "dsa_attn_fwd",
                            "dsa_kl_fwd", "dsa_attn_bwd"}
    for name, products in (("dsa_attn_bwd", 5), ("dsa_kl_fwd", 1)):
        # the loops over the query heads: the tile's, which holds the
        # products, and (the backward) the one that writes dq out
        loops = [list(_equations(eqn.params["jaxpr"].jaxpr))
                 for eqn in _equations(kernels[name])
                 if eqn.primitive.name == "scan"
                 and eqn.params["length"] == heads]
        body, = [loop for loop in loops if any(
            eqn.primitive.name == "dot_general" for eqn in loop)]
        dots = [eqn for eqn in body if eqn.primitive.name == "dot_general"]
        assert len(dots) == products, name
        tiles = {eqn.outvars[0].aval.shape for eqn in dots}
        assert (bk, bq) in tiles and (bq, bk) not in tiles, name
        assert not [eqn for eqn in dots
                    if eqn.params["dimension_numbers"][0][0] == (0,)], name
        for eqn in body:
            if eqn.primitive.name not in ("broadcast_in_dim", "reshape"):
                continue
            src, dst = eqn.invars[0].aval.shape, eqn.outvars[0].aval.shape
            if not src or src[-1] == 1:
                continue                       # a scalar, or a column
            at = eqn.params["broadcast_dimensions"][-1] \
                if eqn.primitive.name == "broadcast_in_dim" \
                else (len(dst) - 1 if dst[-1] == src[-1] else None)
            assert at == len(dst) - 1, (name, eqn)


def test_the_backward_kernels_copies_are_waited_for():
    """The key side's sums travel between HBM and VMEM by the kernel's own
    copies. Under the TPU interpreter a copy lands only when it is WAITED
    for and every read and write is checked against the others' clocks: a
    k block read back before its copy out was waited for is a race (taking
    the waits out of the row's last step and into the next row's first
    shows as one). None here, and the plain interpreter's gradients bit
    for bit."""
    from jax._src.pallas.mosaic.interpret import (
        interpret_pallas_call as mosaic,
    )
    from jax.experimental.pallas import tpu as pltpu
    q, k, v, qi, ki, wi = _inputs(t=512, h=8, hk=1, d=128, hi=2, di=64,
                                  b=1, seed=6)
    heads_first = [D._heads_first(a) for a in (q, k, v, qi)]
    out, lse, tau, cut, lsei, kl, _ = D._forward_kernels(
        *heads_first, ki, wi, 48, 128, 128, True)
    res = (*heads_first, ki, wi, out, lse, tau, cut, lsei)
    g_out = jnp.cos(jnp.arange(out.size, dtype=jnp.float32)).reshape(
        out.shape)
    g_kl = jnp.full(kl.shape, 3.0 / 512)
    plain = D._backward_kernels(res, g_out, g_kl, 128, 128, True)
    pltpu.reset_tpu_interpret_mode_state()
    checked = D._backward_kernels(
        res, g_out, g_kl, 128, 128,
        pltpu.InterpretParams(dma_execution_mode="on_wait",
                              detect_races=True))
    assert not mosaic.races.races_found
    for a, b in zip(checked, plain):
        assert np.abs(np.asarray(b)).max() > 0
        np.testing.assert_array_equal(a, b)


def test_the_kernels_need_tiles_that_divide_the_length():
    assert D.kernel_blocks(32768, 512, 32, 128) == (512, 512)
    assert D.kernel_blocks(32768, 512, 64, 128) == (256, 512)
    assert D.kernel_blocks(8192, 512, 32, 128) == (512, 512)
    assert D.kernel_blocks(1000, 512, 32, 128) is None
    args = _inputs(t=96)
    with pytest.raises(ValueError, match="tiles"):
        D.sparse_attention(*args, topk=8, block_k=512, kernels=True)
    with pytest.raises(ValueError, match="head"):
        D.sparse_attention(args[0], args[1][..., :8], *args[2:], topk=8)


def test_attach_auxiliary_loss_is_the_identity_with_a_cotangent():
    y, aux = jnp.arange(3.0), jnp.asarray(2.0)
    f = lambda y, aux: jnp.sum(attach_auxiliary_loss(y * 2, aux * aux, 0.5))
    assert float(f(y, aux)) == 6.0
    gy, ga = jax.grad(f, (0, 1))(y, aux)
    np.testing.assert_array_equal(gy, [2.0, 2.0, 2.0])
    assert float(ga) == 0.5 * 2 * 2.0


# ------------------------------------------------- rotation and head width
def test_three_rows_of_positions_share_out_the_frequencies():
    """`rope(sections=)` against a loop over the frequencies: frequency j
    turns by the row whose section holds j; on text (three rows alike) it
    is today's `rope` to the bit."""
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 12, 3, 16))
    rows = jnp.stack([jnp.arange(12), 3 + 2 * jnp.arange(12),
                      (jnp.arange(12) * 5) % 7])[:, None, :]
    sections, base = (2, 3, 3), 50.0
    got = rope(x, rows, base, sections)
    want = np.array(x)
    row_of = [0, 0, 1, 1, 1, 2, 2, 2]
    for j in range(8):
        ang = np.asarray(rows[row_of[j], 0], np.float32) * base ** (-j / 8)
        c, s = np.cos(ang)[None, :, None], np.sin(ang)[None, :, None]
        a, b = np.asarray(x[..., j]), np.asarray(x[..., j + 8])
        want[..., j], want[..., j + 8] = a * c - b * s, b * c + a * s
    np.testing.assert_allclose(got, want, atol=2e-6)
    pos = jnp.arange(12)[None]
    np.testing.assert_array_equal(
        rope(x, jnp.broadcast_to(pos, (3, 1, 12)), base, sections),
        rope(x, pos, base))
    with pytest.raises(ValueError, match="sections"):
        rope(x, rows, base, (2, 3, 4))


def _parents_rope(x, positions, base: float = 10000.0):
    """`rope` of this PR's parent, statement for statement."""
    d = x.shape[-1]
    half = d // 2
    acc_t = jnp.promote_types(jnp.float32, x.dtype)
    freqs = base ** (-jnp.arange(0, half, dtype=acc_t) / half)
    angles = positions[..., None].astype(acc_t) * freqs   # (B?, T, half)
    while angles.ndim < x.ndim:
        angles = angles[..., None, :] if angles.ndim == x.ndim - 1 \
            else angles[None]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin],
        axis=-1).astype(x.dtype)


def _parents_layer(self, params, x):
    """`MultiHeadAttention.apply` of this PR's parent, statement for
    statement (no bias, no mask, no dropout, the dense path)."""
    q = x @ params["Wq"]
    k = x @ params["Wk"]
    v = x @ params["Wv"]
    h, hk = self.n_heads, self.n_kv_heads or self.n_heads
    q, k, v = _split_heads(q, h), _split_heads(k, hk), _split_heads(v, hk)
    if self.qk_norm:
        q = rms_norm(q, params["q_norm"], self.norm_epsilon)
        k = rms_norm(k, params["k_norm"], self.norm_epsilon)
    if self.use_rope:
        pos = (0 + jnp.arange(x.shape[1]))[None]
        q = _parents_rope(q, pos, self.rope_base)
        k = _parents_rope(k, pos, self.rope_base)
    group = h // hk
    if group > 1:
        k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    out = dot_product_attention(q, k, v, mask=None, causal=self.causal,
                                dropout=0.0, rng=None)
    return _merge_heads(out) @ params["Wo"]


@pytest.mark.parametrize("fields", [
    {}, {"n_kv_heads": 2, "qk_norm": True, "rope_base": 1e6}],
    ids=["defaults", "grouped_normed"])
def test_at_todays_fields_the_layer_is_the_parents_bit_for_bit(fields):
    """``head_dim``, ``rope_sections`` and ``indexer`` None: the parent's
    parameters from the same key, no state, its result and gradients bit
    for bit, and its lowered program (the scopes are locations only)."""
    layer = MultiHeadAttention(n_out=32, n_heads=4, causal=True, **fields)
    assert layer.head_dim is None and layer.rope_sections is None \
        and layer.indexer is None
    key = jax.random.PRNGKey(3)
    p, state = layer.init(key, InputType.recurrent(32, 24))
    assert state == {}
    from deeplearning4j_tpu.nn.initializers import get_initializer
    kv = 8 * (fields.get("n_kv_heads") or 4)
    for name, k, shape in zip(("Wq", "Wk", "Wv", "Wo"),
                              jax.random.split(key, 4),
                              ((32, 32), (32, kv), (32, kv), (32, 32))):
        np.testing.assert_array_equal(p[name], get_initializer("xavier")(
            k, shape, shape[0], shape[1], jnp.float32))
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 24, 32))

    def new(p, x):
        return jnp.sum(jnp.sin(layer.apply(p, state, x)[0]))

    def old(p, x):
        return jnp.sum(jnp.sin(_parents_layer(layer, p, x)))

    for a, b in zip(
            jax.tree_util.tree_leaves(jax.value_and_grad(new, (0, 1))(p, x)),
            jax.tree_util.tree_leaves(jax.value_and_grad(old, (0, 1))(p, x))):
        np.testing.assert_array_equal(a, b)
    text = lambda f: re.sub(r"loc\([^)]*\)", "", jax.jit(
        jax.grad(f, (0, 1))).lower(p, x).as_text())
    assert text(new).replace("jit_new", "") \
        == text(old).replace("jit_old", "")


def test_a_head_width_of_its_own():
    """``head_dim``: 4 heads of 16 on a stream of 32 (q is twice the
    stream's width, as 32 x 128 is twice 2,048): ``Wq`` (32, 64), ``Wo``
    (64, 32), and the layer is the plain formulas."""
    layer = MultiHeadAttention(n_out=32, n_heads=4, n_kv_heads=2,
                               head_dim=16, causal=True, qk_norm=True)
    p, _ = layer.init(jax.random.PRNGKey(1), InputType.recurrent(32, 20))
    assert {k: v.shape for k, v in p.items()} == {
        "Wq": (32, 64), "Wk": (32, 32), "Wv": (32, 32), "Wo": (64, 32),
        "q_norm": (16,), "k_norm": (16,)}
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 20, 32))
    got, _ = layer.apply(p, {}, x)
    q, k, v = (_split_heads(x @ p[n], h)
               for n, h in (("Wq", 4), ("Wk", 2), ("Wv", 2)))
    pos = jnp.arange(20)[None]
    q = rope(rms_norm(q, p["q_norm"], 1e-5), pos)
    k = rope(rms_norm(k, p["k_norm"], 1e-5), pos)
    want = _merge_heads(dot_product_attention(
        q, jnp.repeat(k, 2, axis=2), jnp.repeat(v, 2, axis=2),
        causal=True)) @ p["Wo"]
    np.testing.assert_allclose(got, want, atol=1e-6)
    with pytest.raises(ValueError, match="even head dim"):
        dataclasses.replace(layer, head_dim=15).init(
            jax.random.PRNGKey(1), InputType.recurrent(32, 20))


def test_an_indexer_needs_whole_causal_sequences():
    layer = MultiHeadAttention(
        n_out=32, n_heads=4, head_dim=16, causal=True,
        indexer=LightningIndexer(n_heads=2, head_dim=8, topk=4))
    p, state = layer.init(jax.random.PRNGKey(1), InputType.recurrent(32, 16))
    assert set(state) == {"pairs_selected_total", "pairs_causal_total",
                          "indexer_kl"}
    assert {k: v.shape for k, v in p["indexer"].items()} == {
        "Wq": (32, 16), "Wk": (32, 8), "Ww": (32, 2), "k_gamma": (8,),
        "k_beta": (8,)}
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 16, 32))
    y, new = layer.apply(p, state, x)
    assert y.shape == (2, 16, 32) and float(new["indexer_kl"]) > 0
    assert int(new["pairs_selected_total"][0]) == 2 * D.pairs_selected(16, 4)
    with pytest.raises(NotImplementedError, match="key mask"):
        layer.apply(p, state, x, mask=jnp.ones((2, 16)))
    with pytest.raises(ValueError, match="causal"):
        dataclasses.replace(layer, causal=False).init(
            jax.random.PRNGKey(1), InputType.recurrent(32, 16))
    with pytest.raises(TypeError, match="part of"):
        layer.indexer.apply({}, {}, x)
    # the layer's conf round-trips with its indexer
    from deeplearning4j_tpu.nn.conf.base import (
        layer_from_dict, layer_to_dict,
    )
    assert layer_from_dict(layer_to_dict(layer)) == layer
