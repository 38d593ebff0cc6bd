"""The block-diffusion visibility rule in the flash kernels' geometry, in
the kernels (interpreted) and in the XLA paths, each against the
brute-force boolean of the three clauses; what `MultiHeadAttention` derives
from the rule and what it refuses; that a causal call still lowers to the
text it had. See `_sdar_common.py`."""
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu import monitor
from deeplearning4j_tpu.nn.conf.base import InputType
from deeplearning4j_tpu.nn.layers import MultiHeadAttention
from deeplearning4j_tpu.nn.layers.attention import (
    block_diffusion_attention, block_diffusion_visible, context_parallel,
)
from deeplearning4j_tpu.ops.flash_attention import flash_attention

from _lm_common import with_gradients as _with_gradients
from _sdar_common import brute_force_visible

FLASH = sys.modules["deeplearning4j_tpu.ops.flash_attention"]

#: (L, block, block_q, block_k): equal and unequal tiles, tiles below, at
#: and above the block length, a block as long as the sequence
SHAPES = [(16, 4, 4, 4), (16, 4, 8, 4), (16, 4, 4, 8), (32, 4, 8, 8),
          (24, 4, 6, 12), (16, 2, 8, 8), (16, 16, 8, 4), (32, 8, 4, 16),
          (16, 4, 2, 2), (16, 4, 16, 16)]


class _OneHead:
    """`_Group` of one query head a key head."""
    n = 1
    step = staticmethod(lambda st: st)


# ------------------------------------------------------------- the geometry
@pytest.mark.parametrize("length,block,bq,bk", SHAPES)
def test_the_geometry_walks_exactly_the_tiles_with_a_visible_pair(
        length, block, bq, bk):
    """Both passes' step -> tile maps against the brute-force boolean:
    every tile with a visible pair is walked once and no other, a dead
    step's index map repeats a live tile, `interior` and `visible` are the
    boolean's, the counts and the grid's third axes follow."""
    nq, nk = 2 * length // bq, 2 * length // bk
    geom = FLASH._BlockDiffusion(block, bq, bk, nq, nk)
    seen = brute_force_visible(length, block)
    np.testing.assert_array_equal(
        block_diffusion_visible(jnp.arange(2 * length),
                                jnp.arange(2 * length), length, block), seen)
    tiles = seen.reshape(nq, bq, nk, bk).transpose(0, 2, 1, 3)
    has, full = tiles.any((2, 3)), tiles.all((2, 3))
    walked = np.zeros((nq, nk), int)
    for qi in range(nq):
        for st in range(geom.k_steps):
            kj = int(geom.k_tile(qi, st))
            if bool(geom.k_live(qi, st, kj)):
                walked[qi, kj] += 1
                assert int(geom.k_index(qi, st)) == kj
                assert bool(geom.interior(qi, kj)) == full[qi, kj]
                np.testing.assert_array_equal(geom.visible(qi, kj),
                                              tiles[qi, kj])
            else:
                assert has[qi, int(geom.k_index(qi, st))]
    np.testing.assert_array_equal(walked, has)
    walked[:] = 0
    for kj in range(nk):
        for st in range(geom.q_steps):
            qi = int(geom.q_tile(kj, st, _OneHead))
            if bool(geom.q_live(kj, st, _OneHead, qi)):
                walked[qi, kj] += 1
                assert int(geom.q_index(kj, st, _OneHead)) == qi
            else:
                assert has[int(geom.q_index(kj, st, _OneHead)), kj]
    np.testing.assert_array_equal(walked, has)
    interior, edge = geom.tile_counts()
    assert interior == full.sum()
    assert interior + edge == has.sum() == geom.tiles_with_a_pair()
    assert geom.k_steps == has.sum(1).max()
    assert geom.q_steps == has.sum(0).max()


def test_the_cells_geometry_is_288_tiles_of_1024():
    """L = 8,192 in blocks of 512, diffusion blocks of 4: 240 interior and
    48 edge tiles a (sequence, head), 17 steps a q tile and 32 a k tile;
    a causal walk over the 2L rows would take 528."""
    geom = FLASH._geometry(("block_diffusion", 4), 512, 512, 32, 32)
    assert geom.tile_counts() == (240, 48)
    assert geom.tiles_with_a_pair() == 288
    assert (geom.k_steps, geom.q_steps) == (17, 32)
    causal = FLASH._geometry(True, 512, 512, 32, 32)
    assert sum(causal.tile_counts()) == causal.tiles_with_a_pair() == 528
    length = 8192
    assert length * length + 4 * length == 67_141_632


# -------------------------------------------------- the kernels, interpreted
def _case(length, h, hk, d=16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed + length + h), 4)
    q = jax.random.normal(ks[0], (2, 2 * length, h, d)) * 0.7
    k = jax.random.normal(ks[1], (2, 2 * length, hk, d)) * 0.7
    v = jax.random.normal(ks[2], (2, 2 * length, hk, d))
    w = jax.random.normal(ks[3], (2, 2 * length, h, d))
    return q, k, v, w


def _dense(q, k, v, seen):
    group = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, group, 2), jnp.repeat(v, group, 2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    p = jax.nn.softmax(jnp.where(seen[None, None], s, -jnp.inf), -1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


@pytest.mark.parametrize("group", [1, 4, 8])
@pytest.mark.parametrize("length,block,bq,bk", [
    (16, 4, 8, 8), (32, 4, 8, 16), (32, 8, 16, 8), (24, 4, 12, 6),
    (16, 4, 16, 16)])
def test_the_kernels_forward_and_the_three_gradients(length, block, bq, bk,
                                                     group):
    """flash_fwd and the fused backward (interpreted; the pair of passes
    equals it bit for bit under the rule, tests/test_flash_attention.py)
    under the rule against plain softmax attention under the brute-force
    boolean, one, four and eight query heads a key head."""
    q, k, v, w = _case(length, 8, 8 // group)
    seen = jnp.asarray(brute_force_visible(length, block))
    run = lambda q, k, v: flash_attention(
        q, k, v, block_diffusion=block, block_q=bq, block_k=bk)
    out, got = _with_gradients(run, w, (q, k, v))
    ref, want = _with_gradients(lambda *a: _dense(*a, seen), w, (q, k, v))
    np.testing.assert_allclose(out, ref, atol=2e-6)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=1e-5)


def test_the_kernels_take_no_mask_operand_and_no_square_mask_is_built(
        flash_backward):
    """Under the rule the kernels (the forward and the fused backward, or
    the pair of passes of a call past the byte budget) take their six
    operands and nothing else, and the trace builds no array of 2L x 2L."""
    from test_flash_attention import _backward_kernels
    q, k, v, w = _case(64, 4, 1)
    jaxpr = jax.make_jaxpr(jax.grad(lambda *a: jnp.sum(flash_attention(
        *a, block_diffusion=4, block_q=16, block_k=16) * w), (0, 1, 2)))(
            q, k, v)
    operands, shapes = {}, []

    def walk(jp):
        for eqn in jp.eqns:
            if eqn.primitive.name == "pallas_call":
                operands[eqn.params["name"]] = len(eqn.invars)
                continue
            shapes.extend(getattr(o.aval, "shape", ()) for o in eqn.outvars)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jaxpr.jaxpr)
    assert operands == {"flash_fwd": 3,
                        **_backward_kernels(flash_backward, 6)}
    assert not [s for s in shapes if s[-2:] == (128, 128)]


@pytest.mark.parametrize("what,kwargs", [
    ("causal", dict(causal=True)),
    ("a key mask", dict(mask=jnp.ones((2, 32)))),
    ("a block that does not divide the half", dict(block_q=12)),
    ("a block length that does not divide the half", dict(block_diffusion=3)),
])
def test_the_kernels_wrapper_refuses_by_name(what, kwargs):
    q, k, v, _ = _case(16, 2, 2)
    with pytest.raises(ValueError, match="block_diffusion"):
        flash_attention(q, k, v, **{"block_diffusion": 4, "block_q": 8,
                                    "block_k": 8, **kwargs})


def test_the_gauges_follow_the_rule():
    """`flash_tile_share{kind}` and `flash_tiles_walked_over_live` of a
    traced call: at the cell's shapes 83.3 % interior and 1.0 (traced
    only: shapes, no arrays)."""
    q = jax.ShapeDtypeStruct((1, 16384, 8, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, 16384, 1, 128), jnp.bfloat16)
    jax.eval_shape(lambda q, k, v: flash_attention(
        q, k, v, block_diffusion=4, block_q=512, block_k=512), q, kv, kv)
    dump = monitor.dump()
    shares = {s["labels"]["kind"]: s["value"]
              for s in dump["flash_tile_share"]["series"]}
    assert shares == {"interior": pytest.approx(100 * 240 / 288),
                      "diagonal": pytest.approx(100 * 48 / 288),
                      "key_masked": 0.0}
    walked = dump["flash_tiles_walked_over_live"]["series"][0]["value"]
    assert walked == 1.0
    jax.eval_shape(lambda q, k, v: flash_attention(
        q, k, v, causal=True, block_q=512, block_k=512), q, kv, kv)
    assert monitor.dump()["flash_tiles_walked_over_live"]["series"][0][
        "value"] == 1.0


# ------------------------------------------------------------ the XLA paths
@pytest.mark.parametrize("block_size", [None, 8, 64])
def test_the_xla_path_computes_the_rule(block_size):
    """`block_diffusion_attention`, all queries at once and in blocks,
    forward and gradients, against the brute-force boolean."""
    q, k, v, w = _case(32, 4, 4)
    seen = jnp.asarray(brute_force_visible(32, 4))
    run = lambda q, k, v: block_diffusion_attention(
        q, k, v, block=4, block_size=block_size)
    out, got = _with_gradients(run, w, (q, k, v))
    ref, want = _with_gradients(lambda *a: _dense(*a, seen), w, (q, k, v))
    np.testing.assert_allclose(out, ref, atol=2e-6)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=1e-5)


# ------------------------------------------------------------------ the layer
def _layer(**over):
    return MultiHeadAttention(**{**dict(
        n_out=32, n_heads=8, n_kv_heads=2, head_dim=8, block_diffusion=4,
        use_rope=True, rope_base=100.0, qk_norm=True, norm_epsilon=1e-6,
        has_bias=False, attention_impl="flash", block_size=16,
        weight_init="normal"), **over})


@pytest.mark.parametrize("impl", ["dense", "blockwise", "flash", "kernel"])
def test_the_layers_paths_agree(impl, monkeypatch):
    """The layer under the rule by its dense, blockwise and flash
    implementations (off the TPU both XLA) and by the fused kernel
    (interpreted, the platform check patched): one number."""
    if impl == "kernel":
        attention = sys.modules["deeplearning4j_tpu.nn.layers.attention"]
        monkeypatch.setattr(attention, "is_tpu_backend", lambda: True)
    kind = InputType.recurrent(32, 64)
    want_layer = _layer(attention_impl="dense")
    params, state = want_layer.init(jax.random.PRNGKey(1), kind)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 64, 32))
    want, _ = want_layer.apply(params, state, x)
    layer = _layer(attention_impl="flash" if impl == "kernel" else impl)
    got, _ = layer.apply(params, state, x)
    np.testing.assert_allclose(got, want, atol=2e-6)


def test_rotary_positions_restart_at_the_clean_half(monkeypatch):
    """``pos(i) = i mod L``: q and k are both rotated at positions that
    count 0..L-1 in the noisy half and again in the clean half."""
    layer = _layer(attention_impl="dense")
    params, state = layer.init(jax.random.PRNGKey(1),
                               InputType.recurrent(32, 32))
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 32, 32))
    attention = sys.modules["deeplearning4j_tpu.nn.layers.attention"]
    seen, rope = [], attention.rope

    def spy(x, positions, *a, **k):
        seen.append(np.asarray(positions))
        return rope(x, positions, *a, **k)

    monkeypatch.setattr(attention, "rope", spy)
    layer.apply(params, state, x)
    assert len(seen) == 2
    for pos in seen:
        np.testing.assert_array_equal(
            pos.reshape(-1), np.concatenate([np.arange(16), np.arange(16)]))


@pytest.mark.parametrize("what", ["causal", "an indexer",
                                  "attention dropout", "a key mask",
                                  "a context-parallel axis",
                                  "twice a whole number of blocks"])
def test_the_layer_refuses_by_name(what):
    from deeplearning4j_tpu.nn.layers import LightningIndexer
    kind = InputType.recurrent(32, 32)
    over = {"causal": dict(causal=True),
            "an indexer": dict(causal=True, indexer=LightningIndexer(
                n_heads=2, head_dim=8, topk=4)),
            "attention dropout": dict(attention_dropout=0.1)}.get(what, {})
    layer = _layer(**over)
    if what == "twice a whole number of blocks":
        kind = InputType.recurrent(32, 36)
    if over or what == "twice a whole number of blocks":
        with pytest.raises(ValueError, match=what):
            layer.init(jax.random.PRNGKey(0), kind)
        return
    params, state = layer.init(jax.random.PRNGKey(0), kind)
    x = jnp.zeros((2, 32, 32))
    with pytest.raises(ValueError, match=what):
        if what == "a key mask":
            layer.apply(params, state, x, mask=jnp.ones((2, 32)))
        else:
            with context_parallel("seq"):
                layer.apply(params, state, x)


@pytest.mark.parametrize("impl", ["dense", "kernel"])
def test_no_leak_through_the_attention(impl, monkeypatch):
    """The answer does not leak: a loss over the noisy half's outputs has
    EXACTLY zero gradient with respect to the clean copy of each noisy
    row's own block (and of every later block), through the attention, by
    the XLA path and by the kernels alike."""
    if impl == "kernel":
        attention = sys.modules["deeplearning4j_tpu.nn.layers.attention"]
        monkeypatch.setattr(attention, "is_tpu_backend", lambda: True)
    layer = _layer(attention_impl="dense" if impl == "dense" else "flash")
    params, state = layer.init(jax.random.PRNGKey(1),
                               InputType.recurrent(32, 64))
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 64, 32))
    # the block's rows picked by a 0/1 weight: one program for the three
    grad = jax.jit(jax.grad(lambda x, picked: jnp.sum(jnp.square(
        layer.apply(params, state, x)[0]) * picked[None, :, None])))
    for blk in (0, 3, 7):
        rows = slice(4 * blk, 4 * blk + 4)
        g = grad(x, jnp.zeros(64).at[rows].set(1.0))
        clean, noisy = np.asarray(g[0, 32:]), np.asarray(g[0, :32])
        assert not np.any(clean[4 * blk:]), (impl, blk)
        assert blk == 0 or np.any(clean[:4 * blk])
        # and nothing of another noisy block
        assert np.any(noisy[rows])
        assert not np.any(np.delete(noisy, np.r_[rows], axis=0))


# ------------------------------------- a causal call lowers as it did before
class _ParentGeometry(FLASH._Geometry):
    """The geometry as the parent of the rule's PR wrote it into the
    index maps and kernels: ``minimum(kj, k_hi(qi))``, ``kj <=
    k_hi(qi)``, ``q_lo(kj) + step``, ``qi <= nq - 1``, the diagonal by
    ``qpos >= kpos``."""

    def k_tile(self, qi, st):
        return st

    def k_live(self, qi, st, kj):
        return kj <= self.k_hi(qi)

    def k_index(self, qi, st):
        return jnp.minimum(st, self.k_hi(qi))

    def q_tile(self, kj, st, grp):
        return self.q_lo(kj) + grp.step(st)

    def q_live(self, kj, st, grp, qi):
        return qi <= self.nq - 1

    def q_index(self, kj, st, grp):
        return jnp.minimum(self.q_lo(kj) + grp.step(st), self.nq - 1)

    def visible(self, qi, kj):
        qpos = qi * self.bq + jax.lax.broadcasted_iota(
            jnp.int32, (self.bq, self.bk), 0)
        kpos = kj * self.bk + jax.lax.broadcasted_iota(
            jnp.int32, (self.bq, self.bk), 1)
        return qpos >= kpos


@pytest.mark.parametrize("causal,hk,masked", [
    (True, 4, False), (True, 1, False), (False, 2, True), (True, 4, True)])
def test_a_causal_call_lowers_as_before_the_rule(causal, hk, masked,
                                                 flash_backward, monkeypatch):
    """A call that is causal, or has no rule, lowers for the TPU to the
    text of the parent's expressions (kernel bodies decoded, locations
    stripped), equal heads and grouped, with a key mask and without, its
    backward the one fused kernel or the pair of passes: the rule's
    interface adds no op to them. (Checked against the parent commit
    itself at the Kimi, GLM and LFM2 cells' shapes in PERF.md section 6,
    PR 40.) A call under the rule does lower to another text."""
    from jax import export
    from test_flash_attention import _without_locations
    q = jnp.zeros((2, 512, 4, 64), jnp.bfloat16)
    k = jnp.zeros((2, 512, hk, 64), jnp.bfloat16)
    mask = [jnp.ones((2, 512), jnp.float32)] if masked else []

    def text(**rule):
        def loss(q, k, v, *m):
            out, lse = flash_attention(
                q, k, v, mask=m[0] if m else None, block_q=128, block_k=128,
                interpret=False, return_lse=True, **rule)
            return jnp.sum(out.astype(jnp.float32) ** 2) + jnp.sum(lse)
        exported = export.export(jax.jit(jax.grad(loss, (0, 1, 2))),
                                 platforms=["tpu"])(q, k, k, *mask)
        return _without_locations(exported.mlir_module())

    now = text(causal=causal)
    assert now.count("tpu_custom_call") >= (
        2 if flash_backward == "fused" else 3)
    with monkeypatch.context() as patch:
        patch.setattr(FLASH, "_Geometry", _ParentGeometry)
        assert text(causal=causal) == now
    if not masked:
        assert text(block_diffusion=4) != now
