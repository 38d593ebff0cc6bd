"""`ops/mhc_mix.py`: the two sides of a multi-stream residual's sub-layer
(`pre`: RMS, the 24 columns, the three mappings with their Sinkhorn steps,
the weighted sum the sub-layer reads; `post`: the streams it leaves) and
their hand-written pull-backs, against the benchmark reference's
token-by-token mappings with a Python loop of Sinkhorn steps
(`benchmark/references/xing4.0-29b-a4b.py`, which imports nothing of the
program), compiled, forward and gradients."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.ops import mhc_mix

from _lm_common import _close, with_gradients
from _xing_common import CFG, REF

N, C, TOKENS = 4, 32, 96
MIX = mhc_mix.Mix(n=N, iters=20, eps=1e-6, rms_eps=1e-6,
                  clamp=(-30.0, 30.0))


def _mapping(seed=0, alpha=(0.7, 1.3, 0.9)):
    k = jax.random.split(jax.random.PRNGKey(seed), 3)
    return {"phi": jax.random.normal(k[0], (N * C, MIX.columns))
            / np.sqrt(N * C),
            "bias": 0.5 * jax.random.normal(k[1], (MIX.columns,))
            + jnp.concatenate([jnp.zeros(2 * N), 2.0 * jnp.eye(N).reshape(-1)]),
            "alpha": jnp.asarray(alpha, jnp.float32)}


def _streams(seed=1, tokens=TOKENS):
    return jax.random.normal(jax.random.PRNGKey(seed), (tokens, N, C)) \
        * jnp.asarray([1.0, 0.5, 2.0, 1.5])[None, :, None]


def _by_token(hc, x, fault=None):
    """The reference's mappings, one token at a time: (H_pre (T, n), H_post
    (T, n), H_res (T, n, n))."""
    return jax.vmap(lambda v: REF.mappings(CFG, hc, v, fault=fault))(x)


def _both_sides(hc, w, x, tokens=TOKENS, width=C):
    """The program's two sides around ``y = tanh(u) w``, and the mappings
    between them."""
    rows = x.reshape(tokens, N * width)
    u, h_pre, h_post, h_res, rows = mhc_mix.pre(
        rows, hc["phi"], hc["bias"], hc["alpha"], MIX)
    out = mhc_mix.post(rows, jnp.tanh(u.astype(jnp.float32)).astype(
        u.dtype) @ w, h_res, h_post, MIX)
    return out, (h_pre, h_post, h_res)


def _sub_layer(hc, w, x):
    return _both_sides(hc, w, x)[0]


def _ref_sub_layer(hc, w, x):
    h_pre, h_post, h_res = _by_token(hc, x)
    u = jnp.einsum("tj,tjc->tc", h_pre, x)
    y = jnp.tanh(u) @ w
    return (jnp.einsum("tij,tjc->tic", h_res, x)
            + h_post[..., None] * y[:, None, :]).reshape(TOKENS, N * C)


def test_the_mappings_are_the_references_token_by_token():
    hc, x = _mapping(), _streams()
    rows = x.reshape(TOKENS, N * C)
    u, h_pre, h_post, h_res, same = jax.jit(
        lambda r: mhc_mix.pre(r, hc["phi"], hc["bias"], hc["alpha"], MIX))(
            rows)
    np.testing.assert_array_equal(same, rows)
    want_pre, want_post, want_res = _by_token(hc, x)
    _close(h_pre.T, want_pre, 2e-6)
    _close(h_post.T, want_post, 2e-6)
    _close(jnp.moveaxis(h_res, -1, 0), want_res, 5e-6)
    _close(u, jnp.einsum("tj,tjc->tc", want_pre, x), 2e-6)
    assert h_pre.shape == (N, TOKENS) and h_res.shape == (N, N, TOKENS)
    assert float(h_post.max()) > 1.0 > float(h_pre.max())      # the 2


def test_both_sides_and_every_gradient_match_the_reference():
    """``X' = post(X, F(pre(X)))`` with a small F between the sides, and
    the gradient in the mappings' three leaves, in F's weight and in the
    streams: the hand-written pull-backs against autodiff of the
    token-by-token form."""
    hc, x = _mapping(), _streams()
    w = jax.random.normal(jax.random.PRNGKey(3), (C, C)) / np.sqrt(C)
    cot = jax.random.normal(jax.random.PRNGKey(4), (TOKENS, N * C))
    got, g = with_gradients(_sub_layer, cot, (hc, w, x))
    want, g_ref = with_gradients(_ref_sub_layer, cot, (hc, w, x))
    _close(got, want, 3e-6)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(g)[0],
                            jax.tree_util.tree_leaves(g_ref)):
        assert np.abs(np.asarray(a - b)).max() <= 1e-4 * max(
            np.abs(np.asarray(b)).max(), 1e-7), jax.tree_util.keystr(path)
    assert float(jnp.abs(g[0]["alpha"]).min()) > 1e-3


def test_sinkhorn_brings_the_sums_to_one_after_twenty_steps_not_after_one():
    """After the 20 steps every row of H_res sums to 1 within ``hc_eps``'s
    scale (the rows come last) and every column within what 20 steps leave
    of the start's imbalance; after ONE step the columns are far off. The
    gauge reads the same."""
    hc, x = _mapping(seed=5, alpha=(1.0, 1.0, 1.0)), _streams(seed=6)
    rows = x.reshape(TOKENS, N * C)
    sums = {}
    for iters in (1, 20):
        mix = dataclasses.replace(MIX, iters=iters)
        _, h_pre, _, h_res, _ = mhc_mix.pre(rows, hc["phi"], hc["bias"],
                                            hc["alpha"], mix)
        assert float(h_res.min()) > 0.0
        row = float(jnp.abs(jnp.sum(h_res, axis=1) - 1.0).max())
        column = float(jnp.abs(jnp.sum(h_res, axis=0) - 1.0).max())
        sums[iters] = (row, column)
        gap, entropy = mhc_mix.gauges(h_pre, h_res)
        np.testing.assert_allclose(gap, max(row, column), rtol=1e-6)
        assert 0.0 < float(entropy) <= np.log(N) + 1e-6
    assert sums[20][0] < 1e-5 and sums[1][0] < 1e-3
    assert sums[20][1] < 2e-2 and sums[1][1] > 10 * sums[20][1]
    # the reference's own loop, a step at a time, says the same
    _, _, one = _by_token(hc, x, fault="one_iteration")
    np.testing.assert_allclose(
        float(jnp.abs(jnp.sum(one, axis=1) - 1.0).max()), sums[1][1],
        rtol=1e-3)


def test_the_clamp_holds_the_exponent_and_stops_its_gradient():
    """Logits driven far past the clamp: H_res stays finite and positive
    and is what the reference's clipped form gives; a clamped logit hands
    no gradient on, so ``alpha[2]``'s is the reference's (that of the few
    logits still inside), and zero once every logit is outside."""
    hc, x = _mapping(alpha=(1.0, 1.0, 400.0)), _streams()
    rows = x.reshape(TOKENS, N * C)
    mix = dataclasses.replace(MIX, clamp=(-3.0, 3.0))
    cfg = {**CFG, "mhc_h_res_clamp_min": -3.0, "mhc_h_res_clamp_max": 3.0}
    h_res = mhc_mix.pre(rows, hc["phi"], hc["bias"], hc["alpha"], mix)[3]
    assert np.isfinite(np.asarray(h_res)).all() and float(h_res.min()) > 0
    by_token = lambda a: jax.vmap(lambda v: REF.mappings(
        cfg, {**hc, "alpha": a}, v))(x)[2]
    _close(jnp.moveaxis(h_res, -1, 0), by_token(hc["alpha"]), 1e-5)
    weigh = jax.random.normal(jax.random.PRNGKey(9), (N, N, TOKENS))
    grad = jax.grad(lambda a: jnp.sum(weigh * mhc_mix.pre(
        rows, hc["phi"], hc["bias"], a, mix)[3]))
    want = jax.grad(lambda a: jnp.sum(
        jnp.moveaxis(weigh, -1, 0) * by_token(a)))(hc["alpha"])
    np.testing.assert_allclose(grad(hc["alpha"])[2], want[2], rtol=1e-3,
                               atol=1e-9)
    tight = dataclasses.replace(MIX, clamp=(-1e-4, 1e-4))
    off = {**hc, "bias": jnp.ones_like(hc["bias"])}     # every logit > 1e-4
    assert float(jax.grad(lambda a: jnp.sum(weigh * mhc_mix.pre(
        rows, off["phi"], off["bias"], a, tight)[3]))(
            jnp.asarray([1.0, 1.0, 1e-3]))[2]) == 0.0


def test_the_mappings_stay_float32_under_a_bfloat16_compute_dtype():
    """bf16 streams and bf16 copies of the leaves, as the containers hand
    them over: the mappings come out float32 with their sums as close to 1
    as in float32, u and X' in bf16 near the float32 ones, and the
    gradients in the leaves' dtype."""
    hc, x = _mapping(), _streams()
    rows = x.reshape(TOKENS, N * C)
    bf = lambda t: jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16), t)
    u, h_pre, h_post, h_res, _ = mhc_mix.pre(
        bf(rows), *bf((hc["phi"], hc["bias"], hc["alpha"])), MIX)
    assert u.dtype == jnp.bfloat16
    assert {h_pre.dtype, h_post.dtype, h_res.dtype} == {jnp.dtype("float32")}
    assert float(jnp.abs(jnp.sum(h_res, axis=1) - 1.0).max()) < 1e-5
    u32, pre32, _, res32, _ = mhc_mix.pre(rows, hc["phi"], hc["bias"],
                                          hc["alpha"], MIX)
    _close(h_pre, pre32, 2e-2)
    _close(h_res, res32, 5e-2)
    _close(u.astype(jnp.float32), u32, 2e-2)
    out = mhc_mix.post(bf(rows), u, h_res, h_post, MIX)
    assert out.dtype == jnp.bfloat16
    g = jax.grad(lambda hc, r: jnp.sum(_sub_layer(
        hc, jnp.eye(C, dtype=jnp.bfloat16), r.reshape(TOKENS, N, C)).astype(
            jnp.float32) ** 2), argnums=(0, 1))(bf(hc), bf(rows))
    assert {a.dtype for a in jax.tree_util.tree_leaves(g)} \
        == {jnp.dtype("bfloat16")}
    g32 = jax.grad(lambda hc, r: jnp.sum(_sub_layer(
        hc, jnp.eye(C), r.reshape(TOKENS, N, C)) ** 2), argnums=(0, 1))(
            hc, rows)
    for a, b in zip(jax.tree_util.tree_leaves(g),
                    jax.tree_util.tree_leaves(g32)):
        _close(a.astype(jnp.float32), b, 6e-2)


@pytest.mark.parametrize("fault", ["static_mappings", "no_sinkhorn",
                                   "one_iteration", "rows_first",
                                   "post_without_2"])
def test_a_planted_fault_of_the_mappings_is_another_function(fault):
    """Each fault the benchmark plants in the mappings moves at least one
    of the three by far more than rounding where the 4 x 4 logits spread
    as the configuration's do (``a_res`` 4: the order of the two
    normalisations shows only in what 20 steps leave unconverged; at
    ``a_res`` 1 they converge and rows-first is the same matrix to
    2e-4)."""
    hc, x = _mapping(alpha=(0.7, 1.3, 4.0)), _streams()
    sound, bad = _by_token(hc, x), _by_token(hc, x, fault=fault)
    moved = max(float(jnp.abs(a - b).max()) for a, b in zip(sound, bad))
    assert moved > 1e-3, (fault, moved)


# ------------------------------------------------------------ the kernels
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 2e-2)])
def test_the_kernels_are_the_xla_sides_with_every_gradient(monkeypatch,
                                                           dtype, tol):
    """The four Pallas kernels, interpreted, at two tiles of tokens and
    streams one lane-tile wide, against the `jax.numpy` sides: both sides'
    results, the mappings, and the gradient in the streams, in F's weight
    and in the three leaves; in bfloat16 to rounding."""
    tokens, width = 256, 128
    k = jax.random.split(jax.random.PRNGKey(7), 4)
    hc = {"phi": jax.random.normal(k[0], (N * width, MIX.columns))
          / np.sqrt(N * width),
          "bias": jnp.concatenate([jnp.zeros(2 * N),
                                   2.0 * jnp.eye(N).reshape(-1)]),
          "alpha": jnp.asarray([1.0, 1.0, 4.0])}
    x = jax.random.normal(k[1], (tokens, N, width))
    w = jax.random.normal(k[2], (width, width)) / np.sqrt(width)
    cot = jax.random.normal(k[3], (tokens, N * width))
    cast = lambda t: jax.tree_util.tree_map(lambda a: a.astype(dtype), t)
    assert mhc_mix.kernels_take(x.reshape(tokens, -1), MIX)
    assert not mhc_mix.kernels_take(x[:100].reshape(100, -1), MIX)

    def run(how):
        monkeypatch.setattr(mhc_mix, "_executor", lambda x, mix: how)
        return with_gradients(
            lambda hc, w, x: _both_sides(hc, w, x, tokens, width),
            cot.astype(dtype), cast((hc, w, x)))

    (out, maps), grads = run("interpret")
    (want, want_maps), want_grads = run("xla")
    f32 = lambda a: np.asarray(a, np.float32)
    _close(f32(out), f32(want), tol)
    for a, b in zip(maps, want_maps):
        assert a.dtype == jnp.float32
        _close(a, b, tol)
    for (path, a), b in zip(
            jax.tree_util.tree_flatten_with_path(grads)[0],
            jax.tree_util.tree_leaves(want_grads)):
        assert a.dtype == b.dtype
        assert np.abs(f32(a) - f32(b)).max() <= 2 * tol * max(
            np.abs(f32(b)).max(), 1e-7), jax.tree_util.keystr(path)


def test_the_packed_mappings_are_the_mappings():
    """`_mapping_groups` on the kernels' packed rows (a group of 8 sublanes
    a mapping, the rows past n empty) is `mappings`, and a compiler that
    joins its divisions finds no 0 / 0 in the empty rows."""
    p = jax.random.normal(jax.random.PRNGKey(0), (MIX.columns, 128))
    hc = _mapping(alpha=(0.7, 1.3, 4.0))
    rows, _ = mhc_mix._columns_to_rows(MIX)
    assert [r for r in rows if r >= 0] == list(range(MIX.columns))
    at = jnp.asarray([max(r, 0) for r in rows])
    live = jnp.asarray([r >= 0 for r in rows], jnp.float32)[:, None]
    phi_t, scale, bias = mhc_mix._packed_leaves(
        hc["phi"], hc["bias"], hc["alpha"], jnp.float32, MIX)
    np.testing.assert_array_equal(phi_t[:4], hc["phi"].T[:4])
    assert not np.any(np.asarray(phi_t[4:8]))
    groups = jax.jit(lambda p: mhc_mix._mapping_groups(
        p, scale, bias, MIX))(p[at] * live)
    want = mhc_mix.mappings(p, hc["bias"], hc["alpha"], MIX)
    _close(groups[0][:N], want[0], 2e-6)
    _close(groups[1][:N], want[1], 2e-6)
    for i in range(N):
        _close(groups[2 + i][:N], want[2][i], 5e-6)
        assert not np.any(np.asarray(groups[2 + i][N:]))
    back = mhc_mix._unpacked(mhc_mix._packed(*want), N)
    for a, b in zip(back, want):
        np.testing.assert_array_equal(a, b)
