"""Kimi Delta Attention's chunk kernels (`ops/kda_chunk.py`) at shapes that
fit the TPU's tiling (widths of 128, chunks of 64): the Pallas forward and
backward with the hand-over between chunks inside them, interpreted on the
CPU, against the other executor of the same two tile functions (vmapped
under XLA, a `lax.scan` over the chunks, plain autodiff), and the tile
functions against the definitions they are arrangements of.
`tests/test_kimi_attention.py` holds the whole chunked recurrence (at small
widths, the XLA executor) to the token recurrence;
`tests/test_tpu_lowering.py` compiles the kernels for the v5e.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nn.layers import linear_attention
from deeplearning4j_tpu.ops import kda_chunk

from _lm_common import jit_unoptimised

D, CHUNK = 128, 64


#: the smallest tile with both halves of the tile algebra (two blocks of 16
#: positions). What a case asks of the COUNT of chunks or of the state
#: between them it asks at this size: a tile of 64, unrolled column by
#: column, takes four times as long to compile (ROADMAP D12)
SMALL = 32


def _tiles(t, decay, pairs=2, seed=0, chunk=CHUNK):
    """(M, N, C, .) tiles of ``pairs`` sequences of t positions as the
    layer hands them over: unit q (scaled) and k, g the running sum of
    log a inside a chunk, beta (M, N, 1, C); a tail that does not fill
    its chunk is the layer's padding (zeros, beta 0, no decay). Last, a
    state (M, d_k, d_v) for the first chunk to receive."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)
    chunks = lambda a: linear_attention._chunked(a, chunk)
    q = unit(jax.random.normal(ks[0], (pairs, t, D))) * D ** -0.5
    k = unit(jax.random.normal(ks[1], (pairs, t, D)))
    v = jax.random.normal(ks[2], (pairs, t, D))
    log_a = -decay * jnp.exp(jax.random.normal(ks[3], (pairs, t, D)) - 1)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (pairs, t, 1)))
    return (chunks(q), chunks(k), chunks(v),
            jnp.cumsum(chunks(log_a), axis=2),
            jnp.swapaxes(chunks(beta), 2, 3),
            jax.random.normal(ks[5], (pairs, D, D)))


def _kernels(mm):
    """The two kernels, interpreted."""
    kernels = kda_chunk._chunk_kernels
    return lambda *a: kernels(*a, mm, True)


@functools.lru_cache(maxsize=None)
def _with_gradients(mm, kernels):
    """args -> the outputs (o and the final state) of the kernels (or of
    the scan) and the gradient of a fixed weighting of both in every input,
    the first state among them: one forward and one backward, compiled as
    one program, and traced once for every case of the same shapes."""
    fn = _kernels(mm) if kernels \
        else lambda *a: kda_chunk._chunk_scan(*a, mm)

    def run(*args):
        out, pull = jax.vjp(fn, *args)
        return out, pull(tuple(
            jax.random.normal(jax.random.PRNGKey(20 + i), x.shape, x.dtype)
            for i, x in enumerate(out)))
    return jit_unoptimised(run)


def _same(got, want, tol, what):
    got = np.asarray(got.astype(jnp.float32))
    want = np.asarray(want.astype(jnp.float32))
    assert np.isfinite(got).all(), what
    assert np.abs(got - want).max() <= tol * max(np.abs(want).max(),
                                                 1e-30), what


def _kernels_against_the_scan(args, mm):
    out, grads = _with_gradients(mm, True)(*args)
    out_ref, grads_ref = _with_gradients(mm, False)(*args)
    assert [x.dtype for x in out] == [jnp.float32, jnp.float32]
    for name, a, b in zip(("o", "the final state"), out, out_ref):
        # one rounding to the products' dtype apart at the most
        _same(a, b, 1e-6 if mm == jnp.float32 else 4e-3, name)
    for name, a, b in zip(("q", "k", "v", "g", "beta", "the first state"),
                          grads, grads_ref):
        # in bf16 a product's operand may round the other way where the
        # float32 values differ in their last bit (a batched product
        # under XLA, one a tile in the kernel)
        _same(a, b, 1e-5 if mm == jnp.float32 else 1e-3,
              "gradient of " + name)


@pytest.mark.parametrize("decay", [0.05, 1.0, 300.0])
@pytest.mark.parametrize("mm", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_kernels_are_the_scan_over_chunks(mm, decay):
    """Forward and backward kernel with the hand-over inside, interpreted,
    against the `lax.scan` executor and its autodiff: o, the final state
    and the gradients in q, k, v, g, beta and a non-zero first state, from
    slow decay to one of e^-300 a step (no positive exponent: everything
    stays finite)."""
    _kernels_against_the_scan(_tiles(3 * CHUNK, decay), mm)


@pytest.mark.parametrize("t", [150, 7 * CHUNK - 3, 11 * CHUNK,
                               16 * CHUNK - 5])
def test_kernels_take_any_count_of_chunks_and_a_padded_tail(t):
    """3, 7, 11 and 16 chunks (a grid step takes the most chunks up to 8
    that divide the count: 3, 7, 1, 8, the forward kernel an even count
    two to a turn of its loop; the state crosses chunks of one grid step
    and grid steps), the last one padded by the layer: half of ``t``
    positions in chunks of `SMALL`, the same counts and the same tails."""
    args = _tiles(t // 2, 1.0, pairs=1, chunk=SMALL)
    assert args[0].shape[1] == -(-t // CHUNK)
    _kernels_against_the_scan(args, jnp.bfloat16)


def test_forward_rule_keeps_the_state_each_chunk_received():
    """What the backward kernel reads beside the inputs: chunk n's state
    is the final state of the first n chunks."""
    args = _tiles(3 * SMALL, 1.0, pairs=1, chunk=SMALL)
    o, end, states = kda_chunk._forward(*args, jnp.bfloat16, True, True)
    assert states.shape == (1, 3, D, D)
    upto = lambda n: kda_chunk._forward(
        *(a[:, :n] for a in args[:5]), args[5], jnp.bfloat16, True, False)
    np.testing.assert_array_equal(states[:, 0], args[5])
    np.testing.assert_array_equal(states[:, 1], upto(1)[1])
    np.testing.assert_array_equal(states[:, 2], upto(2)[1])
    np.testing.assert_array_equal(end, upto(3)[1])
    np.testing.assert_array_equal(o, upto(3)[0])


def test_two_calls_with_the_state_handed_over_are_one_call(monkeypatch):
    """`kda_chunked` by the kernels over 3 chunks at once, and over 1 then
    2 with the final state of the first call as the second's
    ``initial_state``: the same output and final state
    (`tests/test_kimi_attention.py` has the same of the XLA executor)."""
    interpreted = _kernels(jnp.float32)
    monkeypatch.setattr(kda_chunk, "is_tpu_backend", lambda: True)
    monkeypatch.setattr(kda_chunk, "_chunk_kernels",
                        lambda *a: interpreted(*a[:6]))
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    t, cut, h = 3 * SMALL, SMALL, 2
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)
    args = (unit(jax.random.normal(ks[0], (1, t, h, D))) * D ** -0.5,
            unit(jax.random.normal(ks[1], (1, t, h, D))),
            jax.random.normal(ks[2], (1, t, h, D)),
            -jnp.exp(jax.random.normal(ks[3], (1, t, h, D)) - 1),
            jax.nn.sigmoid(jax.random.normal(ks[4], (1, t, h))))
    o, s = linear_attention.kda_chunked(*args, chunk=SMALL)
    o1, s1 = linear_attention.kda_chunked(*(x[:, :cut] for x in args),
                                          chunk=SMALL)
    o2, s2 = linear_attention.kda_chunked(*(x[:, cut:] for x in args),
                                          chunk=SMALL, initial_state=s1)
    assert float(jnp.abs(s1).max()) > 0
    _same(jnp.concatenate([o1, o2], axis=1), o, 1e-6, "o")
    _same(s2, s, 1e-6, "the final state")


@pytest.mark.parametrize("decay", [0.05, 1.0, 300.0])
def test_hand_over_is_the_definition(decay):
    """One tile's hand-over against the module docstring's two lines
    written out in float32."""
    *tile, s = (a[0] for a in _tiles(CHUNK, decay, pairs=1))
    w, u0, q_in, k_out, a_qk = kda_chunk.chunk_tile(
        *(a[0] for a in tile), mm=jnp.float32)
    g_end = tile[3][0, -1:]
    u = u0 - w @ s
    want = (q_in @ s + a_qk @ u, jnp.exp(g_end).T * s + k_out.T @ u)
    got = kda_chunk.hand_over(s, w, u0, q_in, k_out, a_qk, g_end,
                              mm=jnp.float32)
    for name, a, b in zip(("o", "the next state"), got, want):
        _same(a, b, 2e-6, name)
    o, s_next = kda_chunk.chunk_step(*(a[0] for a in tile), s,
                                     mm=jnp.float32)
    np.testing.assert_array_equal(o, got[0])
    np.testing.assert_array_equal(s_next, got[1])


@pytest.mark.parametrize("decay", [0.05, 1.0, 300.0])
def test_tile_function_is_the_definition(decay):
    """One tile at the kernel's shapes against the module docstring's
    formulas written out: every pair's exponent taken directly (masked
    before it is taken), a triangular solve."""
    q, k, v, g, beta = (a[0, 0] for a in _tiles(CHUNK, decay, pairs=1)[:5])
    beta = beta[0][:, None]
    seen = jnp.tril(jnp.ones((CHUNK, CHUNK), bool))
    pair = jnp.exp(jnp.where(seen[..., None], g[:, None] - g[None], -jnp.inf))
    a_kk = jnp.tril(jnp.einsum("tc,ic,tic->ti", k, k, pair), -1)
    want_aqk = jnp.einsum("tc,ic,tic->ti", q, k, pair)
    sol = jax.scipy.linalg.solve_triangular(
        beta * a_kk + jnp.eye(CHUNK),
        jnp.concatenate([beta * k * jnp.exp(g), beta * v], axis=1),
        lower=True, unit_diagonal=True)
    want = (sol[:, :D], sol[:, D:], q * jnp.exp(g),
            k * jnp.exp(g[-1:] - g), want_aqk)
    got = kda_chunk.chunk_tile(q, k, v, g, beta.T, mm=jnp.float32)
    for name, a, b in zip(("w", "u0", "q_in", "k_out", "a_qk"), got, want):
        _same(a, b, 2e-6, name)


@pytest.mark.parametrize("on_tpu,dk,dv,chunk,kernels", [
    (True, 128, 128, 64, True), (True, 256, 128, 16, True),
    (False, 128, 128, 64, False), (True, 8, 8, 32, False),
    (True, 128, 64, 64, False), (True, 128, 128, 8, False)])
def test_the_executor_follows_platform_and_shapes(monkeypatch, on_tpu, dk,
                                                  dv, chunk, kernels):
    """The kernels on a TPU at widths of 128 and chunks of 16 positions or
    more; the two tile functions under XLA everywhere else."""
    called = []

    def kernel_path(q, k, v, g, beta, s0, mm, interpret):
        called.append(interpret)
        return kda_chunk._chunk_scan(q, k, v, g, beta, s0, mm)

    monkeypatch.setattr(kda_chunk, "is_tpu_backend", lambda: on_tpu)
    monkeypatch.setattr(kda_chunk, "_chunk_kernels", kernel_path)
    x = jnp.zeros((1, 2, chunk, dk))
    # traced, not run: the choice is made from the shapes
    o, s = jax.eval_shape(functools.partial(kda_chunk.chunk_scan,
                                            mm=jnp.float32),
                          x, x, jnp.zeros((1, 2, chunk, dv)), x,
                          jnp.zeros((1, 2, chunk, 1)),
                          jnp.zeros((1, dk, dv)))
    assert called == ([False] if kernels else [])
    assert o.shape == (1, 2, chunk, dv) and s.shape == (1, dk, dv)
