"""Kimi Delta Attention's chunk kernels (`ops/kda_chunk.py`) at shapes that
fit the TPU's tiling (widths of 128, chunks of 64): the Pallas forward and
backward, interpreted on the CPU, against the ONE tile function vmapped
under XLA with plain autodiff, and that function against the definition
it is an arrangement of. `tests/test_kimi_attention.py` holds the whole
chunked recurrence (at small widths, the vmapped executor) to the token
recurrence; `tests/test_tpu_lowering.py` compiles the kernels for the v5e.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nn.layers import linear_attention
from deeplearning4j_tpu.ops import kda_chunk

D, CHUNK = 128, 64
NAMES = ("w", "u0", "q_in", "k_out", "a_qk")


def _tiles(t, decay, pairs=2, seed=0):
    """(M, N, C, .) tiles of ``pairs`` sequences of t positions as the
    layer hands them over: unit q (scaled) and k, g the running sum of
    log a inside a chunk, beta (M, N, 1, C); a tail that does not fill
    its chunk is the layer's padding (zeros, beta 0, no decay)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)
    chunks = lambda a: linear_attention._chunked(a, CHUNK)
    q = unit(jax.random.normal(ks[0], (pairs, t, D))) * D ** -0.5
    k = unit(jax.random.normal(ks[1], (pairs, t, D)))
    v = jax.random.normal(ks[2], (pairs, t, D))
    log_a = -decay * jnp.exp(jax.random.normal(ks[3], (pairs, t, D)) - 1)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (pairs, t, 1)))
    return (chunks(q), chunks(k), chunks(v),
            jnp.cumsum(chunks(log_a), axis=2),
            jnp.swapaxes(chunks(beta), 2, 3))


def _vmapped(mm):
    return jax.vmap(jax.vmap(functools.partial(kda_chunk.chunk_tile, mm=mm)))


def _with_gradients(fn, args):
    """fn's five outputs and the gradient of a fixed weighting of them in
    every input."""
    out = fn(*args)
    ws = [jax.random.normal(jax.random.PRNGKey(20 + i), x.shape)
          for i, x in enumerate(out)]
    loss = lambda *a: sum(jnp.sum(x.astype(jnp.float32) * w)
                          for x, w in zip(fn(*a), ws))
    return out, jax.grad(loss, argnums=(0, 1, 2, 3, 4))(*args)


def _same(got, want, tol, what):
    got = np.asarray(got.astype(jnp.float32))
    want = np.asarray(want.astype(jnp.float32))
    assert np.isfinite(got).all(), what
    assert np.abs(got - want).max() <= tol * max(np.abs(want).max(),
                                                 1e-30), what


def _kernels_against_the_tile_function(args, mm):
    kernels = lambda *a: kda_chunk._chunk_kernels(*a, mm, True)
    out, grads = _with_gradients(kernels, args)
    out_ref, grads_ref = _with_gradients(_vmapped(mm), args)
    assert [x.dtype for x in out] == [mm, jnp.float32, mm, mm, mm]
    for name, a, b in zip(NAMES, out, out_ref):
        # one rounding to the products' dtype apart at the most
        _same(a, b, 1e-6 if mm == jnp.float32 else 4e-3, name)
    for name, a, b in zip(("q", "k", "v", "g", "beta"), grads, grads_ref):
        # in bf16 a product's operand may round the other way where the
        # float32 values differ in their last bit (a batched product
        # under XLA, one a tile in the kernel)
        _same(a, b, 1e-5 if mm == jnp.float32 else 1e-3,
              "gradient of " + name)


@pytest.mark.parametrize("decay", [0.05, 1.0, 300.0])
@pytest.mark.parametrize("mm", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_kernels_are_the_tile_function(mm, decay):
    """Forward and backward kernel, interpreted, against the vmapped tile
    function and its autodiff, from slow decay to one of e^-300 a step
    (no positive exponent: everything stays finite)."""
    _kernels_against_the_tile_function(_tiles(2 * CHUNK, decay), mm)


@pytest.mark.parametrize("t", [150, 7 * CHUNK - 3, 11 * CHUNK])
def test_kernels_take_any_count_of_chunks_and_a_padded_tail(t):
    """3, 7 and 11 chunks (a grid step takes the most chunks up to 8 that
    divide the count: 3, 7, 1), the last one padded by the layer."""
    _kernels_against_the_tile_function(_tiles(t, 1.0, pairs=1),
                                       jnp.bfloat16)


@pytest.mark.parametrize("decay", [0.05, 1.0, 300.0])
def test_tile_function_is_the_definition(decay):
    """One tile at the kernel's shapes against the module docstring's
    formulas written out: every pair's exponent taken directly (masked
    before it is taken), a triangular solve."""
    q, k, v, g, beta = (a[0, 0] for a in _tiles(CHUNK, decay, pairs=1))
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
    for name, a, b in zip(NAMES, got, want):
        _same(a, b, 2e-6, name)


@pytest.mark.parametrize("on_tpu,dk,dv,chunk,kernels", [
    (True, 128, 128, 64, True), (True, 256, 128, 16, True),
    (False, 128, 128, 64, False), (True, 8, 8, 32, False),
    (True, 128, 64, 64, False), (True, 128, 128, 8, False)])
def test_the_executor_follows_platform_and_shapes(monkeypatch, on_tpu, dk,
                                                  dv, chunk, kernels):
    """The kernels on a TPU at widths of 128 and chunks of 16 positions or
    more; the vmapped tile function everywhere else."""
    called = []

    def kernel_path(q, k, v, g, beta, mm, interpret):
        called.append(interpret)
        return _vmapped(mm)(q, k, v, g, beta)

    monkeypatch.setattr(kda_chunk, "is_tpu_backend", lambda: on_tpu)
    monkeypatch.setattr(kda_chunk, "_chunk_kernels", kernel_path)
    x = jnp.zeros((1, 2, chunk, dk))
    out = kda_chunk.chunk_algebra(
        x, x, jnp.zeros((1, 2, chunk, dv)), x, jnp.zeros((1, 2, chunk, 1)),
        mm=jnp.float32)
    assert called == ([False] if kernels else [])
    assert [a.shape[2:] for a in out] == [
        (chunk, dk), (chunk, dv), (chunk, dk), (chunk, dk), (chunk, chunk)]
