"""Kimi Delta Attention's chunk algebra as one Pallas kernel a tile.

The part of the chunked gated delta rule that does not depend on the
carried state (`nn/layers/linear_attention.py`, module docstring): for one
(sequence, head) pair and one chunk of C positions, with ``g`` the running
sum of ``log a`` inside the chunk::

    A[t,i]   = b_t sum_c k_tc k_ic exp(g_tc - g_ic)        (i <  t)
    Aqk[t,i] =     sum_c q_tc k_ic exp(g_tc - g_ic)        (i <= t)
    (I + A) [W | U0] = [b k exp(g) | b v]
    q_in = q exp(g);  k_out = k exp(g_C - g)

`chunk_tile` is that algebra for ONE tile in plain `jnp`, written once.
No exponent is ever positive: a pair inside a block of 16 positions takes
``exp(g_t - g_i)`` directly where i <= t, column by column; a pair across
blocks splits it at ``r``, g at the end of the block before t's, into the
two operands ``x_t exp(g_t - r)`` and ``k_i exp(r - g_i)`` of one matrix
product a row block. The solve is a substitution, fused with the loop that
makes A's columns: inside the block on the diagonal column by column on
the VPU (row j of the solution is final once columns 0..j-1 have been
taken off it), the blocks left of it by ONE float32 product a row block at
`Precision.HIGHEST` (the MXU's default for float32 is not float32); A is
never assembled and nothing is inverted. ``g``, every decay, every sum
over channels, the solve and ``U0`` are float32; only the cross-block
products take their operands in the ``mm`` dtype; ``W``, ``q_in``,
``k_out`` and ``Aqk``, which the scan over chunks reads only as operands
of such products, leave in it.

Two executors of the same function (`chunk_algebra`): on a TPU, where the
shapes fit the tiling (key and value widths multiples of 128, the chunk a
multiple of 16), the kernel `kda_chunk_fwd` runs it on tiles held in VMEM,
several chunks a grid step, and `kda_chunk_bwd` runs its `jax.vjp` on the
tiles and their cotangents, making the tile's forward again in VMEM: the
residuals are the inputs and nothing else. Elsewhere it runs vmapped over
the tiles under XLA with plain autodiff.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.util.platform import is_tpu_backend

NEG = -1e30
#: the positions of a block whose pairs take their decay pair by pair
BLOCK = 16
#: contract the last axis of both operands: x @ y^T
_NT = (((1,), (1,)), ((), ()))


def _exact(x, y):
    """x @ y as a float32 product that stays float32 on the MXU."""
    return jax.lax.dot_general(x, y, (((1,), (0,)), ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


def chunk_tile(q, k, v, g, beta, *, mm):
    """The chunk algebra of the module docstring for one tile: q, k, g
    (C, d_k) and v (C, d_v) float32, beta (1, C) float32, C a power of two
    -> ``w (C, d_k), u0 (C, d_v), q_in, k_out (C, d_k), a_qk (C, C)``;
    u0 float32, the others in the ``mm`` dtype."""
    c, dk = k.shape
    f32 = g.dtype
    b = min(c, BLOCK)
    row = lambda shape: jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    lane = lambda shape: jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    # x under `top` rows of zeros
    below = lambda x, top: jnp.concatenate(
        [jnp.zeros((top,) + x.shape[1:], f32), x], axis=0) if top else x
    # beta as a column: the diagonal of a (C, C) matrix that holds the row
    beta = jnp.sum(jnp.where(row((c, c)) == lane((c, c)), beta, 0.0),
                   axis=1, keepdims=True)
    decay = jnp.exp(g)
    rhs = jnp.concatenate([beta * k * decay, beta * v], axis=1)
    sol, qk = [], []
    for lo in range(0, c, b):
        k_p, q_p, g_p = k[lo:lo + b], q[lo:lo + b], g[lo:lo + b]
        beta_p, x_p = beta[lo:lo + b], rhs[lo:lo + b]
        if lo:
            # columns before this block of rows: split at g of the last
            # position before it; their rows of the solution are done
            ref = g[lo - 1:lo]
            later = jnp.exp(g_p - ref)
            rows = jnp.concatenate([k_p * later, q_p * later], axis=0)
            cols = k[:lo] * jnp.exp(ref - g[:lo])
            cross = jax.lax.dot_general(rows.astype(mm), cols.astype(mm),
                                        _NT, preferred_element_type=f32)
            x_p = x_p - _exact(beta_p * cross[:b],
                               jnp.concatenate(sol, axis=0))
            qk_p = jnp.concatenate(
                [cross[b:], jnp.zeros((b, c - lo), f32)], axis=1)
        else:
            qk_p = jnp.zeros((b, c), f32)
        for j in range(b):
            # column lo + j of the block on the diagonal, rows j and later
            # (from the group of 8 rows that holds row j on)
            top = j - j % 8
            k_s, q_s, g_s = k_p[top:], q_p[top:], g_p[top:]
            seen = row((b - top, dk)) >= j - top
            col = jnp.exp(jnp.where(seen, g_s - g_p[j:j + 1], NEG)) \
                * k_p[j:j + 1]
            qk_p = jnp.where(
                lane((b, c)) == lo + j,
                below(jnp.sum(q_s * col, axis=1, keepdims=True), top), qk_p)
            if j < b - 1:
                # row j of the solution is final: take A's column j
                # times it off the rows below
                a_col = jnp.where(
                    row((b - top, 1)) > j - top,
                    beta_p[top:] * jnp.sum(k_s * col, axis=1, keepdims=True),
                    0.0)
                x_p = x_p - below(a_col * x_p[j:j + 1], top)
        sol.append(x_p)
        qk.append(qk_p)
    sol, qk = jnp.concatenate(sol, axis=0), jnp.concatenate(qk, axis=0)
    k_out = k * jnp.exp(g[c - 1:c] - g)
    return (sol[:, :dk].astype(mm), sol[:, dk:], (q * decay).astype(mm),
            k_out.astype(mm), qk.astype(mm))


def _each_chunk(refs, one):
    """``one(i)`` for every chunk i of a grid step's block."""
    def body(i, carry):
        one(i)
        return carry

    jax.lax.fori_loop(0, refs[0].shape[1], body, 0)


def _fwd_kernel(*refs, mm):
    ins, outs = refs[:5], refs[5:]

    def one(i):
        tile = chunk_tile(*(r[0, i] for r in ins), mm=mm)
        for ref, x in zip(outs, tile):
            ref[0, i] = x

    _each_chunk(refs, one)


def _bwd_kernel(*refs, mm):
    ins, cots, grads = refs[:5], refs[5:10], refs[10:]

    def one(i):
        _, pull = jax.vjp(functools.partial(chunk_tile, mm=mm),
                          *(r[0, i] for r in ins))
        for ref, x in zip(grads, pull(tuple(r[0, i] for r in cots))):
            ref[0, i] = x

    _each_chunk(refs, one)


#: chunks a grid step (the most that divide the sequence's chunks), so that
#: a tile does not pay a grid step's fixed cost; on the v5e 1, 4, 8 and 16
#: read the same, the tile's arithmetic being what takes the time
_CHUNKS_A_STEP = 8


def _over_tiles(kernel, name, arrays, out, mm, interpret):
    """`kernel` over the tiles of ``arrays`` ((M, N, C, .) each) into
    arrays of the shapes and dtypes ``out``: grid (M, N / chunks a step),
    every operand and result cut the same way."""
    m, n = arrays[0].shape[:2]
    step = max(s for s in range(1, _CHUNKS_A_STEP + 1) if n % s == 0)
    spec = lambda a: pl.BlockSpec((1, step) + a.shape[2:],
                                  lambda i, j: (i, j, 0, 0))
    return tuple(pl.pallas_call(
        functools.partial(kernel, mm=mm),
        grid=(m, n // step),
        in_specs=[spec(a) for a in arrays],
        out_specs=[spec(a) for a in out],
        out_shape=out,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name=name,
    )(*arrays))


# jitted, so that the layers of a model share one trace of each kernel
@functools.partial(jax.jit, static_argnums=(5, 6))
def _forward(q, k, v, g, beta, mm, interpret):
    like = jax.ShapeDtypeStruct
    return _over_tiles(
        _fwd_kernel, "kda_chunk_fwd", (q, k, v, g, beta),
        [like(k.shape, mm), like(v.shape, v.dtype), like(q.shape, mm),
         like(k.shape, mm), like(k.shape[:3] + k.shape[2:3], mm)],
        mm, interpret)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _backward(res, cots, mm, interpret):
    return _over_tiles(
        _bwd_kernel, "kda_chunk_bwd", res + cots,
        [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in res], mm, interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _chunk_kernels(q, k, v, g, beta, mm, interpret):
    """`chunk_tile` over (M, N, C, .) tiles by the two kernels; beta
    (M, N, 1, C)."""
    return _forward(q, k, v, g, beta, mm, interpret)


def _chunk_kernels_fwd(q, k, v, g, beta, mm, interpret):
    return _forward(q, k, v, g, beta, mm, interpret), (q, k, v, g, beta)


def _chunk_kernels_bwd(mm, interpret, res, cots):
    return _backward(res, tuple(cots), mm, interpret)


_chunk_kernels.defvjp(_chunk_kernels_fwd, _chunk_kernels_bwd)


def chunk_algebra(q, k, v, g, beta, *, mm):
    """`chunk_tile` over every tile: q, k, g (M, N, C, d_k), v (M, N, C,
    d_v), beta (M, N, C, 1), all float32 -> ``w, u0, q_in, k_out, a_qk``
    with the same leading axes. By the Pallas kernels on a TPU where the
    shapes fit its tiling, vmapped under XLA elsewhere."""
    c, dk, dv = k.shape[2], k.shape[3], v.shape[3]
    if c & (c - 1):
        raise ValueError(f"chunk {c} is not a power of two")
    beta = jnp.swapaxes(beta, 2, 3)                      # (M, N, 1, C)
    if is_tpu_backend() and not (dk % 128 or dv % 128 or c % BLOCK):
        return _chunk_kernels(q, k, v, g, beta, mm, False)
    return jax.vmap(jax.vmap(functools.partial(chunk_tile, mm=mm)))(
        q, k, v, g, beta)
