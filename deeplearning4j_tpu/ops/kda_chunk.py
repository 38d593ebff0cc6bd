"""Kimi Delta Attention's chunked recurrence as one Pallas kernel a pass.

The chunked gated delta rule (`nn/layers/linear_attention.py`, module
docstring) for one (sequence, head) pair and one chunk of C positions, with
``g`` the running sum of ``log a`` inside the chunk and ``S`` the state the
chunk receives::

    A[t,i]   = b_t sum_c k_tc k_ic exp(g_tc - g_ic)        (i <  t)
    Aqk[t,i] =     sum_c q_tc k_ic exp(g_tc - g_ic)        (i <= t)
    (I + A) [W | U0] = [b k exp(g) | b v]
    q_in = q exp(g);  k_out = k exp(g_C - g)
    U = U0 - W S;  O = q_in S + Aqk U
    S' = Diag(exp(g_C)) S + k_out^T U                      the hand-over

`chunk_tile` is the part that ``S`` does not enter, for ONE tile in plain
`jnp`, written once. No exponent is ever positive: a pair inside a block
of 16 positions takes ``exp(g_t - g_i)`` directly where i <= t, column by
column; a pair across blocks splits it at ``r``, g at the end of the block
before t's, into the two operands ``x_t exp(g_t - r)`` and
``k_i exp(r - g_i)`` of one matrix product a row block. The solve is a
substitution, fused with the loop that makes A's columns: inside the block
on the diagonal column by column on the VPU (row j of the solution is
final once columns 0..j-1 have been taken off it), the blocks left of it
by ONE float32 product a row block at `Precision.HIGHEST` (the MXU's
default for float32 is not float32); A is never assembled and nothing is
inverted. ``g``, every decay, every sum over channels, the solve and
``U0`` are float32; only the cross-block products take their operands in
the ``mm`` dtype; ``W``, ``q_in``, ``k_out`` and ``Aqk``, which the
hand-over reads only as operands of such products, leave in it.
`hand_over` is the last two lines for one tile (``S`` and ``U`` float32,
the four products' operands in the ``mm`` dtype), `chunk_step` the two
composed: ``(q, k, v, g, beta, S) -> (O, S')``.

`chunk_step_bwd` is `chunk_step`'s pull-back for one tile, written by hand
beside them: ``(q, k, v, g, beta, S, dO, dS') -> (dq, dk, dv, dg, dbeta,
dS)``. It makes the tile's forward again, with `chunk_tile` keeping
``[W | U0]``, A without its beta and each cross-block product's two
operands, and then takes three parts in this order. (1) The hand-over's
pull-back, eight products, each the transpose of one of the forward's
four: the operand in the ``mm`` dtype as the forward had it, the
cotangent and the sum float32, and what comes out, the cotangent OF an
``mm`` operand, rounded to ``mm`` (what autodiff does; the MXU's default
for a float32 operand is one bf16 pass)::

    dU = Aqk^T dO + k_out dS';  dAqk = dO U^T  (i <= t);  dq_in = dO S^T
    dk_out = U dS'^T;  dW = -dU S^T
    dS = q_in^T dO + Diag(exp(g_C)) dS' - W^T dU

and g_C's share ``exp(g_C) (.) rowsum(S (.) dS')``. (2) The solve's
pull-back, ONE transposed substitution ``(I + A)^T Y = [dW | dU]``, the
forward's arrangement mirrored: the blocks of rows from the last to the
first, the blocks right of the diagonal by one float32 product a block at
`Precision.HIGHEST`, inside the block on the diagonal row by row on the
VPU (row j is final once the rows after it have been taken off it);
nothing inverted. Then ``d rhs = Y`` and ``dA = -Y [W | U0]^T`` (i < t),
one float32 product. (3) The decayed products' pull-back: ``x_t exp(g_t -
g_i) k_i`` pulls back to two more such products over the SAME two
operands, across blocks the kept ones (products in the ``mm`` dtype),
inside a block column by column as the forward; its gradient in ``g`` is
``x (.) dx`` of the row side less ``k (.) dk`` of the column side, so no
exponential is differentiated and no exponent is positive here either (a
pair without decay, a position with itself or ``k_out``'s last row, stays
out of that difference: it would leave a rounding where nothing is).
``dbeta`` comes from ``rhs`` and A's rows. Every decay, every sum over
channels and the substitution are float32, as in the forward.

Two executors of the same two functions (`chunk_scan`). On a TPU, where
the shapes fit the tiling (key and value widths multiples of 128, the
chunk a multiple of 16), the kernel `kda_chunk_fwd` walks a pair's chunks
in order along a sequential axis of its grid, several chunks a grid step,
with ``S`` in VMEM scratch from the first chunk to the last: `chunk_step`
a tile, ``O`` and the final state written, nothing else (``W``, ``U0``,
``q_in``, ``k_out``, ``Aqk`` and ``U`` never reach HBM); as the forward
rule of the `custom_vjp` it also writes the state each chunk RECEIVED.
`kda_chunk_bwd` walks the same grid from the last chunk to the first with
the state's cotangent in scratch and runs `chunk_step_bwd` at the tile and
its received state, the tile's forward made again in VMEM: the residuals
are the inputs and those states. Elsewhere `chunk_tile` runs vmapped over
the tiles and `hand_over` vmapped over the pairs under a `lax.scan` over
the chunks, with plain autodiff: the tests' reference for both kernels.
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
#: x @ y
_NN = (((1,), (0,)), ((), ()))
#: contract the last axis of both operands: x @ y^T
_NT = (((1,), (1,)), ((), ()))
#: contract the first axis of both operands: x^T @ y
_TN = (((0,), (0,)), ((), ()))


def _exact(x, y, dims=_NN):
    """x @ y as a float32 product that stays float32 on the MXU."""
    return jax.lax.dot_general(x, y, dims,
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


def _rows(shape):
    """Each element's row."""
    return jax.lax.broadcasted_iota(jnp.int32, shape, 0)


def _lanes(shape):
    """Each element's column."""
    return jax.lax.broadcasted_iota(jnp.int32, shape, 1)


def _column(x):
    """A row (1, n) as a column (n, 1): the diagonal of an (n, n) matrix
    that holds the row."""
    n = x.shape[1]
    return jnp.sum(jnp.where(_rows((n, n)) == _lanes((n, n)), x, 0.0),
                   axis=1, keepdims=True)


def _row(x):
    """A column (n, 1) as a row (1, n): the diagonal of an (n, n) matrix
    that holds the column."""
    n = x.shape[0]
    return jnp.sum(jnp.where(_rows((n, n)) == _lanes((n, n)), x, 0.0),
                   axis=0, keepdims=True)


def chunk_tile(q, k, v, g, beta, *, mm, keep=False):
    """The chunk algebra of the module docstring for one tile: q, k, g
    (C, d_k) and v (C, d_v) float32, beta (1, C) float32, C a power of two
    -> ``w (C, d_k), u0 (C, d_v), q_in, k_out (C, d_k), a_qk (C, C)``;
    u0 float32, the others in the ``mm`` dtype. With ``keep``, those five
    and what `chunk_step_bwd` reads besides: ``sol`` = [W | U0] and ``kk``
    = A without its beta (C, C), float32, and ``blocks``, for each block
    of rows after the first its cross-block product's two operands with
    the decays that made them."""
    c, dk = k.shape
    f32 = g.dtype
    b = min(c, BLOCK)
    # x under `top` rows of zeros
    below = lambda x, top: jnp.concatenate(
        [jnp.zeros((top,) + x.shape[1:], f32), x], axis=0) if top else x
    beta = _column(beta)
    decay = jnp.exp(g)
    rhs = jnp.concatenate([beta * k * decay, beta * v], axis=1)
    sol, qk, kk, blocks = [], [], [], []
    for lo in range(0, c, b):
        k_p, q_p, g_p = k[lo:lo + b], q[lo:lo + b], g[lo:lo + b]
        beta_p, x_p = beta[lo:lo + b], rhs[lo:lo + b]
        if lo:
            # columns before this block of rows: split at g of the last
            # position before it; their rows of the solution are done
            ref = g[lo - 1:lo]
            later = jnp.exp(g_p - ref)
            rows = jnp.concatenate([k_p * later, q_p * later], axis=0)
            cols, earlier = k[:lo], jnp.exp(ref - g[:lo])
            cols = cols * earlier
            rows, cols = rows.astype(mm), cols.astype(mm)
            cross = jax.lax.dot_general(rows, cols, _NT,
                                        preferred_element_type=f32)
            x_p = x_p - _exact(beta_p * cross[:b],
                               jnp.concatenate(sol, axis=0))
            wide = lambda x: jnp.concatenate(
                [x, jnp.zeros((b, c - lo), f32)], axis=1)
            qk_p = wide(cross[b:])
            kk_p = wide(cross[:b]) if keep else None
            blocks.append((rows, cols, later, earlier))
        else:
            qk_p = kk_p = jnp.zeros((b, c), f32)
        for j in range(b):
            # column lo + j of the block on the diagonal, rows j and later
            # (from the group of 8 rows that holds row j on)
            top = j - j % 8
            k_s, q_s, g_s = k_p[top:], q_p[top:], g_p[top:]
            seen = _rows((b - top, dk)) >= j - top
            col = jnp.exp(jnp.where(seen, g_s - g_p[j:j + 1], NEG)) \
                * k_p[j:j + 1]
            qk_p = jnp.where(
                _lanes((b, c)) == lo + j,
                below(jnp.sum(q_s * col, axis=1, keepdims=True), top), qk_p)
            if j < b - 1:
                # row j of the solution is final: take A's column j
                # times it off the rows below
                under, beta_s = _rows((b - top, 1)) > j - top, beta_p[top:]
                kk_col = jnp.sum(k_s * col, axis=1, keepdims=True)
                a_col = jnp.where(under, beta_s * kk_col, 0.0)
                x_p = x_p - below(a_col * x_p[j:j + 1], top)
                if keep:
                    kk_p = jnp.where(
                        _lanes((b, c)) == lo + j,
                        below(jnp.where(under, kk_col, 0.0), top), kk_p)
        sol.append(x_p)
        qk.append(qk_p)
        kk.append(kk_p)
    sol, qk = jnp.concatenate(sol, axis=0), jnp.concatenate(qk, axis=0)
    kk = jnp.concatenate(kk, axis=0) if keep else None
    k_out = k * jnp.exp(g[c - 1:c] - g)
    tile = (sol[:, :dk].astype(mm), sol[:, dk:], (q * decay).astype(mm),
            k_out.astype(mm), qk.astype(mm))
    return (tile, sol, kk, blocks) if keep else tile


def hand_over(s, w, u0, q_in, k_out, a_qk, g_end, *, mm):
    """The hand-over of the module docstring for one tile: the state the
    chunk receives ``s (d_k, d_v)`` float32, `chunk_tile`'s five results
    and ``g_end (1, d_k)``, g at the chunk's last position -> ``o (C, d_v),
    s'``, both float32. ``s`` and ``U`` are float32; the four products
    take their operands in the ``mm`` dtype and accumulate in float32."""
    f32 = s.dtype

    def dot(x, y, dims=_NN):
        return jax.lax.dot_general(x.astype(mm), y.astype(mm), dims,
                                   preferred_element_type=f32)

    u = u0 - dot(w, s)
    o = dot(q_in, s) + dot(a_qk, u)
    return o, _column(jnp.exp(g_end)) * s + dot(k_out, u, _TN)


def chunk_step(q, k, v, g, beta, s, *, mm):
    """One chunk of one pair, whole: `chunk_tile` of the tile, then
    `hand_over` of the state ``s`` it receives -> ``o, s'``."""
    tile = chunk_tile(q, k, v, g, beta, mm=mm)
    return hand_over(s, *tile, g[-1:], mm=mm)


def chunk_step_bwd(q, k, v, g, beta, s, do, ds_next, *, mm):
    """The pull-back of `chunk_step` at one tile and the state ``s`` it
    received, on the cotangents ``do (C, d_v)`` of its output and
    ``ds_next (d_k, d_v)`` of the state it handed on -> ``dq, dk, dv, dg,
    dbeta (1, C), ds``, all float32: the forward made again, then the
    module docstring's three parts."""
    c, dk = k.shape
    f32 = g.dtype
    b = min(c, BLOCK)

    # a forward product's transpose: its operand in the mm dtype as it
    # was, the cotangent float32; what comes out is the cotangent OF an
    # operand in the mm dtype and is rounded to it, as autodiff rounds it
    def dot(x, y, dims=_NN):
        return jax.lax.dot_general(
            x, y, dims, preferred_element_type=f32).astype(mm).astype(f32)

    # x with `change` of its rows lo to hi in their place
    def on_rows(x, lo, hi, change):
        parts = [x[:lo], change(x[lo:hi]), x[hi:]]
        return jnp.concatenate([a for a in parts if a.shape[0]], axis=0)

    (w, u0, q_in, k_out, a_qk), sol, kk, blocks = chunk_tile(
        q, k, v, g, beta, mm=mm, keep=True)
    s_mm = s.astype(mm)
    u = u0 - jax.lax.dot_general(w, s_mm, _NN, preferred_element_type=f32)
    u_mm = u.astype(mm)
    beta = _column(beta)
    decay, at_end = jnp.exp(g), jnp.exp(g[c - 1:c])
    before = _lanes((c, c)) < _rows((c, c))

    # 1. the hand-over
    du = dot(a_qk, do, _TN) + dot(k_out, ds_next)
    d_aqk = dot(do, u_mm, _NT)
    dq_in, dw = dot(do, s_mm, _NT), dot(-du, s_mm, _NT)
    dk_out = dot(u_mm, ds_next, _NT) * jnp.exp(g[c - 1:c] - g)
    ds = _column(at_end) * ds_next + dot(q_in, do, _TN) + dot(w, -du, _TN)
    dg_end = at_end * _row(jnp.sum(s * ds_next, axis=1, keepdims=True))

    # 2. the solve: (I + A)^T Y = [dW | dU0], from the last block up
    a_t = (beta * kk).T
    y = []
    for lo in reversed(range(0, c, b)):
        x = jnp.concatenate([dw[lo:lo + b], du[lo:lo + b]], axis=1)
        if y:
            x = x - _exact(a_t[lo:lo + b, lo + b:], jnp.concatenate(y, axis=0))
        for j in reversed(range(1, b)):
            # row j of the solution is final: take A's row j times it off
            # the rows above (to the end of the group of 8 rows that
            # holds row j - 1)
            rows = min(b, j + -j % 8)
            step = a_t[lo:lo + rows, lo + j:lo + j + 1] * x[j:j + 1]
            x = on_rows(x, 0, rows, lambda x: x - step)
        y.insert(0, x)
    y = jnp.concatenate(y, axis=0)
    y_k, y_v = y[:, :dk], y[:, dk:]
    d_a = jnp.where(before, -_exact(y, sol, _NT), 0.0)
    d_kk = beta * d_a
    dk_rhs = y_k * decay
    dbeta = jnp.sum(dk_rhs * k, axis=1, keepdims=True) \
        + jnp.sum(y_v * v, axis=1, keepdims=True) \
        + jnp.sum(d_a * kk, axis=1, keepdims=True)

    # 3. the decayed products x_t exp(g_t - g_i) k_i: dq_row and dk_row
    # of their row side (x = q in Aqk, x = k in A), dk_col of their column
    # side. A position's pair with itself has no decay: it is left out of
    # the three (so of dg, where it would be one rounding of q k dAqk in
    # place of nothing) and added last
    d_self = jnp.sum(jnp.where(_lanes((c, c)) == _rows((c, c)), d_aqk, 0.0),
                     axis=1, keepdims=True)
    d_aqk = jnp.where(before, d_aqk, 0.0)
    dq_row, dk_row, dk_col = [], [], []
    for lo, block in zip(range(0, c, b), [None] + blocks):
        k_p, q_p, g_p = k[lo:lo + b], q[lo:lo + b], g[lo:lo + b]
        d_aqk_p, d_kk_p = d_aqk[lo:lo + b], d_kk[lo:lo + b]
        dq_p = dk_p = dc_p = jnp.zeros((b, dk), f32)
        if lo:
            rows, cols, later, earlier = block
            d_cross = jnp.concatenate([d_kk_p[:, :lo], d_aqk_p[:, :lo]],
                                      axis=0)
            d_rows = dot(d_cross, cols)
            dk_p, dq_p = d_rows[:b] * later, d_rows[b:] * later
            d_cols = dot(d_cross, rows, _TN) * earlier
            dk_col = [x + d_cols[at:at + b]
                      for at, x in zip(range(0, lo, b), dk_col)]
        for j in range(b - 1):
            # column lo + j of the block on the diagonal, rows after j
            # (from the group of 8 rows that holds row j + 1 on)
            top = j + 1 - (j + 1) % 8
            k_s, q_s, g_s = k_p[top:], q_p[top:], g_p[top:]
            after = _rows((b - top, dk)) > j - top
            e = jnp.exp(jnp.where(after, g_s - g_p[j:j + 1], NEG))
            col = e * k_p[j:j + 1]
            at = slice(lo + j, lo + j + 1)
            d_qk, d_k = d_aqk_p[top:, at], d_kk_p[top:, at]
            dq_p = on_rows(dq_p, top, b, lambda x: x + d_qk * col)
            dk_p = on_rows(dk_p, top, b, lambda x: x + d_k * col)
            d_col = jnp.sum((d_qk * q_s + d_k * k_s) * e, axis=0,
                            keepdims=True)
            dc_p = jnp.where(_rows((b, dk)) == j, d_col, dc_p)
        dq_row.append(dq_p)
        dk_row.append(dk_p)
        dk_col.append(dc_p)
    dq = dq_in * decay + jnp.concatenate(dq_row, axis=0)
    dk_row = beta * dk_rhs + jnp.concatenate(dk_row, axis=0)
    # k_out's last row has no decay either
    last = _rows((c, dk)) == c - 1
    dk_last = jnp.where(last, dk_out, 0.0)
    dk_out = dk_out - dk_last
    dk_col = jnp.concatenate(dk_col, axis=0) + dk_out
    # g's gradient: no exponential is differentiated
    dg = q * dq + k * (dk_row - dk_col)
    dg_end = dg_end + jnp.sum(k * dk_out, axis=0, keepdims=True)
    dg = jnp.where(last, dg + dg_end, dg)
    return (dq + d_self * k, dk_row + dk_col + dk_last + d_self * q,
            beta * y_v, dg, _row(dbeta), ds)


def _each_chunk(ref, one, by=1, reverse=False):
    """``one(i)`` for every chunk i of a grid step's block, in order (or
    from the last to the first), ``by`` to a turn of the loop where that
    divides the count."""
    n = ref.shape[1]
    by = by if n % by == 0 else 1

    def body(i, carry):
        for at in range(by):
            at = by * i + at
            one(n - 1 - at if reverse else at)
        return carry

    jax.lax.fori_loop(0, n // by, body, 0)


def _first_and_last():
    """Whether this is a pair's first grid step, and whether its last."""
    j = pl.program_id(1)
    return j == 0, j == pl.num_programs(1) - 1


def _fwd_kernel(*refs, mm, keep):
    """A grid step's chunks of one pair in order, the state in ``s_ref``
    (VMEM scratch) from chunk to chunk and from grid step to grid step.
    ``keep``: also write the state each chunk received."""
    ins, s0_ref, o_ref, end_ref = refs[:5], refs[5], refs[6], refs[7]
    states_ref, s_ref = refs[8] if keep else None, refs[-1]

    first, last = _first_and_last()

    @pl.when(first)
    def _():
        s_ref[...] = s0_ref[0]

    def one(i):
        s = s_ref[...]
        if keep:
            states_ref[0, i] = s
        o_ref[0, i], s_ref[...] = chunk_step(*(r[0, i] for r in ins), s,
                                            mm=mm)

    # two chunks a turn: the second's algebra, which no state enters, runs
    # beside the first's hand-over (on the v5e 1.19 -> 1.05 ms per 1,024
    # tiles; eight a turn hide the hand-over whole, 0.96, and take five
    # times as long to trace; the backward kernel LOSES 8 % with two a
    # turn, 3.01 against 2.78 ms: one)
    _each_chunk(o_ref, one, by=2)

    @pl.when(last)
    def _():
        end_ref[0] = s_ref[...]


def _bwd_kernel(*refs, mm):
    """The grid and a step's chunks walked from the last to the first,
    the state's cotangent in ``ds_ref`` (VMEM scratch): `chunk_step_bwd`
    at the tile and the state it received, on the chunk's ``do`` and the
    cotangent of the state it handed on."""
    ins, do_ref, dend_ref = refs[:6], refs[6], refs[7]
    grads, ds0_ref, ds_ref = refs[8:13], refs[13], refs[14]

    first, last = _first_and_last()

    @pl.when(first)
    def _():
        ds_ref[...] = dend_ref[0]

    def one(i):
        *tile, ds = chunk_step_bwd(*(r[0, i] for r in ins), do_ref[0, i],
                                   ds_ref[...], mm=mm)
        for ref, x in zip(grads, tile):
            ref[0, i] = x
        ds_ref[...] = ds

    _each_chunk(do_ref, one, reverse=True)

    @pl.when(last)
    def _():
        ds0_ref[0] = ds_ref[...]


#: chunks a grid step (the most that divide the sequence's chunks), so that
#: a tile does not pay a grid step's fixed cost; on the v5e 1, 4, 8 and 16
#: read the same, the tile's arithmetic being what takes the time
_CHUNKS_A_STEP = 8


def _over_chunks(kernel, name, arrays, out, reverse, interpret):
    """`kernel` over the pairs (parallel) and, one after another, the
    chunks of a pair (from the last where ``reverse``): grid (M, N / chunks
    a step). An operand or result of four axes ((M, N, ., .)) is cut by
    pair and chunk, one of three ((M, d_k, d_v): a state or its cotangent)
    by pair; the kernel's scratch is one state."""
    m, n = arrays[0].shape[:2]
    step = max(s for s in range(1, _CHUNKS_A_STEP + 1) if n % s == 0)
    last = n // step - 1
    chunk = lambda i, j: (i, last - j if reverse else j, 0, 0)

    def spec(a):
        if len(a.shape) == 3:
            return pl.BlockSpec((1,) + a.shape[1:], lambda i, j: (i, 0, 0))
        return pl.BlockSpec((1, step) + a.shape[2:], chunk)

    state = next(a for a in arrays if len(a.shape) == 3)
    return tuple(pl.pallas_call(
        kernel,
        grid=(m, n // step),
        in_specs=[spec(a) for a in arrays],
        out_specs=[spec(a) for a in out],
        out_shape=out,
        scratch_shapes=[pltpu.VMEM(state.shape[1:], state.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name=name,
    )(*arrays))


# jitted, so that the layers of a model share one trace of each kernel
@functools.partial(jax.jit, static_argnums=(6, 7, 8))
def _forward(q, k, v, g, beta, s0, mm, interpret, keep):
    like = jax.ShapeDtypeStruct
    states = [like(k.shape[:2] + s0.shape[1:], s0.dtype)] if keep else []
    return _over_chunks(
        functools.partial(_fwd_kernel, mm=mm, keep=keep), "kda_chunk_fwd",
        (q, k, v, g, beta, s0),
        [like(v.shape, v.dtype), like(s0.shape, s0.dtype)] + states,
        False, interpret)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _backward(res, cots, mm, interpret):
    return _over_chunks(
        functools.partial(_bwd_kernel, mm=mm), "kda_chunk_bwd", res + cots,
        [jax.ShapeDtypeStruct(a.shape, a.dtype)
         for a in res[:5] + cots[1:]], True, interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _chunk_kernels(q, k, v, g, beta, s0, mm, interpret):
    """`chunk_step` over the chunks of (M, N, C, .) tiles from the states
    s0 (M, d_k, d_v) by the two kernels; beta (M, N, 1, C). Returns
    (o (M, N, C, d_v), the final states)."""
    return _forward(q, k, v, g, beta, s0, mm, interpret, False)


def _chunk_kernels_fwd(q, k, v, g, beta, s0, mm, interpret):
    o, end, states = _forward(q, k, v, g, beta, s0, mm, interpret, True)
    return (o, end), (q, k, v, g, beta, states)


def _chunk_kernels_bwd(mm, interpret, res, cots):
    return _backward(res, tuple(cots), mm, interpret)


_chunk_kernels.defvjp(_chunk_kernels_fwd, _chunk_kernels_bwd)


def _chunk_scan(q, k, v, g, beta, s0, mm):
    """`_chunk_kernels` under XLA with plain autodiff: `chunk_tile` vmapped
    over every tile, then a `lax.scan` over the chunks that carries the
    pairs' states through `hand_over`."""
    tiles = jax.vmap(jax.vmap(functools.partial(chunk_tile, mm=mm)))(
        q, k, v, g, beta)
    pairs = jax.vmap(functools.partial(hand_over, mm=mm))

    def step(s, xs):
        o, s = pairs(s, *xs)
        return s, o

    s, o = jax.lax.scan(step, s0, tuple(
        jnp.moveaxis(x, 1, 0) for x in tiles + (g[:, :, -1:],)))
    return jnp.moveaxis(o, 0, 1), s


def chunk_scan(q, k, v, g, beta, s0, *, mm):
    """The chunked recurrence from the running sums on: q, k, g (M, N, C,
    d_k), v (M, N, C, d_v), beta (M, N, C, 1), s0 (M, d_k, d_v), all
    float32 -> ``(o (M, N, C, d_v), the final states)``. By the Pallas
    kernels on a TPU where the shapes fit its tiling, as XLA ops
    elsewhere."""
    c, dk, dv = k.shape[2], k.shape[3], v.shape[3]
    if c & (c - 1):
        raise ValueError(f"chunk {c} is not a power of two")
    beta = jnp.swapaxes(beta, 2, 3)                      # (M, N, 1, C)
    if is_tpu_backend() and not (dk % 128 or dv % 128 or c % BLOCK):
        return _chunk_kernels(q, k, v, g, beta, s0, mm, False)
    return _chunk_scan(q, k, v, g, beta, s0, mm)
