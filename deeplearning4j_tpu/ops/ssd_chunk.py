"""Mamba-2's state-space recurrence (SSD) in chunks, one Pallas kernel a pass.

Per head, with a state ``S`` (P x N), a scalar decay ``a_t = exp(dt_t A)``
and the group's ``B_t``, ``C_t`` (N) that its heads share::

    S_t = a_t S_{t-1} + dt_t x_t B_t^T
    y_t = S_t C_t

In a chunk of Q positions, with ``g`` the running sum of ``log a`` inside
the chunk (this position's included) and ``S`` the state the chunk
receives::

    L[t,s] = exp(g_t - g_s)                                  (s <= t)
    Y  = (L * (C B^T) * dt_s) X + Diag(exp(g)) C S^T
    S' = exp(g_Q) S + X^T Diag(exp(g_Q - g) dt) B            the hand-over

No exponent is ever positive: ``g`` falls along a chunk. ``C B^T`` does not
depend on the head: `chunk_step` makes it ONCE a chunk for all the heads of
a group, which is why a tile is a (sequence, group) pair's chunk and not a
(sequence, head) pair's. ``g``, ``dt``, every decay and the state are
float32; only the four matrix products (``C B^T``, the masked matrix times
``X``, ``C S^T``, the hand-over's) take their operands in the ``mm`` dtype
and accumulate in float32. The state is kept transposed, (N, P) a head.

Two executors of the one `chunk_step` (`chunk_scan`), as `ops/kda_chunk.py`
has them. On a TPU, where the shapes fit the tiling (the state width and the
chunk multiples of 128), the kernel `ssd_chunk_fwd` walks a pair's chunks in
order along a sequential axis of its grid with the heads' states in VMEM
scratch from the first chunk to the last; as the forward rule of the
`custom_vjp` it also writes the state each chunk RECEIVED. `ssd_chunk_bwd`
walks the same grid from the last chunk to the first with the states'
cotangent in scratch and runs `jax.vjp` of `chunk_step` at the tile and its
received state, making the tile's forward again in VMEM: the residuals are
the inputs and those states. Elsewhere `chunk_step` runs vmapped over the
pairs under a `lax.scan` over the chunks, with plain autodiff. The gauge
``ssd_scan_path`` says which a traced call takes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.util.platform import is_tpu_backend

#: x @ y
_NN = (((1,), (0,)), ((), ()))
#: contract the last axis of both operands: x @ y^T
_NT = (((1,), (1,)), ((), ()))
#: contract the first axis of both operands: x^T @ y
_TN = (((0,), (0,)), ((), ()))
#: what the kernels' blocks, double-buffered, and a tile's temporaries may
#: take of VMEM (the backward holds a tile's inputs, its received states
#: and every gradient at once: 14 MiB at 16 heads of 64 x 128)
_VMEM_LIMIT_BYTES = 64 << 20


def chunk_step(x, b, c, g, dt, s, *, mm):
    """One chunk of one (sequence, group) pair, all its heads: x (H, Q, P),
    b and c (Q, N), g and dt (H, 1, Q) float32 (g the running sum of
    ``log a`` inside the chunk), s (H, N, P) float32 the states the chunk
    receives -> ``y (H, Q, P), s'``, both float32."""
    f32 = g.dtype
    q = b.shape[0]

    def dot(x, y, dims=_NN):
        return jax.lax.dot_general(x.astype(mm), y.astype(mm), dims,
                                   preferred_element_type=f32)

    at = lambda axis: jax.lax.broadcasted_iota(jnp.int32, (q, q), axis)
    seen = (at(0) >= at(1)).astype(f32)
    eye = (at(0) == at(1)).astype(f32)
    # a row (1, Q) as a column (Q, 1): the diagonal of the (Q, Q) matrix
    # that holds the row
    column = lambda row: jnp.sum(row * eye, axis=1, keepdims=True)
    cb = dot(c, b, _NT)                                   # (Q, Q), once
    ys, states = [], []
    for h in range(x.shape[0]):
        g_row, dt_row = g[h], dt[h]                       # (1, Q)
        g_col = column(g_row)
        # g falls along the chunk: where s > t the difference is positive
        # and the pair is not seen; no exponent is positive
        decay = jnp.exp(jnp.minimum(g_col - g_row, 0.0)) * seen
        y = dot(decay * cb * dt_row, x[h]) + jnp.exp(g_col) * dot(c, s[h])
        g_end = g_row[:, q - 1:q]                          # (1, 1)
        into = column(jnp.exp(g_end - g_row) * dt_row)     # (Q, 1)
        ys.append(y)
        states.append(jnp.exp(g_end) * s[h] + dot(b.astype(f32) * into,
                                                  x[h], _TN))
    return jnp.stack(ys), jnp.stack(states)


def _first_and_last():
    """Whether this is a pair's first grid step, and whether its last."""
    j = pl.program_id(1)
    return j == 0, j == pl.num_programs(1) - 1


def _fwd_kernel(*refs, mm, keep):
    """One chunk of one pair a grid step, the heads' states in ``s_ref``
    (VMEM scratch) from grid step to grid step. ``keep``: also write the
    states the chunk received."""
    ins, s0_ref, y_ref, end_ref = refs[:5], refs[5], refs[6], refs[7]
    states_ref, s_ref = refs[8] if keep else None, refs[-1]
    first, last = _first_and_last()

    @pl.when(first)
    def _():
        s_ref[...] = s0_ref[0]

    s = s_ref[...]
    if keep:
        states_ref[0, 0] = s
    y_ref[0, 0], s_ref[...] = chunk_step(*(r[0, 0] for r in ins), s, mm=mm)

    @pl.when(last)
    def _():
        end_ref[0] = s_ref[...]


def _bwd_kernel(*refs, mm):
    """The grid walked from the last chunk to the first, the states'
    cotangent in ``ds_ref`` (VMEM scratch): `jax.vjp` of `chunk_step` at
    the tile and the states it received, on the chunk's ``dy`` and the
    cotangent of the states it handed on."""
    ins, dy_ref, dend_ref = refs[:6], refs[6], refs[7]
    grads, ds0_ref, ds_ref = refs[8:13], refs[13], refs[14]
    first, last = _first_and_last()

    @pl.when(first)
    def _():
        ds_ref[...] = dend_ref[0]

    _, pull = jax.vjp(functools.partial(chunk_step, mm=mm),
                      *(r[0, 0] for r in ins))
    *tile, ds = pull((dy_ref[0, 0], ds_ref[...]))
    for ref, x in zip(grads, tile):
        ref[0, 0] = x
    ds_ref[...] = ds

    @pl.when(last)
    def _():
        ds0_ref[0] = ds_ref[...]


def _over_chunks(kernel, name, arrays, out, by_pair, reverse, interpret):
    """`kernel` over the pairs (parallel) and, one after another, the
    chunks of a pair (from the last where ``reverse``): grid (M, N). The
    operands and results whose places ``by_pair`` names (a count into
    ``arrays + out``) are a pair's states or their cotangent, (M, H, N, P),
    cut by pair; every other one, (M, N, ...), by pair and chunk. The
    kernel's scratch is one pair's states."""
    m, n = arrays[0].shape[:2]
    every = list(arrays) + list(out)

    def spec(at):
        a = every[at]
        rest = (0,) * (len(a.shape) - 2)
        if at in by_pair:
            return pl.BlockSpec((1,) + tuple(a.shape[1:]),
                                lambda i, j: (i, 0) + rest)
        return pl.BlockSpec(
            (1, 1) + tuple(a.shape[2:]),
            lambda i, j: (i, n - 1 - j if reverse else j) + rest)

    state = every[by_pair[0]]
    return tuple(pl.pallas_call(
        kernel,
        grid=(m, n),
        in_specs=[spec(i) for i in range(len(arrays))],
        out_specs=[spec(len(arrays) + i) for i in range(len(out))],
        out_shape=out,
        scratch_shapes=[pltpu.VMEM(tuple(state.shape[1:]), state.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret,
        name=name,
    )(*arrays))


# jitted, so that the layers of a model share one trace of each kernel
@functools.partial(jax.jit, static_argnums=(6, 7, 8))
def _forward(x, b, c, g, dt, s0, mm, interpret, keep):
    like = jax.ShapeDtypeStruct
    states = [like(x.shape[:2] + s0.shape[1:], s0.dtype)] if keep else []
    return _over_chunks(
        functools.partial(_fwd_kernel, mm=mm, keep=keep), "ssd_chunk_fwd",
        (x, b, c, g, dt, s0),
        [like(x.shape, s0.dtype), like(s0.shape, s0.dtype)] + states,
        (5, 7), False, interpret)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _backward(res, cots, mm, interpret):
    return _over_chunks(
        functools.partial(_bwd_kernel, mm=mm), "ssd_chunk_bwd", res + cots,
        [jax.ShapeDtypeStruct(a.shape, a.dtype)
         for a in res[:5] + cots[1:]], (7, 13), True, interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def chunk_kernels(x, b, c, g, dt, s0, mm, interpret):
    """`chunk_step` over the chunks of M pairs by the two kernels: x (M,
    N, H, Q, P), b and c (M, N, Q, N_state), g and dt (M, N, H, 1, Q), s0
    (M, H, N_state, P). Returns (y (M, N, H, Q, P), the final states)."""
    return _forward(x, b, c, g, dt, s0, mm, interpret, False)


def _chunk_kernels_fwd(x, b, c, g, dt, s0, mm, interpret):
    y, end, states = _forward(x, b, c, g, dt, s0, mm, interpret, True)
    return (y, end), (x, b, c, g, dt, states)


def _chunk_kernels_bwd(mm, interpret, res, cots):
    return _backward(res, tuple(cots), mm, interpret)


chunk_kernels.defvjp(_chunk_kernels_fwd, _chunk_kernels_bwd)


def chunk_scan_xla(x, b, c, g, dt, s0, mm):
    """`chunk_kernels` under XLA with plain autodiff: `chunk_step` vmapped
    over the pairs under a `lax.scan` over the chunks."""
    pairs = jax.vmap(functools.partial(chunk_step, mm=mm))

    def step(s, xs):
        y, s = pairs(*xs, s)
        return s, y

    s, y = jax.lax.scan(step, s0, tuple(
        jnp.moveaxis(a, 1, 0) for a in (x, b, c, g, dt)))
    return jnp.moveaxis(y, 0, 1), s


def _publish_path(kernel):
    """The gauge ``ssd_scan_path``, set where the call is traced."""
    from deeplearning4j_tpu.monitor import metrics
    metrics.gauge(
        "ssd_scan_path",
        "What the state-space recurrence traced last lowered to: 1 the "
        "Pallas kernels ssd_chunk_fwd / ssd_chunk_bwd (the state in VMEM "
        "from chunk to chunk), 0 the XLA path (a lax.scan over the chunks)"
    ).set(1 if kernel else 0)


def chunk_scan(x, b, c, g, dt, s0, *, mm):
    """The chunked recurrence from the running sums on, in the tiles'
    layout (`chunk_kernels`): ``(y, the final states)``, float32. By the
    Pallas kernels on a TPU where the shapes fit its tiling, as XLA ops
    elsewhere."""
    q, n = b.shape[2], b.shape[3]
    kernel = is_tpu_backend() and not (q % 128 or n % 128)
    _publish_path(kernel)
    if kernel:
        return chunk_kernels(x, b, c, g, dt, s0, mm, False)
    return chunk_scan_xla(x, b, c, g, dt, s0, mm)


def ssd_chunked(x, dt, a, b, c, *, chunk=128, initial_state=None,
                mm_dtype=None):
    """The recurrence of the module docstring over whole sequences.

    x: (B, T, H, P); dt: (B, T, H) > 0, the step sizes; a: (H,) < 0, so that
    ``log a_t = dt_t * a``; b, c: (B, T, G, N), head h reading group ``h //
    (H / G)``. Returns ``(y (B, T, H, P), final state (B, H, P, N))``, both
    float32. ``T`` need not divide by ``chunk``: the tail is padded with
    positions that leave the state as it is. ``mm_dtype``: the dtype the
    matrix products take their operands in (None: float32)."""
    bsz, t, h, p = x.shape
    groups, n = b.shape[2], b.shape[3]
    if h % groups:
        raise ValueError(f"{groups} groups do not divide {h} heads")
    per = h // groups
    f32 = jnp.promote_types(jnp.float32, dt.dtype)
    mm = mm_dtype or f32
    pad = (-t) % chunk
    padded = lambda v: jnp.pad(
        v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2)) if pad else v
    chunks = (t + pad) // chunk
    dt = padded(dt.astype(f32))
    # (B, T, H) -> (B * G, chunks, H / G, 1, Q)
    rows = lambda v: v.reshape(bsz, chunks, chunk, groups, per).transpose(
        0, 3, 1, 4, 2).reshape(bsz * groups, chunks, per, 1, chunk)
    g = jnp.cumsum((dt * a.astype(f32)).reshape(bsz, chunks, chunk, h),
                   axis=2).reshape(dt.shape)
    xs = padded(x).reshape(bsz, chunks, chunk, groups, per, p).transpose(
        0, 3, 1, 4, 2, 5).reshape(bsz * groups, chunks, per, chunk, p)
    of_group = lambda v: padded(v).reshape(
        bsz, chunks, chunk, groups, n).transpose(0, 3, 1, 2, 4).reshape(
        bsz * groups, chunks, chunk, n)
    s0 = jnp.zeros((bsz * groups, per, n, p), f32) if initial_state is None \
        else jnp.swapaxes(initial_state.astype(f32), 2, 3).reshape(
            bsz * groups, per, n, p)
    y, s = chunk_scan(xs.astype(mm), of_group(b).astype(mm),
                      of_group(c).astype(mm), rows(g), rows(dt), s0, mm=mm)
    y = y.reshape(bsz, groups, chunks, per, chunk, p).transpose(
        0, 2, 4, 1, 3, 5).reshape(bsz, chunks * chunk, h, p)[:, :t]
    return y, jnp.swapaxes(s.reshape(bsz, h, n, p), 2, 3)
