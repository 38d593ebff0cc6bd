"""The residual mappings of manifold-constrained hyper-connections (mHC,
arXiv 2512.24880 over hyper-connections, arXiv 2409.19606): a residual path
of ``n`` streams that every sub-layer reads and writes through three
learned, input-dependent mappings.

For one token with streams ``X`` (n x C), ``v = vec(X)`` (stream 0 first)::

    p = (v / sqrt(mean(v^2) + rms_eps)) phi        (n + n + n^2 columns)
    H_pre  = sigmoid(a_pre p_pre + b_pre)                          (n)
    H_post = 2 sigmoid(a_post p_post + b_post)                     (n)
    M = exp(clip(a_res mat(p_res) + b_res, lo, hi))                (n x n)
    iters times: M <- M / (column sums + eps); M <- M / (row sums + eps)
    u = sum_j H_pre[j] X[j]                       what the sub-layer reads
    X'[i] = sum_j M[i, j] X[j] + H_post[i] y      what it leaves, y = F(u)

Two sides, one function each, used by the layer everywhere: `pre` (the RMS
of the 14,336-wide row, the 24-column product, the three mappings, ``u``)
and `post` (``X'`` from ``X``, ``y`` and the mappings). Both take the
streams as rows ``(N, n C)`` and keep every mapping with the TOKENS ON THE
LAST AXIS (``(n, N)``, ``(n, n, N)``): a 4 x 4 matrix a token is sixteen
rows of tokens, so the Sinkhorn steps are whole-row arithmetic on the
lanes and no 4 x 4 tile is ever laid out. The mappings, the RMS and the
Sinkhorn steps are float32 whatever the streams' dtype; the products (the
24 columns, and in the backward the two against them) take the streams'
dtype's operands and accumulate in float32, as do the weighted sums over
the streams.

Each side has its pull-back written out (`jax.custom_vjp`): what is kept
for the backward is the side's inputs and the 24 columns a token, never a
float32 copy of the streams. `pre` hands the streams back as it took them
and `post` takes them from there, so that the writing side's cotangent for
the streams arrives in the reading side's pull-back and is added where the
streams' cotangent is made.

Two executors of each side and of each pull-back, the same arithmetic in
both. On a TPU, where the shapes fit the tiling (`kernels_take`: whole tiles
of `_TILE` tokens, a stream's width whole lanes), four Pallas kernels over
tiles of tokens, ``mhc_pre_fwd``, ``mhc_post_fwd``, ``mhc_post_bwd``,
``mhc_pre_bwd``: a tile of the streams is read from HBM ONCE a kernel and
everything made from it (the RMS, the columns, the mappings and their forty
normalisations, ``u``; the sixteen sums over the width that are H_res's
cotangent; the products against the columns' cotangent) is made while it is
in VMEM. There the mappings travel as one array of 8 (2 + n) rows of tokens
(`_packed`: a group of 8 sublanes for H_pre, H_post and each row of H_res),
a row of tokens is turned into a column against the streams' rows on the
diagonal of a (tile, tile) matrix (`_turns`), and the mappings' pull-back is
`jax.vjp` of `_mapping_groups` at the kept columns inside the kernel.
Elsewhere the same sides as `jax.numpy` on the whole arrays, which XLA
fuses as it can: the reading side walks the streams twice forward and three
times backward, the writing side's backward once a sum (PERF.md section
5). The mappings' pull-back is then `jax.vjp` of `mappings`, whose Sinkhorn
steps are a rolled loop.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.util.platform import is_tpu_backend

#: contract the first axis of x with the last of y: x^T @ y^T
_TT = (((0,), (1,)), ((), ()))
#: tokens a grid step of the kernels, and the rows a mapping's group takes
#: in their packed form (a float32 tile's sublanes)
_TILE, _GROUP = 128, 8
#: what a kernel's blocks, double-buffered, and a tile's float32 streams
#: may take of VMEM
_VMEM_LIMIT_BYTES = 96 << 20


@dataclasses.dataclass(frozen=True)
class Mix:
    """What the mappings of one model are made with (static)."""
    n: int = 4
    iters: int = 20
    eps: float = 1e-6
    rms_eps: float = 1e-6
    clamp: Tuple[float, float] = (-30.0, 30.0)

    @property
    def columns(self) -> int:
        return self.n + self.n + self.n * self.n


def mappings(p, bias, alpha, mix: Mix):
    """The three mappings of N tokens from their columns p (columns, N),
    float32, tokens on the last axis: ``(H_pre (n, N), H_post (n, N),
    H_res (n, n, N))``; ``bias`` (columns,) lies [pre | post | res,
    row-major], ``alpha`` (3,) is (a_pre, a_post, a_res)."""
    n = mix.n
    pre = jax.nn.sigmoid(alpha[0] * p[:n] + bias[:n, None])
    post = 2.0 * jax.nn.sigmoid(alpha[1] * p[n:2 * n]
                                + bias[n:2 * n, None])
    z = alpha[2] * p[2 * n:].reshape(n, n, -1) \
        + bias[2 * n:].reshape(n, n, 1)
    m = jnp.exp(jnp.clip(z, mix.clamp[0], mix.clamp[1]))

    def step(_, m):
        m = m / (jnp.sum(m, axis=0, keepdims=True) + mix.eps)   # columns
        return m / (jnp.sum(m, axis=1, keepdims=True) + mix.eps)  # rows

    # a rolled loop: unrolled, the steps of a model's sub-layers, forward,
    # made again and backward, were a third of the step program's
    # instructions (and sixteen rows of tokens written out one by one,
    # which would make them purely element-wise, take XLA:TPU a minute a
    # sub-layer to compile)
    return pre, post, jax.lax.fori_loop(0, mix.iters, step, m)


def _streams(x, n):
    """The n streams of rows x (N, n C), each (N, C) and float32."""
    c = x.shape[1] // n
    return [x[:, j * c:(j + 1) * c].astype(jnp.float32) for j in range(n)]


def _column(row):
    """A mapping's row of tokens (N,) against the streams' rows (N, C)."""
    return row[:, None]


def _columns_of(x, phi, mix):
    """(the 24 columns of every token (columns, N), 1 / RMS (N,)), float32:
    the product takes the streams' dtype's operands."""
    f32 = jnp.float32
    x32 = x.astype(f32)
    inv = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=1) + mix.rms_eps)
    s = jax.lax.dot_general(phi.astype(x.dtype), x, _TT,
                            preferred_element_type=f32)
    return s * inv[None, :], inv


# ------------------------------------------------------------ the kernels
def _packed(h_pre, h_post, h_res):
    """The mappings as ONE array (8 (2 + n), N): a group of 8 rows each
    for H_pre, H_post and every row of H_res, its first n rows filled."""
    n = h_post.shape[0]
    pad = lambda a: jnp.pad(a, ((0, _GROUP - n), (0, 0)))
    return jnp.concatenate([pad(h_pre), pad(h_post)]
                           + [pad(h_res[i]) for i in range(n)])


def _unpacked(h, n):
    group = lambda g: h[_GROUP * g:_GROUP * g + n]
    return group(0), group(1), jnp.stack([group(2 + i) for i in range(n)])


def _turns(tn):
    """(a row of tokens (1, tn) as a column (tn, 1), a column as a row): the
    diagonal of the (tn, tn) matrix that holds it."""
    at = lambda axis: jax.lax.broadcasted_iota(jnp.int32, (tn, tn), axis)
    eye = (at(0) == at(1)).astype(jnp.float32)
    return (lambda row: jnp.sum(row * eye, axis=1, keepdims=True),
            lambda col: jnp.sum(col * eye, axis=0, keepdims=True))


def _row_of(h_ref, group, j=0):
    at = _GROUP * group + j
    return h_ref[at:at + 1, :]


def _post_fwd_kernel(x_ref, y_ref, h_ref, out_ref, *, n):
    """One tile of tokens: X' from X, y and the packed mappings."""
    tn, c = y_ref.shape
    column, _ = _turns(tn)
    y = y_ref[...].astype(jnp.float32)
    xs = [x_ref[:, j * c:(j + 1) * c].astype(jnp.float32) for j in range(n)]
    for i in range(n):
        acc = column(_row_of(h_ref, 1, i)) * y
        for j in range(n):
            acc = acc + column(_row_of(h_ref, 2 + i, j)) * xs[j]
        out_ref[:, i * c:(i + 1) * c] = acc.astype(out_ref.dtype)


def _post_bwd_kernel(d_ref, x_ref, y_ref, h_ref, dx_ref, dy_ref, dh_ref, *,
                     n):
    """One tile: the streams' and y's cotangents and, as rows of tokens,
    the sums over the width that are the mappings' cotangents."""
    tn, c = y_ref.shape
    column, row = _turns(tn)
    y = y_ref[...].astype(jnp.float32)
    xs = [x_ref[:, j * c:(j + 1) * c].astype(jnp.float32) for j in range(n)]
    ds = [d_ref[:, i * c:(i + 1) * c].astype(jnp.float32) for i in range(n)]
    over = lambda a, b: row(jnp.sum(a * b, axis=1, keepdims=True))
    dh_ref[...] = jnp.zeros(dh_ref.shape, dh_ref.dtype)
    dy = jnp.zeros((tn, c), jnp.float32)
    for i in range(n):
        dy = dy + column(_row_of(h_ref, 1, i)) * ds[i]
        at = _GROUP + i
        dh_ref[at:at + 1, :] = over(ds[i], y)
        for j in range(n):
            at = _GROUP * (2 + i) + j
            dh_ref[at:at + 1, :] = over(ds[i], xs[j])
    dy_ref[...] = dy.astype(dy_ref.dtype)
    for j in range(n):
        acc = column(_row_of(h_ref, 2, j)) * ds[0]
        for i in range(1, n):
            acc = acc + column(_row_of(h_ref, 2 + i, j)) * ds[i]
        dx_ref[:, j * c:(j + 1) * c] = acc.astype(dx_ref.dtype)


def _columns_to_rows(mix):
    """For each of the packed form's rows, the column of ``phi`` (and entry
    of ``bias``) it holds, or -1; and the entry of ``alpha`` it takes."""
    n, rows, which = mix.n, [], []
    for group in range(2 + n):
        first = group * n
        rows += list(range(first, first + n)) + [-1] * (_GROUP - n)
        which += [min(group, 2)] * _GROUP
    return rows, which


def _packed_leaves(phi, bias, alpha, dtype, mix):
    """(``phi`` transposed (rows, n C) in the streams' dtype, ``alpha`` and
    ``bias`` a row (rows, 1), float32) in the packed form's rows; a row
    that holds no column is zero."""
    rows, which = _columns_to_rows(mix)
    at = jnp.asarray([max(r, 0) for r in rows])
    live = jnp.asarray([r >= 0 for r in rows], jnp.float32)[:, None]
    return ((phi.T[at] * live.astype(phi.dtype)).astype(dtype),
            alpha.astype(jnp.float32)[jnp.asarray(which)][:, None],
            bias.astype(jnp.float32)[at][:, None] * live)


def _unpacked_leaves(dphi, dscale, dbias, phi, bias, alpha, mix):
    """The leaves' cotangents from the packed rows'."""
    rows, which = _columns_to_rows(mix)
    of_column = jnp.asarray([rows.index(k) for k in range(mix.columns)])
    live = jnp.asarray([r >= 0 for r in rows], jnp.float32)
    dalpha = jnp.zeros((3,), jnp.float32).at[jnp.asarray(which)].add(
        dscale[:, 0] * live)
    return (dphi[of_column].T.astype(phi.dtype),
            dbias[of_column, 0].astype(bias.dtype),
            dalpha.astype(alpha.dtype))


def _mapping_groups(p, scale, bias, mix):
    """`mappings` on the packed rows, a list of (8, tn) groups: H_pre,
    H_post and each row of H_res; the rows past n of a group hold nothing
    (zero in H_res, so that a sum over a group's rows is the sum of its n).
    The Sinkhorn steps are written out: forty normalisations of n groups."""
    n = mix.n
    z = scale * p + bias
    group = lambda g: z[_GROUP * g:_GROUP * (g + 1)]
    live = (jax.lax.broadcasted_iota(jnp.int32, (_GROUP, p.shape[1]), 0)
            < n).astype(jnp.float32)
    m = [jnp.exp(jnp.clip(group(2 + i), mix.clamp[0], mix.clamp[1])) * live
         for i in range(n)]
    # (the rows that hold nothing divide by 1: zero over eps a step is
    # zero over eps^40 once a compiler has joined the divisions)
    idle = 1.0 - live
    for _ in range(mix.iters):
        over = sum(m[1:], m[0]) + mix.eps + idle                # columns
        m = [mi / over for mi in m]
        m = [mi / (jnp.sum(mi, axis=0, keepdims=True) + mix.eps)
             for mi in m]                                       # rows
    return [jax.nn.sigmoid(group(0)), 2.0 * jax.nn.sigmoid(group(1))] + m


def _pre_fwd_kernel(x_ref, phi_ref, scale_ref, bias_ref, u_ref, h_ref, p_ref,
                    inv_ref, *, mix):
    """One tile of tokens: the RMS, the packed columns, the mappings and
    the weighted sum u, the streams read once."""
    tn, c = u_ref.shape
    n, f32 = mix.n, jnp.float32
    column, row = _turns(tn)
    xs = [x_ref[:, j * c:(j + 1) * c].astype(f32) for j in range(n)]
    squares = sum(jnp.sum(xj * xj, axis=1, keepdims=True) for xj in xs)
    inv = jax.lax.rsqrt(row(squares) / (n * c) + mix.rms_eps)
    p = jax.lax.dot_general(phi_ref[...], x_ref[...],
                            (((1,), (1,)), ((), ())),
                            preferred_element_type=f32) * inv
    with jax.named_scope("mhc/sinkhorn"):
        groups = _mapping_groups(p, scale_ref[...], bias_ref[...], mix)
    for g, rows in enumerate(groups):
        h_ref[_GROUP * g:_GROUP * (g + 1), :] = rows
    p_ref[...] = p
    inv_ref[...] = inv
    u = column(_row_of(h_ref, 0, 0)) * xs[0]
    for j in range(1, n):
        u = u + column(_row_of(h_ref, 0, j)) * xs[j]
    u_ref[...] = u.astype(u_ref.dtype)


def _pre_bwd_kernel(x_ref, du_ref, dxp_ref, dh_ref, p_ref, inv_ref, h_ref,
                    phi_ref, scale_ref, bias_ref, dx_ref, dphi_ref,
                    dscale_ref, dbias_ref, *, mix):
    """One tile: H_pre's cotangent from u's, the mappings' pull-back at the
    kept columns, the two products against the columns' cotangent, and the
    streams' cotangent with what the writing side handed back; ``phi``'s,
    ``alpha``'s and ``bias``'s cotangents are summed over the tiles."""
    tn, c = du_ref.shape
    n, f32 = mix.n, jnp.float32
    column, row = _turns(tn)
    xs = [x_ref[:, j * c:(j + 1) * c].astype(f32) for j in range(n)]
    du = du_ref[...].astype(f32)

    @pl.when(pl.program_id(0) == 0)
    def _():
        dphi_ref[...] = jnp.zeros(dphi_ref.shape, f32)
        dscale_ref[...] = jnp.zeros(dscale_ref.shape, f32)
        dbias_ref[...] = jnp.zeros(dbias_ref.shape, f32)

    dh = [dh_ref[_GROUP * g:_GROUP * (g + 1), :] for g in range(2 + n)]
    at = jax.lax.broadcasted_iota(jnp.int32, (_GROUP, 1), 0)
    for j in range(n):        # H_pre's cotangent from u's, into its row
        dh[0] = dh[0] + (at == j).astype(f32) * row(
            jnp.sum(du * xs[j], axis=1, keepdims=True))
    p, inv = p_ref[...], inv_ref[...]
    with jax.named_scope("mhc/sinkhorn"):
        _, pull = jax.vjp(functools.partial(_mapping_groups, mix=mix), p,
                          scale_ref[...], bias_ref[...])
        dp, dscale, dbias = pull(dh)
    dscale_ref[...] += dscale
    dbias_ref[...] += dbias
    ds = (dp * inv).astype(x_ref.dtype)
    along = column(-(inv * inv) * jnp.sum(dp * p, axis=0, keepdims=True)
                   / (n * c))
    dphi_ref[...] += jax.lax.dot_general(
        ds, x_ref[...], (((1,), (0,)), ((), ())), preferred_element_type=f32)
    for j in range(n):
        through = jax.lax.dot_general(
            ds, phi_ref[:, j * c:(j + 1) * c], (((0,), (0,)), ((), ())),
            preferred_element_type=f32)
        dx = column(_row_of(h_ref, 0, j)) * du + through + along * xs[j] \
            + dxp_ref[:, j * c:(j + 1) * c].astype(f32)
        dx_ref[:, j * c:(j + 1) * c] = dx.astype(dx_ref.dtype)


def _over_tiles(kernel, name, arrays, out, interpret, sequential=False):
    """`kernel` over tiles of `_TILE` tokens, one a grid step: an operand
    or result with the tokens FIRST, (N, ...), is cut by rows, one with the
    tokens LAST, (rows, N), by columns, and any other is whole at every
    step (``sequential``: a result that is whole is summed over the
    steps)."""
    n_tokens = arrays[0].shape[0]

    def spec(a):
        if a.shape[0] == n_tokens:
            return pl.BlockSpec((_TILE,) + tuple(a.shape[1:]),
                                lambda t: (t,) + (0,) * (len(a.shape) - 1))
        if a.shape[-1] == n_tokens:
            return pl.BlockSpec((a.shape[0], _TILE), lambda t: (0, t))
        return pl.BlockSpec(tuple(a.shape), lambda t: (0,) * len(a.shape))

    return pl.pallas_call(
        kernel, grid=(n_tokens // _TILE,),
        in_specs=[spec(a) for a in arrays],
        out_specs=[spec(a) for a in out], out_shape=out,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary" if sequential else "parallel",),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret, name=name)(*arrays)


def kernels_take(x, mix: Mix) -> bool:
    """Whether the Pallas kernels take streams x (N, n C): whole tiles of
    tokens, a stream's width whole lanes, the mappings' groups in a tile's
    sublanes."""
    return (x.shape[0] % _TILE == 0 and (x.shape[1] // mix.n) % 128 == 0
            and mix.n <= _GROUP)


def _executor(x, mix):
    """How the two sides run on streams x: "kernels" (a TPU, shapes the
    tiling takes), else "xla". (The tests patch this to "interpret".)"""
    return "kernels" if is_tpu_backend() and kernels_take(x, mix) else "xla"


# ------------------------------------------------------------- the sides
@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def pre(x, phi, bias, alpha, mix: Mix):
    """The reading side: streams x (N, n C), ``phi`` (n C, columns),
    ``bias`` (columns,), ``alpha`` (3,) -> ``(u (N, C) in x's dtype, H_pre
    (n, N), H_post (n, N), H_res (n, n, N), x)``, the mappings float32.
    The streams come back as they went in, for `post` to take from here:
    their cotangent from the writing side then arrives in THIS side's
    pull-back and is added where the streams' cotangent is made, not in a
    pass of its own."""
    return _pre_fwd(x, phi, bias, alpha, mix)[0]


def _pre_fwd(x, phi, bias, alpha, mix):
    how = _executor(x, mix)
    if how != "xla":
        like = jax.ShapeDtypeStruct
        tokens, f32 = x.shape[0], jnp.float32
        packed = _packed_leaves(phi, bias, alpha, x.dtype, mix)
        rows = packed[0].shape[0]
        with jax.named_scope("mhc/pre"):
            u, h, p, inv = _over_tiles(
                functools.partial(_pre_fwd_kernel, mix=mix), "mhc_pre_fwd",
                (x,) + packed,
                [like((tokens, x.shape[1] // mix.n), x.dtype),
                 like((rows, tokens), f32), like((rows, tokens), f32),
                 like((1, tokens), f32)], how == "interpret")
            h_pre, h_post, h_res = _unpacked(h, mix.n)
        return (u, h_pre, h_post, h_res, x), (x, phi, bias, alpha, p, inv, h)
    with jax.named_scope("mhc/pre"):
        p, inv = _columns_of(x, phi, mix)
    with jax.named_scope("mhc/sinkhorn"):
        h_pre, h_post, h_res = mappings(p, bias.astype(jnp.float32),
                                        alpha.astype(jnp.float32), mix)
    with jax.named_scope("mhc/pre"):
        u = sum(_column(h_pre[j]) * xj
                for j, xj in enumerate(_streams(x, mix.n))).astype(x.dtype)
    return (u, h_pre, h_post, h_res, x), (x, phi, bias, alpha, p, inv,
                                          h_pre)


def _pre_bwd(mix, res, cots):
    x, phi, bias, alpha, p, inv, h_pre = res
    du, dh_pre, dh_post, dh_res, dx_post = cots
    f32 = jnp.float32
    how = _executor(x, mix)
    if how != "xla":
        like = jax.ShapeDtypeStruct
        packed = _packed_leaves(phi, bias, alpha, x.dtype, mix)
        rows = packed[0].shape[0]
        with jax.named_scope("mhc/pre"):
            dx, dphi, dscale, dbias = _over_tiles(
                functools.partial(_pre_bwd_kernel, mix=mix), "mhc_pre_bwd",
                (x, du, dx_post, _packed(dh_pre, dh_post, dh_res), p, inv,
                 h_pre) + packed,
                [like(x.shape, x.dtype), like(packed[0].shape, f32),
                 like((rows, 1), f32), like((rows, 1), f32)],
                how == "interpret", sequential=True)
            return (dx,) + _unpacked_leaves(dphi, dscale, dbias, phi, bias,
                                            alpha, mix)
    with jax.named_scope("mhc/pre"):
        du32 = du.astype(f32)
        # the first walk of the streams: H_pre's cotangent from u's
        dh_pre = dh_pre + jnp.stack([jnp.sum(du32 * xj, axis=1)
                                     for xj in _streams(x, mix.n)])
    with jax.named_scope("mhc/sinkhorn"):
        _, pull = jax.vjp(
            lambda p, b, a: mappings(p, b, a, mix), p,
            bias.astype(f32), alpha.astype(f32))
        dp, dbias, dalpha = pull((dh_pre, dh_post, dh_res))
    with jax.named_scope("mhc/pre"):
        # p = s / rms: the product's cotangent, and the RMS's through every
        # element of the row
        ds = (dp * inv[None, :]).astype(x.dtype)
        along = -(inv * inv) * jnp.sum(dp * p, axis=0) / x.shape[1]
        dphi = jax.lax.dot_general(x, ds, _TT, preferred_element_type=f32)
        through = jax.lax.dot_general(ds, phi.astype(x.dtype), _TT)
        # the second walk: the three ways into the streams, and what the
        # writing side handed back
        c = x.shape[1] // mix.n
        dx = jnp.concatenate(
            [_column(h_pre[j]) * du32 + through[:, j * c:(j + 1) * c]
             + _column(along) * xj + dj
             for j, (xj, dj) in enumerate(zip(_streams(x, mix.n),
                                              _streams(dx_post, mix.n)))],
            axis=1)
    return (dx.astype(x.dtype), dphi.astype(phi.dtype),
            dbias.astype(bias.dtype), dalpha.astype(alpha.dtype))


pre.defvjp(_pre_fwd, _pre_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def post(x, y, h_res, h_post, mix: Mix):
    """The writing side: streams x (N, n C), the sub-layer's y (N, C), the
    mappings of `pre` -> the new streams (N, n C) in x's dtype."""
    return _post_fwd(x, y, h_res, h_post, mix)[0]


def _post_fwd(x, y, h_res, h_post, mix):
    how = _executor(x, mix)
    with jax.named_scope("mhc/post"):
        if how != "xla":
            (out,) = _over_tiles(
                functools.partial(_post_fwd_kernel, n=mix.n), "mhc_post_fwd",
                (x, y, _packed(jnp.zeros_like(h_post), h_post, h_res)),
                [jax.ShapeDtypeStruct(x.shape, x.dtype)],
                how == "interpret")
            return out, (x, y, h_res, h_post)
        y32, xs = y.astype(jnp.float32), _streams(x, mix.n)
        out = jnp.concatenate(
            [sum(_column(h_res[i, j]) * xj for j, xj in enumerate(xs))
             + _column(h_post[i]) * y32 for i in range(mix.n)], axis=1)
    return out.astype(x.dtype), (x, y, h_res, h_post)


def _post_bwd(mix, res, dout):
    x, y, h_res, h_post = res
    n, how = mix.n, _executor(x, mix)
    with jax.named_scope("mhc/post"):
        if how != "xla":
            like = jax.ShapeDtypeStruct
            packed = _packed(jnp.zeros_like(h_post), h_post, h_res)
            dx, dy, dh = _over_tiles(
                functools.partial(_post_bwd_kernel, n=n), "mhc_post_bwd",
                (dout, x, y, packed),
                [like(x.shape, x.dtype), like(y.shape, y.dtype),
                 like(packed.shape, jnp.float32)], how == "interpret")
            _, dh_post, dh_res = _unpacked(dh, n)
            return (dx, dy, dh_res.astype(h_res.dtype),
                    dh_post.astype(h_post.dtype))
        y32, xs, ds = y.astype(jnp.float32), _streams(x, n), \
            _streams(dout, n)
        dx = jnp.concatenate(
            [sum(_column(h_res[i, j]) * ds[i] for i in range(n))
             for j in range(n)], axis=1)
        dy = sum(_column(h_post[i]) * ds[i] for i in range(n))
        dh_res = jnp.stack([jnp.stack([jnp.sum(ds[i] * xs[j], axis=1)
                                       for j in range(n)])
                            for i in range(n)])
        dh_post = jnp.stack([jnp.sum(ds[i] * y32, axis=1)
                             for i in range(n)])
    return (dx.astype(x.dtype), dy.astype(y.dtype),
            dh_res.astype(h_res.dtype), dh_post.astype(h_post.dtype))


post.defvjp(_post_fwd, _post_bwd)


def gauges(h_pre, h_res):
    """(the largest ``|row sum - 1|`` or ``|column sum - 1|`` of H_res over
    the tokens, the mean over the tokens of the entropy, in nats, of
    ``H_pre / sum(H_pre)``: ``ln n`` where a sub-layer reads the streams
    alike, 0 where it reads one), float32 scalars outside every gradient."""
    h_pre, h_res = jax.lax.stop_gradient((h_pre, h_res))
    gap = jnp.maximum(jnp.max(jnp.abs(jnp.sum(h_res, axis=0) - 1.0)),
                      jnp.max(jnp.abs(jnp.sum(h_res, axis=1) - 1.0)))
    q = h_pre / jnp.sum(h_pre, axis=0, keepdims=True)
    entropy = jnp.mean(-jnp.sum(q * jnp.log(jnp.maximum(q, 1e-30)), axis=0))
    return gap, entropy
