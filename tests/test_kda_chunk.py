"""Kimi Delta Attention's chunk kernels (`ops/kda_chunk.py`) at shapes that
fit the TPU's tiling (widths of 128, chunks of 64): the Pallas forward and
backward with the hand-over between chunks inside them, interpreted on the
CPU, against the other executor of the same two tile functions (vmapped
under XLA, a `lax.scan` over the chunks, plain autodiff), the tile
functions against the definitions they are arrangements of, and the tile's
pull-back written by hand (what the backward kernel runs) against the
definition's pull-back in float64 and, in bfloat16, autodiff of the tile
function.
`tests/test_kimi_attention.py` holds the whole chunked recurrence (at small
widths, the XLA executor) to the token recurrence;
`tests/test_tpu_lowering.py` compiles the kernels for the v5e.
"""
import collections
import functools
import inspect

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


def _same(got, want, tol, what, floor=0.0):
    """``got`` within ``tol`` of ``want``'s largest entry (and, where an
    entry has a ``floor``, that much more)."""
    got = np.asarray(got.astype(jnp.float32))
    want = np.asarray(want.astype(jnp.float32))
    assert np.isfinite(got).all(), what
    assert (np.abs(got - want) <= tol * max(np.abs(want).max(), 1e-30)
            + floor).all(), what


def _autodiffs_rounding(k, dk):
    """What autodiff's float32 gradient of g may be off by beside its
    bound: nothing but at a chunk's LAST position. There it is what is
    left of k (.) dk once that product has been taken off the sum over the
    chunk that holds it (`k_out`'s last row has no decay): one float32
    rounding of the product, which under a fast decay is more than the
    gradient (`test_tile_pull_back_is_the_tile_functions` prints it
    beside `chunk_step_bwd`'s, which has no such term, both against
    float64). k, dk (..., C, d_k)."""
    last = np.arange(k.shape[-2])[:, None] == k.shape[-2] - 1
    return last * float(jnp.abs(k * dk).max()) \
        * float(jnp.finfo(jnp.float32).eps)


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
              "gradient of " + name,
              _autodiffs_rounding(args[1], grads_ref[1])
              if name == "g" and mm == jnp.float32 else 0.0)


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


def _tile_by_definition(q, k, v, g, beta):
    """`chunk_tile` as the module docstring's formulas written out in the
    inputs' precision: every pair's exponent taken directly (masked before
    it is taken), a triangular solve."""
    c, dk = k.shape
    beta = beta[0][:, None]
    seen = jnp.tril(jnp.ones((c, c), bool))
    pair = jnp.exp(jnp.where(seen[..., None], g[:, None] - g[None], -jnp.inf))
    a_kk = jnp.tril(jnp.einsum("tc,ic,tic->ti", k, k, pair), -1)
    a_qk = jnp.einsum("tc,ic,tic->ti", q, k, pair)
    sol = jax.scipy.linalg.solve_triangular(
        beta * a_kk + jnp.eye(c),
        jnp.concatenate([beta * k * jnp.exp(g), beta * v], axis=1),
        lower=True, unit_diagonal=True)
    return (sol[:, :dk], sol[:, dk:], q * jnp.exp(g),
            k * jnp.exp(g[-1:] - g), a_qk)


def _pull_back_in_float64(*args):
    """`chunk_step`'s pull-back by autodiff of the definition (the tile's,
    then the hand-over's two lines) in float64, on float32 ``(q, k, v, g,
    beta, s, do, ds')``: what both float32 pull-backs round."""
    def step(q, k, v, g, beta, s):
        w, u0, q_in, k_out, a_qk = _tile_by_definition(q, k, v, g, beta)
        u = u0 - w @ s
        return q_in @ s + a_qk @ u, jnp.exp(g[-1])[:, None] * s + k_out.T @ u

    with jax.enable_x64():
        args = [jnp.asarray(np.asarray(a), jnp.float64) for a in args]
        _, pull = jax.vjp(step, *args[:6])
        return [np.asarray(x) for x in pull(tuple(args[6:]))]


@functools.lru_cache(maxsize=None)
def _pull_backs(mm):
    """A tile, the state it received and the cotangents of ``o`` and of the
    state handed on -> the six gradients: by autodiff of `chunk_step`, and
    by `chunk_step_bwd`. Each compiled once a shape."""
    def autodiff(*args):
        _, pull = jax.vjp(functools.partial(kda_chunk.chunk_step, mm=mm),
                          *args[:6])
        return pull(args[6:])
    return jit_unoptimised(autodiff), jit_unoptimised(
        functools.partial(kda_chunk.chunk_step_bwd, mm=mm))


@pytest.mark.parametrize("tail", [0, 11], ids=["full", "padded"])
@pytest.mark.parametrize("decay", [0.05, 1.0, 300.0])
@pytest.mark.parametrize("mm", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("chunk", [SMALL, CHUNK])
def test_tile_pull_back_is_the_tile_functions(chunk, mm, decay, tail):
    """`chunk_step_bwd` on one tile: the six gradients, at a non-zero
    received state, from slow decay to one of e^-300 a step, everything
    finite, at the bounds `_kernels_against_the_scan` holds and with
    nothing beside them. In bfloat16, where the products' rounding is part
    of the function, against `jax.vjp` of `chunk_step`. In float32 against
    the definition's pull-back in float64: autodiff of `chunk_step` in
    float32 is no reference for the gradient of g under a fast decay (it
    takes a pair without decay off a sum that holds it), and is printed
    beside it. The layer's padding (no q, k, v, beta 0, flat g, no
    cotangent of its rows of ``o``) gets exact zeros, but for g's last row,
    which holds the decay of the state."""
    t = chunk - tail
    *tile, s = (a[0] for a in _tiles(t, decay, pairs=1, chunk=chunk))
    tile = [a[0] for a in tile]
    ks = jax.random.split(jax.random.PRNGKey(46), 2)
    do = jax.random.normal(ks[0], (chunk, D)) \
        * (jnp.arange(chunk) < t)[:, None]
    args = (*tile, s, do, jax.random.normal(ks[1], (D, D)))
    autodiff, by_hand = _pull_backs(mm)
    got, auto = by_hand(*args), autodiff(*args)
    if mm == jnp.float32:
        want = _pull_back_in_float64(*args)
        off = lambda x: np.abs(np.asarray(x) - want[3]).max(axis=1) \
            / np.abs(want[3]).max()
        print("gradient of g over its largest entry, off float64's, a "
              "chunk's last position and the worst of the others: autodiff "
              f"{off(auto[3])[-1]:.1e}, {off(auto[3])[:-1].max():.1e}; by "
              f"hand {off(got[3])[-1]:.1e}, {off(got[3])[:-1].max():.1e}")
    for name, a, b in zip(("q", "k", "v", "g", "beta", "the state"), got,
                          want if mm == jnp.float32 else auto):
        _same(a, b, 1e-5 if mm == jnp.float32 else 1e-3,
              "gradient of " + name)
    if tail:
        dq, dk, dv, dg, dbeta = got[:5]
        for x in (dq[t:], dk[t:], dv[t:], dg[t:-1], dbeta[:, t:]):
            np.testing.assert_array_equal(x, 0.0)


def _primitives(jaxpr, count=None):
    """How often each primitive stands in a jaxpr, whatever it calls
    included."""
    count = collections.Counter() if count is None else count
    for eqn in jaxpr.eqns:
        inner = [x.jaxpr if hasattr(x, "jaxpr") else x
                 for x in eqn.params.values()
                 if hasattr(x, "jaxpr") or hasattr(x, "eqns")]
        for sub in inner:
            _primitives(sub, count)
        if not inner:
            count[eqn.primitive.name] += 1
    return count


def test_the_backward_kernel_runs_the_pull_back_written_by_hand():
    """What `kda_chunk_bwd` holds at the cell's tile (64 x 128, bf16
    products), counted from the jaxprs and printed beside the forward's
    and autodiff's. Autodiff transposes the tile function's column steps
    one by one: every slice comes back as a `pad` and every broadcast as a
    sum (347 `pad`s and 453 sums under JAX 0.9; its 20 products are no
    more than the 25 of `chunk_step_bwd`, which makes the forward's 10
    again: the surplus was vector work). Nothing is asserted of autodiff's
    counts, which are JAX's. `chunk_step_bwd` has no `pad`, and the
    kernel's body is that function, a chunk a loop turn."""
    mm = jnp.bfloat16
    x, beta, s = (jnp.zeros(shape) for shape in
                  ((CHUNK, D), (1, CHUNK), (D, D)))
    step = functools.partial(kda_chunk.chunk_step, mm=mm)
    forward = _primitives(jax.make_jaxpr(step)(x, x, x, x, beta, s).jaxpr)
    _, pull = jax.vjp(step, x, x, x, x, beta, s)
    autodiff = _primitives(jax.make_jaxpr(pull)((x, s)).jaxpr)
    by_hand = _primitives(jax.make_jaxpr(functools.partial(
        kda_chunk.chunk_step_bwd, mm=mm))(x, x, x, x, beta, s, x, s).jaxpr)
    for name, count in (("the forward", forward), ("autodiff's pull-back "
                        "(the forward's residuals given)", autodiff),
                        ("chunk_step_bwd (the forward made again)", by_hand)):
        print(name, {k: count[k] for k in
                     ("dot_general", "reduce_sum", "mul", "exp", "pad")},
              sum(count.values()))
    assert by_hand["pad"] == 0

    tiles = _tiles(2 * CHUNK, 1.0, pairs=1)
    res = tiles[:5] + (jnp.zeros((1, 2, D, D)),)
    kernel = _primitives(jax.make_jaxpr(
        lambda res, cots: kda_chunk._backward(res, cots, mm, True))(
            res, (tiles[2], tiles[5])).jaxpr)
    assert {k: kernel[k] for k in ("dot_general", "exp", "pad")} \
        == {k: by_hand[k] for k in ("dot_general", "exp", "pad")}
    assert "vjp" not in inspect.getsource(kda_chunk._bwd_kernel)


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
    tile = [a[0, 0] for a in _tiles(CHUNK, decay, pairs=1)[:5]]
    want = _tile_by_definition(*tile)
    got = kda_chunk.chunk_tile(*tile, mm=jnp.float32)
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
