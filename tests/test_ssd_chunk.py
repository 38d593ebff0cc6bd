"""Mamba-2's chunked state-space recurrence (`ops/ssd_chunk.py`): the two
Pallas kernels, interpreted, against the XLA executor of the same chunk
function, and both against the recurrence written out TOKEN BY TOKEN (the
benchmark reference's own `recurrence`, which imports nothing of the
program): values and gradients, at two chunk counts, with and without a
state before position 0."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib.manifest import load_module
from deeplearning4j_tpu.ops import ssd_chunk

REF = load_module("references", "nemotron-3-super-120b-a12b")

#: 2 sequences, 4 heads of 16 in 2 groups of state 32, chunks of 32
B, H, P, G, N, Q = 2, 4, 16, 2, 32, 32


def _inputs(t, with_state, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (B, t, H, P))
    # step sizes and decays as the family draws them: dt in (0.001, 0.3),
    # A in (-16, -1)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, t, H)) - 2.0)
    a_log = jnp.log(jax.random.uniform(ks[2], (H,), minval=1.0, maxval=16.0))
    b = 0.3 * jax.random.normal(ks[3], (B, t, G, N))
    c = 0.3 * jax.random.normal(ks[4], (B, t, G, N))
    s0 = 0.5 * jax.random.normal(ks[5], (B, H, P, N)) if with_state \
        else jnp.zeros((B, H, P, N))
    return x, dt, a_log, b, c, s0


def _on(scan, **how):
    """`ssd_chunked` on the executor ``scan`` (the XLA one, or the kernels
    interpreted) whatever the platform."""
    old = ssd_chunk.chunk_scan
    ssd_chunk.chunk_scan = lambda *a, mm: scan(*a, mm)
    try:
        return ssd_chunk.ssd_chunked(**how)
    finally:
        ssd_chunk.chunk_scan = old


_EXECUTORS = {False: ssd_chunk.chunk_scan_xla,
              True: lambda *a: ssd_chunk.chunk_kernels(*a, True)}


def _chunked(kernels, mm=None):
    """`ssd_chunked` on the XLA executor or on the interpreted kernels."""
    return lambda x, dt, a_log, b, c, s0: _on(
        _EXECUTORS[kernels], x=x, dt=dt, a=-jnp.exp(a_log), b=b, c=c,
        chunk=Q, initial_state=s0, mm_dtype=mm)


def _token_by_token(x, dt, a_log, b, c, s0):
    return REF.recurrence(x, dt, a_log, b, c, state=s0)


def _close(got, want, tol):
    got, want = np.asarray(got), np.asarray(want)
    assert np.abs(got - want).max() <= tol * max(np.abs(want).max(), 1e-30)


@pytest.fixture(autouse=True)
def _float32_products():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.mark.parametrize("with_state", [False, True],
                         ids=["from_zero", "from_a_state"])
@pytest.mark.parametrize("chunks", [2, 3])
@pytest.mark.parametrize("kernels", [False, True], ids=["xla", "kernels"])
def test_the_chunked_scan_is_the_recurrence_token_by_token(kernels, chunks,
                                                           with_state):
    args = _inputs(chunks * Q, with_state)
    y, s = _chunked(kernels)(*args)
    want_y, want_s = _token_by_token(*args)
    _close(y, want_y, 2e-5)
    _close(s, want_s, 2e-5)


@pytest.mark.parametrize("with_state", [False, True],
                         ids=["from_zero", "from_a_state"])
@pytest.mark.parametrize("chunks", [2, 3])
@pytest.mark.parametrize("kernels", [False, True], ids=["xla", "kernels"])
def test_its_gradients_are_the_recurrences(kernels, chunks, with_state):
    """In every input, the state before position 0 and the final state's
    cotangent included: the backward kernel hands the state's cotangent
    from chunk to chunk as the forward hands the state."""
    args = _inputs(chunks * Q, with_state, seed=1)
    wy = jax.random.normal(jax.random.PRNGKey(8), (B, chunks * Q, H, P))
    ws = jax.random.normal(jax.random.PRNGKey(9), (B, H, P, N))

    def loss(fn):
        def of(*a):
            y, s = fn(*a)
            return jnp.sum(y * wy) + jnp.sum(s * ws)
        return of

    got = jax.grad(loss(_chunked(kernels)), argnums=tuple(range(6)))(*args)
    want = jax.grad(loss(_token_by_token), argnums=tuple(range(6)))(*args)
    for g, w in zip(got, want):
        _close(g, w, 2e-4)


def test_the_kernels_are_the_xla_executor_of_the_same_chunk_function():
    """One function, two executors: the interpreted kernels give what the
    `lax.scan` gives, in bfloat16 products too."""
    args = _inputs(2 * Q, True, seed=2)
    for mm in (None, jnp.bfloat16):
        runs = [_chunked(kernels, mm)(*args) for kernels in (False, True)]
        for a, b in zip(*runs):
            _close(a, b, 1e-6)
    # bfloat16 operands stay near the float32 recurrence
    _close(runs[0][0], _token_by_token(*args)[0], 2e-2)


def test_a_tail_that_does_not_fill_a_chunk_leaves_the_state_alone():
    args = _inputs(2 * Q + 11, True, seed=3)
    y, s = _chunked(False)(*args)
    want_y, want_s = _token_by_token(*args)
    assert y.shape == want_y.shape
    _close(y, want_y, 2e-5)
    _close(s, want_s, 2e-5)


def test_no_exponent_is_positive_whatever_the_decay():
    """A decay far past float32's range inside one chunk (g falls by
    thousands) gives zeros where the recurrence does, never inf or nan."""
    x, dt, a_log, b, c, s0 = _inputs(2 * Q, True, seed=4)
    y, s = _chunked(False)(x, 50.0 * dt, a_log, b, c, s0)
    want_y, want_s = _token_by_token(x, 50.0 * dt, a_log, b, c, s0)
    assert np.isfinite(np.asarray(y)).all()
    _close(y, want_y, 2e-5)
    _close(s, want_s, 2e-5)


def test_groups_that_do_not_divide_the_heads_are_refused():
    x, dt, a_log, b, c, _ = _inputs(Q, False)
    with pytest.raises(ValueError, match="groups"):
        ssd_chunk.ssd_chunked(x[:, :, :3], dt[:, :, :3], -jnp.exp(a_log[:3]),
                              b, c, chunk=Q)


def test_the_gauge_names_the_path_a_call_lowered_to():
    from deeplearning4j_tpu import monitor
    x, dt, a_log, b, c, _ = _inputs(Q, False)
    ssd_chunk.ssd_chunked(x, dt, -jnp.exp(a_log), b, c, chunk=Q)
    series = monitor.dump()["ssd_scan_path"]["series"]
    assert [s["value"] for s in series] == [0]     # no TPU here: the scan
