"""The denoising pre-processor of block-diffusion training, the weighted
score of `RnnOutputLayer` (whole and blocked) and the vertex that hands a
head the first L time steps. See `_sdar_common.py`."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu import monitor
from deeplearning4j_tpu.data.async_iterator import AsyncDataSetIterator
from deeplearning4j_tpu.data.dataset import DataSet, MultiDataSet
from deeplearning4j_tpu.data.denoise import BlockDiffusionPreProcessor
from deeplearning4j_tpu.data.iterator import (ArrayDataSetIterator,
                                              DataSetIterator)
from deeplearning4j_tpu.nn.conf.base import InputType, Kind
from deeplearning4j_tpu.nn.conf.graph_vertices import TimeSliceVertex
from deeplearning4j_tpu.nn.layers import recurrent
from deeplearning4j_tpu.nn.layers.recurrent import RnnOutputLayer

from _sdar_common import CFG, REF

MASK = 95


def _ids(n=4, length=64, seed=0):
    return np.random.default_rng(seed).integers(0, MASK, (n, length))


class _Batches(DataSetIterator):
    def __init__(self, ids, batch):
        self.ids, self.batch = ids, batch

    def __iter__(self):
        for i in range(0, len(self.ids), self.batch):
            yield self._pp(MultiDataSet((self.ids[i:i + self.batch],), None,
                                        None, None))


# ----------------------------------------------------------- the pre-processor
@pytest.mark.parametrize("kind", ["multi", "single", "column"])
def test_the_example_is_the_stream_the_targets_and_the_weights(kind):
    """(N, L) ids -> features (N, 2L): the masked copy then the clean one;
    labels the clean ids; weights 1 / t of the block where masked, else 0;
    from a `MultiDataSet`, a `DataSet` or ids with a trailing 1."""
    ids = _ids()
    pp = BlockDiffusionPreProcessor(MASK, block_length=4, noise_seed=5)
    given = {"multi": MultiDataSet((ids,), None, None, None),
             "single": DataSet(ids, None),
             "column": DataSet(ids[..., None], None)}[kind]
    out = pp.preprocess(given)
    (stream,), (labels,), (weights,) = (out.features, out.labels,
                                        out.labels_masks)
    assert stream.shape == (4, 128) and stream.dtype == np.int32
    assert weights.dtype == np.float32 and out.features_masks is None
    np.testing.assert_array_equal(stream[:, 64:], ids)
    np.testing.assert_array_equal(labels, ids)
    masked = stream[:, :64] == MASK
    np.testing.assert_array_equal(masked, weights > 0)
    np.testing.assert_array_equal(stream[:, :64][~masked], ids[~masked])
    for row in range(4):
        hit, t = pp.noise(row, 64)
        np.testing.assert_array_equal(hit, masked[row])
        assert np.all(t.reshape(-1, 4) == t.reshape(-1, 4)[:, :1])
        assert 1e-3 <= t.min() and t.max() < 1.0
        np.testing.assert_allclose(weights[row][hit], 1.0 / t[hit],
                                   rtol=1e-6)


@pytest.mark.parametrize("batch", [1, 2, 4])
def test_the_draws_depend_on_seed_and_index_alone(batch):
    """The same noise whatever the batch size, another for another seed or
    another index; the reference's own copy of the rule makes the same
    example."""
    ids = _ids()
    pp = BlockDiffusionPreProcessor(MASK, block_length=4, noise_seed=9)
    got = [b for b in _Batches(ids, batch).set_pre_processor(pp)]
    stream = np.concatenate([b.features[0] for b in got])
    weights = np.concatenate([b.labels_masks[0] for b in got])
    cfg = {**CFG, "noise_t_min": 1e-3}
    want = REF.targets(cfg, ids, 9, 0)
    np.testing.assert_array_equal(stream, want[0])
    np.testing.assert_array_equal(weights, want[2])
    other = BlockDiffusionPreProcessor(MASK, 4, noise_seed=10)
    assert np.any(other.preprocess(DataSet(ids, None)).features[0]
                  != stream)
    assert np.any(pp.noise(0, 64)[0] != pp.noise(1, 64)[0])


@pytest.mark.parametrize("source", ["plain", "array", "async"])
def test_a_reset_and_a_resumed_iterator_make_the_runs_noise(source):
    """The reset of the iterator that holds the pre-processor counts
    sequences from 0 again: a source that keeps the base's reset, one
    with a reset of its own, and either behind the async prefetch; a run
    resumed at sequence 2 (`reset(2)`) makes what the uninterrupted run
    made from there."""
    ids = _ids()
    pp = BlockDiffusionPreProcessor(MASK, 4, noise_seed=3)
    it = ArrayDataSetIterator(ids, batch_size=2) if source == "array" \
        else _Batches(ids, 2)
    if source == "async":
        it = AsyncDataSetIterator(it, device_put=False)
    it = it.set_pre_processor(pp)
    first = [b.features[0] for b in it]
    assert pp._next == 4
    it.reset()
    assert pp._next == 0
    again = [b.features[0] for b in it]
    np.testing.assert_array_equal(np.concatenate(first),
                                  np.concatenate(again))
    pp.reset(2)
    resumed = pp.preprocess(DataSet(ids[2:], None)).features[0]
    np.testing.assert_array_equal(resumed, first[1])


def test_half_the_positions_are_masked_and_the_counters_say_so():
    """t ~ U(1e-3, 1) a block: 50.05 % of the positions masked, expected;
    `denoise_positions_total` and `denoise_masked_total` count them, and
    the span `etl/denoise` is left on the thread that made the batch."""
    total = lambda name: sum(
        s["value"] for s in monitor.dump().get(name, {}).get("series", []))
    before = total("denoise_positions_total"), total("denoise_masked_total")
    pp = BlockDiffusionPreProcessor(MASK, 4, noise_seed=1)
    monitor.enable_tracing()
    try:
        out = pp.preprocess(DataSet(_ids(16, 1024), None))
        spans = [e for e in monitor.trace_events()
                 if e["name"] == "etl/denoise"]
    finally:
        monitor.disable_tracing()
    assert spans and spans[-1]["args"]["first_sequence"] == 0
    masked = int((out.labels_masks[0] > 0).sum())
    assert total("denoise_positions_total") - before[0] == 16 * 1024
    assert total("denoise_masked_total") - before[1] == masked
    assert 0.47 < masked / (16 * 1024) < 0.53
    # E[w] = E[t * 1 / t] = 1: the weighted loss is an unbiased sum
    assert 0.9 < out.labels_masks[0].mean() < 1.1


@pytest.mark.parametrize("bad", [dict(block_length=0), dict(t_min=0.0),
                                 dict(t_min=1.0), "ragged"])
def test_what_the_pre_processor_refuses(bad):
    if bad == "ragged":
        pp = BlockDiffusionPreProcessor(MASK, block_length=4)
        with pytest.raises(ValueError, match="whole number of blocks"):
            pp.preprocess(DataSet(_ids(2, 66), None))
        return
    with pytest.raises(ValueError, match="block_length"):
        BlockDiffusionPreProcessor(MASK, **bad)


# ------------------------------------------------------- the weighted score
def _head(**over):
    return RnnOutputLayer(**{**dict(
        n_out=24, activation="softmax", loss="sparse_mcxent",
        has_bias=False, weight_init="normal", weighted=True), **over})


@pytest.mark.parametrize("how", ["whole", "blocked", "blocked_ragged",
                                 "tied"])
def test_the_weighted_score_divides_by_the_count_of_positions(
        how, monkeypatch):
    """``sum_i w_i CE_i / positions``: not the mean over the mask's sum,
    whole and over blocks of positions (a block count that does not divide
    the positions too), value and gradients."""
    n, t, f = 2, 24 if how != "blocked_ragged" else 21, 16
    if how.startswith("blocked"):
        monkeypatch.setattr(recurrent, "_LOSS_LIVE_BYTES", 8 * 24 * 8)
    head = _head(tied_embedding=how == "tied")
    params, _ = head.init(jax.random.PRNGKey(0), InputType.recurrent(f, t))
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    x = jax.random.normal(ks[0], (n, t, f))
    y = jax.random.randint(ks[1], (n, t), 0, 24)
    w = jnp.where(jax.random.uniform(ks[2], (n, t)) < 0.5, 0.0,
                  1.0 / jax.random.uniform(ks[2], (n, t), minval=1e-3))

    def want(params, x):
        z = x @ (params["W"].T if how == "tied" else params["W"])
        nll = jax.nn.logsumexp(z, -1) - jnp.take_along_axis(
            z, y[..., None], -1)[..., 0]
        return jnp.sum(w * nll) / (n * t)

    got = lambda params, x: head.score(params, x, y, mask=w)
    np.testing.assert_allclose(got(params, x), want(params, x), rtol=2e-6)
    for a, b in zip(jax.tree_util.tree_leaves(jax.grad(got, (0, 1))(params, x)),
                    jax.tree_util.tree_leaves(jax.grad(want, (0, 1))(params, x))):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=1e-7)
    # the unweighted head's mean over the mask's sum is another number
    plain = _head(weighted=False, tied_embedding=how == "tied")
    assert abs(float(plain.score(params, x, y, mask=w))
               - float(want(params, x))) > 1e-3
    # and without a label mask every weight is 1
    np.testing.assert_allclose(head.score(params, x, y),
                               plain.score(params, x, y), rtol=2e-6)


# ----------------------------------------------------------------- the vertex
def test_the_time_slice_hands_on_the_first_steps_and_their_mask():
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    vertex = TimeSliceVertex(steps=6)
    kind = vertex.output_type(InputType.recurrent(5, 12))
    assert kind.kind == Kind.RNN and tuple(kind.shape) == (6, 5)
    x = jnp.arange(2 * 12 * 5.0).reshape(2, 12, 5)
    np.testing.assert_array_equal(vertex.apply(x), x[:, :6])
    mask = jnp.arange(24.0).reshape(2, 12)
    np.testing.assert_array_equal(
        ComputationGraph._vertex_out_mask(vertex, [mask], [x], kind),
        mask[:, :6])
    assert ComputationGraph._vertex_out_mask(vertex, [None], [x],
                                             kind) is None
    with pytest.raises(ValueError, match="TimeSliceVertex"):
        TimeSliceVertex(steps=13).output_type(InputType.recurrent(5, 12))
