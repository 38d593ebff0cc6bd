"""Denoising examples for block-diffusion training, made on the feed.

A block-diffusion LM (`models.SdarMoeLM`) is trained to put back the tokens
a noising process masked: every block of ``block_length`` tokens of every
sequence draws a noise level ``t`` uniformly in ``[t_min, 1)``, each token
of the block is replaced by the mask id with probability ``t``,
independently (the linear schedule of BD3-LMs' Algorithm 1, ``t`` clipped
at ``t_min``), and the loss at a masked position is weighted by ``1 / t``.
The model reads the stream ``[noisy copy ; clean copy]`` of twice the
sequence (`nn/layers/attention.py::block_diffusion_visible`) and row i of
the noisy half predicts token i itself: no shift.

`BlockDiffusionPreProcessor` makes such an example of a batch of token ids
where an iterator routes its batches through its pre-processor
(`DataSetIterator.set_pre_processor`): behind `AsyncDataSetIterator` that
is the prefetch thread, beside the host cast and the transfer. Its draws
come from a counter-based generator (Philox) keyed by ``(noise_seed, index
of the sequence since the last reset)``, so a run, a run resumed at a
known sequence (``reset(index)``) and a reference that copies the rule
make the same noise whatever the batch size.
"""
from __future__ import annotations

import numpy as np

from deeplearning4j_tpu.data.dataset import DataSet, MultiDataSet
from deeplearning4j_tpu.data.normalization import DataSetPreProcessor


class BlockDiffusionPreProcessor(DataSetPreProcessor):
    """``(N, L)`` token ids (the first features of a `DataSet` or
    `MultiDataSet`; labels and masks are not read) -> a `MultiDataSet` of
    features ``(N, 2L)`` int32 (the noisy copy, then the clean one),
    labels ``(N, L)`` int32 (the clean ids: the target of row i is token
    i) and a label mask ``(N, L)`` float32 that holds the loss WEIGHTS:
    ``1 / t`` of the position's block where the token was masked, else 0
    (`RnnOutputLayer(weighted=True)` scores with them over the count of
    positions).

    Sequence ``index`` (counted from the last `reset`) draws from
    ``numpy.random.Philox(key=[noise_seed, index])``: first ``L /
    block_length`` doubles ``u`` for the blocks' levels ``t = t_min + (1 -
    t_min) u``, then ``L`` doubles for the tokens; a token is masked iff
    its double is below its block's ``t``.

    Span ``etl/denoise`` a batch (with tracing on); counters
    ``denoise_positions_total`` and ``denoise_masked_total``."""

    def __init__(self, mask_token_id: int, block_length: int = 4,
                 noise_seed: int = 0, t_min: float = 1e-3):
        if block_length < 1 or not 0.0 < t_min < 1.0:
            raise ValueError(f"block_length {block_length} has to be "
                             f"positive and t_min {t_min} inside (0, 1)")
        self.mask_token_id = int(mask_token_id)
        self.block_length = int(block_length)
        self.noise_seed = int(noise_seed)
        self.t_min = float(t_min)
        self._next = 0

    def reset(self, index: int = 0):
        """Count sequences from ``index`` again (0: an iterator's reset;
        a resumed run names the sequence it resumes at)."""
        self._next = int(index)

    def noise(self, index: int, length: int):
        """((L,) bool masked, (L,) float64 noise level of each position's
        block) of sequence ``index``."""
        b = self.block_length
        if length % b:
            raise ValueError(f"sequence of {length} tokens is no whole "
                             f"number of blocks of {b}")
        gen = np.random.Generator(np.random.Philox(
            key=[self.noise_seed, int(index)]))
        t = self.t_min + (1.0 - self.t_min) * gen.random(length // b)
        t = np.repeat(t, b)
        return gen.random(length) < t, t

    def preprocess(self, ds):
        from deeplearning4j_tpu import monitor
        ids = ds.features[0] if isinstance(ds, MultiDataSet) else ds.features
        with monitor.span("etl/denoise", first_sequence=self._next):
            ids = np.asarray(ids)
            if ids.ndim == 3:
                ids = ids[..., 0]
            ids = ids.astype(np.int32)
            n, length = ids.shape
            noisy = ids.copy()
            weights = np.zeros((n, length), np.float32)
            for row in range(n):
                masked, t = self.noise(self._next + row, length)
                noisy[row, masked] = self.mask_token_id
                weights[row, masked] = (1.0 / t[masked]).astype(np.float32)
            self._next += n
        monitor.counter(
            "denoise_positions_total",
            "Token positions the denoising pre-processor saw").inc(n * length)
        monitor.counter(
            "denoise_masked_total",
            "Positions it replaced by the mask id (each with its block's "
            "noise level as probability)").inc(
                int(np.count_nonzero(weights)))
        return MultiDataSet((np.concatenate([noisy, ids], axis=1),), (ids,),
                            None, (weights,))
