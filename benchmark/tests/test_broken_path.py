"""A whole run (everything but the look for a chip) with the timed path
broken underneath comes out with ``correct`` false: a training step that
returns its state unchanged, a served token altered where it is
produced."""
import os

import pytest

from benchmark.lib import manifest

M = manifest.load_manifest()
#: the serving cell this PR measured and withheld (PERF.md section 7)
SERVING = manifest.load_manifest(os.path.join(
    os.path.dirname(__file__), "data", "withheld-serving.json"))


def test_a_step_that_returns_its_state_unchanged_is_not_correct(
        tmp_path, monkeypatch):
    import jax.numpy as jnp
    from benchmark.lib import train_cell
    from deeplearning4j_tpu.nn.graph import ComputationGraph

    def idle_scan_step(self):
        def kstep(params, opt_state, state, inputs, labels, fmasks, lmasks,
                  subs):
            return params, opt_state, state, jnp.full((subs.shape[0],), 7.0)
        return kstep

    monkeypatch.setattr(ComputationGraph, "_make_scan_step", idle_scan_step)
    cell = manifest.Cell(M, "resnet50-fit-1chip").rehearsal()
    result, _, _ = train_cell.run(cell, 5, 3.0, False, str(tmp_path), 0.0)
    assert result["correct"] is False


def test_an_altered_token_is_not_correct(tmp_path, monkeypatch):
    from benchmark.lib import serve_cell
    from deeplearning4j_tpu.serving.decode import DecodeEngine
    sample = DecodeEngine._sample

    def off_by_one(self, logits, temps, topks, counter):
        return (sample(self, logits, temps, topks, counter) + 1) \
            % logits.shape[-1]

    monkeypatch.setattr(DecodeEngine, "_sample", off_by_one)
    cell = manifest.Cell(SERVING, "rpj3b-chat-steady").rehearsal()
    result, _, _ = serve_cell.run(cell, 6, 4.0, False, str(tmp_path), 0.0)
    assert result["correct"] is False
