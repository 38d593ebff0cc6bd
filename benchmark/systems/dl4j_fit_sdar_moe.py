"""The system under test for ``sdar-30b-a3b-chat``: the zoo's ``SdarMoeLM``
(block-diffusion training over the stream [noisy ; clean]) at the
configuration's sizes as a ``ComputationGraph``, trained through ``fit()``.
Everything the benchmark takes from the program for this configuration is
here: how to build the network from the configuration file and hand it the
seeded weights, how the harness's host bytes become the token batches a
user's iterator would yield and where the program's own pre-processor
makes denoising examples of them, where AdamW keeps its first moment, and
what the program's counters, gauges and its compiled-step ledger say to the
per-layer readers."""
from __future__ import annotations

import jax

from benchmark.lib.manifest import load_module

# the import the parent of the PR that brought this configuration fails
# at, at once: it has no such zoo model
from deeplearning4j_tpu.models import SdarMoeLM

STEP_PROGRAM = "jit_kstep"        # the scan-of-K program's name in a trace
_LEDGER_NAME = "graph/scan_step"  # the same program in the program's ledger
_REF = load_module("references", "sdar-30b-a3b-chat")
_CFG = {}                          # the configuration build() was given

# what does not depend on the model is the first LM adapters': where AdamW
# keeps its first moment, the stamping `ExpertLoadListener`, the goodput
# ledger's totals, the expert counters, the step's op -> scope map
_LM = load_module("systems", "dl4j_fit_lfm2_moe")
trained, momentum, make_plan = _LM.trained, _LM.momentum, _LM.make_plan
stamp_listener = _LM.stamp_listener
fit_seconds_by_category = _LM.fit_seconds_by_category
expert_rows_per_step = _LM.expert_rows_per_step
expert_load_max_over_mean = _LM.expert_load_max_over_mean
expert_rows_walked_over_live = _LM.expert_rows_walked_over_live
op_scopes = _LM.op_scopes


def network(cfg: dict):
    """An initialised ``ComputationGraph`` at the configuration's sizes
    (the zoo's own weights)."""
    assert cfg["updater"] == "adamw"
    assert cfg["norm_topk_prob"] and not cfg["attention_bias"]
    assert cfg["hidden_act"] == "silu" and not cfg["tie_word_embeddings"]
    assert not cfg["mlp_only_layers"] and cfg["decoder_sparse_step"] == 1
    assert cfg["rope_scaling"] is None and not cfg["use_sliding_window"]
    assert cfg["mask_token_id"] == _REF.mask_token_id(cfg)
    return SdarMoeLM(
        vocab_size=cfg["vocab_size"], seq_length=_REF.seq_length(cfg),
        block_length=cfg["block_length"], n_embd=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        rope_theta=float(cfg["rope_theta"]),
        n_experts=cfg["router_experts"], top_k=cfg["num_experts_per_tok"],
        expert_hidden=cfg["moe_intermediate_size"],
        experts_held=tuple(cfg["experts_held"]),
        rms_norm_eps=cfg["rms_norm_eps"],
        learning_rate=cfg["learning_rate"], beta1=cfg["beta1"],
        beta2=cfg["beta2"], epsilon=cfg["epsilon"],
        weight_decay=cfg["weight_decay"],
        compute_dtype=cfg["compute_dtype"],
        gradient_checkpointing=cfg["gradient_checkpointing"],
        block_size=cfg["attention_block"]).init()


def build(cfg: dict, params: dict):
    """The network holding the benchmark's seeded float32 weights (same
    names, same shapes)."""
    net = network(cfg)
    shapes = lambda t: jax.tree_util.tree_map(
        lambda a: (tuple(a.shape), str(a.dtype)), t)
    if shapes(net.params) != shapes(params):
        raise SystemExit("benchmark: the zoo's SdarMoeLM and the "
                         "configuration file disagree on the parameters")
    net.params = params
    _CFG.clear()
    _CFG.update(cfg)
    return net


def feed(batches, plan=None):
    """The data iterator a user hands to ``fit()``: token batches behind
    the async prefetch, the program's denoising pre-processor attached to
    the iterator (it makes the stream [noisy ; clean], the targets and the
    loss weights of each batch as the prefetch thread pulls it).
    ``batches`` are the harness's (uint8 rows, one-hot) pairs; a row's
    bytes are its token ids as uint16 on disk would be, the reference's
    ``decode_tokens`` reads them, the one-hot is ignored. The noise is
    seeded by the first batch's bytes (`noise_seed`), which the harness
    draws from ``--seed``. A traced run also switches the program's
    compiled-step ledger on, which keeps the step's op -> scope map for
    the readers."""
    from deeplearning4j_tpu import monitor
    from deeplearning4j_tpu.data.async_iterator import AsyncDataSetIterator
    from deeplearning4j_tpu.data.dataset import MultiDataSet
    from deeplearning4j_tpu.data.denoise import BlockDiffusionPreProcessor
    from deeplearning4j_tpu.data.iterator import DataSetIterator
    if monitor.tracing_enabled():
        monitor.xla.enable_ledger()
    denoise = BlockDiffusionPreProcessor(
        _CFG["mask_token_id"], _CFG["block_length"],
        t_min=_CFG["noise_t_min"])

    class TokenBatches(DataSetIterator):
        def __iter__(self):
            for i, (rows, _) in enumerate(batches):
                if i == 0:
                    denoise.noise_seed = _REF.noise_seed(rows)
                yield self._pp(MultiDataSet(
                    (_REF.decode_tokens(_CFG, rows),), None, None, None))

    device = None if plan is None else plan.batch_sharding()
    return AsyncDataSetIterator(TokenBatches(), device=device) \
        .set_pre_processor(denoise)


# ------------------------------------------- for the per-layer readers
def _dump(family: str) -> list:
    from deeplearning4j_tpu import monitor
    return monitor.dump().get(family, {}).get("series", [])


def _total(family: str) -> float:
    return sum(s["value"] for s in _dump(family))


def tokens_with_held_pair_share():
    """Stream rows with at least one of their experts held here over the
    rows routed, all expert layers together:
    ``moe_tokens_with_held_pair_total`` over ``moe_tokens_routed_total``
    (pairs) / ``num_experts_per_tok``; None without the counter."""
    held = _total("moe_tokens_with_held_pair_total")
    pairs = _total("moe_tokens_routed_total")
    if not held or not pairs:
        return None
    return held / (pairs / _CFG["num_experts_per_tok"])


def denoise_counts():
    """(positions the pre-processor masked, positions it saw) since the
    process began: ``denoise_masked_total`` and
    ``denoise_positions_total``; None without the counters."""
    masked, seen = (_total("denoise_masked_total"),
                    _total("denoise_positions_total"))
    return (masked, seen) if seen else None


def tiles_walked_over_live():
    """The gauge ``flash_tiles_walked_over_live`` of the flash call traced
    last; None without it."""
    series = _dump("flash_tiles_walked_over_live")
    return series[0]["value"] if series else None
