"""The system under test for ``keye-vl-2.0-30b-a3b``: the zoo's
``KeyeVL2LM`` (the language model; no vision tower) at the configuration's
sizes as a ``ComputationGraph``, trained through ``fit()``. Everything the
benchmark takes from the program for this configuration is here: how to
build the network from the configuration file and hand it the seeded
weights, how the harness's host bytes become the token batches a user's
iterator would yield, where AdamW keeps its first moment, and what the
program's counters and its compiled-step ledger say to the per-layer
readers."""
from __future__ import annotations

import jax

from benchmark.lib.manifest import load_module

# the import the parent of the PR that brought this configuration fails
# at, at once: it has no such zoo model
from deeplearning4j_tpu.models import KeyeVL2LM

STEP_PROGRAM = "jit_kstep"        # the scan-of-K program's name in a trace
_LEDGER_NAME = "graph/scan_step"  # the same program in the program's ledger
_REF = load_module("references", "keye-vl-2.0-30b-a3b")
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
    assert cfg["rope_scaling"]["rope_type"] == "default"
    assert not cfg["use_sliding_window"]
    sa = cfg["sa_config"]
    assert sa["indexer_num_kv_heads"] == 1
    return KeyeVL2LM(
        vocab_size=cfg["vocab_size"], seq_length=_REF.seq_length(cfg),
        n_embd=cfg["hidden_size"], n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        rope_theta=float(cfg["rope_theta"]),
        mrope_section=tuple(cfg["rope_scaling"]["mrope_section"]),
        indexer_heads=sa["indexer_num_heads"],
        indexer_head_dim=sa["indexer_head_dim"], topk=sa["topk"],
        indexer_loss_coef=cfg["indexer_loss_coef"],
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
        raise SystemExit("benchmark: the zoo's KeyeVL2LM and the "
                         "configuration file disagree on the parameters")
    net.params = params
    _CFG.clear()
    _CFG.update(cfg)
    return net


def feed(batches, plan=None):
    """The data iterator a user hands to ``fit()``: token batches behind
    the async prefetch. ``batches`` are the harness's (uint8 rows, one-hot)
    pairs; a row's bytes are its token ids as uint16 on disk would be, the
    reference's ``decode_tokens`` reads them, the one-hot is ignored, and
    the targets are the next token (none for a sequence's last position).
    A traced run also switches the program's compiled-step ledger on,
    which keeps the step's op -> scope map for the readers."""
    from deeplearning4j_tpu import monitor
    from deeplearning4j_tpu.data.async_iterator import AsyncDataSetIterator
    from deeplearning4j_tpu.data.dataset import MultiDataSet
    from deeplearning4j_tpu.data.iterator import DataSetIterator
    if monitor.tracing_enabled():
        monitor.xla.enable_ledger()

    class TokenBatches(DataSetIterator):
        def __iter__(self):
            for rows, _ in batches:
                ids = _REF.decode_tokens(_CFG, rows)
                nxt, keep = _REF.targets(ids)
                yield MultiDataSet((ids,), (nxt,), None, (keep,))

    device = None if plan is None else plan.batch_sharding()
    return AsyncDataSetIterator(TokenBatches(), device=device)


# ------------------------------------------- for the per-layer readers
def _total(family: str) -> float:
    from deeplearning4j_tpu import monitor
    return sum(s["value"] for s in
               monitor.dump().get(family, {}).get("series", []))


def tokens_with_held_pair_share():
    """Tokens with at least one of their experts held here over the tokens
    routed, all expert layers together: ``moe_tokens_with_held_pair_total``
    over ``moe_tokens_routed_total`` (pairs) / ``num_experts_per_tok``;
    None without the counter."""
    held = _total("moe_tokens_with_held_pair_total")
    pairs = _total("moe_tokens_routed_total")
    if not held or not pairs:
        return None
    return held / (pairs / _CFG["num_experts_per_tok"])


def sparse_pairs():
    """(pairs the sparse attentions' selections kept, causal pairs they
    chose among), all layers together since the process began:
    ``dsa_pairs_selected_total`` and ``dsa_pairs_causal_total``; None
    without the counters."""
    kept, causal = (_total("dsa_pairs_selected_total"),
                    _total("dsa_pairs_causal_total"))
    return (kept, causal) if kept and causal else None
