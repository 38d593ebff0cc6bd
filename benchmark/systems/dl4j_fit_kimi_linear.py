"""The system under test for ``kimi-linear-48b-a3b``: the zoo's
``KimiLinearLM`` at the configuration's sizes as a ``MultiLayerNetwork``,
trained through ``fit()``. Everything the benchmark takes from the program
for this configuration is here: how to build the network from the
configuration file and hand it the seeded weights, how the harness's host
bytes become the token batches a user's iterator would yield, where AdamW
keeps its first moment, and what the program's counters and its
compiled-step ledger say to the per-layer readers."""
from __future__ import annotations

import time

import jax

from benchmark.lib.manifest import load_module

# the import the parent of the PR that brought this configuration fails
# at, at once: it has no such zoo model
from deeplearning4j_tpu.models import KimiLinearLM

STEP_PROGRAM = "jit_kstep"      # the scan-of-K program's name in a trace
_LEDGER_NAME = "mln/scan_step"  # the same program in the program's ledger
_REF = load_module("references", "kimi-linear-48b-a3b")
_CFG = {}                        # the configuration build() was given


def network(cfg: dict):
    """An initialised ``MultiLayerNetwork`` at the configuration's sizes
    (the zoo's own weights)."""
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    assert cfg["updater"] == "adamw"
    # the one sigmoid router the expert layer has: renormalised, one group
    assert cfg["moe_router_activation_func"] == "sigmoid"
    assert cfg["moe_renormalize"] and cfg["num_expert_group"] == 1
    assert cfg["mla_use_nope"] and cfg["q_lora_rank"] is None
    assert cfg["hidden_act"] == "silu" and cfg["moe_layer_freq"] == 1
    la = cfg["linear_attn_config"]
    assert la["num_heads"] == cfg["num_attention_heads"]
    model = KimiLinearLM(
        vocab_size=cfg["vocab_size"], seq_length=_REF.seq_length(cfg),
        n_embd=cfg["hidden_size"], n_layers=cfg["num_hidden_layers"],
        kda_layers=tuple(la["kda_layers"]),
        full_attn_layers=tuple(la["full_attn_layers"]),
        n_heads=la["num_heads"], kda_head_dim=la["head_dim"],
        conv_kernel=la["short_conv_kernel_size"],
        kda_chunk=cfg["kda_chunk"],
        kda_low_rank=cfg["kda_low_rank"],
        nope_dim=cfg["qk_nope_head_dim"], rope_dim=cfg["qk_rope_head_dim"],
        v_dim=cfg["v_head_dim"], kv_rank=cfg["kv_lora_rank"],
        first_k_dense=cfg["first_k_dense_replace"],
        dense_hidden=cfg["intermediate_size"],
        n_experts=cfg["router_experts"],
        top_k=cfg["num_experts_per_token"],
        expert_hidden=cfg["moe_intermediate_size"],
        n_shared=cfg["num_shared_experts"],
        routed_scale=cfg["routed_scaling_factor"],
        experts_held=tuple(cfg["experts_held"]),
        rms_norm_eps=cfg["rms_norm_eps"],
        learning_rate=cfg["learning_rate"], beta1=cfg["beta1"],
        beta2=cfg["beta2"], epsilon=cfg["epsilon"],
        weight_decay=cfg["weight_decay"],
        compute_dtype=cfg["compute_dtype"],
        gradient_checkpointing=cfg["gradient_checkpointing"],
        block_size=cfg["attention_block"])
    return MultiLayerNetwork(model.conf()).init()


def build(cfg: dict, params: dict):
    """The network holding the benchmark's seeded float32 weights (same
    names, same shapes)."""
    net = network(cfg)
    shapes = lambda t: jax.tree_util.tree_map(
        lambda a: (tuple(a.shape), str(a.dtype)), t)
    if shapes(net.params) != shapes(params):
        raise SystemExit("benchmark: the zoo's KimiLinearLM and the "
                         "configuration file disagree on the parameters")
    net.params = params
    _CFG.clear()
    _CFG.update(cfg)
    return net


def trained(tree):
    """The entries of a program tree that hold parameters: all of them."""
    return tree


def momentum(net):
    """AdamW's first moment, a tree shaped like the params."""
    found = []

    def walk(node):
        if hasattr(node, "mu"):
            found.append(node.mu)
        elif isinstance(node, (tuple, list)):
            for child in node:
                walk(child)

    walk(net.opt_state)
    (mu,) = found
    return mu


def feed(batches, plan=None):
    """The data iterator a user hands to ``fit()``: token batches behind
    the async prefetch. ``batches`` are the harness's (uint8 rows, one-hot)
    pairs; a row's bytes are its token ids as uint16 on disk would be, the
    reference's ``decode_tokens`` reads them, the one-hot is ignored, and
    the targets are the next token (none for a sequence's last
    position). A traced run also switches the program's compiled-step
    ledger on, which keeps the step's op -> scope map for the readers."""
    from deeplearning4j_tpu import monitor
    from deeplearning4j_tpu.data.async_iterator import AsyncDataSetIterator
    from deeplearning4j_tpu.data.dataset import DataSet
    from deeplearning4j_tpu.data.iterator import DataSetIterator
    if monitor.tracing_enabled():
        monitor.xla.enable_ledger()

    class TokenBatches(DataSetIterator):
        def __iter__(self):
            for rows, _ in batches:
                ids = _REF.decode_tokens(_CFG, rows)
                nxt, keep = _REF.targets(ids)
                yield DataSet(ids, nxt, None, keep)

    device = None if plan is None else plan.batch_sharding()
    return AsyncDataSetIterator(TokenBatches(), device=device)


def make_plan(kind):
    assert kind is None, "this configuration's cell runs on one chip"
    return None


def stamp_listener():
    """A listener that keeps ``(time.monotonic(), loss)`` of every
    optimizer step as ``fit()`` reports it (a chunk's steps together, once
    its losses are on the host); being the program's ``ExpertLoadListener``
    it also publishes the expert layers' counters when a ``fit()`` ends."""
    from deeplearning4j_tpu.train.listeners import ExpertLoadListener

    class Stamps(ExpertLoadListener):
        def __init__(self):
            super().__init__()
            self.rows = []

        def iteration_done(self, model, iteration, epoch, score,
                           etl_ms=0.0, batch_size=0):
            self.rows.append((time.monotonic(), float(score)))

    return Stamps()


def fit_seconds_by_category():
    """The goodput ledger's running totals (``train_time_seconds_total``):
    seconds of ``fit()`` wall time by category since the process began."""
    from deeplearning4j_tpu.monitor import metrics
    family = metrics.counter("train_time_seconds_total", "",
                             labels=("category",))
    return {c: family.value(category=c)
            for c in ("step_compute", "data_wait", "host_sync", "compile",
                      "checkpoint", "eval_gate", "resume_replay", "other")}


# ------------------------------------------- for the per-layer readers
def op_scopes():
    """{compiled instruction name: op_name} of the step program, from the
    program's ledger; None where the program keeps no such map."""
    from deeplearning4j_tpu.monitor import xla
    recs = [r for r in xla.records() if r.name == _LEDGER_NAME]
    scopes = getattr(recs[-1], "op_scopes", None) if recs else None
    return scopes or None


def expert_rows_per_step():
    """Token rows the HELD experts of one layer drew in a mean optimizer
    step, by layer, from ``moe_tokens_routed_total{layer,held}`` over
    ``train_iterations_total``; None without the counter."""
    from deeplearning4j_tpu import monitor
    dump = monitor.dump()
    steps = sum(s["value"] for s in dump.get(
        "train_iterations_total", {}).get("series", []))
    routed = dump.get("moe_tokens_routed_total", {}).get("series", [])
    rows = {s["labels"]["layer"]: s["value"] / steps for s in routed
            if s["labels"].get("held") == "yes" and steps}
    return rows or None


def expert_load_max_over_mean():
    """The busiest layer's ``moe_expert_load_max_over_mean`` gauge."""
    from deeplearning4j_tpu import monitor
    series = monitor.dump().get("moe_expert_load_max_over_mean", {}).get(
        "series", [])
    return max((s["value"] for s in series), default=None)
