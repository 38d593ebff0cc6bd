"""What the tests of the zoo's language models share: the byte budgets, the
seeded rows, the record of a family, and ONE body for each check that every
family's `test_*_model.py` makes of its model against the benchmark's plain
reference (`benchmark/references/<config>.py`, which imports nothing of the
program) at tiny widths on the CPU in float32.

A family is a `Family` in its own `_<family>_common.py`; what differs
between families is a field of the record, never a branch on a name. A new
family adds a record and the checks only it has, not a copy of these.

What a body costs is paid once: every whole-model gradient runs under
`jax.jit` (op by op it took three times as long, ROADMAP D12), the
reference's sound `train_steps` is followed once a family (both `fit()`
paths and every planted fault compare with it), its float32 gradient once a
batch (the float32 and the bfloat16 check compare with it), and a net that a
check only reads is built once a configuration. A check that calls `fit()`
builds its own net: `fit()` changes it. All of it is made under the budgets
below, which every case of every file sets alike.
"""
import contextlib
import dataclasses
import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import checks


@pytest.fixture(autouse=True)
def _budgets_at_the_tests_sizes(monkeypatch):
    """The layers work out from their shapes how much goes through at
    once; at the tests' sizes everything would. The budgets are cut so
    that the whole model (2 x 128 tokens, 8 (sequence, head) pairs) takes
    the paths the cell's sizes take: 2 groups of pairs, 4 dispatches of 64
    tokens, loss blocks of 64 positions."""
    from deeplearning4j_tpu.nn.layers import (
        attention, linear_attention, recurrent,
    )
    monkeypatch.setattr(linear_attention, "_SCAN_LIVE_BYTES",
                        4 * 20 * 128 * 16 * 4)
    monkeypatch.setattr(attention, "_DISPATCH_LIVE_BYTES",
                        64 * 2 * (2 * 32 + 2 * 24) * 4)
    monkeypatch.setattr(recurrent, "_LOSS_LIVE_BYTES", 64 * 96 * 8)


def _rows(seed, n, batch=2):
    rng = np.random.default_rng(seed)
    return [(np.frombuffer(rng.bytes(batch * 8 * 8 * 4), np.uint8).reshape(
        batch, 8, 8, 4), np.zeros((batch, 1), np.float32))
        for _ in range(n)]


def _close(got, want, tol):
    got, want = np.asarray(got), np.asarray(want)
    assert np.abs(got - want).max() <= tol * max(np.abs(want).max(), 1e-30)


#: `jax.jit` without the back end's own optimisation: of a program that
#: unrolls KDA's tile algebra column by column it is two fifths of the
#: compile and buys nothing a test reads (such a program runs in 0.1 s)
jit_unoptimised = functools.partial(
    jax.jit, compiler_options={"xla_backend_optimization_level": 0})


def with_gradients(fn, w, args, jit=jax.jit):
    """``fn(*args)`` whole and the gradient, in every argument, of its
    result's (of a tuple: its first result's) sum weighted by ``w``: one
    forward and one backward, ONE compiled program (op by op, and with the
    forward run again for the gradient, a layer took two to three times as
    long)."""
    def loss(*a):
        out = fn(*a)
        return jnp.sum((out[0] if isinstance(out, tuple) else out) * w), out
    (_, out), grads = jit(jax.value_and_grad(
        loss, tuple(range(len(args))), has_aux=True))(*args)
    return out, grads


def _next_token(ref, cfg, rows):
    """(ids, next-token labels, their mask) of one host batch."""
    ids = ref.decode_tokens(cfg, rows)
    nxt, keep = ref.targets(ids)
    return ids, nxt, keep


def _one_output(example):
    """A graph with one input and one output takes each in a tuple."""
    return tuple((part,) for part in example)


def score_is_the_loss(loss_fn):
    """`Family.ref_loss` of a reference whose ``loss_fn(cfg, params,
    example)`` is both what it differentiates and its score."""
    def ref_loss(cfg, params, example):
        loss = loss_fn(cfg, params, example)
        return loss, (loss, ())
    return ref_loss


@dataclasses.dataclass(frozen=True, eq=False)
class Family:
    """One LM family under test: its reference, its adapter, the published
    keys at test widths, and what its checks do differently."""
    ref: Any
    system: Any
    cfg: dict
    #: the stages `correct` compares first moments by, in the adapter's order
    stages: tuple
    #: (cfg, params, example) -> (what the step differentiates, (the score
    #: `fit()` reports, whatever else the family's own test compares))
    ref_loss: Callable
    #: (ref, cfg, uint8 rows of one host batch) -> the example
    example_of: Callable = _next_token
    #: example -> (inputs, labels, label masks) as `_score_fn` takes them
    operands: Callable = _one_output
    #: (cfg, params, example) -> the logits of the leading sequences
    ref_logits: Optional[Callable] = None
    #: sequences a batch (the cell's), and of the bfloat16 check's
    sequences: int = 2
    bf16_sequences: Optional[int] = None
    #: every stage's gradient norm under bfloat16 compute, relative
    bf16_stage_gap: float = 3e-2
    #: what a planted fault has to move at least, and whether a stage's
    #: first moment may be what moves (else the losses alone)
    fault_gap: float = 1e-4
    fault_by_stage: bool = False
    #: named scopes the compiled step has to carry
    scopes: tuple = ()
    #: rows one expert layer of the reference takes (the eight shares)
    layer_rows: int = 128
    #: what two float32 steps may differ by in the update's norm, and the
    #: leaves whose gradient is rounding noise by construction (Adam divides
    #: noise by its own size: their update is not compared leaf by leaf)
    update_norm_gap: float = 1e-5
    noise_leaves: tuple = ()

    def net(self, **over):
        """(a net of its own, its configuration): for a check that fits.
        Its leaves start COMMITTED to the device: a step leaves its results
        committed, so a net that starts uncommitted compiles its step a
        second time at the second call (ROADMAP D22)."""
        cfg = {**self.cfg, **over}
        net = self.system.build(cfg, self.ref.make_params(cfg))
        net.params, net.opt_state, net.state = jax.device_put(
            (net.params, net.opt_state, net.state), jax.devices()[0])
        return net, cfg

    def reader(self, **over):
        """`net(**over)` built once: for a check that only reads it."""
        return _reader(self, tuple(sorted(over.items())))

    def example(self, cfg, rows):
        """The example of one host batch's uint8 ``rows``."""
        return self.example_of(self.ref, cfg, rows)

    def rows(self, seed, n):
        """The harness's rows, cut to the family's sequences a batch."""
        return [(r[:self.sequences], y[:self.sequences])
                for r, y in _rows(seed, n)]

    def score_fn(self, net, example):
        """params -> (score, (state, carries)) of ``net`` on ``example``."""
        inputs, labels, masks = self.operands(example)
        return lambda params: net._score_fn(
            params, net.state, inputs, labels, None, masks, True,
            jax.random.PRNGKey(0))

    def score(self, net, params, example):
        return self.score_fn(net, example)(params)[0]


@functools.lru_cache(maxsize=None)
def _reader(family, over):
    return family.net(**dict(over))


def gradient(fn, params):
    """((value, aux), gradient) of ``fn`` at ``params``, compiled."""
    return jax.jit(jax.value_and_grad(fn, has_aux=True))(params)


# ----------------------------------------------- what is made once a family
@functools.lru_cache(maxsize=None)
def sound_steps(family):
    """(losses, first moment, params) of the SOUND reference over the two
    batches every `fit()` path and every planted fault is compared with."""
    return family.ref.train_steps(
        family.cfg, family.ref.make_params(family.cfg), family.rows(11, 2))


@functools.lru_cache(maxsize=None)
def reference_gradient(family, sequences):
    """(example, params, score, extras, gradient): the float32 reference on
    the leading ``sequences`` of the gradient checks' batch."""
    cfg = family.cfg
    example = family.example(cfg, _rows(4, 1)[0][0][:sequences])
    params = family.ref.make_params(cfg)
    (_, (score, extras)), grads = gradient(
        lambda p: family.ref_loss(cfg, p, example), params)
    return example, params, score, extras, grads


# ------------------------------------------------------ the checks' bodies
def two_adamw_steps_match(family, how):
    """Two optimizer steps through `fit()` against the reference's
    `train_steps`: the losses, AdamW's first moment by stage and the update,
    as the benchmark's `correct` compares them, then leaf by leaf. Returns
    the program's and the reference's rows for what a family adds."""
    ref, system = family.ref, family.system
    net, cfg = family.net()
    stamps = system.stamp_listener()
    net.set_listeners(stamps)
    net.fit(system.feed(family.rows(11, 2)), **how)
    losses = [loss for _, loss in stamps.rows]
    r_losses, r_m, r_params = sound_steps(family)
    np.testing.assert_allclose(losses, r_losses, rtol=2e-6)
    init = jax.device_get(ref.make_params(cfg))
    diff = lambda new: checks.leaf_norms(jax.tree_util.tree_map(
        lambda a, b: np.asarray(a) - np.asarray(b), new, init))
    prog = {"losses": losses, "update": diff(net.params),
            "momentum": checks.leaf_norms(system.momentum(net))}
    want = {"losses": r_losses, "update": diff(r_params),
            "momentum": checks.leaf_norms(r_m)}
    limits = {"loss_gap": 2e-6, "head_momentum_gap": 1e-4,
              "head_update_gap": 1e-4,
              "update_norm_gap": family.update_norm_gap,
              "stage_momentum_gap": {s: 1e-4 for s in family.stages}}
    rows = checks.training_rows(prog, want,
                                lambda leaf: ref.stage_of(cfg, leaf), limits)
    assert len(rows) == 4 + len(family.stages) and checks.verdict(rows)
    # gains, per-head scalars and biases are not decayed; matrices are
    sound = lambda rows: {k: v for k, v in rows.items()
                          if k not in family.noise_leaves}
    assert checks.worst_leaf_gap(sound(prog["update"]),
                                 sound(want["update"])) < 1e-3
    return prog, want


def logits_match(family):
    """The program's probabilities are the softmax of the reference's
    logits (of as many leading sequences as the reference gives). Returns
    the program's."""
    net, cfg = family.reader()
    example, params, *_ = reference_gradient(family, family.sequences)
    want = np.asarray(jax.nn.softmax(
        family.ref_logits(cfg, params, example), axis=-1))
    got = net.output(example[0])
    np.testing.assert_allclose(got[:len(want)], want, atol=2e-6)
    return got


def every_gradient_leaf_matches(family):
    """Float32 on both sides: the score and EVERY leaf of the step's
    gradient element by element. Returns the state the program's step
    leaves and the reference's extras for what a family adds."""
    net, _ = family.reader()
    example, params, want_l, extras, want = reference_gradient(
        family, family.sequences)
    (got_l, (state, _)), got = gradient(family.score_fn(net, example), params)
    np.testing.assert_allclose(got_l, want_l, rtol=2e-6)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                            jax.tree_util.tree_leaves(want)):
        scale = max(np.abs(np.asarray(b)).max(), 1e-7)
        assert np.abs(np.asarray(a - b)).max() <= 2e-4 * scale, \
            jax.tree_util.keystr(path)
    return state, extras


def bfloat16_stays_near(family):
    """bf16 operands over float32 weights, as the cell runs: the score to
    half a percent of the float32 reference's, every stage's gradient norm
    to the family's `bf16_stage_gap`."""
    net, cfg = family.reader(compute_dtype="bfloat16")
    example, params, want_l, _, want = reference_gradient(
        family, family.bf16_sequences or family.sequences)
    (got_l, _), got = gradient(family.score_fn(net, example), params)
    assert abs(float(got_l) - float(want_l)) < 5e-3 * float(want_l)
    gaps = checks.stage_gaps(checks.leaf_norms(got), checks.leaf_norms(want),
                             lambda leaf: family.ref.stage_of(cfg, leaf))
    assert set(gaps) == set(family.stages) \
        and max(gaps.values()) < family.bf16_stage_gap, gaps


def a_planted_fault_moves(family, fault):
    """One fault the limits have to catch, at the test's sizes: it moves the
    losses (or, where the family's faults show there, a stage's first
    moment) far more than float32 rounding. Returns the stages' gaps."""
    sound = sound_steps(family)
    bad = family.ref.train_steps(
        family.cfg, family.ref.make_params(family.cfg), family.rows(11, 2),
        fault=fault)
    loss = max(abs(a - b) / abs(b) for a, b in zip(bad[0], sound[0]))
    stage = checks.stage_gaps(checks.leaf_norms(bad[1]),
                              checks.leaf_norms(sound[1]),
                              lambda leaf: family.ref.stage_of(family.cfg,
                                                               leaf))
    moved = max(loss, *stage.values()) if family.fault_by_stage else loss
    assert moved > family.fault_gap, (fault, loss, stage)
    return stage


@contextlib.contextmanager
def fitted_under_the_ledger(family):
    """Four steps, scan-of-2, behind the adapter's listener with the
    ledger on: yields the net for what a family reads of its counters, then
    holds the compiled step to the family's scopes."""
    from deeplearning4j_tpu.monitor import xla
    net, _ = family.net()
    net.set_listeners(family.system.stamp_listener())
    xla.enable_ledger()
    try:
        net.fit(family.system.feed(_rows(6, 4)), scan_steps=2)
        yield net
        scopes = family.system.op_scopes()
        seen = {m for m in family.scopes
                if any(m in s for s in scopes.values())}
        assert seen == set(family.scopes), set(family.scopes) - seen
    finally:
        xla.disable_ledger()
        xla.clear_ledger()


def the_eight_shares_add_up(family):
    """`y = alike + sum over the chips of (what each chip's experts add)`:
    with a softmax router and no shared expert the eight shares' partial
    results of one expert layer add up to the uncut reference's whole
    layer, and a row none of whose experts a chip holds gets exactly zero
    from that chip."""
    from deeplearning4j_tpu.nn.conf.base import InputType
    from deeplearning4j_tpu.nn.layers import MoEFeedForward
    ref, t = family.ref, family.layer_rows
    first = lambda out: out[0] if isinstance(out, tuple) else out
    cfg = {**family.cfg, "experts_held": [0, 16], "num_experts": 16}
    whole = ref.make_params(cfg)["layer1"]
    x = jax.random.normal(jax.random.PRNGKey(5), (t, 32))
    want = first(ref.layer(cfg, whole, x))
    eps = cfg["rms_norm_eps"]
    alike = x + first(ref.attention(
        cfg, whole["attn"], ref._rms(x, whole["ln1"]["gamma"], eps)))
    normed = ref._rms(alike, whole["ln2"]["gamma"], eps)
    idx = np.asarray(ref.routing(cfg, whole["ffn"], normed)[0])
    total = np.zeros((t, 32), np.float32)
    for lo in range(0, 16, 2):
        ffn = MoEFeedForward(
            n_out=32, n_experts=16, top_k=2, hidden=24, activation="swish",
            gated=True, has_bias=False, experts_held=(lo, lo + 2),
            router="softmax", n_shared=0, weight_init="normal")
        p = {"Wr": whole["ffn"]["Wr"],
             **{k: whole["ffn"][k][lo:lo + 2]
                for k in ("Wgate", "Wup", "Wdown")}}
        _, state = ffn.init(jax.random.PRNGKey(0),
                            InputType.recurrent(32, t))
        out, new = ffn.apply(p, state, normed[None])
        out = np.asarray(out[0])
        # one share alone is the reference told to hold the same experts
        share = ref.experts({**cfg, "experts_held": [lo, lo + 2],
                             "num_experts": 2}, p, normed)
        _close(out, share, 2e-5)
        unheld = ~np.any((idx >= lo) & (idx < lo + 2), axis=-1)
        assert unheld.any() and not np.any(out[unheld])
        assert int(new["tokens_with_held_pair_total"]) == int((~unheld).sum())
        total += out
    _close(alike + total, want, 2e-5)
