"""The training loop under both containers.

`MultiLayerNetwork.fit()` and `ComputationGraph.fit()` are this module's
`fit()`: the compiled steps (`build_step`: per-call `step`, scan-of-K
`kstep`, accumulation `kaccum`, all ending in ONE update tail), the epoch
drivers around them (per-call, scan, accumulation; the tBPTT chunk loop)
and the body that engages the plan, launders the donated trees, engages
the device affine, opens the goodput session and runs the epochs.

What a container supplies, as methods and class constants:

- `_score_fn(params, state, inputs, labels, fmasks, lmasks, train, rng,
  carries=)`: its forward's loss;
- `_operands(batch)` -> `(inputs, labels, fmasks, lmasks)` staged on the
  device and placed per the active plan, and `_stage_stacked(group)`, the
  same for K same-shape batches stacked on a new leading axis;
- `_batch_sig(batch)`, `_batch_examples(batch)`: the shape signature that
  groups batches into chunks, and a batch's rows;
- `_fit_source(data, stacking, ...)` once a `fit()` and
  `_epoch_batches(source, stacking)` once an epoch: the source policy
  (what is wrapped in a prefetch thread, and on which path);
- `_fit_epoch_tbptt(batches, rng)`: its time-slicing rule around
  `_fit_tbptt_batch`;
- `_make_scan_step()`: the scan-of-K step (`build_step(self, "kstep")`);
- `_LEDGER_PREFIX` (the ledger's and the goodput session's names),
  `_RNG_MULT`, `_RNG_MULT_TBPTT`, `_RNG_PER_EPOCH` (the RNG stream).

The module holds the `own_tree` laundering (`_engage_plan_impl`) next to
the donating programs it builds: graftlint's `donated-aliasing` contract.
"""
from __future__ import annotations

import logging
import time
import weakref
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.data.async_iterator import host_cast
from deeplearning4j_tpu.nn.updaters import apply_update
from deeplearning4j_tpu.util import params as param_util
from deeplearning4j_tpu.util.env import env_int
from deeplearning4j_tpu.util.platform import is_tpu_backend

log = logging.getLogger("deeplearning4j_tpu")


def _scan_incompatible_listeners(listeners) -> bool:
    """Listeners that inspect the model (params/opt state) or capture
    gradients need iteration_done in lockstep with the params — the
    pipelined scan fit delivers it up to 2K-1 steps late, so their
    presence forces the per-call path."""
    return any(getattr(lst, "wants_gradients", False)
               or getattr(lst, "reads_model", False)
               for lst in listeners)


def _record_iteration(score: float, batch_size: int,
                      step_seconds: Optional[float] = None,
                      sync_seconds: Optional[float] = None):
    """One optimizer step's worth of telemetry (monitor/metrics.py) —
    shared by every fit path of both containers and the resilient
    trainer, so `train_*` series mean the same thing everywhere. Only
    host scalars are touched: no device sync is introduced."""
    from deeplearning4j_tpu import monitor
    monitor.counter("train_iterations_total",
                    "Optimizer steps applied").inc()
    monitor.counter("train_examples_total",
                    "Training examples consumed").inc(batch_size)
    monitor.gauge("train_score", "Last training loss/score").set(score)
    if step_seconds is not None:
        monitor.histogram("train_step_seconds",
                          "Train step wall time (dispatch + host sync)"
                          ).observe(step_seconds)
    if sync_seconds is not None:
        monitor.histogram("train_host_sync_seconds",
                          "Blocking device->host loss fetch per step"
                          ).observe(sync_seconds)


def _run_scan_pipeline(batches, K, *, sig_of, examples_of, stage, launch,
                       fetch, notify, defer=True, first_chunk=0):
    """Shared chunking/deferral loop of the input-pipelined fit paths
    (`_fit_epoch_scan` / `_fit_epoch_accum`).

    One turn pulls consecutive batches with identical shape signature
    `sig_of(b)` into a chunk of at most K, stages and launches it
    (`stage(group)` -> staged device inputs, `launch(staged, etl_ms)` ->
    an opaque pending record whose device values are still futures), and
    only then syncs the chunk launched one turn EARLIER
    (`fetch(pending)` blocks on its losses, `notify(pending, fetched)`
    runs the per-step bookkeeping and listeners and returns the number
    of optimizer steps it reported) — so staging and launching chunk i
    overlap the device compute of chunk i-1, and the one blocking loss
    fetch per chunk happens while the device is busy (on a TPU the
    staging's own enqueues can block first: PERF.md section 5). The last turn
    pulls nothing and drains. defer=False syncs each chunk in the turn
    that launched it (model-reading listeners must observe the params
    as of the step they're told about).

    Every phase is an ENTERED span, so with
    `enable_tracing(jax_annotations=True)` the whole tree is on the
    profiler's host plane (docs/OBSERVABILITY.md "Tracing"):

        train/chunk                 chunk=i batches= examples= steps=
          train/etl                 batches=    (etl/queue_wait inside)
          train/dispatch            chunk=i
            train/stage / train/launch
          train/chunk_sync          chunk=i-1
            train/loss_fetch / train/listeners steps=

    `chunk` counts from `first_chunk` (`fit()` keeps it running over the
    epochs of one call); returns the next chunk's number."""
    from deeplearning4j_tpu import monitor
    span = monitor.span
    it = iter(batches)
    chunk = first_chunk
    held = None          # the batch whose shape change closed the last group
    pending = None       # (chunk, record) launched and not yet synced
    exhausted = False
    while not (exhausted and held is None and pending is None):
        with span("train/chunk", chunk=chunk) as turn:
            etl_start = time.perf_counter()
            with span("train/etl") as etl:
                group, held = ([] if held is None else [held]), None
                gsig = sig_of(group[0]) if group else None
                while len(group) < K and not exhausted:
                    try:
                        b = next(it)
                    except StopIteration:
                        exhausted = True
                        break
                    s = sig_of(b)
                    if group and s != gsig:
                        held = b
                        break
                    group.append(b)
                    gsig = s
                etl.set(batches=len(group))
            etl_ms = (time.perf_counter() - etl_start) * 1e3
            fresh = None
            if group:
                with span("train/dispatch", chunk=chunk):
                    with span("train/stage"):
                        staged = stage(group)
                    with span("train/launch"):
                        fresh = (chunk, launch(staged, etl_ms))
                    # the launched program alone keeps its inputs from
                    # here: a reference held into the next turn's stage()
                    # adds a whole chunk of device memory to the peak
                    # (PERF.md section 6, PR 24)
                    del staged
            due, pending = (pending, fresh) if defer else (fresh, None)
            steps = 0
            if due is not None:
                with span("train/chunk_sync", chunk=due[0]):
                    with span("train/loss_fetch"):
                        fetched = fetch(due[1])
                    with span("train/listeners") as told:
                        steps = notify(due[1], fetched)
                        told.set(steps=steps)
            turn.set(batches=len(group), steps=steps,
                     examples=len(group) * examples_of(group[0])
                     if group else 0)
        if not group:
            break
        chunk += 1
    return chunk


def _default_scan_steps() -> int:
    """Production fit() pipelining default: scan-of-10 on the TPU, which
    removes all per-step dispatch and is the path both cells of the
    benchmark run (`fit_window_rate_ratio` 99.5 % and 99.7 %, ledger
    PR 28); scan against per-call has no cell on the chip yet (ROADMAP
    W5). On the CPU XLA pessimizes convolutions inside scan, so
    per-call stays the CPU default. DL4J_TPU_SCAN_STEPS overrides
    either way."""
    env = env_int("DL4J_TPU_SCAN_STEPS")
    if env is not None:
        return env
    # TPU only — GPU/other backends are unmeasured, and conv-in-scan can
    # regress badly off-TPU
    return 10 if is_tpu_backend() else 1


def _engage_plan_impl(net, plan):
    """Shared by `fit()` and the resilience drivers: activate a GSPMD
    ShardingPlan for a net's compiled steps — or plain single-device
    training when None. Either way
    params/opt/state are laundered into XLA-owned buffers
    (donated-buffer safety, util/params.owned_leaf); under a plan the
    laundered copies additionally land on the plan's placements
    (sharding-aware own_tree), and a plan CHANGE drops the compiled-step
    cache so the next step re-lowers against the new layout instead of
    silently running the old one."""
    prior = net._plan
    if plan != prior:
        net._plan = plan
        net._steps = {}
        net._output_fn = None
        # the ledger cache keys on id(step_fn): with the old jitted fns
        # dropped above, CPython may reuse their ids for the NEW steps —
        # a stale hit would misattribute the re-compiled (sharded)
        # program's timings to the old record
        net._ledger_cache = {}
    if plan is None:
        if prior is not None:
            # leaving a plan: gather mesh-committed leaves back to the
            # default device FIRST — the owned copy below preserves
            # committed shardings, and a plain fit stages its batches
            # single-device (incompatible-devices error otherwise)
            dev = jax.local_devices()[0]
            gather = lambda t: jax.tree_util.tree_map(
                lambda a: jax.device_put(a, dev), t)
            net.params = gather(net.params)
            net.state = gather(net.state)
            net.opt_state = gather(net.opt_state)
        net.params = param_util.own_tree(net.params)
        net.state = param_util.own_tree(net.state)
        net.opt_state = param_util.own_tree(net.opt_state)
    else:
        net.params = param_util.own_tree(
            net.params, plan.param_shardings(net.params))
        net.state = param_util.own_tree(
            net.state, plan.replicated_shardings(net.state))
        net.opt_state = param_util.own_tree(
            net.opt_state, plan.opt_shardings(net.opt_state, net.params))


def _stage_with_affine(net, a):
    """Features -> device, for both containers' `_operands`. With a
    device affine engaged (fit through
    a `device_affine()` pre-processor), RAW features ship over the
    host->HBM link (uint8 pixels stay uint8: 4x fewer bytes than
    float32, 2x fewer than the bf16 host cast) and the normalization
    runs on device in one fused jit; otherwise plain _as_jnp."""
    if net._input_affine is None:
        return _as_jnp(a, net._compute_dtype)
    if net._affine_fn is None:
        from deeplearning4j_tpu.data.normalization import make_affine_fn
        net._affine_fn = make_affine_fn(net._compute_dtype)
    shift, scale = net._input_affine
    return net._affine_fn(jnp.asarray(a), shift, scale)


def _as_jnp(a, dtype=None):
    if a is None:
        return None
    # 16-bit compute dtypes (bfloat16 training): cast float32 host arrays
    # BEFORE the device transfer (bit-identical to the device cast; f64 is
    # excluded — its old path double-rounds via f32 with x64 disabled).
    # Shared rule: data/async_iterator.host_cast (DL4J_TPU_HOST_CAST=0
    # restores transfer-then-cast).
    a = host_cast(a, dtype)
    arr = jnp.asarray(a)
    # floats cast to the compute dtype; so do raw uint8 image bytes
    # (ImageRecordReader reference parity) used WITHOUT a normalizer.
    # Wider int dtypes stay integer — they are embedding/sparse-label
    # token ids, not pixels.
    if dtype is not None and (jnp.issubdtype(arr.dtype, jnp.floating)
                              or arr.dtype == jnp.uint8):
        arr = arr.astype(dtype)
    return arr


# ------------------------------------------------------- the compiled steps
def build_step(net, kind, with_stats=False):
    """One of a net's three compiled train programs, each with donated
    params/opt-state/state:

    - `"step"`: one optimizer step a call; also returns the recurrent
      carries (tBPTT). `with_stats` additionally returns the raw gradient
      and update pytrees for `wants_gradients` listeners (DL4J
      onGradientCalculation / onBackwardPass hooks): a separate jit
      variant, so the fast path transfers nothing extra.
    - `"kstep"`: K optimizer steps fused by lax.scan — the same math
      applied K times; the K per-step losses come back as one device
      array, so the host never syncs inside the chunk.
    - `"kaccum"`: gradient accumulation — K micro-batch gradients
      averaged into ONE optimizer step (TPU-native big-effective-batch
      training: the HBM cost is one extra gradient-sized accumulator,
      not a K-times batch). For equal micro-batch sizes and
      batch-independent layers the result is bit-comparable to one
      big-batch step (mean of equal-size micro means == full-batch mean;
      tested); BatchNormalization statistics remain per micro-batch, the
      same semantics every framework's accumulation has. `with_stats`
      returns the AVERAGED gradients and their updates.

    One jit serves every chunk length and mask presence (jax retraces by
    pytree structure and shape)."""
    from deeplearning4j_tpu.nn.regularization import (
        apply_constraints, constraint_map, has_constraints,
    )
    # the program lives in `net._steps` and traces through the net: it
    # holds the net weakly, or net and program would keep each other (and
    # the parameters' device memory) until the collector's next full pass
    net = weakref.proxy(net)
    tx = net._tx
    layer_map = constraint_map(net)
    constrained = has_constraints(layer_map.values())
    plan = net._plan   # GSPMD plan: sharding constraints in-jit

    # a graph with several outputs returns each output's own loss beside
    # the score: `loss` is then [score, parts...] (`score_of`)
    several = len(getattr(net.conf, "network_outputs", ())) > 1

    def grads_of(params, state, batch, rng, carries=None):
        inputs, labels, fmasks, lmasks = batch
        def loss_fn(p):
            if several:
                score, aux, parts = net._score_parts(
                    p, state, inputs, labels, fmasks, lmasks, True, rng,
                    carries)
                return score, (aux, parts)
            return net._score_fn(p, state, inputs, labels, fmasks, lmasks,
                                 True, rng, carries=carries)
        (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params)
        if several:
            aux, parts = aux
            loss = jnp.stack([loss, *(p.astype(loss.dtype) for p in parts)])
        return (loss, aux), grads

    def update(params, opt_state, state, grads):
        """The one tail of all three programs."""
        new_params, new_opt, updates = apply_update(
            tx, grads, opt_state, params, plan)
        if constrained:     # post-update projection (DL4J applyConstraints)
            new_params = apply_constraints(layer_map, new_params)
        if plan is not None:
            new_params = plan.constrain_params(new_params)
            new_opt = plan.constrain_opt(new_opt, new_params)
            state = plan.constrain_replicated(state)
        return new_params, new_opt, state, updates

    def one_step(params, opt_state, state, batch, rng, carries=None):
        (loss, (state, new_carries)), grads = grads_of(
            params, state, batch, rng, carries)
        if plan is not None:
            # pin grads to the ZeRO/TP compute layout: this single
            # hint makes XLA derive reduce-scatter -> sharded update
            # -> all-gather (parallel/plan.py)
            grads = plan.constrain_grads(grads)
        params, opt_state, state, updates = update(
            params, opt_state, state, grads)
        return (params, opt_state, state), loss, new_carries, (grads,
                                                               updates)

    def step(params, opt_state, state, inputs, labels, fmasks, lmasks, rng,
             carries):
        new, loss, new_carries, stats = one_step(
            params, opt_state, state, (inputs, labels, fmasks, lmasks),
            rng, carries)
        return new + (loss, new_carries) + (stats if with_stats else ())

    def kstep(params, opt_state, state, inputs, labels, fmasks, lmasks,
              subs):
        def body(carry, batch):
            new, loss, _, _ = one_step(*carry, batch[:4], batch[4])
            return new, loss

        (params, opt_state, state), losses = jax.lax.scan(
            body, (params, opt_state, state),
            (inputs, labels, fmasks, lmasks, subs))
        return params, opt_state, state, losses

    def kaccum(params, opt_state, state, inputs, labels, fmasks, lmasks,
               subs):
        def body(carry, batch):
            gsum, state = carry
            (loss, (new_state, _)), grads = grads_of(
                params, state, batch[:4], batch[4])
            gsum = jax.tree_util.tree_map(jnp.add, gsum, grads)
            if plan is not None:
                # the accumulator carries in the ZeRO layout: micro-
                # batch grads reduce-scatter into it instead of ever
                # materializing whole per chip
                gsum = plan.constrain_grads(gsum)
            return (gsum, new_state), loss

        zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
        (gsum, state), losses = jax.lax.scan(
            body, (zeros, state), (inputs, labels, fmasks, lmasks, subs))
        grads = jax.tree_util.tree_map(lambda g: g / subs.shape[0], gsum)
        new_params, new_opt, state, updates = update(
            params, opt_state, state, grads)
        return (new_params, new_opt, state, jnp.mean(losses, axis=0)
                if several else jnp.mean(losses)) + (
            (grads, updates) if with_stats else ())

    return jax.jit({"step": step, "kstep": kstep, "kaccum": kaccum}[kind],
                   donate_argnums=(0, 1, 2))


def compiled_step(net, kind="step", with_stats=False):
    """A net's compiled step of that kind, from its one cache
    (`net._steps`, dropped by `_engage_plan_impl` on a plan change and by
    `_build_optimizer`). The scan step is built through the container's
    `_make_scan_step()`."""
    key = (kind, with_stats)
    if key not in net._steps:
        net._steps[key] = (net._make_scan_step() if kind == "kstep"
                           else build_step(net, kind, with_stats))
    return net._steps[key]


# --------------------------------------------------------- the epoch drivers
def _capture_program(net, program, fn, operands, args, **per_call):
    """The compiled-step ledger's record of `fn` at these operands'
    shapes, and whether this was its first sight (whose AOT compile the
    caller keeps out of the step clock)."""
    from deeplearning4j_tpu.monitor import xla as xla_ledger
    key = (id(fn), xla_ledger.shape_key(operands))
    fresh = key not in net._ledger_cache
    rec = xla_ledger.capture_cached(
        net._ledger_cache, key, f"{net._LEDGER_PREFIX}/{program}", fn,
        args, **per_call)
    return rec, fresh


def _split_keys(rng, n):
    """The next n step keys of a fit's RNG stream, drawn one split a
    step (the same stream on every path), and the stream's new head."""
    subs = []
    for _ in range(n):
        rng, sub = jax.random.split(rng)
        subs.append(sub)
    return rng, subs


def _capturing(net):
    """The `wants_gradients` listeners that ask for this step's trees."""
    return [lst for lst in net.listeners
            if getattr(lst, "wants_gradients", False)
            and lst.should_capture(net.iteration_count)]


def score_of(net, loss) -> float:
    """The score in a step's fetched loss. A graph with several outputs
    returns ``[score, each output's own loss...]`` from its compiled
    steps (`build_step`), so that one fetch brings them all: the parts
    go to the gauge ``train_output_loss{output}``, unweighted."""
    if np.ndim(loss) == 0:
        return float(loss)
    from deeplearning4j_tpu import monitor
    loss = np.asarray(loss)
    gauge = monitor.gauge(
        "train_output_loss", "Last training loss of one output of a graph "
        "with several (unweighted; train_score is their weighted sum)",
        labels=("output",))
    for name, part in zip(net.conf.network_outputs, loss[1:]):
        gauge.set(float(part), output=name)
    return float(loss[0])


def _fit_epoch_per_call(net, batches, rng):
    """One compiled step a batch, with the one budgeted loss fetch a
    step; returns the RNG stream's head."""
    from deeplearning4j_tpu import monitor
    from deeplearning4j_tpu.monitor import goodput
    from deeplearning4j_tpu.monitor import xla as xla_ledger
    etl_start = time.perf_counter()
    for batch in batches:
        step_start = time.perf_counter()
        etl_ms = (step_start - etl_start) * 1e3
        monitor.add_span("train/etl", etl_start, step_start,
                         iteration=net.iteration_count)
        rng, sub = jax.random.split(rng)
        capture = _capturing(net)
        step = compiled_step(net, "step", with_stats=bool(capture))
        operands = net._operands(batch)
        out = step(net.params, net.opt_state, net.state, *operands, sub,
                   None)
        net.params, net.opt_state, net.state, loss, _ = out[:5]
        grads, updates = out[5:] if capture else (None, None)
        sync_start = time.perf_counter()
        # block for device completion FIRST (goodput: step_compute;
        # banks per-shard barrier wait under a plan), so the
        # host_sync span below covers only the narrow D2H fetch
        goodput.device_wait(loss)
        fetch_start = time.perf_counter()
        monitor.add_span("train/device_wait", sync_start, fetch_start)
        # the step's ONE budgeted loss fetch (the deliberate per-iteration
        # sync; PERF.md), bracketed by the train/host_sync span
        net._score = score_of(net, loss)
        step_end = time.perf_counter()
        bs = net._batch_examples(batch)
        monitor.add_span("train/host_sync", fetch_start, step_end)
        monitor.add_span("train/step", step_start, step_end,
                         iteration=net.iteration_count,
                         score=net._score, batch_size=bs)
        if xla_ledger.enabled():
            rec, fresh = _capture_program(
                net, "train_step", step, operands,
                (net.params, net.opt_state, net.state, *operands, sub,
                 None), examples_per_call=bs)
            if not fresh:
                # the debut execution's wall time includes the jit
                # compile — only steady-state steps feed the MFU gauge
                xla_ledger.observe_step(rec, step_end - step_start)
        _record_iteration(net._score, bs,
                          step_seconds=step_end - step_start,
                          sync_seconds=step_end - fetch_start)
        for lst in capture:
            lst.on_gradients(net, net.iteration_count, net.epoch_count,
                             grads, updates)
        for lst in net.listeners:
            lst.iteration_done(net, net.iteration_count,
                               net.epoch_count, net._score, etl_ms, bs)
        net.iteration_count += 1
        etl_start = time.perf_counter()
    return rng


def _observe_chunk(rec, last_sync):
    """Steady-state chunk wall time = spacing between chunk syncs (the
    pipelined path has no un-overlapped "this chunk only" interval to
    time; the first chunk is skipped). The stamp advances on EVERY chunk
    — a ragged tail (rec None) must not leak its wall time into the next
    chunk's interval."""
    from deeplearning4j_tpu.monitor import xla as xla_ledger
    if xla_ledger.enabled():
        now = time.perf_counter()
        if rec is not None and last_sync[0] is not None:
            xla_ledger.observe_step(rec, now - last_sync[0])
        last_sync[0] = now


def _fit_epoch_scan(net, batches, rng, K):
    """Input-pipelined epoch: group consecutive same-shape batches into
    chunks of K, stack host-side, run one scan-of-K jit per chunk, and
    defer the loss fetch by one chunk so stacking/dispatch of chunk i+1
    overlaps chunk i's device compute. Ragged tails (or a shape change
    mid-epoch) fall back to per-call steps for those batches."""
    from deeplearning4j_tpu.monitor import xla as xla_ledger
    last_sync = [None]   # previous chunk-sync stamp: chunk wall clock

    def fetch(p):
        return np.asarray(p[0])             # single blocking fetch/chunk

    def notify(p, arr):
        _, bs, etl_ms, rec = p
        _observe_chunk(rec, last_sync)
        for loss in arr:
            # chunk losses are already host-resident (fetch() above IS the
            # deferred chunk sync); this is per-iteration bookkeeping
            net._score = score_of(net, loss)
            _record_iteration(net._score, bs)
            for lst in net.listeners:
                lst.iteration_done(net, net.iteration_count,
                                   net.epoch_count, net._score, etl_ms,
                                   bs)
            net.iteration_count += 1
            etl_ms = 0.0
        return len(arr)

    def stage(group):
        nonlocal rng
        rng, subs = _split_keys(rng, len(group))
        bs = net._batch_examples(group[0])
        if len(group) < K:
            # ragged tail / shape-change remainder: staged batch by
            # batch for the already compiled per-call step rather than
            # compiling a one-off scan-of-len(group) program
            return [net._operands(b) for b in group], subs, bs, True
        return net._stage_stacked(group), jnp.stack(subs), bs, False

    def launch(staged, etl_ms):
        operands, subs, bs, tail = staged
        if tail:
            step = compiled_step(net, "step")
            losses = []
            for one, sub in zip(operands, subs):
                (net.params, net.opt_state, net.state, loss,
                 _) = step(net.params, net.opt_state, net.state, *one, sub,
                           None)
                losses.append(loss)
            return jnp.stack(losses), bs, etl_ms, None
        n = int(subs.shape[0])
        kstep = compiled_step(net, "kstep")
        (net.params, net.opt_state, net.state,
         losses) = kstep(net.params, net.opt_state, net.state, *operands,
                         subs)
        rec = None
        if xla_ledger.enabled():
            rec, fresh = _capture_program(
                net, "scan_step", kstep, operands,
                (net.params, net.opt_state, net.state, *operands, subs),
                examples_per_call=bs * n, steps_per_call=n)
            if fresh:
                # the capture's AOT compile sat inside this
                # inter-chunk interval — restart the MFU clock so
                # it can't read as a slow chunk
                last_sync[0] = None
        return losses, bs, etl_ms, rec

    net._fit_chunk = _run_scan_pipeline(
        batches, K, sig_of=net._batch_sig, examples_of=net._batch_examples,
        stage=stage, launch=launch, fetch=fetch, notify=notify,
        first_chunk=net._fit_chunk)
    return rng


def _fit_epoch_accum(net, batches, rng, K):
    """One optimizer step per K micro-batches (gradient accumulation).
    Iteration counting follows DL4J's meaning (one iteration = one
    optimizer step); a ragged tail (< K same-shape batches) still
    accumulates into one step with the correct 1/len mean. Gradient
    listeners receive the AVERAGED per-step grads/updates (lockstep
    — wants_gradients forces defer=False below, so iteration_count
    at dispatch is the step being reported)."""
    from deeplearning4j_tpu.monitor import xla as xla_ledger
    sigs_seen = set()
    warned_partial = False
    last_sync = [None]

    def fetch(p):
        return np.asarray(p[0])     # the chunk's one blocking fetch

    def notify(p, loss):
        _, bs, etl_ms, capture, grads, updates, rec = p
        net._score = score_of(net, loss)
        _observe_chunk(rec, last_sync)
        _record_iteration(net._score, bs)
        for lst in capture:
            lst.on_gradients(net, net.iteration_count, net.epoch_count,
                             grads, updates)
        for lst in net.listeners:
            lst.iteration_done(net, net.iteration_count, net.epoch_count,
                               net._score, etl_ms, bs)
        net.iteration_count += 1
        return 1

    def stage(group):
        nonlocal rng, warned_partial
        if len(group) < K and not warned_partial:
            # _run_scan_pipeline only groups CONSECUTIVE same-shape
            # batches: a shape change (e.g. a non-drop-last partial
            # tail) cuts the accumulation group short, and the short
            # group still takes ONE full-learning-rate optimizer step
            # with the mean of len(group) gradients — K is silently
            # not honored for it. Surface that once.
            warned_partial = True
            cause = ("the micro-batch shape changed mid-epoch (use "
                     "drop_last or padded iterators for uniform "
                     "shapes)" if len(sigs_seen) > 1
                     else "the epoch ended mid-group")
            log.warning(
                "fit(accumulate_steps=%d): dispatching an accumulation "
                "group of only %d micro-batch(es) because %s; the "
                "partial group takes one full-learning-rate step with "
                "the 1/%d gradient mean", K, len(group), cause,
                len(group))
        rng, subs = _split_keys(rng, len(group))
        bs = net._batch_examples(group[0]) * len(group)
        return net._stage_stacked(group), jnp.stack(subs), bs, len(group)

    def launch(staged, etl_ms):
        operands, subs, bs, n = staged
        capture = _capturing(net)
        kaccum = compiled_step(net, "kaccum", with_stats=bool(capture))
        out = kaccum(net.params, net.opt_state, net.state, *operands, subs)
        net.params, net.opt_state, net.state, loss = out[:4]
        grads, updates = out[4:] if capture else (None, None)
        rec = None
        if xla_ledger.enabled():
            rec, fresh = _capture_program(
                net, "accum_step", kaccum, operands,
                (net.params, net.opt_state, net.state, *operands, subs),
                examples_per_call=bs, steps_per_call=n)
            if fresh:
                last_sync[0] = None   # exclude the AOT compile interval
        return loss, bs, etl_ms, capture, grads, updates, rec

    def sig_of(batch):
        s = net._batch_sig(batch)
        sigs_seen.add(s)
        return s

    # unlike scan-fit, accumulation cannot fall back to per-call for
    # model-reading listeners (that would change the optimization) —
    # it drops the one-chunk deferral instead so each callback sees
    # the params of the step it reports
    net._fit_chunk = _run_scan_pipeline(
        batches, K, sig_of=sig_of, examples_of=net._batch_examples,
        stage=stage, launch=launch, fetch=fetch, notify=notify,
        defer=not _scan_incompatible_listeners(net.listeners),
        first_chunk=net._fit_chunk)
    return rng


def _fit_tbptt_batch(net, chunks, rng, etl_ms, bs):
    """Truncated BPTT over ONE batch: `chunks` are its time slices as
    staged operand tuples; the recurrent carries go from chunk to chunk
    with stop_gradient at the boundaries, one optimizer step and one
    iteration a chunk (doTruncatedBPTT). `etl_ms` is reported with the
    first chunk."""
    step = compiled_step(net, "step")
    carries = {}
    for operands in chunks:
        rng, sub = jax.random.split(rng)
        (net.params, net.opt_state, net.state, loss,
         new_carries) = step(net.params, net.opt_state, net.state,
                             *operands, sub, carries)
        # stop gradient across chunk boundary
        carries = jax.tree_util.tree_map(jax.lax.stop_gradient,
                                         new_carries)
        # the tbptt chunk's one budgeted loss fetch
        net._score = score_of(net, loss)
        _record_iteration(net._score, bs)
        for lst in net.listeners:
            lst.iteration_done(net, net.iteration_count, net.epoch_count,
                               net._score, etl_ms, bs)
        net.iteration_count += 1
        etl_ms = 0.0
    return rng


# ------------------------------------------------------------------- fit()
def fit(net, data, epochs, scan_steps, accumulate_steps, plan, **source):
    """The body of both containers' `fit()` (whose docstrings say what
    the arguments mean); `source` are the container's own keywords for
    its `_fit_source`."""
    if net.params is None:
        net.init()
    # donated-buffer safety: params from ANY host source (checkpoint,
    # keras/dl4j import, set_params_flat) may alias numpy memory that
    # the donating train step must not free (util/params.owned_leaf);
    # under a plan the laundered copies land on the plan placements
    from deeplearning4j_tpu.parallel.plan import active_plan
    if plan is None:
        plan = active_plan()
    _engage_plan_impl(net, plan)
    tbptt = net.conf.backprop_type == "tbptt"
    if accumulate_steps > 1:
        if tbptt:
            raise ValueError("accumulate_steps does not apply to "
                             "tbptt (chunked-time) training")
        if scan_steps is not None and scan_steps > 1:
            raise ValueError("accumulate_steps and scan_steps are "
                             "mutually exclusive (one fuses K "
                             "optimizer steps, the other folds K "
                             "micro-batches into one step)")
        scan_steps = 1
    if scan_steps is None:
        scan_steps = _default_scan_steps()
    # scan-fit and accumulation STACK K host batches before one
    # transfer. The scan path falls back to per-call under model-reading
    # listeners and tbptt never scans, so this is the path that will
    # actually run — the source policy and the dispatch below both go by
    # it.
    stacking = not tbptt and (
        accumulate_steps > 1
        or (scan_steps > 1
            and not _scan_incompatible_listeners(net.listeners)))
    # device-side normalization (data/normalization.py
    # engaged_device_affine — env gate, listener gate, detach/restore,
    # feature-cast pause): an affine-representable pre-processor is
    # applied on device instead of host (_stage_with_affine), so raw
    # uint8 pixels ship over the link. Engaged BEFORE the container
    # wraps its source, so a wrap skips the 16-bit FEATURE host cast —
    # normalize-then-cast preserves the f32 signal a premature bf16 cast
    # would quantize away (labels still ship 16-bit).
    from deeplearning4j_tpu.data.normalization import engaged_device_affine
    with engaged_device_affine(data, net.listeners) as aff:
        if aff is not None:
            net._input_affine = (jnp.asarray(aff[0]), jnp.asarray(aff[1]))
        copy_marked = []
        if stacking:
            # stacking holds K live batches before ONE transfer —
            # shared-memory ring iterators must yield copies for it
            # (their normal view batches are recycled on the next
            # pull; data/pipeline.mark_copy_for_stacking)
            from deeplearning4j_tpu.data.pipeline import (
                mark_copy_for_stacking)
            copy_marked = mark_copy_for_stacking(data)
        data = net._fit_source(data, stacking, **source)
        from deeplearning4j_tpu import monitor
        from deeplearning4j_tpu.monitor import goodput
        gp_session = goodput.fit_begin(f"{net._LEDGER_PREFIX}/fit")
        net._fit_chunk = 0     # train/chunk numbers run over epochs
        rng = None
        try:
            for _ in range(epochs):
                if rng is None or net._RNG_PER_EPOCH:
                    mult = net._RNG_MULT_TBPTT if tbptt else net._RNG_MULT
                    rng = jax.random.PRNGKey(
                        net.conf.seed + mult * (net.epoch_count + 1))
                for lst in net.listeners:
                    lst.on_epoch_start(net, net.epoch_count)
                with monitor.span("train/epoch", epoch=net.epoch_count):
                    batches = net._epoch_batches(data, stacking)
                    if tbptt:
                        rng = net._fit_epoch_tbptt(batches, rng)
                    elif accumulate_steps > 1:
                        rng = _fit_epoch_accum(net, batches, rng,
                                               accumulate_steps)
                    elif stacking:
                        rng = _fit_epoch_scan(net, batches, rng, scan_steps)
                    else:
                        rng = _fit_epoch_per_call(net, batches, rng)
                for lst in net.listeners:
                    lst.on_epoch_end(net, net.epoch_count)
                net.epoch_count += 1
                if hasattr(data, "reset"):
                    data.reset()
        finally:
            goodput.fit_end(gp_session)
            net._input_affine = None
            for it_ in copy_marked:
                it_._copy = False
    return net


# CPython (3.11+) keeps interpreter frames in 16 KiB chunks of a per-thread
# data stack and unmaps a chunk the moment the frame at its base returns.
# Tracing and lowering a step go up and down thousands of frames; where a
# chunk boundary happens to fall inside that recursion, every crossing maps,
# faults and unmaps 16 KiB (ROADMAP S6, D14: 100,000-250,000 times a fit() of
# the benchmark's cells, a quarter of `setup_s` on the chip, and how many
# depends on the byte depth of the Python stack at the call, so on every
# refactor above it). A frame that asks for 512 KiB gets a 1 MiB chunk of
# its own, and everything fit() calls runs in the rest of it: no boundary
# is crossed. The slots are never touched, so the room costs no memory.
fit.__code__ = fit.__code__.replace(co_stacksize=1 << 16)
