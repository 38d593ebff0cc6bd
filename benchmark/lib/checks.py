"""The comparisons that decide ``correct``. Each returns
``(name, value, limit)`` rows; a run is correct when every value is finite
and within its limit. The limits live in the configuration's file, with the
readings they were set from in PERF.md."""
from __future__ import annotations

import functools
import math

import numpy as np


@functools.lru_cache(maxsize=None)
def _norms():
    import jax
    import jax.numpy as jnp
    return jax.jit(lambda t: jax.tree_util.tree_map(
        lambda a: jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)))), t))


def leaf_norms(tree) -> dict:
    """L2 norm of every leaf, by path, as Python floats."""
    import jax
    flat, _ = jax.tree_util.tree_flatten_with_path(_norms()(tree))
    return {jax.tree_util.keystr(k): float(v) for k, v in flat}


def worst_leaf_gap(prog: dict, ref: dict) -> float:
    """The largest gap between the program's norm of a leaf and the
    reference's, against the reference's norm of that leaf or of the
    median leaf, whichever is larger (some gradients are all but zero)."""
    return worst_leaves(prog, ref, 1)[0][0]


def worst_leaves(prog: dict, ref: dict, n: int = 3) -> list:
    """The ``n`` leaves with the largest gap: (gap, leaf, program's norm,
    reference's norm)."""
    assert prog.keys() == ref.keys()
    median = float(np.median(list(ref.values())))
    rows = [(abs(prog[k] - ref[k]) / max(ref[k], median, 1e-30), k, prog[k],
             ref[k]) for k in ref]
    return sorted(rows, reverse=True)[:n]


def global_gap(prog: dict, ref: dict) -> float:
    """Gap between the norms over all leaves together."""
    norm = lambda d: math.sqrt(sum(v * v for v in d.values()))
    return abs(norm(prog) - norm(ref)) / norm(ref)


def stage_gaps(prog: dict, ref: dict, stage_of) -> dict:
    """By stage of the net (``stage_of(leaf)`` names it), the gap between
    the program's norm over all the stage's leaves together and the
    reference's."""
    sums = {}
    for k in ref:
        p, r = sums.setdefault(stage_of(k), [0.0, 0.0])
        sums[stage_of(k)] = [p + prog[k] ** 2, r + ref[k] ** 2]
    return {s: abs(math.sqrt(p) - math.sqrt(r)) / math.sqrt(r)
            for s, (p, r) in sums.items()}


def training_rows(prog: dict, ref: dict, stage_of, limits: dict):
    """The numbers a training cell is judged on. ``prog`` and ``ref``
    hold ``losses`` (one per step of the first chunk) and the per-leaf
    norms of ``momentum`` and ``update`` after it. The leaves of the
    ``head`` stage (next to the loss) are judged leaf by leaf; every other
    stage by its norm over its leaves together, each with its own limit
    under ``stage_momentum_gap``."""
    pick = lambda d: {k: v for k, v in d.items() if stage_of(k) == "head"}
    values = {
        "loss_gap": loss_gap(prog["losses"], ref["losses"]),
        "head_momentum_gap": worst_leaf_gap(pick(prog["momentum"]),
                                            pick(ref["momentum"])),
        "head_update_gap": worst_leaf_gap(pick(prog["update"]),
                                          pick(ref["update"])),
        "update_norm_gap": global_gap(prog["update"], ref["update"]),
    }
    rows = [(k, v, limits[k]) for k, v in values.items()]
    by_stage = stage_gaps(prog["momentum"], ref["momentum"], stage_of)
    for stage, limit in limits["stage_momentum_gap"].items():
        rows.append((f"stage_momentum_gap.{stage}", by_stage[stage], limit))
    return rows


def loss_gap(prog, ref) -> float:
    return max(abs(p - r) / abs(r) for p, r in zip(prog, ref))


def verdict(rows) -> bool:
    """Print each number compared beside its limit; all must hold."""
    ok = True
    for name, value, limit in rows:
        good = math.isfinite(value) and value <= limit
        ok = ok and good
        print(f"[check] {name} = {value:.6g} (limit {limit:g}) "
              f"{'ok' if good else 'FAILED'}", flush=True)
    return ok


def served_gaps(ref_logits: np.ndarray, tokens) -> np.ndarray:
    """For each position of one request, the gap by which the token's
    logit lies below the reference's best: ``ref_logits`` is (n, vocab)
    for the n positions that produced ``tokens``. Zero where the token is
    the reference's own first choice."""
    tokens = np.asarray(tokens)
    return ref_logits.max(axis=-1) - ref_logits[np.arange(len(tokens)),
                                                tokens]
