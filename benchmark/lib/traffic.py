"""The one traffic generator. A traffic mix is a data file of parameters
under ``benchmark/traffic/``; this module turns it, with ``--seed`` and the
window's length, into what a run offers.

What is fixed by the FILE, and so is the same for every seed:

- the arrival offsets of an open loop (a Poisson process drawn from the
  file's own ``schedule_seed``);
- the (prompt length, output length) pair of every arrival: equally spaced
  quantiles of the stated distributions, dealt in blocks of ``strata``
  requests, one prompt length from each of ``strata`` length strata and one
  output length from each of ``strata`` output strata in every block, in an
  order drawn from ``schedule_seed``. Block boundaries are laid from the
  opening of the window, so the lead-in ends and the window opens at the
  same place of the cycle whatever the window's length.

What ``--seed`` decides: the token ids (and, outside this module, the
weights). It does not reorder the lengths: with the order inside a block
shuffled by the seed, six seeds of ``chat-steady`` spread the mean TTFT by
11 % where two runs of one order differ by about 1 % (PERF.md section 2),
because a request's wait depends on which long prompts it arrives beside.
So two seeds offer the same work at the same times and differ in content.
"""
from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np


def _quantiles(dist: dict, n: int) -> list:
    """``n`` equally spaced quantiles of a clipped distribution, sorted."""
    if dist["dist"] == "lognormal":
        nd = NormalDist()
        vals = [dist["median"] * math.exp(dist["sigma"]
                                          * nd.inv_cdf((i + 0.5) / n))
                for i in range(n)]
    elif dist["dist"] == "fixed":
        vals = [dist["value"]] * n
    else:
        raise ValueError(f"unknown distribution {dist['dist']!r}")
    return [int(min(max(round(v), dist["min"]), dist["max"])) for v in vals]


def length_cycle(traffic: dict) -> list:
    """The file's cycle of blocks: ``blocks`` lists of ``strata``
    (prompt, output) pairs. Over the whole cycle every quantile of either
    distribution is used exactly once."""
    strata, blocks = int(traffic["strata"]), int(traffic["blocks"])
    n = strata * blocks
    prompts = _quantiles(traffic["prompt_tokens"], n)
    outputs = _quantiles(traffic["output_tokens"], n)
    rng = np.random.default_rng([int(traffic["schedule_seed"]), 1])
    p_order = [rng.permutation(blocks) for _ in range(strata)]
    o_order = [rng.permutation(blocks) for _ in range(strata)]
    cycle = []
    for b in range(blocks):
        out_stratum = rng.permutation(strata)
        block = []
        for s in range(strata):
            so = int(out_stratum[s])
            block.append((prompts[s * blocks + int(p_order[s][b])],
                          outputs[so * blocks + int(o_order[so][b])]))
        # the file's own order inside a block is mixed too, not by length
        cycle.append([block[int(i)] for i in rng.permutation(strata)])
    return cycle


def arrival_offsets(traffic: dict, horizon_s: float) -> list:
    """Open loop: offsets in seconds from the start of the lead-in, up to
    ``horizon_s``, from the file's ``schedule_seed`` alone."""
    rng = np.random.default_rng([int(traffic["schedule_seed"]), 2])
    rate = float(traffic["rate_per_s"])
    if traffic.get("arrivals", "poisson") == "poisson":
        out, t = [], 0.0
        while True:
            t += float(rng.exponential(1.0 / rate))
            if t >= horizon_s:
                return out
            out.append(t)
    raise ValueError(f"unknown arrival process {traffic['arrivals']!r}")


def _tokens(traffic: dict, seed: int, k: int, n: int, vocab: int) -> list:
    rng = np.random.default_rng([seed, 4, k])
    ids = rng.integers(0, vocab, n)
    shared = int(traffic.get("shared_prefix_tokens", 0))
    if shared:
        pre = np.random.default_rng([seed, 5]).integers(0, vocab, shared)
        ids[:min(shared, n)] = pre[:n]
    return [int(t) for t in ids]


def schedule(traffic: dict, seed: int, seconds: float, vocab: int) -> dict:
    """What one run offers: ``requests`` in issue order, each with its
    prompt ids, its forced output length and its ``due`` offset from the
    start of the lead-in."""
    strata = int(traffic["strata"])
    lead_in = float(traffic["lead_in_s"])
    if traffic["loop"] != "open":
        raise ValueError(f"unknown loop {traffic['loop']!r}")
    offs = arrival_offsets(traffic, lead_in + seconds)
    n_lead = sum(1 for t in offs if t < lead_in)
    # block ordinals counted from the window's opening
    ordinals = [((i - n_lead) // strata, (i - n_lead) % strata)
                for i in range(len(offs))]
    cycle = length_cycle(traffic)
    pairs = [cycle[block % len(cycle)][slot] for block, slot in ordinals]
    requests = []
    for k, ((p, o), due) in enumerate(zip(pairs, offs)):
        requests.append({"k": k, "due": due, "prompt_tokens": p,
                         "max_tokens": o,
                         "prompt": _tokens(traffic, seed, k, p, vocab)})
    return {"loop": traffic["loop"], "lead_in_s": lead_in,
            "seconds": seconds, "ttft_limit_s": float(traffic["ttft_limit_s"]),
            "requests": requests}
