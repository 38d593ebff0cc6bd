"""The shape of the traffic does not move with ``--seed``: the arrival
offsets and every arrival's lengths are the file's, the token ids are the
seed's."""
from collections import Counter

import pytest

from benchmark.lib import manifest, traffic as gen

SEEDS = (7, 2147483659, 3000000019)


def _sched(name, seed, seconds=45.0):
    return gen.schedule(manifest.load_json("traffic", name + ".json"), seed,
                        seconds, vocab=50432)


def _lengths(reqs):
    return Counter((r["prompt_tokens"], r["max_tokens"]) for r in reqs)


@pytest.mark.parametrize("seconds", (10.0, 45.0))
def test_open_loop_offers_the_same_arrivals_and_lengths(seconds):
    runs = [_sched("chat-steady", s, seconds) for s in SEEDS]
    lead = runs[0]["lead_in_s"]
    for other in runs[1:]:
        assert [r["due"] for r in other["requests"]] == \
            [r["due"] for r in runs[0]["requests"]]
        assert _lengths(other["requests"]) == _lengths(runs[0]["requests"])
        # and so are the requests due inside the window, and the lead-in's
        for inside in (True, False):
            pick = lambda s: [r for r in s["requests"]
                              if (r["due"] >= lead) == inside]
            assert _lengths(pick(other)) == _lengths(pick(runs[0]))
        assert [(r["prompt_tokens"], r["max_tokens"])
                for r in other["requests"]] == \
            [(r["prompt_tokens"], r["max_tokens"])
             for r in runs[0]["requests"]]
        assert other["requests"][0]["prompt"] != \
            runs[0]["requests"][0]["prompt"]
    assert _sched("chat-steady", SEEDS[1], seconds) == runs[1]


def test_a_longer_window_keeps_the_lead_in_and_extends_the_window():
    short, long = _sched("chat-steady", 7, 30.0), _sched("chat-steady", 7,
                                                         45.0)
    n = len(short["requests"])
    assert [(r["due"], r["prompt_tokens"], r["max_tokens"])
            for r in long["requests"][:n]] == \
        [(r["due"], r["prompt_tokens"], r["max_tokens"])
         for r in short["requests"]]


def test_cycle_uses_every_quantile_once_and_one_of_each_stratum():
    t = manifest.load_json("traffic", "chat-steady.json")
    cycle = gen.length_cycle(t)
    n = t["strata"] * t["blocks"]
    prompts = sorted(p for blk in cycle for p, _ in blk)
    outputs = sorted(o for blk in cycle for _, o in blk)
    assert prompts == gen._quantiles(t["prompt_tokens"], n)
    assert outputs == gen._quantiles(t["output_tokens"], n)
    assert prompts[0] == t["prompt_tokens"]["min"]
    assert prompts[-1] == t["prompt_tokens"]["max"]
    edges = prompts[::t["blocks"]] + [prompts[-1] + 1]
    for blk in cycle:
        # one prompt from each stratum of the sorted quantiles
        ranks = sorted(sum(1 for e in edges[1:] if p >= e) for p, _ in blk)
        assert len(blk) == t["strata"]
        assert ranks == sorted(ranks) and len(set(ranks)) >= t["strata"] - 2
