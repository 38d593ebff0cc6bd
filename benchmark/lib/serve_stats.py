"""From the load generator's records to the serving metrics. All times are
``time.monotonic()`` seconds; the window is ``[w0, w1)``.

- TTFT is sampled for the requests DUE inside the window and timed from
  when each was due, so the wait a stall imposes on later arrivals counts.
- A gap between tokens is sampled where it ENDS inside the window, whether
  its request began before the window or ends after it.
"""
from __future__ import annotations

import statistics


def percentile(values, q: float) -> float:
    """Linear-interpolated percentile, q in [0, 100]."""
    v = sorted(values)
    if not v:
        return float("nan")
    pos = (len(v) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


def reduce(records, w0: float, w1: float, ttft_limit_s: float) -> dict:
    recs = [r for r in records if r.get("k", -1) >= 0]
    out = {"window_s": w1 - w0}
    gaps = []
    for r in recs:
        t = r["token_t"]
        gaps += [b - a for a, b in zip(t, t[1:]) if w0 <= b < w1]
    out["itl_samples"] = len(gaps)
    out["itl_p99_ms"] = 1e3 * percentile(gaps, 99)
    out["itl_p95_ms"] = 1e3 * percentile(gaps, 95)
    out["itl_p50_ms"] = 1e3 * percentile(gaps, 50)
    errors = [r for r in records
              if r.get("error") and r["error"] != "aborted"]
    due = [r for r in recs if w0 <= r["due"] < w1]
    ttft = [r["token_t"][0] - r["due"] for r in due if r["token_t"]]
    late = [r for r in due if not r["token_t"]
            or r["token_t"][0] - r["due"] > ttft_limit_s]
    out["attempted"] = len(due)
    out["failed"] = len({r["k"] for r in late}
                        | {r["k"] for r in errors if r in due})
    out["ttft_samples"] = len(ttft)
    out["ttft_mean_ms"] = 1e3 * statistics.fmean(ttft) if ttft \
        else float("nan")
    out["ttft_p50_ms"] = 1e3 * percentile(ttft, 50)
    out["ttft_p90_ms"] = 1e3 * percentile(ttft, 90)
    lateness = [r["sent"] - r["due"] for r in due if r["sent"]]
    out["gen_lateness_p99_ms"] = 1e3 * percentile(lateness, 99)
    out["errors"] = [r["error"] for r in errors][:5]
    return out


def live_load(records, t0: float, t1: float) -> dict:
    """Time-averaged number of sequences decoding and of tokens they hold
    in the cache over ``[t0, t1)``: a sequence with ``p`` prompt tokens
    holds ``p + j`` from its token ``j`` to its next."""
    slots = tokens = 0.0
    for r in records:
        t = r.get("token_t") or []
        for j, (a, b) in enumerate(zip(t, t[1:])):
            ov = min(b, t1) - max(a, t0)
            if ov > 0:
                slots += ov
                tokens += ov * (r["prompt_tokens"] + j + 1)
    span = t1 - t0
    return {"live_slots": slots / span, "live_tokens": tokens / span}
