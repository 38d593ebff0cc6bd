#!/usr/bin/env python
"""Perf regression gate + roofline report — the CI teeth of the ledger.

Merges two artifact streams:

- the banked bench trajectory (``BENCH_r*.json``): every throughput
  series that appears in more than one round — per-mode/batch ResNet imgs/sec, char-LSTM
  chars/sec, Word2Vec pairs/sec, LeNet imgs/sec, h2d MB/s, and the
  headline — is compared LATEST vs. BEST-EARLIER within its own device
  class (CPU rows never gate TPU rows and vice versa). Artifacts that
  bank a ``calib_cpu_ms`` machine-speed reference (decode smokes, r17+)
  are compared in HOST-NORMALIZED space: baselines are rescaled by the
  calibration ratio so a slower/faster container does not masquerade as
  a code regression/improvement, and uncalibrated earlier rounds are
  excluded (reported as skipped when no calibrated baseline exists);
- the compiled-program ledger (``monitor.xla.save_ledger()`` JSON,
  ``--ledger``): each program's arithmetic intensity is placed on the
  device roofline (ridge = peak_flops / hbm_bandwidth) to report whether
  it is compute- or memory-bound and what MFU ceiling the roofline allows
  — the standing context for ROADMAP item 2's 27% -> 40% chase.

Exit codes: 0 = no tracked series regressed beyond ``--threshold``
(default 15%); 2 = regression(s); 1 = usage/IO error. CI usage:

    python tools/perf_report.py                          # gate the repo
    python tools/perf_report.py --ledger perf_ledger.json --json
    python tools/perf_report.py --dir /path/to/artifacts --threshold 0.10
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys

#: throughput keys a sweep row may carry; each becomes its own series.
#: fit_e2e_* are the PRODUCT-path (disk->decode->device, ETL included)
#: rows from `bench.py --mode fit_e2e`. fit_e2e_baseline_imgs_sec (the
#: deliberately-slow per-sample-loop reference the pipeline's speedup is
#: computed against) is NOT gated: it measures the path we replaced, and
#: its run-to-run spread exceeds the regression threshold.
#: mesh_imgs_sec is the GSPMD-plan scaling sweep (`bench.py --mode
#: mesh`, banked as MULTICHIP_r*.json): one row per plan config
#: (mesh-single / mesh-dp / mesh-dp_tp / mesh-zero1 / mesh-zero3).
#: decode_tokens_sec is the continuous-batching generate surface
#: (`tools/decode_smoke.py`, banked as DECODE_r*.json): generated tokens
#: per wall second across concurrent streams through a mid-traffic swap.
#: decode_cache_hit_rate is the shared-prefix workload's KV prefix-cache
#: hit fraction (DECODE_r*.json, r14+): higher = more prefill compute
#: skipped, gated like a throughput so a cache regression trips CI.
#: train_goodput_pct is the clean-fit step-compute share of wall-clock
#: from the goodput ledger (`tools/goodput_report.py`, banked as
#: GOODPUT_r*.json, r19+): an attribution regression (more time leaking
#: into data_wait/host_sync/other) trips CI even when raw imgs/sec
#: noise hides it.
#: decode_spill_hit_rate is the tiered-KV-fabric host-RAM tier's
#: admission hit fraction under pool pressure (DECODE_r*.json, r20+):
#: spill-probing admissions whose HBM-missed blocks promoted back from
#: host memory — a drop means evicted prefixes stopped coming back.
THROUGHPUT_KEYS = ("imgs_sec", "lenet_imgs_sec", "chars_sec", "pairs_sec",
                   "h2d_f32_mbytes_sec", "h2d_u8_mbytes_sec",
                   "fit_e2e_imgs_sec",
                   "fit_e2e_chars_sec", "fit_e2e_pairs_sec",
                   "chaos_goodput_under_fault_rps", "mesh_imgs_sec",
                   "decode_tokens_sec", "decode_cache_hit_rate",
                   "decode_spec_acceptance_rate", "train_goodput_pct",
                   "decode_spill_hit_rate")

#: lower-is-better series (latencies). Banked by tools/serve_chaos.py
#: (CHAOS_r*.json): p99 while a replica is killed + another wedged, and
#: post-fault recovered p99. decode_* are the streaming-generation tail
#: latencies from tools/decode_smoke.py (DECODE_r*.json): time-to-first-
#: token p99 and inter-token p99. Gated inverted: baseline = best
#: (lowest) earlier round, regression = latest above baseline by >
#: threshold.
#: decode_ttft_hot_p99_ms is time-to-first-token p99 for prefix-cache
#: HITS on the shared-prefix workload; decode_itl_interferer_p99_ms is
#: short-stream inter-token p99 while a long-prompt interferer admits
#: (chunked prefill keeps it bounded). Both r14+. The cold-TTFT and
#: chunking-off interferer numbers are banked for the ratio but NOT
#: gated (they measure the path the cache/chunking replaced).
#: rollout_* are the continuous-rollout control-loop latencies from
#: tools/rollout_drill.py (ROLLOUT_r*.json, r18+): fleet-wide staggered
#: promote fan-out seconds, and wall seconds from a poisoned blessing
#: landing on disk to the auto-rollback decision. Host-calibrated like
#: the decode series (both scale with model-load / probe round-trips).
#: decode_affinity_ttft_hot_p99_ms is the repeat-prefix (would-be-hot)
#: TTFT p99 through a 2-replica fleet router with prefix-affinity
#: steering ON (DECODE_r*.json, r20+); the random-routing arm of the
#: same A/B is banked as decode_affinity_ttft_random_p99_ms but NOT
#: gated (it measures the policy affinity replaced).
LATENCY_KEYS = ("chaos_p99_under_fault_ms", "chaos_recovered_p99_ms",
                "decode_ttft_p99_ms", "decode_itl_p99_ms",
                "decode_ttft_hot_p99_ms", "decode_itl_interferer_p99_ms",
                "rollout_promote_s", "rollout_rollback_detect_s",
                "decode_affinity_ttft_hot_p99_ms")

#: dimensionless series (fractions of work, not work per second): host
#: speed cannot move them, so calibration normalization never applies —
#: they always compare raw, against every earlier round.
RATIO_KEYS = ("decode_cache_hit_rate", "decode_spec_acceptance_rate",
              "train_goodput_pct", "decode_spill_hit_rate")


def _round_of(name: str) -> int:
    m = re.search(r"_r(\d+)", name)
    return int(m.group(1)) if m else 0


def load_rounds(directory: str):
    """Parse every banked bench artifact into (round, on_tpu, payload)
    entries. Artifacts wrap the bench JSON under "parsed" (driver capture)
    or are the bare JSON; unparseable or payload-less rounds are skipped,
    not fatal."""
    entries = []
    names = (sorted(glob.glob(os.path.join(directory, "BENCH_r*.json")))
             + sorted(glob.glob(os.path.join(directory, "CHAOS_r*.json")))
             # GSPMD-plan scaling sweeps; pre-r06 MULTICHIP artifacts
             # are driver dryrun stamps without a sweep and skip below
             + sorted(glob.glob(os.path.join(directory,
                                             "MULTICHIP_r*.json")))
             # continuous-batching decode smokes (tokens/sec, TTFT, ITL)
             + sorted(glob.glob(os.path.join(directory,
                                             "DECODE_r*.json")))
             # continuous-rollout drills (promote fan-out / rollback
             # detection latency from tools/rollout_drill.py)
             + sorted(glob.glob(os.path.join(directory,
                                             "ROLLOUT_r*.json")))
             # goodput-ledger acceptance runs (clean-fit goodput% from
             # tools/goodput_report.py)
             + sorted(glob.glob(os.path.join(directory,
                                             "GOODPUT_r*.json"))))
    for path in names:
        try:
            with open(path) as f:
                doc = json.load(f)
        except (OSError, ValueError):
            continue
        payload = doc.get("parsed", doc) if isinstance(doc, dict) else None
        if not isinstance(payload, dict):
            continue
        if "sweep" not in payload and payload.get("value") is None:
            continue
        # absent flag = the early TPU rounds (r01/r02) that predate it
        on_tpu = not payload.get("tpu_unavailable", False)
        # machine-speed reference (decode_smoke r17+): wall-ms for a
        # fixed numpy workload on the banking host; None on older rounds
        calib = payload.get("calib_cpu_ms")
        if not isinstance(calib, (int, float)) or calib <= 0:
            calib = None
        entries.append({"artifact": os.path.basename(path),
                        "round": _round_of(os.path.basename(path)),
                        "on_tpu": on_tpu, "calib": calib,
                        "payload": payload})
    entries.sort(key=lambda e: (e["round"], e["artifact"]))
    return entries


def extract_series(entries):
    """{series_id: [(round, artifact, value, calib), ...]} — series_id
    keys are (on_tpu, mode, batch, metric); the headline rides as
    (on_tpu, "__headline__", None, "value"). ``calib`` is the artifact's
    machine-speed reference (None when the round predates it)."""
    series = {}

    def add(sid, rnd, artifact, value, calib):
        series.setdefault(sid, []).append(
            (rnd, artifact, float(value), calib))

    for e in entries:
        p = e["payload"]
        if isinstance(p.get("value"), (int, float)):
            add((e["on_tpu"], "__headline__", None, "value"),
                e["round"], e["artifact"], p["value"], e["calib"])
        for row in p.get("sweep", []) or []:
            if not isinstance(row, dict) or "error" in row \
                    or "skipped" in row:
                continue
            on_tpu = bool(row.get("on_tpu", e["on_tpu"]))
            for key in THROUGHPUT_KEYS + LATENCY_KEYS:
                if isinstance(row.get(key), (int, float)):
                    add((on_tpu, row.get("mode"), row.get("batch"), key),
                        e["round"], e["artifact"], row[key], e["calib"])
    return series


def check_regressions(series, threshold: float):
    """LATEST occurrence vs BEST of strictly-earlier rounds, per series.
    "Best" is direction-aware: highest for throughput series, lowest for
    LATENCY_KEYS series, and a regression is a move AWAY from best beyond
    the threshold in either regime. Single-round series (e.g. a config
    measured only once) cannot gate.

    Machine-speed normalization: when the LATEST artifact banked a
    ``calib_cpu_ms`` reference, every baseline candidate that also has
    one is mapped to the latest host's speed before the comparison
    (throughput scales with 1/calib, latency with calib) — the gate then
    measures the CODE, not which container the round happened to run in.
    Earlier rounds WITHOUT a reference cannot give a fair verdict against
    a calibrated latest, so they are excluded from baseline selection; if
    none remain the series is reported as skipped, not gated. A latest
    without a reference keeps the legacy raw comparison.

    Calibration may EXCUSE, never convict: the reference is one matmul
    kernel, so the ratio tracks the host's compute speed but not its
    Python/dispatch overhead — a faster-matmul host does not make every
    latency proportionally cheaper. A slow host's raw regression is
    forgiven when the normalized delta is clean (the original purpose),
    but a conviction additionally requires the RAW delta against the
    same baseline round to exceed the threshold; otherwise a fast-calib
    round would manufacture regressions out of series whose raw numbers
    held steady or improved."""
    checked, regressions, skipped = [], [], []
    for sid, points in sorted(series.items(), key=lambda kv: str(kv[0])):
        lower_better = sid[3] in LATENCY_KEYS
        better = (lambda a, b: a < b) if lower_better \
            else (lambda a, b: a > b)
        rounds = {}
        for rnd, artifact, value, calib in points:
            cur = rounds.get(rnd)
            if cur is None or better(value, cur[1]):  # same-round: best
                rounds[rnd] = (artifact, value, calib)
        if len(rounds) < 2:
            continue
        latest_round = max(rounds)
        latest_art, latest, latest_calib = rounds[latest_round]
        earlier = {r: v for r, v in rounds.items() if r != latest_round}
        on_tpu, mode, batch, key = sid
        sdesc = {"on_tpu": on_tpu, "mode": mode, "batch": batch,
                 "metric": key}
        calibrated = latest_calib is not None and key not in RATIO_KEYS
        if calibrated:
            earlier = {r: v for r, v in earlier.items()
                       if v[2] is not None}
            if not earlier:
                skipped.append({
                    "series": sdesc,
                    "latest": {"round": latest_round,
                               "artifact": latest_art, "value": latest},
                    "reason": "no calibrated baseline round",
                })
                continue

            def adjust(value, calib):
                # map a baseline taken at `calib` to the latest host
                ratio = latest_calib / calib
                return value * (ratio if lower_better else 1.0 / ratio)
        else:
            def adjust(value, calib):
                return value
        base_round, (base_art, base_raw, base_calib) = \
            (min if lower_better else max)(
                earlier.items(), key=lambda rv: adjust(rv[1][1], rv[1][2]))
        baseline = adjust(base_raw, base_calib)
        delta = (latest - baseline) / baseline if baseline > 0 else 0.0
        if lower_better:
            delta = -delta      # normalized: negative delta == worse
        raw_delta = (latest - base_raw) / base_raw if base_raw > 0 else 0.0
        if lower_better:
            raw_delta = -raw_delta
        calibration = {
            "latest_calib_ms": latest_calib,
            "baseline_calib_ms": base_calib,
            "host_speed_ratio": round(latest_calib / base_calib, 4),
            "baseline_raw": base_raw,
            "raw_delta_pct": round(raw_delta * 100, 2),
        } if calibrated else None
        rec = {
            "series": sdesc,
            "baseline": {"round": base_round, "artifact": base_art,
                         "value": baseline},
            "latest": {"round": latest_round, "artifact": latest_art,
                       "value": latest},
            "delta_pct": round(delta * 100, 2),
            "regressed": delta < -threshold
            and (not calibrated or raw_delta < -threshold),
        }
        if calibration:
            rec["calibration"] = calibration
        checked.append(rec)
        if rec["regressed"]:
            regressions.append(rec)
    return checked, regressions, skipped


def roofline(ledger: dict):
    """Place every ledger program on the device roofline. Returns [] when
    the ledger carries no peak numbers (unlisted device, no override) —
    informational, never gating."""
    peak = ledger.get("peak_flops")
    bw = ledger.get("hbm_bytes_per_sec")
    rows = []
    for prog in ledger.get("programs", []):
        ai = prog.get("arithmetic_intensity")
        row = {"name": prog.get("name"),
               "fingerprint": prog.get("fingerprint"),
               "flops": prog.get("flops"),
               "arithmetic_intensity": ai,
               "hbm_peak_bytes": prog.get("hbm_peak_bytes"),
               "compile_seconds": prog.get("compile_seconds"),
               # sharded (GSPMD plan) vs replicated programs roofline
               # differently — per-chip flops and HBM are 1/N figures
               "sharded": bool(prog.get("sharded", False)),
               "arg_shardings": prog.get("arg_shardings")}
        if ai and peak and bw:
            ridge = peak / bw
            attainable = min(peak, ai * bw)
            row.update({
                "ridge_intensity": round(ridge, 2),
                "bound": "compute" if ai >= ridge else "memory",
                "attainable_flops": attainable,
                "mfu_ceiling_pct": round(100.0 * attainable / peak, 1),
            })
        rows.append(row)
    return rows


def _fmt_series(sid_rec) -> str:
    s = sid_rec["series"]
    where = "tpu" if s["on_tpu"] else "cpu"
    mode = s["mode"] if s["mode"] != "__headline__" else "headline"
    batch = "" if s["batch"] is None else f" b{s['batch']}"
    return f"{where} {mode}{batch} [{s['metric']}]"


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--dir", default=os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))),
        help="directory holding BENCH_*.json artifacts (default: repo root)")
    p.add_argument("--ledger", default=None, metavar="PATH",
                   help="perf-ledger JSON (monitor.xla.save_ledger / "
                        "--perf-ledger) to roofline-annotate")
    p.add_argument("--threshold", type=float, default=0.15,
                   help="fractional regression that fails the gate "
                        "(default 0.15 = 15%%)")
    p.add_argument("--json", action="store_true",
                   help="emit the full machine-readable report on stdout")
    args = p.parse_args(argv)

    entries = load_rounds(args.dir)
    if not entries:
        print(f"perf_report: no BENCH_*.json artifacts under {args.dir}",
              file=sys.stderr)
        return 1
    series = extract_series(entries)
    checked, regressions, skipped = check_regressions(series,
                                                      args.threshold)

    ledger_doc, roof = None, []
    if args.ledger:
        try:
            with open(args.ledger) as f:
                ledger_doc = json.load(f)
        except (OSError, ValueError) as e:
            print(f"perf_report: cannot read ledger {args.ledger}: {e}",
                  file=sys.stderr)
            return 1
        roof = roofline(ledger_doc)

    report = {
        "artifacts": [e["artifact"] for e in entries],
        "threshold": args.threshold,
        "series_tracked": len(series),
        "series_compared": len(checked),
        "series_skipped": skipped,
        "comparisons": checked,
        "regressions": regressions,
        "roofline": roof,
        "ok": not regressions,
    }
    if args.json:
        print(json.dumps(report, indent=1))
    else:
        print(f"perf_report: {len(entries)} artifacts, {len(series)} "
              f"series, {len(checked)} compared "
              f"(threshold {args.threshold:.0%})")
        for rec in checked:
            mark = "REGRESSED" if rec["regressed"] else "ok"
            cal = rec.get("calibration")
            note = (f"  [host x{cal['host_speed_ratio']:.2f}, baseline "
                    f"{cal['baseline_raw']:.2f} raw, "
                    f"{cal['raw_delta_pct']:+.1f}% raw]" if cal else "")
            print(f"  {mark:>9}  {_fmt_series(rec):<42} "
                  f"{rec['baseline']['value']:>12.2f} (r{rec['baseline']['round']})"
                  f" -> {rec['latest']['value']:>12.2f} "
                  f"(r{rec['latest']['round']})  {rec['delta_pct']:+.1f}%"
                  f"{note}")
        for rec in skipped:
            print(f"    skipped  {_fmt_series(rec):<42} "
                  f"{rec['reason']} (latest r{rec['latest']['round']})")
        for row in roof:
            pos = (f"{row['bound']}-bound, MFU ceiling "
                   f"{row['mfu_ceiling_pct']}%"
                   if "bound" in row else "roofline n/a (no device peak)")
            ai = row["arithmetic_intensity"]
            print(f"  roofline  {row['name']:<28} "
                  f"AI={'n/a' if ai is None else f'{ai:.1f}'}  {pos}")
        if regressions:
            print(f"perf_report: {len(regressions)} series regressed "
                  f"beyond {args.threshold:.0%} — failing the gate")
        else:
            print("perf_report: gate clean")
    return 2 if regressions else 0


if __name__ == "__main__":
    raise SystemExit(main())
