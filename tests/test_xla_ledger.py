"""Compiled-program ledger tests — monitor/xla.py (program capture,
fingerprint dedup, MFU accounting, zero-cost-when-disabled), its fit-path
and serving integration, and the tools/perf_report.py regression gate."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax

from deeplearning4j_tpu import monitor
from deeplearning4j_tpu.monitor import xla as xla_ledger
from deeplearning4j_tpu.nn.conf.base import InputType
from deeplearning4j_tpu.nn.conf.network import NeuralNetConfiguration
from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.nn.updaters import Sgd

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_ledger():
    """Fresh registry + disabled empty ledger around every test."""
    monitor.REGISTRY.reset()
    xla_ledger.disable_ledger()
    xla_ledger.clear_ledger()
    yield
    monitor.REGISTRY.reset()
    xla_ledger.disable_ledger()
    xla_ledger.clear_ledger()


def _small_net(seed=7):
    conf = (NeuralNetConfiguration.Builder().seed(seed).updater(Sgd(0.1))
            .list()
            .layer(DenseLayer(n_out=8, activation="tanh"))
            .layer(OutputLayer(n_out=3, activation="softmax", loss="mcxent"))
            .set_input_type(InputType.feed_forward(5)).build())
    return MultiLayerNetwork(conf).init()


def _blobs(n=48, d=5, k=3, seed=0):
    rs = np.random.RandomState(seed)
    X = rs.randn(n, d).astype("float32")
    Y = np.eye(k, dtype="float32")[rs.randint(0, k, n)]
    return X, Y


# --------------------------------------------------------------- capture
def test_capture_dedups_by_fingerprint_but_counts_every_compile():
    xla_ledger.enable_ledger()
    f = jax.jit(lambda x: (x * 2.0).sum())
    x = np.ones((8, 4), "float32")
    r1 = xla_ledger.capture("t/prog", f, (x,))
    r2 = xla_ledger.capture("t/prog", f, (x,))   # recompile event, same fp
    assert r1 is not None and r2 is not None
    assert r1.fingerprint == r2.fingerprint
    assert len(xla_ledger.records()) == 1        # deduped to one entry
    assert r1.compiles == 2
    ctr = monitor.REGISTRY.collect("xla_compiles_total")
    assert ctr.value(program="t/prog") == 2      # ...but both counted
    assert monitor.REGISTRY.collect("xla_programs").value() == 1
    hist = monitor.REGISTRY.collect("xla_compile_seconds")
    assert hist.snapshot(program="t/prog")["count"] == 2


def test_distinct_shapes_get_distinct_fingerprints():
    xla_ledger.enable_ledger()
    f = jax.jit(lambda x: (x * 2.0).sum())
    r1 = xla_ledger.capture("t/prog", f, (np.ones((8, 4), "float32"),))
    r2 = xla_ledger.capture("t/prog", f, (np.ones((16, 4), "float32"),))
    assert r1.fingerprint != r2.fingerprint
    assert len(xla_ledger.records()) == 2


def test_capture_reads_cost_and_memory_analysis_on_cpu():
    xla_ledger.enable_ledger()
    f = jax.jit(lambda a, b: a @ b)
    args = (np.ones((32, 16), "float32"), np.ones((16, 8), "float32"))
    rec = xla_ledger.capture("t/matmul", f, args)
    assert rec.flops and rec.flops > 0
    assert rec.bytes_accessed and rec.bytes_accessed > 0
    assert rec.arithmetic_intensity == rec.flops / rec.bytes_accessed
    assert rec.hbm_peak_bytes and rec.hbm_peak_bytes > 0
    g = monitor.REGISTRY.collect("xla_hbm_peak_bytes")
    assert g.value(program="t/matmul",
                   fingerprint=rec.fingerprint) == rec.hbm_peak_bytes


@pytest.mark.parametrize("names,calls", [
    # a rematerialised block that runs the forward kernel again: two
    # flash_fwd instructions a backward pair (a call too long for the
    # fused backward)
    (("flash_fwd.22", "flash_fwd.23", "flash_bwd_dq.11", "flash_bwd_dkv.11",
      "fusion.7", "pallas_call.300"),
     {"flash_fwd": 2, "flash_bwd_dq": 1, "flash_bwd_dkv": 1}),
    # the cells' steps: the fused backward keeps the name flash_bwd_dq,
    # once a forward, and no flash_bwd_dkv
    (("flash_fwd.55", "flash_fwd.56", "flash_bwd_dq.55", "flash_bwd_dq.56",
      "fusion.9"), {"flash_fwd": 2, "flash_bwd_dq": 2}),
    (("kda_chunk_fwd.1", "kda_chunk_fwd.2", "kda_chunk_bwd.37", "flash_fwd",
      "flash_fwd_like.3", "ragged-dot-none.3"),
     {"kda_chunk_fwd": 2, "kda_chunk_bwd": 1, "flash_fwd": 1}),
    (("fusion.1", "copy.2"), {}), ((), {})])
def test_kernel_calls_counts_instructions_by_their_kernels_name(names, calls):
    assert xla_ledger.kernel_calls(dict.fromkeys(names, "")) == calls


@pytest.mark.parametrize("backward", ["fused", "pair"])
def test_capture_publishes_the_programs_kernel_calls(monkeypatch, backward):
    """On a TPU a Pallas call's instruction is named after its kernel; here
    the instruction table is planted: a program whose flash backward is the
    one fused kernel sets no `flash_bwd_dkv` series, one that took the pair
    of passes does."""
    planted = {
        "flash_fwd.1": "jit(f)/mla/attn/flash_fwd/pallas_call",
        "flash_bwd_dq.1": "jit(f)/transpose(mla/attn)/flash_bwd_dq",
        "fusion.1": "jit(f)/add"}
    if backward == "pair":
        planted["flash_bwd_dkv.1"] = \
            "jit(f)/transpose(mla/attn)/flash_bwd_dkv"
    monkeypatch.setattr(xla_ledger, "compiled_op_scopes",
                        lambda compiled: planted)
    xla_ledger.enable_ledger()
    xla_ledger.capture("t/prog", jax.jit(lambda x: x + 1),
                       (np.ones(3, "float32"),))
    series = monitor.dump()["xla_program_kernel_calls"]["series"]
    assert {s["labels"]["kernel"]: s["value"] for s in series} == {
        "flash_fwd": 1, "flash_bwd_dq": 1,
        **({"flash_bwd_dkv": 1} if backward == "pair" else {})}
    # a program without a kernel sets no series
    monkeypatch.setattr(xla_ledger, "compiled_op_scopes", lambda c: {})
    xla_ledger.capture("t/plain", jax.jit(lambda x: x * 2),
                       (np.ones(3, "float32"),))
    series = monitor.dump()["xla_program_kernel_calls"]["series"]
    assert {s["labels"]["program"] for s in series} == {"t/prog"}


def test_disabled_ledger_is_a_noop():
    f = jax.jit(lambda x: x + 1)
    assert xla_ledger.capture("t/p", f, (np.ones(3, "float32"),)) is None
    cache = {}
    assert xla_ledger.capture_cached(cache, "k", "t/p", f,
                                     (np.ones(3, "float32"),)) is None
    assert cache == {}                      # not even a negative entry
    xla_ledger.observe_step(None, 0.1)
    assert xla_ledger.records() == []
    assert not any(name.startswith("xla_") for name in monitor.dump())


def test_capture_cached_caches_failures_too():
    xla_ledger.enable_ledger()

    class NotJitted:                        # no .lower(): capture fails
        pass

    cache = {}
    assert xla_ledger.capture_cached(cache, "k", "t/bad", NotJitted(),
                                     ()) is None
    assert cache == {"k": None}             # probed once, not every step
    ctr = monitor.REGISTRY.collect("xla_analysis_unavailable_total")
    assert ctr.value(kind="lower") == 1


# ---------------------------------------------------------- fit paths
def test_fit_captures_per_call_and_scan_as_distinct_programs(monkeypatch):
    monkeypatch.setenv("DL4J_TPU_PEAK_FLOPS", "1e12")
    xla_ledger.enable_ledger()
    X, Y = _blobs()
    net = _small_net()
    net.fit((X, Y), epochs=1, batch_size=16, scan_steps=1)
    names = {r.name for r in xla_ledger.records()}
    assert "mln/train_step" in names
    per_call = [r for r in xla_ledger.records()
                if r.name == "mln/train_step"]
    net.fit((X, Y), epochs=1, batch_size=16, scan_steps=3)
    names = {r.name for r in xla_ledger.records()}
    assert "mln/scan_step" in names
    scan = [r for r in xla_ledger.records() if r.name == "mln/scan_step"]
    # the fused scan-of-K program is a different compiled artifact
    assert scan[0].fingerprint != per_call[0].fingerprint
    # XLA counts the scan body once; steps_per_call carries the K that
    # total_flops_per_call scales by
    assert scan[0].steps_per_call == 3
    assert per_call[0].steps_per_call == 1
    assert scan[0].total_flops_per_call > per_call[0].total_flops_per_call * 2
    # the MFU accountant went live off the measured steps
    assert monitor.REGISTRY.collect("train_mfu_pct").value() > 0
    assert xla_ledger.last_mfu("train") > 0


def test_fit_with_ledger_disabled_leaves_no_trace():
    X, Y = _blobs()
    net = _small_net()
    net.fit((X, Y), epochs=1, batch_size=16, scan_steps=1)
    assert xla_ledger.records() == []
    assert net._ledger_cache == {}
    assert not any(name.startswith("xla_") for name in monitor.dump())


def test_graph_fit_captures_program():
    from deeplearning4j_tpu.nn.conf.network import GraphBuilder
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    xla_ledger.enable_ledger()
    g = (GraphBuilder(NeuralNetConfiguration.Builder().seed(3)
                      .updater(Sgd(0.1)))
         .add_inputs("in")
         .set_input_types(InputType.feed_forward(5)))
    g.add_layer("d", DenseLayer(n_out=8, activation="tanh"), "in")
    g.add_layer("out", OutputLayer(n_out=3, activation="softmax",
                                   loss="mcxent"), "d")
    g.set_outputs("out")
    net = ComputationGraph(g.build()).init()
    X, Y = _blobs()
    net.fit((X, Y), epochs=1, scan_steps=1)
    assert any(r.name == "graph/train_step" for r in xla_ledger.records())


def test_serving_forward_captured_with_serving_domain(monkeypatch):
    monkeypatch.setenv("DL4J_TPU_PEAK_FLOPS", "1e12")
    from deeplearning4j_tpu.parallel.inference import (
        InferenceMode, ParallelInference,
    )
    xla_ledger.enable_ledger()
    net = _small_net()
    X, _ = _blobs(n=16)
    with ParallelInference(net, mode=InferenceMode.SEQUENTIAL) as pi:
        out = pi.output(X)      # debut: captured, MFU skipped (compile)
        out = pi.output(X)      # steady state: feeds serving_mfu_pct
    assert out.shape == (16, 3)
    recs = [r for r in xla_ledger.records() if r.domain == "serving"]
    assert recs and recs[0].name == "inference/forward"
    assert monitor.REGISTRY.collect("serving_mfu_pct").value() > 0


# ------------------------------------------------------------ persistence
def test_save_ledger_schema_and_atomicity(tmp_path):
    xla_ledger.enable_ledger(str(tmp_path / "ledger.json"))
    f = jax.jit(lambda x: (x * 2.0).sum())
    xla_ledger.capture("t/prog", f, (np.ones((8, 4), "float32"),))
    n = xla_ledger.save_ledger()
    assert n == 1
    doc = json.loads((tmp_path / "ledger.json").read_text())
    assert doc["version"] == xla_ledger.LEDGER_SCHEMA_VERSION
    for key in ("created_unix", "device_kind", "backend", "peak_flops",
                "hbm_bytes_per_sec", "programs"):
        assert key in doc
    prog = doc["programs"][0]
    for key in ("fingerprint", "name", "domain", "arg_shapes", "hlo_hash",
                "compile_seconds", "compiles", "flops", "bytes_accessed",
                "arithmetic_intensity", "hbm", "hbm_peak_bytes"):
        assert key in prog
    assert not [p for p in os.listdir(tmp_path)
                if ".tmp." in p]            # atomic write left no temp


def test_save_ledger_merge_existing_across_processes(tmp_path):
    """bench runs every sweep config in its own subprocess against ONE
    DL4J_TPU_PERF_LEDGER file — merge_existing folds prior programs in
    instead of overwriting them."""
    path = str(tmp_path / "ledger.json")
    xla_ledger.enable_ledger(path)
    f = jax.jit(lambda x: (x * 2.0).sum())
    xla_ledger.capture("t/a", f, (np.ones((8, 4), "float32"),))
    xla_ledger.save_ledger()
    # simulate the next config subprocess: fresh in-memory ledger
    xla_ledger.clear_ledger()
    xla_ledger.enable_ledger(path)
    xla_ledger.capture("t/b", f, (np.ones((16, 4), "float32"),))
    assert xla_ledger.save_ledger(merge_existing=True) == 2
    doc = json.loads((tmp_path / "ledger.json").read_text())
    assert {p["name"] for p in doc["programs"]} == {"t/a", "t/b"}
    # re-running the same config dedups by fingerprint, never duplicates
    assert xla_ledger.save_ledger(merge_existing=True) == 2


def test_save_ledger_without_path_raises():
    xla_ledger.enable_ledger()
    with pytest.raises(ValueError):
        xla_ledger.save_ledger()


# ------------------------------------------------------------ perf gate
def _bench_round(value, imgs_sec, on_tpu=False):
    return {"parsed": {
        "metric": "resnet50_train_imgs_per_sec_per_chip",
        "value": value, "unit": "imgs/sec", "vs_baseline": None,
        "tpu_unavailable": not on_tpu,
        "sweep": [{"batch": 8, "mode": "per-call", "on_tpu": on_tpu,
                   "imgs_sec": imgs_sec}],
    }}


def _run_perf_report(directory, *extra):
    return subprocess.run(
        [sys.executable, os.path.join(_REPO, "tools", "perf_report.py"),
         "--dir", str(directory), "--json", *extra],
        capture_output=True, text=True, timeout=60)


def test_perf_report_flags_synthetic_regression(tmp_path):
    (tmp_path / "BENCH_r01.json").write_text(
        json.dumps(_bench_round(100.0, 100.0)))
    (tmp_path / "BENCH_r02.json").write_text(
        json.dumps(_bench_round(80.0, 80.0)))       # -20% > 15% threshold
    r = _run_perf_report(tmp_path)
    assert r.returncode == 2, r.stdout + r.stderr
    report = json.loads(r.stdout)
    assert not report["ok"]
    assert len(report["regressions"]) == 2          # headline + sweep row
    assert report["regressions"][0]["delta_pct"] == -20.0


def test_perf_report_passes_small_delta_and_improvement(tmp_path):
    (tmp_path / "BENCH_r01.json").write_text(
        json.dumps(_bench_round(100.0, 100.0)))
    (tmp_path / "BENCH_r02.json").write_text(
        json.dumps(_bench_round(95.0, 120.0)))      # -5% and +20%
    r = _run_perf_report(tmp_path)
    assert r.returncode == 0, r.stdout + r.stderr
    report = json.loads(r.stdout)
    assert report["ok"] and report["series_compared"] == 2


def test_perf_report_threshold_is_configurable(tmp_path):
    (tmp_path / "BENCH_r01.json").write_text(
        json.dumps(_bench_round(100.0, 100.0)))
    (tmp_path / "BENCH_r02.json").write_text(
        json.dumps(_bench_round(95.0, 95.0)))
    assert _run_perf_report(tmp_path).returncode == 0
    assert _run_perf_report(tmp_path,
                            "--threshold", "0.02").returncode == 2


def test_perf_report_roofline_from_ledger(tmp_path):
    (tmp_path / "BENCH_r01.json").write_text(
        json.dumps(_bench_round(100.0, 100.0)))
    ledger = {
        "version": 1, "created_unix": 0, "device_kind": "TPU v5 lite",
        "backend": "tpu", "peak_flops": 197e12, "hbm_bytes_per_sec": 819e9,
        "programs": [
            {"fingerprint": "aa", "name": "mln/train_step",
             "flops": 1e12, "arithmetic_intensity": 500.0,
             "hbm_peak_bytes": 1 << 30, "compile_seconds": 1.0},
            {"fingerprint": "bb", "name": "inference/forward",
             "flops": 1e9, "arithmetic_intensity": 2.0,
             "hbm_peak_bytes": 1 << 20, "compile_seconds": 0.5},
        ],
    }
    lpath = tmp_path / "perf_ledger.json"
    lpath.write_text(json.dumps(ledger))
    r = _run_perf_report(tmp_path, "--ledger", str(lpath))
    assert r.returncode == 0, r.stdout + r.stderr
    roof = json.loads(r.stdout)["roofline"]
    by_fp = {row["fingerprint"]: row for row in roof}
    # ridge = 197e12/819e9 ~= 240.5: AI 500 is compute-bound (ceiling
    # 100%), AI 2.0 is memory-bound with ceiling 2*819e9/197e12
    assert by_fp["aa"]["bound"] == "compute"
    assert by_fp["aa"]["mfu_ceiling_pct"] == 100.0
    assert by_fp["bb"]["bound"] == "memory"
    assert by_fp["bb"]["mfu_ceiling_pct"] == pytest.approx(0.8, abs=0.05)


def _decode_round(tokens_sec, itl_ms, calib_ms=None):
    doc = {"sweep": [{"batch": 4, "mode": "decode", "on_tpu": False,
                      "decode_tokens_sec": tokens_sec,
                      "decode_itl_p99_ms": itl_ms}],
           "tpu_unavailable": True}
    if calib_ms is not None:
        doc["calib_cpu_ms"] = calib_ms
    return doc


def test_perf_report_calibration_normalizes_host_drift(tmp_path):
    """A 2x slower host halves throughput and doubles latency; with both
    rounds calibrated the gate compares in host-normalized space and
    stays clean — a genuine regression on top of the drift still trips."""
    (tmp_path / "DECODE_r01.json").write_text(
        json.dumps(_decode_round(2000.0, 5.0, calib_ms=20.0)))
    (tmp_path / "DECODE_r02.json").write_text(
        json.dumps(_decode_round(1000.0, 10.0, calib_ms=40.0)))
    r = _run_perf_report(tmp_path)
    assert r.returncode == 0, r.stdout + r.stderr
    report = json.loads(r.stdout)
    assert report["ok"] and report["series_compared"] == 2
    for rec in report["comparisons"]:
        assert rec["calibration"]["host_speed_ratio"] == 2.0
        assert rec["delta_pct"] == 0.0
    # same drift + a real 30% code regression: the gate still fires
    (tmp_path / "DECODE_r02.json").write_text(
        json.dumps(_decode_round(700.0, 10.0, calib_ms=40.0)))
    r = _run_perf_report(tmp_path)
    assert r.returncode == 2, r.stdout + r.stderr
    reg = json.loads(r.stdout)["regressions"]
    assert [x["series"]["metric"] for x in reg] == ["decode_tokens_sec"]
    assert reg[0]["delta_pct"] == -30.0


def test_perf_report_calibration_excuses_never_convicts(tmp_path):
    """The matmul reference tracks compute speed, not dispatch overhead:
    a faster-calib host must not manufacture a regression out of a
    series whose RAW numbers held steady. Conviction requires the raw
    delta to exceed the threshold too."""
    (tmp_path / "DECODE_r01.json").write_text(
        json.dumps(_decode_round(2000.0, 5.0, calib_ms=40.0)))
    # host calib halved (2x faster matmul) but the code's raw numbers
    # are unchanged — normalized this looks like -50% throughput / 2x
    # latency, yet nothing actually regressed
    (tmp_path / "DECODE_r02.json").write_text(
        json.dumps(_decode_round(2000.0, 5.0, calib_ms=20.0)))
    r = _run_perf_report(tmp_path)
    assert r.returncode == 0, r.stdout + r.stderr
    report = json.loads(r.stdout)
    assert report["ok"]
    for rec in report["comparisons"]:
        assert rec["calibration"]["raw_delta_pct"] == 0.0
        assert not rec["regressed"]
    # a genuine raw regression on the same faster host still trips
    (tmp_path / "DECODE_r02.json").write_text(
        json.dumps(_decode_round(1200.0, 5.0, calib_ms=20.0)))
    r = _run_perf_report(tmp_path)
    assert r.returncode == 2, r.stdout + r.stderr
    reg = json.loads(r.stdout)["regressions"]
    assert [x["series"]["metric"] for x in reg] == ["decode_tokens_sec"]
    assert reg[0]["calibration"]["raw_delta_pct"] == -40.0


def test_perf_report_skips_uncalibrated_baselines(tmp_path):
    """A calibrated latest cannot be fairly judged by pre-calibration
    rounds: those are excluded and the series reports as skipped rather
    than gating on raw wall-clock."""
    (tmp_path / "DECODE_r01.json").write_text(
        json.dumps(_decode_round(2000.0, 5.0)))             # legacy round
    (tmp_path / "DECODE_r02.json").write_text(
        json.dumps(_decode_round(1000.0, 10.0, calib_ms=40.0)))
    r = _run_perf_report(tmp_path)
    assert r.returncode == 0, r.stdout + r.stderr
    report = json.loads(r.stdout)
    assert report["ok"] and report["series_compared"] == 0
    assert {s["series"]["metric"] for s in report["series_skipped"]} \
        == {"decode_tokens_sec", "decode_itl_p99_ms"}
    assert all(s["reason"] == "no calibrated baseline round"
               for s in report["series_skipped"])


def test_perf_report_uncalibrated_latest_keeps_raw_comparison(tmp_path):
    """Legacy behavior is untouched when the LATEST round lacks a
    calibration reference — even if an earlier round has one."""
    (tmp_path / "DECODE_r01.json").write_text(
        json.dumps(_decode_round(2000.0, 5.0, calib_ms=20.0)))
    (tmp_path / "DECODE_r02.json").write_text(
        json.dumps(_decode_round(1000.0, 5.0)))             # raw -50%
    r = _run_perf_report(tmp_path)
    assert r.returncode == 2, r.stdout + r.stderr
    reg = json.loads(r.stdout)["regressions"]
    assert [x["series"]["metric"] for x in reg] == ["decode_tokens_sec"]
    assert "calibration" not in reg[0]


def test_perf_report_banked_repo_trajectory_is_clean():
    """The acceptance gate: the repo's own banked BENCH history exits 0."""
    r = _run_perf_report(_REPO)
    assert r.returncode == 0, r.stdout + r.stderr
    report = json.loads(r.stdout)
    assert report["ok"] and report["series_compared"] >= 1
