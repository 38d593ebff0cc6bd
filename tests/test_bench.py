"""Unit tests for the bench orchestration (driver contract pieces that
need no device): config ordering, the shared compile-cache location,
the no-CPU-fallback rule, and the headline-aggregation rule.

bench.py's module level imports no jax, so these are instant.
"""
import os
import subprocess

import pytest

import bench

_KNOBS = ("DL4J_TPU_BENCH_BATCHES", "DL4J_TPU_BENCH_ATTENTION",
          "DL4J_TPU_BENCH_LSTM", "DL4J_TPU_BENCH_W2V",
          "DL4J_TPU_BENCH_LENET", "DL4J_TPU_BENCH_FIT_E2E")


@pytest.fixture
def clean_knobs(monkeypatch):
    """_configs() reads DL4J_TPU_BENCH_* — isolate from the caller's
    shell so an exported knob can't flip these assertions."""
    for k in _KNOBS:
        monkeypatch.delenv(k, raising=False)


class TestConfigs:
    def test_tpu_order_banks_decisive_trio_first(self, clean_knobs):
        cfgs = bench._configs(True)
        kinds = [(c.get("kind"), c.get("mode", "")) for c in cfgs]
        # the per-call/scan/fit trio at batch 128 comes first
        assert kinds[:3] == [("resnet", "per-call"), ("resnet", "scan"),
                             ("resnet", "fit")]
        # the cheap h2d bandwidth micro (attributes the fit number) rides
        # right behind the trio
        assert kinds[3] == ("h2d", "")
        assert kinds[4] == ("attention", "")
        assert {c["batch"] for c in cfgs[:3]} == {128}
        # full sweep carries all 4 BASELINE configs
        assert {"char-lstm", "word2vec", "lenet"} <= {k for k, _ in kinds}
        # plus the fit()-end-to-end (product path incl. ETL) rows
        assert [c.get("model") for c in cfgs if c["kind"] == "fit_e2e"] \
            == ["lenet", "char-lstm", "word2vec"]

    def test_cpu_order_single_batch(self, clean_knobs):
        cfgs = bench._configs(False)
        batches = {c.get("batch") for c in cfgs if "batch" in c
                   and c["kind"] == "resnet"}
        assert batches == {8}

    def test_env_knobs_disable_entries(self, clean_knobs, monkeypatch):
        monkeypatch.setenv("DL4J_TPU_BENCH_LSTM", "0")
        monkeypatch.setenv("DL4J_TPU_BENCH_W2V", "0")
        monkeypatch.setenv("DL4J_TPU_BENCH_LENET", "0")
        monkeypatch.setenv("DL4J_TPU_BENCH_ATTENTION", "0")
        monkeypatch.setenv("DL4J_TPU_BENCH_H2D", "0")
        monkeypatch.setenv("DL4J_TPU_BENCH_FIT_E2E", "0")
        kinds = {c["kind"] for c in bench._configs(True)}
        assert kinds == {"resnet"}


class TestCacheDir:
    """util/platform.enable_compile_cache: ONE cache location for every
    entry point, placeable from outside."""

    @pytest.fixture
    def updates(self, monkeypatch):
        """Record jax.config.update calls instead of applying them: the
        suite's own cache (set by conftest) stays where it is."""
        import deeplearning4j_tpu.util.platform as plat
        seen = {}
        monkeypatch.setattr(plat.jax.config, "update",
                            lambda k, v: seen.__setitem__(k, v))
        return seen

    def test_default_is_checkout_local(self, monkeypatch, updates):
        import deeplearning4j_tpu.util.platform as plat
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        d = plat.enable_compile_cache()
        assert d == os.path.join(repo, ".jaxcache")
        assert os.path.isdir(d)
        assert updates["jax_compilation_cache_dir"] == d

    def test_env_places_it_and_code_sets_no_directory(
            self, monkeypatch, updates, tmp_path):
        import deeplearning4j_tpu.util.platform as plat
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert plat.enable_compile_cache() == str(tmp_path)
        assert "jax_compilation_cache_dir" not in updates

    def test_unwritable_checkout_is_an_error(self, monkeypatch, updates,
                                             tmp_path):
        import deeplearning4j_tpu.util.platform as plat
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        blocker = tmp_path / "not-a-dir"
        blocker.write_text("")
        monkeypatch.setattr(plat, "CHECKOUT", str(blocker))
        with pytest.raises(OSError):
            plat.enable_compile_cache()
        assert "jax_compilation_cache_dir" not in updates


class TestNoFallback:
    def test_child_without_tpu_exits_unless_cpu_was_asked_for(
            self, monkeypatch):
        # the suite's jax is on the CPU; with JAX_PLATFORMS not naming it
        # the child must refuse before running anything
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
        monkeypatch.setitem(bench._KIND_RUNNERS, "h2d", lambda cfg: 1 / 0)
        with pytest.raises(SystemExit, match="no TPU"):
            bench.run_one({"kind": "h2d"})

    def test_failed_config_fails_the_run(self, monkeypatch, capsys):
        calls = []

        def fake_run(argv, **kw):
            calls.append(argv)
            return subprocess.CompletedProcess(argv, 7, stdout="")

        monkeypatch.setattr(bench.subprocess, "run", fake_run)
        assert bench.main() == 7
        assert len(calls) == 1          # fail-fast: no later config ran
        assert capsys.readouterr().out == ""    # and no result printed


class TestHeadlineAggregation:
    def test_best_is_max_imgs_sec_and_micro_entries_cannot_win(self):
        results = [
            {"batch": 128, "mode": "per-call", "imgs_sec": 2400.0},
            {"batch": 128, "mode": "scan10", "imgs_sec": 3300.0},
            {"mode": "lenet-mnist", "lenet_imgs_sec": 99999.0},
            {"mode": "char-lstm", "chars_sec": 1e9},
        ]
        best = bench._headline(results)
        assert best["mode"] == "scan10"   # micro benches ride along only
        assert bench._headline([{"mode": "h2d-micro"}]) is None
