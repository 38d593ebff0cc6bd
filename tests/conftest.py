"""Test configuration: force an 8-device virtual CPU mesh BEFORE jax import.

This mirrors the reference's strategy of testing distributed code without a
real cluster (SURVEY.md §4: Spark local[N] masters) — multi-chip sharding
logic runs on 8 virtual CPU devices; the driver separately dry-runs the
multi-chip path, and chip_smoke.py / bench.py run on the TPU.

Markers (README "Running the tests"):
- `slow`: the FIXED list `_SLOW` below (45 names: big pipeline / ring
  compiles, f64 gradcheck matrices, zoo forwards, multi-OS-process runs).
  Tier-1, the gate the driver runs, is `-m "not slow"` under `-n 6 --dist
  loadfile` (the command: ROADMAP.md "Tier-1 verify" and
  `/root/TESTS_LAST_RUN.json`); it leaves the list out, so the list guards
  nothing the ledger sees (ROADMAP D21). The mark does NOT mean "every test
  of 7 s or more": nothing has been added to it since the seed, and tier-1
  holds longer cases (ROADMAP D12 has the table by file).
- `distributed`: tests that spawn real extra OS processes.

A persistent XLA compilation cache ($JAX_COMPILATION_CACHE_DIR, default
<repo>/.jaxcache, gitignored) makes repeat runs compile-free: the first
run pays the jit cost, later runs reload compiled programs from disk. The
driver's checkout starts without one, so what tier-1 costs there is the
COLD cost, and most of that is compiling: a test that takes a gradient of
a whole model or layer does it under `jax.jit`, once a side (op by op it
took two to three times as long), and shares what several cases need
(`tests/_lm_common.py`).
"""
import os
import sys

# allow invoking pytest from inside tests/ (package not pip-installed)
_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()
# XLA's cpu_aot_loader logs an E-level "could lead to SIGILL" wall of
# text for every compile-cache hit whose recorded machine-feature string
# differs textually from the host's (the compile side records XLA tuning
# pseudo-features like +prefer-no-scatter that host detection never
# lists — same box, pure noise). Real failures surface as Python
# exceptions, so silence C++ glog in tests unless the caller overrides.
os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "3")

import jax  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_enable_x64", False)

# Persistent compile cache: repeat suite runs skip XLA compilation. ONE
# location shared with every entry point (util/platform.py).
from deeplearning4j_tpu.util.platform import (  # noqa: E402
    enable_compile_cache,
)

enable_compile_cache()


# the tests tier-1 leaves out (as measured at the seed: big pipeline and
# ring-attention compiles, f64 gradchecks, zoo forwards, multi-process
# distributed runs): a fixed list, names without any parametrize suffix,
# so every variant of a listed test is marked
_SLOW = {
    "tests/test_tpu_lowering.py::TestFlashKernelLowering::test_backward_kernels_with_lse_cotangent",
    "tests/test_tpu_lowering.py::TestFlashKernelLowering::test_cross_attention_shapes",
    "tests/test_tpu_lowering.py::TestRingFlashLowering::test_ring_flash_over_seq_mesh",
    "tests/test_tpu_lowering.py::TestFlagshipLowering::test_graft_entry_forward_lowers_for_tpu",
    "tests/test_tpu_lowering.py::TestFlagshipLowering::test_resnet_train_step_lowers_for_tpu",
    "tests/test_attention.py::test_context_parallel_dp_sp_mesh_trains",
    "tests/test_attention.py::test_context_parallel_graph_matches_single_device",
    "tests/test_attention.py::test_context_parallel_honors_label_mask",
    "tests/test_attention.py::test_context_parallel_masked_matches_single_device",
    "tests/test_attention.py::test_context_parallel_step_matches_single_device",
    "tests/test_attention.py::test_pipeline_parallel_honors_masks",
    "tests/test_attention.py::test_pipeline_parallel_step_matches_single_device",
    "tests/test_attention.py::test_pipeline_parallel_trains",
    "tests/test_attention.py::test_ring_attention_masked_matches_dense",
    "tests/test_attention.py::test_ring_attention_matches_dense",
    "tests/test_attention.py::test_transformer_block_and_moe_shapes",
    "tests/test_attention.py::test_transformer_lm_trains",
    "tests/test_attention.py::test_transformer_tp_sharded_step",
    "tests/test_gradientcheck.py::test_gc_attention_dropout_fixed_rng",
    "tests/test_gradientcheck.py::test_gc_graves_bidirectional_lstm",
    "tests/test_gradientcheck.py::test_gc_graves_lstm",
    "tests/test_gradientcheck.py::test_gc_lstm_last_time_step_global_pool",
    "tests/test_gradientcheck.py::test_gc_ring_attention_fd",
    "tests/test_gradientcheck.py::test_gc_separable_conv",
    "tests/test_gradientcheck.py::test_gc_transformer_block_blockwise",
    "tests/test_gradientcheck.py::test_gc_vae_pretrain_elbo",
    "tests/test_gradientcheck.py::test_gc_vae_supervised",
    "tests/test_gradientcheck.py::test_gc_yolo_loss",
    "tests/test_keras_import.py::test_separable_and_depthwise_conv_parity",
    "tests/test_keras_import.py::test_sequential_cnn_parity",
    "tests/test_memory.py::test_memory_report_graph",
    "tests/test_nlp.py::test_paragraph_vectors_labels",
    "tests/test_nlp.py::test_spark_word2vec_partition_parallel",
    "tests/test_nlp.py::test_word2vec_cbow_and_hs",
    "tests/test_nlp.py::test_word2vec_separates_topics",
    "tests/test_parallel.py::test_shared_gradients_two_os_processes_over_socket_transport",
    "tests/test_parallel.py::test_two_process_checkpoint_crash_resume_matches_uninterrupted",
    "tests/test_parallel.py::test_two_process_jax_distributed_parallel_wrapper",
    "tests/test_pretraining.py::test_vae_pretrain_via_driver",
    "tests/test_regularization.py::test_dropout_variants_train_only_and_nets_train",
    "tests/test_server_cli.py::test_cli_trains_and_saves",
    "tests/test_solvers.py::test_lbfgs_beats_gradient_descent_iterations",
    "tests/test_zoo.py::test_darknet19_small_input_forward",
    "tests/test_zoo.py::test_simplecnn_forward",
    "tests/test_zoo.py::test_tinyyolo_small_forward_and_loss",
}

_DISTRIBUTED = {
    "tests/test_parallel.py::test_shared_gradients_two_os_processes_over_socket_transport",
    "tests/test_parallel.py::test_two_process_checkpoint_crash_resume_matches_uninterrupted",
    "tests/test_parallel.py::test_two_process_jax_distributed_parallel_wrapper",
}


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: the fixed list tier-1 leaves out (-m 'not slow')")
    config.addinivalue_line(
        "markers", "distributed: spawns extra OS processes")


def pytest_collection_modifyitems(config, items):
    for item in items:
        # normalize to the repo-relative "tests/file.py::name" form so the
        # match is independent of the invocation directory/rootdir
        base = "tests/" + item.path.name + "::" + \
            item.nodeid.split("::", 1)[-1].split("[")[0]
        if base in _SLOW:
            item.add_marker(pytest.mark.slow)
        if base in _DISTRIBUTED:
            item.add_marker(pytest.mark.distributed)


# ------------------------------------------------------ deadlock sentinel
# A wedged test used to be a MUTE hang: the tier-1 `timeout` kill left
# no evidence of who held what. Importing the hook arms the sentinel
# (util/sentinel.py): per-test wall-time watchdog that dumps every
# thread's stack + the DiagnosedLock holder table, then exits 3.
# Knobs: DL4J_TPU_DEADLOCK_SENTINEL (only "0" disables),
# DL4J_TPU_SENTINEL_TIMEOUT (seconds, default 300).
from deeplearning4j_tpu.util.sentinel import (  # noqa: E402,F401
    pytest_runtest_protocol,
)


@pytest.fixture(params=["fused", "pair"])
def flash_backward(request, monkeypatch):
    """Both forms of the flash attention's backward, by name: "fused", the
    ONE kernel (named ``flash_bwd_dq``) of a call whose key-side sums fit
    VMEM, and "pair", the two passes a longer call keeps (forced here by a
    byte budget nothing fits)."""
    import deeplearning4j_tpu.ops  # noqa: F401  (the module is loaded)
    if request.param == "pair":
        monkeypatch.setattr(
            sys.modules["deeplearning4j_tpu.ops.flash_attention"],
            "_RESIDENT_SUM_BYTES", 0)
    return request.param
