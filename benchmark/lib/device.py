"""The device a run is on, its published peaks, and the compile cache.

No fallback: a run that finds no TPU, or fewer chips than its cell asks
for, exits non-zero and prints no result. The one exception is asked for
explicitly, ``JAX_PLATFORMS=cpu`` in the environment: a rehearsal of the
control flow at tiny sizes that reports counts and no device metric.
"""
from __future__ import annotations

import os
import sys

from benchmark.lib.manifest import ROOT

#: Published peaks of ONE chip, keyed by ``device_kind``. Source: Google
#: Cloud TPU documentation, "TPU v5e" system architecture (197 TFLOP/s
#: bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s, 1,600 Gbit/s ICI). A copy
#: of ``deeplearning4j_tpu/monitor/xla.py::DEVICE_PEAKS`` kept here so that
#: no PR that claims a gain can move a denominator. A device that is not in
#: the table is an error, never a default.
PEAKS = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9, "ici_bits_per_s": 1600e9},
}

NO_DEVICE_RC = 3


def rehearsing() -> bool:
    return os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"


def prepare_environment(chips: int):
    """Before JAX is imported: the cache directory at a fixed path inside
    the checkout, and in a rehearsal as many virtual CPU devices as the
    cell has chips."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    if rehearsing() and chips > 1:
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + f" --xla_force_host_platform_device_count={chips}"
            ).strip()


def enable_compile_cache():
    """JAX's persistent cache at ``<checkout>/.jaxcache`` (the path is part
    of the key, so it never moves), or where ``JAX_COMPILATION_CACHE_DIR``
    says. The program's own helper takes the same decision; the benchmark
    sets it first so that a program that stops doing so changes nothing."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or os.path.join(ROOT, ".jaxcache")
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def require(chips: int) -> dict:
    """The device as JAX reports it, or exit: no TPU, or too few chips."""
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if rehearsing():
        if info["platform"] != "cpu" or info["count"] < chips:
            sys.stderr.write(f"benchmark: rehearsal wants {chips} CPU "
                             f"device(s), jax found {info}\n")
            raise SystemExit(NO_DEVICE_RC)
        return info
    if info["platform"] != "tpu" or info["count"] < chips:
        sys.stderr.write(f"benchmark: the cell needs {chips} TPU chip(s), "
                         f"jax found {info}; nothing was run\n")
        raise SystemExit(NO_DEVICE_RC)
    if info["kind"] not in PEAKS:
        sys.stderr.write(f"benchmark: no published peaks for "
                         f"{info['kind']!r} in benchmark/lib/device.py\n")
        raise SystemExit(NO_DEVICE_RC)
    return info


def memory_peak_bytes(chips: int) -> int:
    """Peak bytes on the fullest of the chips the cell used: the
    allocator's peak of live buffers plus the largest reservation the
    runtime made for a running program's temporaries. The TPU runtime
    counts the two apart (``peak_bytes_in_use`` alone read 3.2 GB for a
    step that then reserved 6 GB more), and a program runs while its
    operands and the next chunk's are live."""
    import jax
    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0))
                     + int(stats.get("peak_bytes_reserved", 0)))
    return max(peaks)
