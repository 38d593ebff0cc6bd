"""Backend detection and the persistent compile cache's location.

One predicate for "are we on a TPU", shared by every choice the code
makes from the platform (fused-kernel eligibility, Pallas compile vs
interpret mode, the scan-of-K fit default), and one helper that every
entry point that compiles calls so their caches cannot split.
"""
from __future__ import annotations

import os

import jax

#: the checkout that holds this package (the directory with bench.py and
#: chip_smoke.py); the default compile cache lives inside it
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def is_tpu_backend() -> bool:
    """True when the default JAX backend is a TPU. A backend that fails
    to initialise raises here: it must not read as "not a TPU" and
    quietly switch off the kernels and the scan default."""
    return jax.default_backend() == "tpu"


def device_info() -> dict:
    """The device as JAX reports it — what every result, probe and
    smoke line names: ``{"platform", "kind", "count"}``."""
    devices = jax.devices()
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its
    directory. Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it
    itself and no directory is set in code; otherwise the cache is
    ``<checkout>/.jaxcache`` (gitignored). The path is part of what the
    caller can rely on: processes of one command share it, so the second
    process to need a program loads it from disk. An unwritable checkout
    raises ``OSError``."""
    # small decode/bucket programs are worth caching too: a server
    # start-up compiles dozens of them
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    d = os.path.join(CHECKOUT, ".jaxcache")
    os.makedirs(d, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", d)
    return d
