"""Host-platform helpers: the compilation cache, backend provenance and
the forced virtual CPU mesh the tests, examples and dry runs use."""

from __future__ import annotations

import logging
import os

import jax

logger = logging.getLogger("spfft_tpu")

#: The cache directory used when ``JAX_COMPILATION_CACHE_DIR`` is unset:
#: one fixed path inside the checkout (listed in .gitignore), so that
#: every process of a checkout shares it and nothing is written outside.
CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

_cache_configured = False


def enable_persistent_compilation_cache() -> None:
    """Turn on JAX's persistent compilation cache for TPU compiles.

    TPU FFT compiles are the dominant plan-time cost for large grids;
    the reference plans in sub-second time because FFTW_ESTIMATE does no
    measurement (reference: src/parameters/parameters.cpp:43-140). With
    the cache, SCF codes that rebuild plans per geometry step pay each
    compile once per (shape, pipeline), not once per process.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    this sets nothing. Otherwise the cache lives at :data:`CACHE_DIR`.
    CPU compiles are not cached: they are fast, and XLA:CPU entries
    embed host-feature strings that mismatch noisily across loads.
    Called lazily at the first plan build (never at package import);
    safe to call again."""
    global _cache_configured
    if _cache_configured:
        return
    _cache_configured = True
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    if jax.default_backend() != "tpu":
        return
    try:
        os.makedirs(CACHE_DIR, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
        # the many small stage executables each compile in under JAX's
        # default 1 s floor but add up; cache them too
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.2)
    except Exception as e:  # pragma: no cover - read-only checkout
        logger.warning("spfft_tpu: persistent compilation cache not "
                       "enabled (%s)", e)


def platform_summary() -> dict:
    """Backend provenance for serving metrics and benchmark JSON: which
    backend the process resolved, how many devices it sees and their
    kind. Initialises the backend on first call (same cost the first
    transform would pay anyway); serving exports embed this so recorded
    throughput numbers carry the platform they were measured on."""
    devices = jax.devices()
    return {
        "backend": jax.default_backend(),
        "device_count": len(devices),
        "device_kind": devices[0].device_kind if devices else "none",
    }


def force_virtual_cpu_devices(n: int) -> None:
    """Force an ``n``-device virtual CPU platform through the live
    config (``jax_platforms`` + ``jax_num_cpu_devices``). Effective only
    before the backend initialises; afterwards the updates raise and are
    swallowed — callers check ``len(jax.devices())``."""
    try:
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", max(n, 1))
    except Exception:
        pass
