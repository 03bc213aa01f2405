"""Computation-environment helpers: platform, XLA flags, host device count.

One place for the process-level knobs every entry point (``python -m
repro.bench``, ``launch/serve.py``, the distributed tests) otherwise
re-implements ad hoc. All of these only take full effect when called BEFORE
the jax backend initializes (i.e. before the first array op / device query),
so CLIs call them first thing in ``main``.
"""
from __future__ import annotations

import os
import warnings
from multiprocessing import cpu_count
from pathlib import Path

import jax

__all__ = [
    "set_platform",
    "set_host_device_count",
    "jax_enable_x64",
    "set_debug_nan",
    "add_xla_flags",
    "platform_provenance",
    "use_compile_cache",
]

# <checkout>/.xla-cache: a fixed path (the cache key includes it), listed
# in .gitignore, and the directory CI restores.
_CHECKOUT_XLA_CACHE = Path(__file__).resolve().parents[3] / ".xla-cache"


def use_compile_cache() -> str:
    """Place JAX's persistent compilation cache; return its directory.

    Where ``$JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing else is set here. Otherwise the cache goes to
    ``<checkout>/.xla-cache``. Entry points call this first thing.
    """
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", str(_CHECKOUT_XLA_CACHE))
    return str(_CHECKOUT_XLA_CACHE)


def platform_provenance() -> dict:
    """Where-did-this-number-come-from stamp for every emitted artifact.

    One dict — backend name, physical device kind/count, whether Pallas
    launches run the interpreter on this backend, and the jax version —
    attached to bench payloads (``repro.bench``), metrics snapshots and
    trace headers (``repro.obs``). The point is ROADMAP item 1's nag made
    structural: an artifact claiming kernel performance must SAY it was
    measured on interpret-mode CPU. Calling this initializes the jax
    backend, so CLIs stamp AFTER ``set_platform``/``set_host_device_count``.
    """
    from repro.kernels.common import default_interpret

    devices = jax.devices()
    return {
        "backend": jax.default_backend(),
        "device_kind": devices[0].device_kind if devices else "none",
        "device_count": len(devices),
        "interpret": bool(default_interpret()),
        "jax_version": jax.__version__,
    }


def add_xla_flags(flags: str) -> None:
    """Append to ``XLA_FLAGS`` without clobbering flags already set."""
    existing = os.environ.get("XLA_FLAGS", "")
    os.environ["XLA_FLAGS"] = (existing + " " + flags).strip()


def set_platform(platform: str = "cpu") -> None:
    """Pin the jax platform ('cpu' | 'gpu' | 'tpu').

    Only takes effect at the beginning of the program (before backend
    init). On GPU also sets the standard XLA perf flags from the jax GPU
    performance-tips page.
    """
    if platform not in ("cpu", "gpu", "tpu"):
        raise ValueError(
            f"platform must be 'cpu', 'gpu' or 'tpu'; got {platform!r}")
    jax.config.update("jax_platform_name", platform)
    if platform == "gpu":
        # https://jax.readthedocs.io/en/latest/gpu_performance_tips.html
        add_xla_flags(
            "--xla_gpu_triton_gemm_any=True "
            "--xla_gpu_enable_latency_hiding_scheduler=true"
        )


def set_host_device_count(n: int) -> None:
    """Expose ``n`` host (CPU) devices to jax via XLA_FLAGS.

    The multi-device tests and data-parallel serving smoke runs use this to
    build a mesh on one machine. Must run before backend init; warns and
    clamps when asked for more than the physical core count.
    """
    n = int(n)
    total = cpu_count()
    if n > total:
        warnings.warn(
            f"only {total} CPUs available; using {total} host devices",
            stacklevel=2)
        n = total
    add_xla_flags(f"--xla_force_host_platform_device_count={n}")


def jax_enable_x64(use_x64: bool) -> None:
    """Switch default array precision to 64-bit (or back to 32-bit).

    Falls back to ``$JAX_ENABLE_X64`` when called with False, mirroring the
    env-var behavior jax itself honors.
    """
    if not use_x64:
        use_x64 = bool(os.getenv("JAX_ENABLE_X64", 0))
    jax.config.update("jax_enable_x64", use_x64)


def set_debug_nan(flag: bool) -> None:
    """Raise on NaN production (jax debugging flag); expensive — debug only."""
    jax.config.update("jax_debug_nans", flag)
