"""Backend/platform selection for the stacked-sweep launch paths.

The serving kernels pick their execution form per-backend
(:func:`repro.kernels.stacked_sweep.resolve_stacked_backend`): the Mosaic
Pallas kernel on TPU, the jitted jnp twin compiled by XLA:GPU on GPU (the
TPU-shaped ``PrefetchScalarGridSpec`` has no Triton lowering -- the twin
*is* the GPU lowering, and forcing ``use_kernel=True`` there degrades to
``interpret=True`` parity mode), and the interpreted/jnp twin on CPU.
This module owns the process-level switches that make that dispatch land
where intended:

* :func:`set_platform` -- pin ``jax_platform_name`` and, for GPU, apply
  the XLA performance-flag recipe (async collectives, latency-hiding
  scheduler, Triton gemm) *before* the first computation runs;
* :func:`set_host_cpu_devices` -- fabricate N host CPU devices (the CI
  mesh lane's 4-device topology on GPU-less runners);
* :func:`platform_diagnostics` -- what a bug report needs: resolved
  backend, device inventory, and how the stacked sweep will route;
* :func:`use_compile_cache` -- the persistent compilation cache of the
  entry points (``chip_smoke.py``, ``benchmarks/run.py``).

Flag edits only take effect before JAX initializes its backends; both
setters therefore *merge* into ``XLA_FLAGS`` (never clobber -- a user's
``--xla_force_host_platform_device_count`` must survive a later
``set_platform('gpu')``) and warn when called after backend init.
"""
from __future__ import annotations

import os
import warnings
from pathlib import Path

import jax

__all__ = ["set_platform", "set_host_cpu_devices", "platform_diagnostics",
           "use_compile_cache", "GPU_XLA_FLAGS"]

#: the compile cache's fixed home when ``JAX_COMPILATION_CACHE_DIR`` is
#: unset: inside the checkout (ignored by git), so every run of the same
#: checkout finds the programs the previous one compiled -- the path is
#: part of the cache key, so it must not move between runs.
_CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"

#: the XLA:GPU serving recipe (jax.readthedocs.io gpu_performance_tips):
#: async collectives + latency-hiding scheduling overlap the mesh path's
#: all_gathers with compute; the Triton gemm knobs route the jnp twin's
#: scoring matmuls (bf16/int8 probe included) through Triton.
GPU_XLA_FLAGS = (
    "--xla_gpu_enable_triton_softmax_fusion=true",
    "--xla_gpu_triton_gemm_any=True",
    "--xla_gpu_enable_async_collectives=true",
    "--xla_gpu_enable_latency_hiding_scheduler=true",
    "--xla_gpu_enable_highest_priority_async_stream=true",
)


def _backends_initialized() -> bool:
    """Whether JAX has already committed to its backends (flag edits
    after this point silently do nothing)."""
    from jax._src import xla_bridge

    return xla_bridge.backends_are_initialized()


def _merge_xla_flags(flags) -> None:
    """Append ``flags`` to ``XLA_FLAGS``, skipping any whose option name
    is already present (user settings win)."""
    current = os.environ.get("XLA_FLAGS", "")
    have = {f.split("=", 1)[0] for f in current.split() if f}
    add = [f for f in flags if f.split("=", 1)[0] not in have]
    if add:
        os.environ["XLA_FLAGS"] = " ".join(
            ([current] if current else []) + add)


def set_platform(platform: str = "cpu") -> None:
    """Pin the JAX platform to ``'cpu'``/``'gpu'``/``'tpu'`` and, on GPU,
    merge :data:`GPU_XLA_FLAGS` into the environment.  Call before the
    first JAX computation of the process -- platform/flag changes after
    backend initialization do not take effect (warned, not raised: tests
    exercise the GPU *route* on CPU hosts via the interpret twin)."""
    if platform not in ("cpu", "gpu", "tpu"):
        raise ValueError(f"platform {platform!r} not in "
                         "('cpu', 'gpu', 'tpu')")
    if _backends_initialized():
        warnings.warn(
            "set_platform() called after JAX backend initialization; "
            "the platform pin (and any XLA flags) may not take effect",
            RuntimeWarning, stacklevel=2)
    if platform == "gpu":
        _merge_xla_flags(GPU_XLA_FLAGS)
    jax.config.update("jax_platform_name", platform)


def set_host_cpu_devices(n: int) -> None:
    """Fabricate ``n`` host CPU devices
    (``--xla_force_host_platform_device_count``) -- the GPU-less mesh
    topology CI runs the ``-m mesh`` lane under.  Must run before
    backend initialization, like :func:`set_platform`."""
    if n < 1:
        raise ValueError(f"need >= 1 device, got {n}")
    if _backends_initialized():
        warnings.warn(
            "set_host_cpu_devices() called after JAX backend "
            "initialization; the device count will not change",
            RuntimeWarning, stacklevel=2)
    current = os.environ.get("XLA_FLAGS", "")
    kept = [f for f in current.split()
            if not f.startswith("--xla_force_host_platform_device_count")]
    kept.append(f"--xla_force_host_platform_device_count={n}")
    os.environ["XLA_FLAGS"] = " ".join(kept)


def platform_diagnostics() -> dict:
    """Resolved platform state + how the stacked sweep will route on it:
    ``backend``, ``device_count``, ``devices`` (kind strings),
    ``use_kernel``/``interpret`` (the launch form
    :func:`resolve_stacked_backend` picks), and the active
    ``XLA_FLAGS``."""
    from repro.kernels.stacked_sweep import resolve_stacked_backend

    use_kernel, interpret = resolve_stacked_backend(None, None)
    return {
        "backend": jax.default_backend(),
        "device_count": jax.device_count(),
        "devices": [d.device_kind for d in jax.devices()],
        "use_kernel": use_kernel,
        "interpret": interpret,
        "xla_flags": os.environ.get("XLA_FLAGS", ""),
    }


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its
    directory: ``JAX_COMPILATION_CACHE_DIR`` when set (JAX reads it
    itself, so nothing else is set here), else the checkout's
    ``.jax_cache/``.  Call before the first compile."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(_CHECKOUT_CACHE)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
