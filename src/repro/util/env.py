"""Process-level JAX environment configuration, in one place.

Every driver that needs a non-default JAX environment — the forced
multi-device subprocess checks, the GPU benchmark scripts, the x64 oracle
comparisons — used to splice its own ``XLA_FLAGS`` string before importing
jax.  That pattern is fragile twice over: a second assignment silently
clobbers the first (the olmax run scripts' classic bug), and a flag set
*after* jax initialises its backends does nothing at all.  This module owns
the assembly:

  * :func:`force_host_device_count` — N fake host devices (the CPU-hosted
    mesh every distributed check runs on), merged into ``XLA_FLAGS``
    without clobbering other flags;
  * :func:`set_platform` — pin the backend (cpu/gpu/tpu) before or after
    jax import;
  * :func:`enable_x64` — the fp64 switch, env-var or config API;
  * :func:`gpu_xla_flags` — the standard GPU performance flag set
    (latency-hiding scheduler, triton gemms, async collectives) as a
    string, merged via :func:`merge_xla_flags`;
  * :func:`configure` — the one-call spelling the test drivers use.

Flag-level helpers are import-order safe: they touch only ``os.environ``
and never import jax themselves, so calling them at the top of a driver
(before jax is imported anywhere in the process) is guaranteed effective.
Helpers that go through ``jax.config`` import jax lazily and say so.
"""

from __future__ import annotations

import os
import re

__all__ = ["compile_cache", "configure", "enable_x64",
           "force_host_device_count", "gpu_xla_flags", "merge_xla_flags",
           "set_platform"]

#: Environment variable JAX reads its persistent compilation cache from.
COMPILE_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"

#: The GPU flag set from jax's own performance-tips page; a starting point,
#: not gospel — benchmarks should re-validate on their hardware.
GPU_PERF_FLAGS = (
    "--xla_gpu_triton_gemm_any=True",
    "--xla_gpu_enable_latency_hiding_scheduler=true",
    "--xla_gpu_enable_highest_priority_async_stream=true",
)


def merge_xla_flags(*flags: str) -> str:
    """Merge ``flags`` into ``os.environ['XLA_FLAGS']``, replacing any
    existing setting of the same ``--flag_name`` instead of appending a
    duplicate (XLA takes the LAST occurrence, so duplicates are at best
    confusing and at worst mask the value a driver thinks it set).
    Returns the resulting flag string."""
    current = os.environ.get("XLA_FLAGS", "").split()
    for flag in flags:
        name = flag.split("=", 1)[0]
        current = [f for f in current if f.split("=", 1)[0] != name]
        current.append(flag)
    merged = " ".join(current)
    os.environ["XLA_FLAGS"] = merged
    return merged


def force_host_device_count(n: int) -> None:
    """Make the CPU platform report ``n`` devices — the substrate of every
    CPU-hosted mesh test (faun grids, serve meshes).  MUST run before jax
    is first imported in the process; it edits ``XLA_FLAGS`` only, so
    import this module at the very top of a driver, call this, then import
    jax."""
    if "jax" in _loaded_modules():
        import warnings
        warnings.warn(
            "force_host_device_count called after jax was imported — the "
            "XLA CPU client is already initialised and the flag will not "
            "take effect until a new process", RuntimeWarning, stacklevel=2)
    merge_xla_flags(f"--xla_force_host_platform_device_count={int(n)}")


def _loaded_modules():
    import sys
    return sys.modules


def set_platform(platform: str = "cpu") -> None:
    """Pin the JAX backend.  Before jax import this sets ``JAX_PLATFORMS``
    (the authoritative spelling); after import it additionally updates
    ``jax.config`` so the change still lands where possible."""
    if platform not in ("cpu", "gpu", "tpu"):
        raise ValueError(f"platform must be cpu|gpu|tpu, got {platform!r}")
    os.environ["JAX_PLATFORMS"] = platform
    if "jax" in _loaded_modules():
        import jax
        jax.config.update("jax_platform_name", platform)


def compile_cache(root: str) -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already uses it and
    nothing else is set here.  Otherwise the cache goes to ``.jax_cache``
    under ``root`` (a checkout; the directory is gitignored): a fixed path,
    because the path is part of the cache key, so a name made from a
    process id or the time would never hit.  Imports jax."""
    import jax
    path = os.environ.get(COMPILE_CACHE_ENV)
    if not path:
        path = os.path.join(os.path.abspath(root), ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def enable_x64(on: bool = True) -> None:
    """Toggle 64-bit array defaults.  Effective at any point (jax reads the
    config dynamically); also exports ``JAX_ENABLE_X64`` so subprocesses
    launched from here inherit the choice."""
    os.environ["JAX_ENABLE_X64"] = "1" if on else "0"
    if "jax" in _loaded_modules():
        import jax
        jax.config.update("jax_enable_x64", bool(on))


def gpu_xla_flags(extra: tuple[str, ...] = ()) -> str:
    """Merge the standard GPU performance flags (plus ``extra``) into
    ``XLA_FLAGS`` and return the result.  Call before jax import."""
    return merge_xla_flags(*GPU_PERF_FLAGS, *extra)


def configure(*, platform: str | None = None, x64: bool | None = None,
              host_device_count: int | None = None,
              gpu_perf_flags: bool = False) -> None:
    """One-call environment setup — the spelling the distributed-check
    drivers use::

        from repro.util import env
        env.configure(host_device_count=8)   # before importing jax
        import jax
    """
    if host_device_count is not None:
        force_host_device_count(host_device_count)
    if gpu_perf_flags:
        gpu_xla_flags()
    if platform is not None:
        set_platform(platform)
    if x64 is not None:
        enable_x64(x64)
