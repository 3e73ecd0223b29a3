"""Process-level jax setup the entry points share: forcing the CPU
backend for smoke modes, and placing the persistent compilation cache.

``JAX_PLATFORMS`` binds the backend when jax is imported, and spawned
workers inherit it.  ``force_cpu()`` exists for the examples' ``--cpu``
switches, which run after ``import horovod_tpu`` has already imported
jax: there the environment variable alone comes too late for THIS
process, so the jax config is updated as well.

Reference analog: the reference pins devices per process via
``CUDA_VISIBLE_DEVICES`` at spawn time (horovod/runner/gloo_run.py).
"""

from __future__ import annotations

import os

# Inside the checkout, and fixed: the directory is part of the cache key,
# so a path that moves (tempfile, pid, time) never hits.
DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def force_cpu(virtual_chips: int | None = None) -> None:
    """Force this process (and spawned children) onto the CPU backend.

    ``virtual_chips`` additionally requests N virtual CPU devices via
    XLA's host-platform device-count flag (the smoke-mode mesh every
    example uses); an existing device-count flag in ``XLA_FLAGS`` wins,
    so launcher-provided settings are never clobbered.

    Safe to call multiple times; raises RuntimeError if a non-CPU
    backend was already initialized (the caller ran too late to be a
    CPU-only process).
    """
    if virtual_chips:
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + f" --xla_force_host_platform_device_count="
                f"{virtual_chips}").strip()
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    if jax.config.jax_platforms != "cpu":
        try:
            jax.config.update("jax_platforms", "cpu")
        except Exception as e:  # backends already initialized
            raise RuntimeError(
                "force_cpu() called after a non-cpu jax backend "
                "initialized; call it before any jax-touching import"
            ) from e


def enable_compile_cache() -> str | None:
    """Turn on jax's persistent compilation cache before the first
    compile; returns the directory in use, or None when left off.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax has already read it
    and NO directory is set in code (the machine that runs us chose the
    place, and only that place survives from one run to the next);
    otherwise the fixed ``DEFAULT_COMPILE_CACHE_DIR`` in the checkout.

    Left OFF when the process is pinned to the CPU backend: XLA:CPU
    caches AOT results keyed without the exact host machine features,
    so an entry written on one machine loads on another with a "could
    lead to execution errors such as SIGILL" warning and can compute
    GARBAGE (observed: bitwise-constant losses).  CPU compiles are
    seconds anyway; the cache exists for the minutes-long TPU compiles.
    """
    import jax

    if jax.config.jax_platforms == "cpu":
        jax.config.update("jax_enable_compilation_cache", False)
        return None
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR", "")
    if env_dir:
        return env_dir
    os.makedirs(DEFAULT_COMPILE_CACHE_DIR, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", DEFAULT_COMPILE_CACHE_DIR)
    return DEFAULT_COMPILE_CACHE_DIR
