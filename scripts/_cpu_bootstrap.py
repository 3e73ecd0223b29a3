"""CPU virtualization BEFORE jax backend init — the canonical copy.

Every CPU-side multi-process entrypoint (integration workers, dryrun
native leg, eager bench) calls bootstrap() as its first act, before it
imports jax, so the platform and the per-process device count live in
exactly one place.
"""

import os


def bootstrap(default_chips: int = 1) -> None:
    """Force the CPU backend with HVD_CPU_CHIPS virtual devices
    (default `default_chips`) for this process and its children."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        chips = os.environ.get("HVD_CPU_CHIPS", str(default_chips))
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count="
            + chips).strip()
