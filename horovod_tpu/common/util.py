"""Small cross-frontend utilities (reference: horovod/common/util.py).

The reference's module mixes build-capability probes, extension checks,
and list helpers; the TPU analogs that make sense here are implemented
against this stack (native core instead of per-framework C extensions;
jax backends instead of CUDA devices).
"""

from __future__ import annotations

import os
from typing import List, Sequence


def check_extension(ext_name: str = "horovod_tpu.csrc") -> None:
    """Verify the native coordination core is buildable/loadable
    (reference: util.py check_extension — raises ImportError with
    install guidance when the framework's C extension is absent).
    Raises ImportError with the build error when the core cannot load.
    """
    try:
        from .basics import load_library
        load_library()
    except Exception as e:
        raise ImportError(
            f"native core unavailable for {ext_name}: {e}\n"
            "Build it with `make -C csrc` (requires g++), or reinstall "
            "the wheel which ships the prebuilt library") from e


def gpu_available(ext_base_name: str = "jax", verbose: bool = False) -> bool:
    """Is an accelerator backend attached? (reference: util.py
    gpu_available — probes the framework's CUDA extension.)

    TPU analog: consult jax WITHOUT forcing backend init when the
    process is CPU-pinned — its answer is known without asking."""
    import jax

    if (os.environ.get("JAX_PLATFORMS") == "cpu"
            or jax.config.jax_platforms == "cpu"):
        return False
    try:
        devs = jax.local_devices()
    except Exception as e:  # backend init failed: no accelerator
        if verbose:
            print(f"gpu_available: backend init failed: {e}")
        return False
    return any(d.platform != "cpu" for d in devs)


def check_num_rank_power_of_2(num_rank: int) -> bool:
    """True when ``num_rank`` is a power of two (reference:
    mpi_ops.check_num_rank_power_of_2 — the Adasum recursive-halving
    precondition; parallel/adasum.py enforces the same rule)."""
    return num_rank > 0 and (num_rank & (num_rank - 1)) == 0


def backoff_delays(retries: int, base_ms: float, cap_ms: float = 2000.0,
                   rng=None) -> List[float]:
    """Exponential-backoff schedule in SECONDS with jitter: attempt i
    sleeps U[step/2, step] where step = min(cap, base * 2**i).

    One implementation shared by every retry loop that talks to a peer
    (rendezvous KV writes in ``runner/http_client.py``; the native
    transport mirrors the same schedule in ``csrc/transport.cc``), so the
    chaos suite can assert sequencing once.  ``rng`` (a
    ``random.Random``) makes the jitter deterministic for tests; the
    module-global stream is used otherwise."""
    import random as _random
    rng = rng or _random
    out: List[float] = []
    step = float(base_ms)
    for _ in range(max(0, retries)):
        step_c = min(step, float(cap_ms))
        out.append(rng.uniform(step_c / 2.0, step_c) / 1000.0)
        step *= 2.0
    return out


def split_list(items: Sequence, num_parts: int) -> List[list]:
    """Split into ``num_parts`` nearly-equal contiguous chunks
    (reference: util.py split_list, used by grouped allreduce)."""
    n = len(items)
    base, extra = divmod(n, num_parts)
    out, start = [], 0
    for i in range(num_parts):
        size = base + (1 if i < extra else 0)
        out.append(list(items[start:start + size]))
        start += size
    return out
