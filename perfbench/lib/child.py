"""What the chip-holding children share: bring-up that fails without the
chips, compile counting, memory readings, tagged output lines."""

from __future__ import annotations

import importlib.metadata
import json
import os
import sys
import time

TAG = "PB-RESULT "


def say(**fields):
    """A diagnostic line (relayed by the parent before the last line)."""
    print("perfbench: " + json.dumps(fields, default=str), flush=True)


def emit(kind, **fields):
    print(TAG + json.dumps(dict(fields, kind=kind), default=str), flush=True)


class CompileCounter:
    """Programs lowered and persistent-cache hits, from jax's monitoring."""

    def __init__(self):
        from jax import monitoring
        self.lowerings = self.cache_hits = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, name, _secs, **_kw):
        if name == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            self.lowerings += 1

    def _event(self, name, **_kw):
        if name == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def bring_up(chips, dry):
    """(device record, compile counter).  Exits 3 when jax finds another
    platform or number of chips than the cell asks for, or a device_kind
    outside the peaks table."""
    import jax
    import jaxlib

    from horovod_tpu.utils.platform import enable_compile_cache
    from . import peaks
    cache_dir = enable_compile_cache()
    counter = CompileCounter()
    devs = jax.devices()
    want = "cpu" if dry else "tpu"
    if devs[0].platform != want or len(devs) != chips:
        print(f"perfbench: need {chips} {want} device(s), jax found "
              f"{len(devs)} x {devs[0].platform}", file=sys.stderr)
        sys.exit(3)
    if not dry:
        peaks.device_peaks(devs[0].device_kind)
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = None
    say(platform=devs[0].platform, device_kind=devs[0].device_kind,
        count=len(devs), jax=jax.__version__, jaxlib=jaxlib.__version__,
        libtpu=libtpu, compile_cache_dir=cache_dir,
        compile_cache_from_env=bool(os.environ.get(
            "JAX_COMPILATION_CACHE_DIR")))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}, counter


def memory(key):
    import jax
    vals = [(d.memory_stats() or {}).get(key) for d in jax.local_devices()]
    return max((int(v) for v in vals if v is not None), default=0)


def since_start():
    """Seconds since the parent started (its clock, in PB_T0)."""
    return time.time() - float(os.environ["PB_T0"])


def llama_config(config, max_seq=None):
    """The program's config object for a configuration file."""
    import jax.numpy as jnp
    from horovod_tpu.models import llama
    return llama.LlamaConfig(
        vocab=config["vocab_size"], dim=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        ffn_dim=config["intermediate_size"],
        max_seq=max_seq or config["max_position_embeddings"],
        rope_theta=float(config["rope_theta"]),
        dtype={"bfloat16": jnp.bfloat16,
               "float32": jnp.float32}[config["torch_dtype"]])


def trace_options():
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts
