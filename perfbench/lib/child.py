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


def thread_placement():
    """Where the calling thread runs and how often it was switched out: the
    core it is on (field 39 of /proc/thread-self/stat), how many cores it
    may use, and its voluntary and involuntary context switches so far.
    For the notes of a run whose host phases read slow (PERF.md)."""
    import resource
    try:
        with open("/proc/thread-self/stat") as f:
            core = int(f.read().rsplit(")", 1)[1].split()[36])
    except (OSError, ValueError, IndexError):
        core = None
    ru = resource.getrusage(resource.RUSAGE_THREAD)
    return {"core": core, "cores_allowed": len(os.sched_getaffinity(0)),
            "switches_voluntary": ru.ru_nvcsw,
            "switches_involuntary": ru.ru_nivcsw,
            "cpu_user_s": ru.ru_utime, "cpu_system_s": ru.ru_stime}


def since_start():
    """Seconds since the parent started (its clock, in PB_T0)."""
    return time.time() - float(os.environ["PB_T0"])


def trace_options():
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts
