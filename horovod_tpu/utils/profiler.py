"""xprof / JAX-profiler integration — the TPU-native analog of the
reference's NVTX op ranges (reference: horovod/common/nvtx_op_range.h +
operations.cc:1018-1033: every user-facing op opens an NVTX range so
device traces attribute time to the op that launched it).

On TPU the tracer is the JAX profiler (xprof/TensorBoard): ``start`` /
``stop`` wrap a trace session, and ``annotate`` opens a named host range
that xprof correlates with device activity.  The framework's eager
collectives annotate themselves (ops/collectives.py), so a captured
trace shows HOROVOD_ALLREDUCE etc. exactly where the reference would
show its NVTX ranges.  The Chrome-trace Timeline (utils/timeline.py)
remains the lightweight always-on story; this is the deep-dive tool.
"""

from __future__ import annotations

import contextlib
import time
from typing import Callable, Dict, Iterator, Optional, Tuple

_active_logdir: Optional[str] = None
_compiles = {"compiles": 0, "cache_hits": 0, "listening": False}


def start(logdir: str) -> None:
    """Begin an xprof trace session writing into ``logdir`` (view with
    TensorBoard's profile plugin or xprof)."""
    global _active_logdir
    import jax
    jax.profiler.start_trace(logdir)
    _active_logdir = logdir


def stop() -> None:
    global _active_logdir
    import jax
    try:
        jax.profiler.stop_trace()
    finally:
        # Clear even when stop_trace raises (e.g. the session was already
        # stopped directly through jax.profiler) — a stuck is_active()
        # would block every future session in this process.
        _active_logdir = None


def is_active() -> bool:
    return _active_logdir is not None


@contextlib.contextmanager
def trace(logdir: str) -> Iterator[None]:
    """``with hvd.profiler.trace("/tmp/prof"): step()`` — session-scoped
    capture."""
    start(logdir)
    try:
        yield
    finally:
        stop()


def annotate(name: str):
    """Named range correlated with device activity in the captured trace
    (NVTX-range analog).  Context manager; cheap enough to leave on
    unconditionally — outside a trace session the annotation is a no-op.
    For the decorator form use :func:`annotate_function`."""
    import jax
    return jax.profiler.TraceAnnotation(name)


def annotate_function(fn, name: Optional[str] = None):
    """Decorator form: every call of ``fn`` opens a named range
    (``jax.profiler.annotate_function`` passthrough)."""
    import jax
    return jax.profiler.annotate_function(fn, name=name)


def timed(fn: Callable[[], object],
          name: str = "HOROVOD_EXEC") -> Tuple[object, float]:
    """Run ``fn`` inside a named profiler range and return
    ``(result, duration_us)``.

    The measured-duration bridge between this deep-dive tracer and the
    lightweight timeline: the negotiated dispatch path wraps each
    collective's execution here and feeds the duration into its EXEC
    timeline span (ops/negotiated.py), so the Chrome trace shows how
    long the op actually ran instead of a zero-width begin/end pair —
    and an xprof capture correlates the same range with device activity.
    The annotation is best-effort; the measurement never is."""
    try:
        ctx = annotate(name)
    except Exception:
        ctx = contextlib.nullcontext()  # no jax: keep the measurement
    t0 = time.perf_counter_ns()
    with ctx:
        result = fn()
    return result, (time.perf_counter_ns() - t0) / 1e3


def compile_counts() -> Dict[str, int]:
    """Programs this process lowered (each a compile or a persistent-cache
    read) and persistent-cache hits, from jax's own monitoring.  The
    listeners are registered on the first call and count from then on."""
    if not _compiles["listening"]:
        from jax import monitoring

        def duration(name, _secs, **_kw):
            if name == "/jax/core/compile/jaxpr_to_mlir_module_duration":
                _compiles["compiles"] += 1

        def event(name, **_kw):
            if name == "/jax/compilation_cache/cache_hits":
                _compiles["cache_hits"] += 1
        monitoring.register_event_duration_secs_listener(duration)
        monitoring.register_event_listener(event)
        _compiles["listening"] = True
    return {"compiles": _compiles["compiles"],
            "cache_hits": _compiles["cache_hits"]}


class _Span:
    """One entry of a PhaseClock phase: the profiler range, and on exit
    the phase's seconds and count."""
    __slots__ = ("clock", "name", "note", "t0")

    def __init__(self, clock: "PhaseClock", name: str):
        self.clock, self.name = clock, name
        self.note = annotate("hvd:" + name)

    def __enter__(self):
        self.note.__enter__()
        self.t0 = time.perf_counter()

    def __exit__(self, *exc):
        c, dt = self.clock, time.perf_counter() - self.t0
        c.phase_s[self.name] = c.phase_s.get(self.name, 0.0) + dt
        c.phase_n[self.name] = c.phase_n.get(self.name, 0) + 1
        self.note.__exit__(*exc)


class PhaseClock:
    """Cumulative seconds and entries per named phase of a host loop.
    ``span(name)`` also opens the ``hvd:<name>`` profiler range, so inside
    a trace session the phase lies on the device trace's own clock; with
    none running that costs a flag test.  One thread enters spans; readers
    take ``snapshot()`` and subtract (``delta``)."""

    def __init__(self):
        self.phase_s: Dict[str, float] = {}
        self.phase_n: Dict[str, int] = {}
        compile_counts()  # count compiles from the loop's first tick on

    def span(self, name: str) -> _Span:
        return _Span(self, name)

    def snapshot(self) -> Dict[str, object]:
        return dict(compile_counts(), phase_s=dict(self.phase_s),
                    phase_n=dict(self.phase_n))

    def delta(self, since: Dict[str, object]) -> Dict[str, object]:
        """``snapshot()`` less the earlier snapshot ``since``."""
        now = self.snapshot()
        out = {k: now[k] - since.get(k, 0) for k in ("compiles", "cache_hits")}
        for k in ("phase_s", "phase_n"):
            out[k] = {p: v - since.get(k, {}).get(p, 0)
                      for p, v in now[k].items()}
        return out


__all__ = ["start", "stop", "trace", "annotate", "annotate_function",
           "is_active", "timed", "compile_counts", "PhaseClock"]
