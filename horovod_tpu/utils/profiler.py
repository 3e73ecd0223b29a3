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

import numpy as np

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
    the phase's seconds and count.  ``t0`` and ``t1`` are its two
    ``perf_counter`` readings, for a caller that stamps between them."""
    __slots__ = ("clock", "name", "note", "t0", "t1")

    def __init__(self, clock: "PhaseClock", name: str):
        self.clock, self.name = clock, name
        self.note = annotate("hvd:" + name)

    def __enter__(self):
        self.note.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        c = self.clock
        self.t1 = time.perf_counter()
        c.phase_s[self.name] = c.phase_s.get(self.name, 0.0) \
            + (self.t1 - self.t0)
        c.phase_n[self.name] = c.phase_n.get(self.name, 0) + 1
        self.note.__exit__(*exc)


class PhaseClock:
    """Cumulative seconds and entries per named phase of a host loop.
    ``span(name)`` also opens the ``hvd:<name>`` profiler range, so inside
    a trace session the phase lies on the device trace's own clock; with
    none running that costs a flag test.  ``add(name, value)`` keeps any
    other running figure of the loop (``sums``).  One thread enters spans
    and adds; readers take ``snapshot()`` and subtract (``delta``).

    A total cannot say WHEN.  ``second()``, called once a tick, closes a
    bucket whenever the wall second has moved on: what every phase and sum
    gained since the bucket was opened, one row of a preallocated ring of
    the last ``SECONDS`` such seconds, which ``timeline()`` reads.  A span
    or an ``add`` does nothing for it."""

    SECONDS = 128
    # the ring's columns, in the order the names were first seen: a name
    # past them is kept in its total and not in the ring
    PHASES, SUMS = 16, 32

    def __init__(self):
        self.phase_s: Dict[str, float] = {}
        self.phase_n: Dict[str, int] = {}
        self.sums: Dict[str, float] = {}
        # a row of the ring: where each table's columns start, and how many
        self._tables = ((0, self.phase_s, self.PHASES),
                        (self.PHASES, self.phase_n, self.PHASES),
                        (2 * self.PHASES, self.sums, self.SUMS))
        self._ring = np.zeros((self.SECONDS, 2 * self.PHASES + self.SUMS))
        # row 0 takes what comes before the first ``second()``: it keeps
        # second 0, which ``timeline()`` never shows
        self._sec = [0] * self.SECONDS
        self._row = 0
        self._opened = self._figures()
        compile_counts()  # count compiles from the loop's first tick on

    def span(self, name: str) -> _Span:
        return _Span(self, name)

    def add(self, name: str, value: float) -> None:
        self.sums[name] = self.sums.get(name, 0) + value

    def _figures(self) -> np.ndarray:
        """The running figures as one row of the ring."""
        row = np.zeros(self._ring.shape[1])
        for at, table, width in self._tables:
            values = list(table.values())[:width]
            row[at:at + len(values)] = values
        return row

    def second(self) -> None:
        """Bucket by the present wall second (``int(time.time())``: the
        clock an observer outside the process and a profiler's host plane
        share); the loop calls it once a tick."""
        sec = int(time.time())
        if sec == self._sec[self._row]:
            return
        now = self._figures()
        self._ring[self._row] = now - self._opened
        self._opened = now
        self._row = (self._row + 1) % self.SECONDS
        self._sec[self._row] = sec

    def timeline(self) -> Dict[str, object]:
        """The ring's seconds as column lists, oldest first, the open one
        (so far) last: ``sec``, every phase's seconds and entries under
        ``phase_s`` and ``phase_n``, every sum under its name."""
        order = [(self._row + 1 + i) % self.SECONDS
                 for i in range(self.SECONDS)]
        order = [row for row in order if self._sec[row]]
        table = self._ring[order]
        if order:
            table[-1] = self._figures() - self._opened
        columns = np.round(table, 6).T.tolist()
        phase_s, phase_n, sums = (
            dict(zip(list(names)[:width], columns[at:]))
            for at, names, width in self._tables)
        return dict(sums, sec=[self._sec[row] for row in order],
                    phase_s=phase_s, phase_n=phase_n)

    def snapshot(self) -> Dict[str, object]:
        return dict(compile_counts(), phase_s=dict(self.phase_s),
                    phase_n=dict(self.phase_n), sums=dict(self.sums))

    def delta(self, since: Dict[str, object]) -> Dict[str, object]:
        """``snapshot()`` less the earlier snapshot ``since``."""
        now = self.snapshot()
        out = {k: now[k] - since.get(k, 0) for k in ("compiles", "cache_hits")}
        for k in ("phase_s", "phase_n", "sums"):
            out[k] = {p: v - since.get(k, {}).get(p, 0)
                      for p, v in now[k].items()}
        return out


__all__ = ["start", "stop", "trace", "annotate", "annotate_function",
           "is_active", "timed", "compile_counts", "PhaseClock"]
