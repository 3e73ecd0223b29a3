"""The commit point of the serving loop: WHEN the next tick's plan is fixed
(docs/serving.md#the-loops-order).

``ServeEngine.step()`` launches tick N+1 while tick N runs, so that no
host work lies between two programs.  Launched the moment N has started,
N+1's plan is a whole tick old when N+1 begins, and a request that arrives
meanwhile waits out a program it is not in.  Launched when N is about to
end, the plan is a few milliseconds old.  :class:`CommitPoint` says when
that is, from what the loop itself measures and nothing else:

* a tick's length — the distance between the ready stamps of two
  back-to-back ticks, kept by the width of the executable that ran (the
  plan of N+1 does not change how long N takes): the SHORTEST of the last
  ``TICKS`` of that width, so that a stamp taken late errs towards an
  early commit;
* the margin — what the loop needs from the instant it stops holding to
  the return of the launch (the wake-up, ``poll``, ``submit``, ``plan``,
  ``stage``, ``launch`` and what lies between them): the LONGEST of the
  last ``LAUNCHES`` such paths, so that the periodic ones (a drain probe,
  an arrival's admission) are inside it.

``due = began + length - margin``.  Both windows are the measurements' own
scatter, not a setting; there is none.
"""

from __future__ import annotations

import collections
import time
from typing import Callable, Dict, Optional

# why the loop did not hold, as ``stats()["loop"]["hold_skipped_n"]`` names
# them: no tick in flight; no length of that width, no margin or no exact
# start yet; an arrival could get no row of the next tick anyway; the tick
# would be over before the loop could launch
SKIPS = ("nothing_in_flight", "no_estimate", "no_room", "not_worth_it")
# the figures the commit point keeps on the loop's clock (PhaseClock.add);
# ``hold_n`` and ``hold_s`` are added by whoever waits (serve/worker.py)
SUMS = ("hold_n", "hold_s", "late_n") + tuple("hold_skip_" + s for s in SKIPS)


class CommitPoint:
    """One engine's commit point.  The engine tells it of every launch
    (:meth:`launched`) and every fence (:meth:`fenced`); the loop asks it
    once an iteration, after it has published the fenced tick's tokens, how
    long to hold (:meth:`due`).  A caller that never asks (``flush()``, a
    test that drives ``step()``) is told nothing and nothing changes for
    it.  ``add`` is the loop clock's (``PhaseClock.add``), ``now`` a
    ``perf_counter``."""

    # Both windows cover what recurs: a second of launches holds the loop's
    # periodic paths; 32 ticks of a width hold a whole prompt's chunks, so
    # that the next prompt's first, shallow and shorter ones are not
    # estimated by the last one's deepest.
    TICKS = 32
    LAUNCHES = 64

    def __init__(self, add: Callable[[str, float], None],
                 now: Callable[[], float] = time.perf_counter):
        self._add, self._now = add, now
        self._ticks: Dict[str, collections.deque] = {}
        self._paths: collections.deque = collections.deque(
            maxlen=self.LAUNCHES)
        # read by ``stats()`` from another thread: plain values, replaced
        self.estimate_s: Dict[str, float] = {}
        self.margin_s: Optional[float] = None
        # when the oldest tick in flight began on the device: the ready
        # stamp of the tick before it, where that stamp saw the tick end
        # and this one was queued behind it; else unknown
        self._began: Optional[float] = None
        # where the next launch's path is counted from (the due time, or
        # the instant the loop asked and was told not to hold), and whether
        # the loop holds for it
        self._from: Optional[float] = None
        self._held = False

    # ------------------------------------------------------------ the loop
    def due(self, width: Optional[str], room: Callable[[], bool]
            ) -> Optional[float]:
        """When to stop holding and commit the next plan, or None: commit
        now, as a loop without a commit point does (the reason counted).
        ``width`` is the executable of the tick in flight (None: nothing in
        flight); ``room()`` whether a request that arrives meanwhile could
        still get a row of the next tick."""
        now = self._now()
        self._from, self._held = now, False
        length = self.estimate_s.get(width)
        if width is None:
            reason = "nothing_in_flight"
        elif self._began is None or length is None or self.margin_s is None:
            reason = "no_estimate"
        elif not room():
            reason = "no_room"
        else:
            due = self._began + length - self.margin_s
            if due > now:
                self._from, self._held = due, True
                return due
            reason = "not_worth_it"
        self._add("hold_skip_" + reason, 1)
        return None

    # ---------------------------------------------------------- the engine
    def launched(self, t1: float, behind: Optional[str], late: bool,
                 timed: bool = True) -> None:
        """A launch returned at ``t1``, queued behind a tick of the width
        ``behind`` (None: nothing was in flight).  ``late``: that tick had
        already ended — the device idled, which ``ahead_n`` cannot see,
        since the tick was still unfenced.  ``timed``: the path to this
        launch is one the next will take too (no program was built in
        it)."""
        if self._from is not None and timed:
            self._paths.append(t1 - self._from)
            self.margin_s = max(self._paths)
        if late:
            if self._held:
                self._add("late_n", 1)
            if self._began is not None:
                # a measurement too: the tick took less than this.  Without
                # it a length that reads too long would keep every launch
                # late, and no late tick is ever timed.
                self._tick(behind, t1 - self._began)
        self._from, self._held = None, False

    def fenced(self, width: str, ready: float, exact: bool, ahead: bool
               ) -> None:
        """A tick of ``width`` was fenced, found ready at ``ready``.
        ``exact``: the wait saw it end (it was not over before the wait
        began, as behind a late launch); ``ahead``: a newer tick is queued
        behind it, and began when this one ended."""
        if exact and self._began is not None:
            self._tick(width, ready - self._began)
        self._began = ready if exact and ahead else None

    def _tick(self, width: str, seconds: float) -> None:
        ticks = self._ticks.setdefault(
            width, collections.deque(maxlen=self.TICKS))
        ticks.append(seconds)
        self.estimate_s[width] = min(ticks)

    def view(self) -> Dict[str, object]:
        """What the next due time would be made of."""
        return {"estimate_s": dict(self.estimate_s),
                "margin_s": self.margin_s}
