"""Arrivals are pushed to the serving loop, not polled by it
(docs/serving.md#the-loops-order).

The router numbers the requests of a replica densely (``req.000000``,
``req.000001``, ...; serve/router.py), so the next one's key is known
before it exists.  :class:`Arrivals` keeps ONE reader thread beside rank
0's loop that waits for that key — a GET the rendezvous server holds until
the key is written (``?wait=``, runner/http_server.py; the router's
enqueue and a PUT both wake it), over a connection the reader keeps
(runner/http_client.py ``KeyWaiter``) —, decodes the record, puts it on a
queue and waits for the next number.  The loop's poll
(``FleetFrontend._drain_requests``) empties the queue and touches no
socket; a loop with nothing to do blocks on the queue (:meth:`wait`) and
wakes the moment a record is put, where it used to sleep out a fixed
20 ms.

What the loop can count of it goes on its clock (``PhaseClock.add``;
``stats()["loop"]``): ``arrival_n`` records handed to the loop and
``arrival_lag_s``, the sum over them of the router's ``submitted_t`` to
the put on the queue (wall clocks: exact on one host); ``idle_wait_n``
waits of an idle loop and ``arrival_wake_n``, those of them that a record
ended.
"""

from __future__ import annotations

import json
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

from .router import req_key

# the figures kept on the loop's clock
SUMS = ("arrival_n", "arrival_lag_s", "arrival_wake_n", "idle_wait_n")
# How long the server may hold one GET.  No latency hangs on it: a wait
# that runs out costs one round trip on an idle connection, and the server
# keeps a thread that long for a reader that went away without a word.
WAIT_S = 1.0
# how long a reader that has been told to stop is waited for: one step of
# the KV legs' backoff (common/util.backoff_delays caps a step at 2 s), in
# which a reader that rides out an outage may be asleep
_JOIN_S = 3.0


def decode(raw: bytes) -> Optional[Dict[str, Any]]:
    """A request's record, or None for a torn PUT: it holds its number in
    the dense numbering and nothing is submitted for it."""
    try:
        return json.loads(raw)
    except (ValueError, TypeError):
        return None


class Arrivals:
    """The reader and its queue, for one ``FleetFrontend.run``.  ``waiter``
    is the kept connection (its ``wait_kv(key, seconds)``, ``interrupt()``
    and ``close()``); ``kv_op`` the front's bounded retry, which every
    serving KV leg rides (hvdlint ``serve-kv-retry``): an outage stalls
    the reader, and so the arrivals, while the loop goes on ticking;
    ``next_seq`` the first number not yet handed to the loop; ``add`` the
    loop clock's; ``pace_s`` the cadence at which a server that cannot
    hold a GET is probed instead.  The loop's thread calls everything but
    ``_read``."""

    def __init__(self, waiter, kv_op: Callable[[Callable[[], Any], str], Any],
                 next_seq: int, add: Callable[[str, float], None],
                 pace_s: float):
        self._waiter, self._kv_op, self._add = waiter, kv_op, add
        self._seq, self._pace_s = int(next_seq), float(pace_s)
        self._cond = threading.Condition()
        self._records: List[Tuple[Optional[Dict[str, Any]], float]] = []
        self._error: Optional[Exception] = None
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        for name in SUMS:       # every figure is there from the start
            add(name, 0)

    @property
    def started(self) -> bool:
        return self._thread is not None

    def start(self) -> None:
        self._thread = threading.Thread(
            target=self._read, name="hvd-serve-arrivals", daemon=True)
        self._thread.start()

    # -------------------------------------------------------- the reader
    def _read(self) -> None:
        try:
            while not self._stop.is_set():
                key = req_key(self._seq)
                raw, held = self._kv_op(
                    lambda: self._waiter.wait_kv(key, WAIT_S),
                    f"wait {self._waiter.scope}/{key}")
                if raw is None:
                    if not held:
                        # a server that does not know ``?wait=``: probe
                        self._stop.wait(self._pace_s)
                    continue
                rec = decode(raw)
                lag = 0.0
                if isinstance(rec, dict) and \
                        rec.get("submitted_t") is not None:
                    lag = max(0.0, time.time() - float(rec["submitted_t"]))
                with self._cond:
                    self._records.append((rec, lag))
                    self._cond.notify()
                self._seq += 1
        except Exception as e:
            # the retry's budget is spent or the fault is no outage: the
            # loop's next poll raises it, as its own probe would have
            with self._cond:
                self._error = e
                self._cond.notify()
        finally:
            self._waiter.close()

    # ---------------------------------------------------------- the loop
    def drain(self) -> List[Optional[Dict[str, Any]]]:
        """Every record that has come, in sequence order (None: a torn
        PUT); raises what ended the reader."""
        with self._cond:
            taken, self._records = self._records, []
            error = self._error
        if error is not None and not taken:
            raise error
        if taken:
            self._add("arrival_n", len(taken))
            self._add("arrival_lag_s", sum(lag for _, lag in taken))
        return [rec for rec, _ in taken]

    def wait(self, timeout: float) -> bool:
        """An idle loop's wait: until a record is on the queue (True; the
        next poll takes it) or ``timeout`` has passed."""
        with self._cond:
            self._cond.wait_for(
                lambda: bool(self._records) or self._error is not None,
                max(0.0, timeout))
            woken = bool(self._records)
        self._add("idle_wait_n", 1)
        if woken:
            self._add("arrival_wake_n", 1)
        return woken

    def stop(self) -> None:
        """End the reader.  What it had fetched and the loop has not taken
        stays in the store, under numbers the loop has not passed."""
        self._stop.set()
        self._waiter.interrupt()
        if self._thread is not None:
            self._thread.join(_JOIN_S)
