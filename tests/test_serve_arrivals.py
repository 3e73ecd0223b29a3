"""Arrivals are pushed to the serving loop, not polled by it
(horovod_tpu/serve/arrivals.py, ``FleetFrontend._drain_requests`` /
``_idle_wait``, the held GET of runner/http_server.py and
``http_client.KeyWaiter``; docs/serving.md#the-loops-order).

Host-side machinery only, through the real rendezvous server with the
scripted engine of tests/test_serve_ft.py: counts and order, and no bound
on a wall clock tighter than a second."""

import json
import threading
import time
import urllib.error
import urllib.request
from http.server import ThreadingHTTPServer

import pytest

import horovod_tpu.chaos as chaos
import horovod_tpu.serve.worker as worker_mod
from horovod_tpu.runner import http_client
from horovod_tpu.runner.http_server import RendezvousServer, _KVHandler
from horovod_tpu.serve import arrivals as arrivals_mod
from horovod_tpu.serve import router as R
from horovod_tpu.serve.arrivals import SUMS, Arrivals
from horovod_tpu.serve.replica import scoped
from horovod_tpu.serve.router import (DRAIN_KEY, OUT_SCOPE, REQ_SCOPE,
                                      STATS_SCOPE, RouterState, req_key)
from horovod_tpu.serve.worker import FleetFrontend
from horovod_tpu.utils import metrics as M
from horovod_tpu.utils.profiler import PhaseClock

from test_serve_ft import ScriptedEngine, scripted_tokens

READER = "hvd-serve-arrivals"
FAR = 600.0     # a bound of the idle wait far above any test's length


class Engine(ScriptedEngine):
    """The scripted engine with the loop's clock (so that the test reads
    what ``stats()["loop"]`` would), the order of its submissions, and a
    step that takes ``step_s``."""

    def __init__(self, step_s=0.0):
        super().__init__()
        self.clock = PhaseClock()
        self.order = []
        self.step_s = step_s

    def submit(self, tokens, max_new_tokens, req_id=None, eos_id=None):
        self.order.append(req_id)
        super().submit(tokens, max_new_tokens, req_id, eos_id)

    def step(self):
        if self.active and self.step_s:
            time.sleep(self.step_s)
        return super().step()


@pytest.fixture()
def rendezvous():
    server = RendezvousServer(host="127.0.0.1")
    port = server.start()
    yield server, server._httpd, port
    server.stop()


@pytest.fixture()
def far_bounds(monkeypatch):
    """The loop's own cadences, which bound an idle wait, far away: a wait
    that ends inside a test was ended by a record."""
    monkeypatch.setattr(worker_mod, "_DRAIN_POLL_S", FAR)
    monkeypatch.setattr(worker_mod, "_STATS_INTERVAL_S", FAR)


def _record(i, new=2):
    return {"id": req_key(i), "tokens": [i + 1, i + 2],
            "max_new_tokens": new, "submitted_t": time.time()}


def _enqueue(httpd, i, new=2, replica=0, journal=False):
    """As ``handle_generate`` does it: the router's in-process enqueue."""
    R._enqueue_request(httpd, RouterState(journal=journal), replica,
                       _record(i, new), req_key(i))


def _front(engine, port, **kw):
    kw.setdefault("journal", False)
    kw.setdefault("direct", False)
    return FleetFrontend(engine, "127.0.0.1", port, 0, 1, **kw)


def _running(front, **kw):
    """``front.run`` on a thread; what it returned or raised lands in the
    dict."""
    out = {}

    def run():
        try:
            out["rc"] = front.run(**kw)
        except BaseException as e:     # KeyboardInterrupt too: the test's
            out["error"] = e
    t = threading.Thread(target=run)
    t.start()
    return t, out


def _until(what, timeout=20.0):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if what():
            return True
        time.sleep(0.005)
    return False


def _readers():
    return [t for t in threading.enumerate() if t.name == READER]


def _blackouts():
    return M.CHAOS_INJECTIONS.value(kind="kv_blackout")


def _done(server, i, scope=OUT_SCOPE):
    raw = server.get(scope, f"{req_key(i)}.done")
    return None if raw is None else json.loads(raw)


# ------------------------------------------------------------ the order
def test_records_reach_submit_in_order_and_a_torn_put_holds_its_number(
        rendezvous):
    server, httpd, port = rendezvous
    engine = Engine(step_s=0.01)
    front = _front(engine, port)
    t, out = _running(front, ttl_s=0.6)
    assert _until(_readers)
    _enqueue(httpd, 0)
    server.put(REQ_SCOPE, req_key(1), b'{"id": "req.0000')    # torn
    for i in (2, 3, 4, 5):
        _enqueue(httpd, i)
    t.join(20)
    assert out == {"rc": 0}
    assert engine.order == [req_key(i) for i in (0, 2, 3, 4, 5)]
    assert front._next_seq == 6     # the torn one kept its number
    for i in (0, 2, 3, 4, 5):
        assert _done(server, i)["tokens"] == scripted_tokens(
            [i + 1, i + 2], 2)
    sums = engine.clock.sums
    assert sums["arrival_n"] == 6 and sums["arrival_lag_s"] >= 0.0


def test_resume_from_kv_then_new_arrivals_none_twice(rendezvous):
    """The redrive sets the cursor before the reader starts: what the
    journal held comes once, by the first plan; what arrives afterwards
    once, by the reader."""
    server, httpd, port = rendezvous
    for i in (0, 1, 2):
        _enqueue(httpd, i, journal=True)
    server.put(OUT_SCOPE, f"{req_key(0)}.done",
               json.dumps({"done": True, "tokens": [9, 9]}).encode())
    engine = Engine()
    front = _front(engine, port, journal=True, epoch=1)
    t, out = _running(front, ttl_s=0.6)
    assert _until(lambda: len(engine.order) == 2)
    for i in (3, 4):
        _enqueue(httpd, i, journal=True)
    t.join(20)
    assert out == {"rc": 0}
    assert engine.order == [req_key(i) for i in (1, 2, 3, 4)]
    assert front._next_seq == 5
    assert engine.clock.sums["arrival_n"] == 2     # 3 and 4 alone


# ------------------------------------------------------ the idle engine
def test_an_arrival_wakes_an_idle_loop(rendezvous, far_bounds, monkeypatch):
    """Every arrival finds the scripted engine idle and the loop blocked
    on the queue, with nothing else to end the wait before the ttl:
    each is submitted by a wake, none by a wait that ran out."""
    server, httpd, port = rendezvous
    waiting = threading.Event()
    wait = Arrivals.wait

    def watched(self, timeout):
        waiting.set()
        try:
            return wait(self, timeout)
        finally:
            waiting.clear()
    monkeypatch.setattr(Arrivals, "wait", watched)
    engine = Engine()
    front = _front(engine, port)
    t, out = _running(front, ttl_s=1.5)
    n = 5
    for i in range(n):
        assert waiting.wait(10)
        assert not engine.active
        _enqueue(httpd, i)
        assert _until(lambda: engine.completed == i + 1)
    t.join(20)
    assert out == {"rc": 0}
    sums = engine.clock.sums
    assert sums["arrival_wake_n"] == n == sums["arrival_n"]
    assert sums["idle_wait_n"] == n + 1     # the last one ran into the ttl
    assert engine.order == [req_key(i) for i in range(n)]
    assert engine.clock.phase_n["idle"] >= n + 1


def test_an_idle_loop_still_looks_alive(rendezvous, monkeypatch):
    """The idle wait is bounded by the loop's own cadences: with no
    arrival at all the loop keeps iterating (``record_step``), probing its
    drain latch and publishing its stats."""
    server, httpd, port = rendezvous
    monkeypatch.setattr(worker_mod, "_DRAIN_POLL_S", 0.05)
    engine = Engine()
    front = _front(engine, port)
    assert front.run(ttl_s=0.5) == 0
    sums = engine.clock.sums
    assert 3 <= sums["idle_wait_n"] <= 40 and sums["arrival_wake_n"] == 0 \
        and sums["arrival_n"] == 0
    assert front.tick == sums["idle_wait_n"] + 1    # and the stop's
    assert server.get(STATS_SCOPE, "stats") is not None


# ------------------------------------------------------------- the ends
@pytest.mark.parametrize("how", ["ttl", "drain", "stalled-reader"])
def test_what_is_in_the_store_is_finished_before_the_loop_stops(
        rendezvous, monkeypatch, how):
    """``ttl_s`` passes or the drain latch is set while requests are still
    on the queue, or in the store behind a reader that an outage holds
    up: the loop finishes them, then stops."""
    server, httpd, port = rendezvous
    monkeypatch.setattr(worker_mod, "_DRAIN_POLL_S", 0.01)
    monkeypatch.setattr(worker_mod, "_KV_BACKOFF_MS", 40.0)
    engine = Engine(step_s=0.01)
    front = _front(engine, port)
    t, out = _running(front, ttl_s=0.0 if how == "drain" else 0.2)
    assert _until(_readers)
    time.sleep(0.1)
    n = 6
    if how == "stalled-reader":
        # the reader is inside its GET for number 0: the outage meets
        # the one for number 1, and the loop's own look at the store
        chaos.install(chaos.parse_spec({"events": [
            {"kind": "kv_blackout", "op": "get", "scope": REQ_SCOPE,
             "count": 5}]}), 0)
    try:
        for i in range(n):
            _enqueue(httpd, i, new=8)
        if how == "drain":
            server.put(STATS_SCOPE, DRAIN_KEY, b"1")
        t.join(30)
    finally:
        chaos.uninstall()
    assert out == {"rc": 0}
    assert engine.order == [req_key(i) for i in range(n)]
    assert all(_done(server, i)["tokens"] == scripted_tokens(
        [i + 1, i + 2], 8) for i in range(n))
    assert (server.get(STATS_SCOPE, "drained") is not None) == \
        (how == "drain")
    assert not _readers()


@pytest.mark.parametrize("how", ["return", "keyboard-interrupt", "error"])
def test_the_reader_ends_with_the_loop(rendezvous, how):
    server, httpd, port = rendezvous
    engine = Engine()
    if how != "return":
        step = engine.step

        def broken():
            if engine.active:
                raise KeyboardInterrupt() if how == "keyboard-interrupt" \
                    else RuntimeError("the engine broke")
            return step()
        engine.step = broken
    front = _front(engine, port)
    t, out = _running(front, ttl_s=0.5)
    assert _until(lambda: len(_readers()) == 1)
    _enqueue(httpd, 0)
    t.join(20)
    assert not t.is_alive()
    if how == "return":
        assert out == {"rc": 0}
    else:
        assert isinstance(out["error"], KeyboardInterrupt
                          if how == "keyboard-interrupt" else RuntimeError)
    assert _until(lambda: not _readers(), 5)
    assert front._arrivals is None


def test_a_reader_that_gives_up_ends_the_loop_with_its_error(
        rendezvous, monkeypatch):
    """An outage wider than the retry's whole budget is a real failure:
    the loop's poll raises what its own probe would have raised."""
    server, httpd, port = rendezvous
    monkeypatch.setattr(worker_mod, "_KV_RETRIES", 2)
    monkeypatch.setattr(worker_mod, "_KV_BACKOFF_MS", 5.0)
    chaos.install(chaos.parse_spec({"events": [
        {"kind": "kv_blackout", "op": "get", "scope": REQ_SCOPE,
         "count": 100}]}), 0)
    try:
        with pytest.raises(urllib.error.URLError):
            _front(Engine(), port).run(ttl_s=5.0)
    finally:
        chaos.uninstall()
    assert _until(lambda: not _readers(), 5)


# ---------------------------------------------------------- the outage
def test_a_blackout_stalls_arrivals_and_not_the_loop(rendezvous,
                                                     monkeypatch):
    """The reader rides a KV blackout out in its own thread: the request
    behind it waits, the stream in flight keeps its ticks and its
    parts."""
    server, httpd, port = rendezvous
    monkeypatch.setattr(worker_mod, "_KV_BACKOFF_MS", 100.0)
    engine = Engine(step_s=0.005)
    front = _front(engine, port)
    t, out = _running(front, ttl_s=0.5)
    assert _until(_readers)
    _enqueue(httpd, 0, new=400)
    assert _until(lambda: engine.order == [req_key(0)])
    # the reader is inside the GET for number 1, behind the chaos hook:
    # the blackout meets its GET for number 2
    fired0 = _blackouts()
    chaos.install(chaos.parse_spec({"events": [
        {"kind": "kv_blackout", "op": "get", "scope": REQ_SCOPE,
         "count": 3}]}), 0)
    try:
        _enqueue(httpd, 1, new=1)
        assert _until(lambda: engine.order == [req_key(0), req_key(1)])
        _enqueue(httpd, 2, new=1)
        tick0 = engine.tick
        assert _until(lambda: len(engine.order) == 3)
        ticks = engine.tick - tick0
        fired = _blackouts() - fired0
    finally:
        chaos.uninstall()
    t.join(30)
    assert out == {"rc": 0}
    # three failed GETs, each followed by a backoff of 50 ms or more
    assert fired == 3 and ticks >= 10
    assert engine.order == [req_key(i) for i in range(3)]
    assert _done(server, 0)["tokens"] == scripted_tokens([1, 2], 400)
    parts = [k for k in server.scope_items(OUT_SCOPE)
             if k.startswith(req_key(0) + ".part.")]
    assert len(parts) == 400


# ------------------------------------------------- the server's held GET
@pytest.mark.parametrize("replica", [0, 1])
@pytest.mark.parametrize("how", ["put", "enqueue", "timeout"])
def test_the_held_get_returns_on_a_write_and_on_its_timeout(
        rendezvous, how, replica):
    server, httpd, port = rendezvous
    scope = scoped(REQ_SCOPE, replica)
    waiter = http_client.KeyWaiter("127.0.0.1", port, scope)
    record = _record(7)

    def write():
        if how == "put":
            http_client.put_kv("127.0.0.1", port, scope, req_key(7),
                               json.dumps(record).encode())
        elif how == "enqueue":
            R._enqueue_request(httpd, RouterState(journal=False), replica,
                               record, req_key(7))
    timer = threading.Timer(0.1, write)
    timer.start()
    try:
        t0 = time.monotonic()
        raw, held = waiter.wait_kv(req_key(7), 0.3 if how == "timeout"
                                   else 30.0)
        took = time.monotonic() - t0
        assert held
        if how == "timeout":
            assert raw is None and took >= 0.25
        else:
            # woken by the write, a tenth of a second in: not by the end
            # of a wait of thirty
            assert json.loads(raw) == record and took < 15.0
            # the key is there: the same connection answers at once
            assert waiter.wait_kv(req_key(7), 30.0) == (raw, True)
    finally:
        timer.join()
        waiter.close()


def test_a_wait_is_answered_at_once_where_no_write_wakes(rendezvous):
    """Another scope, a malformed wait: the parameter is not known there,
    and the 404 comes at once, without the header."""
    server, httpd, port = rendezvous
    waiter = http_client.KeyWaiter("127.0.0.1", port, "serve_plan")
    try:
        assert waiter.wait_kv("nothing", 30.0) == (None, False)
    finally:
        waiter.close()
    with pytest.raises(urllib.error.HTTPError) as e:
        urllib.request.urlopen(
            f"http://127.0.0.1:{port}/{REQ_SCOPE}/nothing?wait=soon",
            timeout=10)
    assert e.value.code == 404 and \
        e.value.headers.get(http_client.WAITED_HEADER) is None


def test_an_interrupt_ends_a_wait_in_progress(rendezvous):
    server, httpd, port = rendezvous
    waiter = http_client.KeyWaiter("127.0.0.1", port, REQ_SCOPE)
    got = []
    t = threading.Thread(
        target=lambda: got.append(waiter.wait_kv(req_key(0), 30.0)))
    t.start()
    assert _until(lambda: waiter._conn is not None
                  and waiter._conn.sock is not None)
    time.sleep(0.05)
    waiter.interrupt()
    t.join(10)
    assert not t.is_alive() and got == [(None, True)]
    assert waiter.wait_kv(req_key(0), 30.0) == (None, True)
    waiter.close()


# -------------------------------------------------------- an old server
@pytest.fixture()
def old_server():
    """A rendezvous server from before the held GET: no wakeup condition,
    so ``?wait=`` is not known to it."""
    httpd = ThreadingHTTPServer(("127.0.0.1", 0), _KVHandler)
    httpd.kv, httpd.kv_times = {}, {}
    httpd.kv_lock = threading.Lock()
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    yield httpd, httpd.server_address[1]
    httpd.shutdown()
    httpd.server_close()


def test_an_old_server_is_served_by_probing(old_server):
    httpd, port = old_server
    waiter = http_client.KeyWaiter("127.0.0.1", port, REQ_SCOPE)
    assert waiter.wait_kv(req_key(0), 30.0) == (None, False)
    waiter.close()
    engine = Engine()
    front = _front(engine, port)
    t, out = _running(front, ttl_s=0.6)
    assert _until(_readers)     # the cursor is set: number 0 is news
    for i in range(4):
        http_client.put_kv("127.0.0.1", port, REQ_SCOPE, req_key(i),
                           json.dumps(_record(i)).encode())
        assert _until(lambda: engine.completed == i + 1)
    t.join(20)
    assert out == {"rc": 0}
    assert engine.order == [req_key(i) for i in range(4)]
    assert engine.clock.sums["arrival_n"] == 4
    assert not _readers()


# ------------------------------------------------- a poll that is not ours
class _OwnPoll(FleetFrontend):
    def _drain_requests(self):
        return []


@pytest.mark.parametrize("how", ["subclass", "instance", "annotated"])
def test_a_replaced_poll_starts_no_reader(rendezvous, monkeypatch, how):
    """A subclass or a test that polls in its own way gets no reader and
    the sleep the loop always had; a wrapper around the loop's own poll
    (perfbench's ``pb:poll-requests`` annotation) gets the reader."""
    server, httpd, port = rendezvous
    started = []
    start = Arrivals.start
    monkeypatch.setattr(Arrivals, "start",
                        lambda self: (started.append(1), start(self))[1])
    engine = Engine()
    cls = _OwnPoll if how == "subclass" else FleetFrontend
    front = cls(engine, "127.0.0.1", port, 0, 1, journal=False,
                direct=False)
    if how == "instance":
        front._drain_requests = lambda: []
    if how == "annotated":
        own = front._drain_requests
        front._drain_requests = lambda: own()
    t, out = _running(front, ttl_s=0.4)
    assert _until(_readers, 0.1) == (how == "annotated")
    _enqueue(httpd, 0)
    t.join(20)
    assert out == {"rc": 0}
    sums = engine.clock.sums
    if how == "annotated":
        assert started == [1] and engine.order == [req_key(0)]
        assert sums["arrival_n"] == 1
    else:
        assert not started and not engine.order
        assert not any(sums.get(name) for name in SUMS)
        assert engine.clock.phase_n["idle"] >= 5    # 20 ms a sleep
    assert not _readers()


def test_a_poll_by_hand_probes_the_store_itself(rendezvous):
    """Outside ``run`` there is no reader: ``_drain_requests`` answers
    from the store before it returns, as tests and tools expect."""
    server, httpd, port = rendezvous
    front = _front(Engine(), port)
    assert front._drain_requests() == []
    _enqueue(httpd, 0)
    server.put(REQ_SCOPE, req_key(1), b"{")
    got = front._drain_requests()
    assert [r and r["id"] for r in got] == [req_key(0), None]
    assert front._next_seq == 2 and not _readers()


# ------------------------------------------------------------ the figures
def test_the_loop_reports_the_arrival_figures():
    from horovod_tpu.serve import engine as engine_mod
    assert set(SUMS) <= set(engine_mod._LOOP_SUMS)
    assert len(engine_mod._LOOP_SUMS) <= PhaseClock.SUMS    # all in the ring
    sums = dict.fromkeys(engine_mod._LOOP_SUMS, 0)
    sums.update(arrival_n=7, arrival_lag_s=0.014, arrival_wake_n=5,
                idle_wait_n=9)
    figures = engine_mod._loop_figures(sums)
    assert {k: figures[k] for k in SUMS} == {
        "arrival_n": 7, "arrival_lag_s": 0.014, "arrival_wake_n": 5,
        "idle_wait_n": 9}


def test_the_queue_alone():
    """``Arrivals`` over a scripted waiter: order, the torn record, the
    figures, and an error that waits behind the records that came before
    it."""
    class Waiter:
        scope = REQ_SCOPE
        script = [(json.dumps(_record(0)).encode(), True), (b"{", True),
                  (None, True), (json.dumps(_record(2)).encode(), True)]
        closed = interrupted = False

        def wait_kv(self, key, wait):
            if not self.script:
                raise OSError("the store is gone")
            return self.script.pop(0)

        def interrupt(self):
            self.interrupted = True

        def close(self):
            self.closed = True
    clock, waiter = PhaseClock(), Waiter()
    a = Arrivals(waiter, lambda fn, what: fn(), 0, clock.add, 0.001)
    a.start()
    assert _until(lambda: waiter.closed)
    assert a.wait(FAR) is True
    got = a.drain()
    assert [r and r["id"] for r in got] == [req_key(0), None, req_key(2)]
    assert clock.sums["arrival_n"] == 3 and clock.sums["idle_wait_n"] == 1
    assert a.wait(FAR) is False         # the error ends it, not a record
    with pytest.raises(OSError):
        a.drain()
    a.stop()
    assert waiter.interrupted and not _readers()
    assert arrivals_mod.decode(b"{") is None


def test_no_record_is_lost_or_doubled_between_the_two_threads():
    """The reader puts while the loop waits and drains, the interpreter
    switching threads as often as it can: every number comes once, in
    order, and the figures add up."""
    import sys
    n = 3000

    class Waiter:
        scope, sent = REQ_SCOPE, 0

        def wait_kv(self, key, wait):
            if self.sent == n:
                time.sleep(0.001)
                return None, True
            assert key == req_key(self.sent)
            self.sent += 1
            return json.dumps({"id": key}).encode(), True

        def interrupt(self):
            pass

        close = interrupt
    clock = PhaseClock()
    a = Arrivals(Waiter(), lambda fn, what: fn(), 0, clock.add, 0.001)
    got, interval = [], sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        a.start()
        deadline = time.time() + 60
        while len(got) < n and time.time() < deadline:
            a.wait(0.05)
            got += a.drain()
    finally:
        sys.setswitchinterval(interval)
        a.stop()
    assert [r["id"] for r in got] == [req_key(i) for i in range(n)]
    assert clock.sums["arrival_n"] == n and a.drain() == []
    assert clock.sums["arrival_wake_n"] <= clock.sums["idle_wait_n"]
    assert not _readers()
