"""The commit point of the serving loop (horovod_tpu/serve/commit.py,
``ServeEngine.commit_due``, ``FleetFrontend._hold``;
docs/serving.md#the-loops-order): the loop fixes the next tick's plan when
the tick in flight is about to end, by its own measurements.

Three tiers, none of which reads a real clock: ``CommitPoint`` alone, fed
stamps by hand; ``Scheduler.room`` on a slot table; and the real loop —
``FleetFrontend.run`` over a real ``ServeEngine`` — on a stepped clock with
a scripted device (a tick of each width takes what the test says, whatever
the CPU took), over the scripted model of tests/test_serve_chain.py and the
six families of tests/test_serve_chain_families.py."""

import numpy as np
import pytest

from horovod_tpu.serve import engine as engine_mod
from horovod_tpu.serve import worker as worker_mod
from horovod_tpu.serve.commit import SKIPS, CommitPoint
from horovod_tpu.serve.config import ServeConfig
from horovod_tpu.serve.engine import Request, Scheduler, ServeEngine
from horovod_tpu.serve.worker import FleetFrontend
from horovod_tpu.utils import profiler as profiler_mod

from test_serve_chain import _markov_engine, _markov_stream, _mesh
from test_serve_chain_families import (FAMILIES, _assert_reference, _load,
                                       _prompts, _scfg)

MS = 1e-3


# ------------------------------------------------------ CommitPoint alone
class Sums(dict):
    """``PhaseClock.add`` on a dict."""

    def add(self, name, value):
        self[name] = self.get(name, 0) + value


def _point(now=0.0):
    sums, clock = Sums(), [now]
    return CommitPoint(sums.add, now=lambda: clock[0]), sums, clock


def _back_to_back(point, clock, width, seconds, path, n=2, start=None):
    """``n`` ticks of ``width``, each launched ``path`` after the loop asked
    and queued behind the one before; returns the last ready stamp."""
    t = clock[0] if start is None else start
    if point._began is None:        # a first tick, queued behind this fence
        point.fenced(width, t, exact=True, ahead=True)
    for _ in range(n):
        clock[0] = t
        point.due(width, lambda: True)
        point.launched(t + path, width, late=False)
        t += seconds
        point.fenced(width, t, exact=True, ahead=True)
    clock[0] = t
    return t


def test_due_is_the_ready_stamp_plus_the_estimate_less_the_margin():
    point, sums, clock = _point()
    ready = _back_to_back(point, clock, "narrow", 16 * MS, 3 * MS, start=5.0)
    assert point.view() == {"estimate_s": {"narrow": pytest.approx(16 * MS)},
                            "margin_s": pytest.approx(3 * MS)}
    clock[0] = ready + 1 * MS       # the fenced tick's tokens are published
    due = point.due("narrow", lambda: True)
    assert due == pytest.approx(ready + 16 * MS - 3 * MS)
    assert not any(k.startswith("hold_skip_") and v for k, v in sums.items()
                   if k != "hold_skip_no_estimate")
    # the path of the launch behind a hold is counted from the due time:
    # a wake-up that overslept is in the margin from then on
    point.launched(due + 4 * MS, "narrow", late=False)
    assert point.margin_s == pytest.approx(4 * MS)


@pytest.mark.parametrize("why", SKIPS + ("estimate_below_margin",))
def test_no_hold(why):
    """Nothing in flight; a width that has never been timed; no room for an
    arrival; an estimate that does not exceed the margin; a due time that
    has passed: the loop is told to commit at once, and the reason is
    counted."""
    point, sums, clock = _point()
    seconds = 2 * MS if why == "estimate_below_margin" else 16 * MS
    ready = _back_to_back(point, clock, "narrow", seconds, 3 * MS)
    clock[0] = ready + (14 * MS if why == "not_worth_it" else 1 * MS)
    width = {"nothing_in_flight": None, "no_estimate": "wide"}.get(
        why, "narrow")
    before = dict(sums)
    assert point.due(width, lambda: why != "no_room") is None
    name = "not_worth_it" if why == "estimate_below_margin" else why
    assert {k: v - before.get(k, 0) for k, v in sums.items()
            if v != before.get(k, 0)} == {"hold_skip_" + name: 1}


def test_no_hold_before_a_tick_has_a_known_start():
    """The tick in flight was not queued behind another (launched after an
    idle engine, or behind a fence): when it began is not known."""
    point, sums, clock = _point()
    _back_to_back(point, clock, "narrow", 16 * MS, 3 * MS)
    point.fenced("narrow", clock[0] + 16 * MS, exact=True, ahead=False)
    assert point.due("narrow", lambda: True) is None
    assert sums["hold_skip_no_estimate"] >= 1


def test_estimates_are_kept_by_width_and_late_ticks_are_not_timed():
    point, sums, clock = _point()
    t = _back_to_back(point, clock, "narrow", 16 * MS, 3 * MS)
    t = _back_to_back(point, clock, "wide", 40 * MS, 3 * MS, start=t)
    assert point.estimate_s == {"narrow": pytest.approx(16 * MS),
                                "wide": pytest.approx(40 * MS)}
    # a launch that found the tick in flight over: that tick's ready stamp
    # comes late (not exact), and the tick behind it began at the launch,
    # not at that stamp — neither distance is a tick's length
    clock[0] = t
    due = point.due("wide", lambda: True)
    point.launched(t + 45 * MS, "wide", late=True)
    assert sums["late_n"] == 1
    point.fenced("wide", t + 46 * MS, exact=False, ahead=True)
    point.fenced("narrow", t + 50 * MS, exact=True, ahead=True)
    assert point.estimate_s["narrow"] == pytest.approx(16 * MS)
    assert point.estimate_s["wide"] == pytest.approx(40 * MS)
    assert due == pytest.approx(t + 40 * MS - 3 * MS)
    # the shortest of the last TICKS: a stamp taken late errs early
    _back_to_back(point, clock, "narrow", 17 * MS, 3 * MS, n=1,
                  start=t + 50 * MS)
    assert point.estimate_s["narrow"] == pytest.approx(16 * MS)
    _back_to_back(point, clock, "narrow", 17 * MS, 3 * MS,
                  n=CommitPoint.TICKS)
    assert point.estimate_s["narrow"] == pytest.approx(17 * MS)


def test_a_late_launch_bounds_an_estimate_that_read_too_long():
    """Ticks got shorter than every length kept: each launch behind a hold
    is late, no late tick is timed — the launch itself says the tick took
    less than began-to-launch, and the next due time is early enough."""
    point, sums, clock = _point()
    t = _back_to_back(point, clock, "narrow", 16 * MS, 3 * MS)
    assert point.due("narrow", lambda: True) == pytest.approx(t + 13 * MS)
    point.launched(t + 15 * MS, "narrow", late=True)    # it took 12
    assert sums["late_n"] == 1
    assert point.estimate_s["narrow"] == pytest.approx(15 * MS)
    # without a hold before it a late launch is not counted (the loop did
    # not cause it), and is a measurement all the same
    point.fenced("narrow", t + 15.5 * MS, exact=False, ahead=True)
    assert point.due("narrow", lambda: True) is None    # no known start
    point.launched(t + 16 * MS, "narrow", late=True)
    assert sums["late_n"] == 1


# ------------------------------------------------- the scheduler's room
def _sched(**kw):
    base = dict(max_slots=2, block_size=4, cache_blocks=32, max_seq_len=48,
                max_batch_tokens=16, prefill_chunk=8, spec_k=3,
                prefix_cache=False)
    base.update(kw)
    return Scheduler(ServeConfig(**base))


def test_room_with_a_free_slot_and_budget_left():
    s = _sched()
    assert s.room()
    s.submit(Request(list(range(1, 6)), 4, req_id="a"))
    assert not s.room()             # it waits in front of any newcomer
    s.plan()
    assert s.room()                 # admitted: a slot and 11 tokens left


def test_no_room_without_a_free_slot():
    s = _sched()
    for rid in "ab":
        s.submit(Request([1, 2, 3], 4, req_id=rid))
    s.plan()
    assert s.active == 2 and not s.waiting and not s.room()


def test_no_room_with_the_budget_taken_by_the_prefill_in_progress():
    for budget, room in ((8, False), (12, True)):    # the chunk is 8
        s = _sched(max_batch_tokens=budget)
        s.submit(Request(list(range(1, 21)), 4, req_id="long"))  # 3 chunks
        (_, req, n), = s.plan()
        req.pos += n                # as _dispatch does
        assert None in s.slots and s.room() == room
        planned = s.plan()          # room() changed nothing
        assert [(r.req_id, k) for _, r, k in planned] == [("long", 8)]


# -------------------------------------- the real loop on a stepped clock
class FakeTime:
    """In the place of the ``time`` module: a clock that moves when a test
    (or a sleep) moves it."""

    def __init__(self, t=1000.0):
        self.t = t

    def perf_counter(self):
        return self.t

    monotonic = time = perf_counter

    def sleep(self, seconds):
        self.t += max(0.0, seconds)


class _Report:
    """A tick's report that is ready when the scripted device says so."""

    def __init__(self, value, ends, fake):
        self.value, self.ends, self.fake = value, ends, fake

    def is_ready(self):         # it ended before now, by more than rounding
        return self.fake.t > self.ends + 1e-9

    def block_until_ready(self):
        self.fake.t = max(self.fake.t, self.ends)
        return self

    def __array__(self, dtype=None, copy=None):
        return np.asarray(self.value)


class Device:
    """The device's timeline on the stepped clock: a program starts when it
    is launched or when the one before ends, whichever is later, and takes
    ``narrow`` or ``wide`` seconds by its executable."""

    def __init__(self, fake, engine, narrow, wide):
        self.fake, self.free_at, self.ran = fake, 0.0, []
        self.planned = {}           # tick -> the request ids of its rows
        engine._compile_steps()
        chunk = engine.cfg.prefill_chunk

        def timed(C, step):
            def run(*args):
                out = list(step(*args))
                start = max(fake.t, self.free_at)
                self.free_at = start + (wide if C >= chunk else narrow)
                self.ran.append((start, self.free_at))
                out[-2] = _Report(out[-2], self.free_at, fake)
                return out
            return run
        engine._steps = {C: timed(C, s) for C, s in engine._steps.items()}
        engine._commit._now = fake.perf_counter
        plan = engine._plan

        def recorded():
            work, copies = plan()
            if work:
                self.planned[engine.tick] = [r.req_id for _, r, _ in work]
            return work, copies
        engine._plan = recorded

    def tick_at(self, t):
        return next(i for i, (a, b) in enumerate(self.ran) if a <= t < b)

    def first_tick(self, rid):
        return min(k for k, rids in self.planned.items() if rid in rids)


class Front(FleetFrontend):
    """``FleetFrontend`` on one rank over a key-value store that holds
    nothing: requests arrive by the stepped clock, a poll takes
    ``poll_s``."""

    def __init__(self, engine, fake, arrivals, poll_s, hold=True):
        super().__init__(engine, "fake", 1, 0, 1, journal=False,
                         direct=False)
        self.fake, self.poll_s = fake, poll_s
        self.arrivals = sorted(arrivals, key=lambda r: r["at"])
        self.done = {}
        if not hold:
            self._hold = lambda clock: None

    def _kv_get(self, scope, key, timeout=0):
        return None

    def _kv_put(self, scope, key, value):
        if key.endswith(".done"):
            self.done[key[:-len(".done")]] = value

    def _drain_requests(self):
        self.fake.t += self.poll_s
        due = [r for r in self.arrivals if r["at"] <= self.fake.t]
        self.arrivals = self.arrivals[len(due):]
        return due


@pytest.fixture
def fake(monkeypatch):
    clock = FakeTime()
    for mod in (engine_mod, worker_mod, profiler_mod):
        monkeypatch.setattr(mod, "time", clock)
    return clock


def _serve(fake, engine, arrivals, hold, narrow=10 * MS, wide=20 * MS,
           poll_s=2 * MS, ttl_s=2.0):
    device = Device(fake, engine, narrow, wide)
    t0 = fake.t
    front = Front(engine, fake, [dict(r, at=t0 + r["at"]) for r in arrivals],
                  poll_s, hold=hold)
    assert front.run(ttl_s=ttl_s) == 0
    assert not front.arrivals and not engine.has_work()
    return device, front, t0


def _arrival(rid, at, tokens, new):
    return {"id": rid, "at": at, "tokens": tokens, "max_new_tokens": new}


# `a` decodes all along (5 -> 6 -> 9 -> 9 ...: its drafts are right, rows
# of 4); `b` arrives 3.5 ms into a tick that `a` runs alone (the first
# began at 2 ms, behind the first poll)
MARKOV = [_arrival("a", 0.0, [5, 6, 5], 40),
          _arrival("b", 95.5 * MS, [1, 2, 3, 1], 6)]


@pytest.mark.parametrize("hold", [True, False], ids=["hold", "no-hold"])
def test_an_arrival_during_the_hold_is_in_the_next_launched_tick(fake, hold):
    """Ticks of 10 ms, a poll of 2: the loop holds until 8 ms into the tick
    in flight, polls, and launches the next tick with the newcomer's rows;
    the loop that does not hold polled when that tick began, launched the
    next tick without the newcomer, and plans it one tick later."""
    engine = _markov_engine(max_slots=2)
    device, front, t0 = _serve(fake, engine, MARKOV, hold)
    loop = engine.stats()["loop"]
    running = device.tick_at(t0 + MARKOV[1]["at"])
    assert device.planned[running] == ["a"]
    assert device.first_tick("b") == running + (1 if hold else 2)
    # no program waited for the host either way: each began when the one
    # before ended
    busy = [i for i in range(1, len(device.ran))
            if device.ran[i][0] < t0 + 0.3]
    assert all(device.ran[i][0] == device.ran[i - 1][1] for i in busy[2:])
    assert loop["late_n"] == 0
    if hold:
        assert loop["hold_n"] >= 10
        # due = ready + 10 ms - the poll's 2: 8 ms a hold
        assert loop["hold_s"] / loop["hold_n"] == pytest.approx(8 * MS,
                                                                abs=1e-4)
        assert loop["commit"]["estimate_s"]["narrow"] == pytest.approx(
            10 * MS)
        assert loop["commit"]["margin_s"] == pytest.approx(2 * MS)
        skipped = loop["hold_skipped_n"]
        assert skipped["nothing_in_flight"] >= 1 and \
            skipped["no_estimate"] >= 1 and skipped["not_worth_it"] == 0
        assert loop["phase_s"]["idle"] >= loop["hold_s"]
    else:
        assert loop["hold_n"] == 0 and loop["hold_s"] == 0
    for r in MARKOV:
        assert engine_mod.json.loads(front.done[r["id"]])["tokens"] == \
            _markov_stream(r["tokens"], r["max_new_tokens"])
    engine.close()


def test_a_poll_that_costs_nothing_shortens_the_margin_and_lengthens_the_hold(
        fake):
    """The poll is the margin on the stepped clock (nothing else moves it
    between "stop holding" and "launch returned"): a poll that empties a
    queue instead of probing a socket takes its 2 ms out of the margin,
    and the loop holds that much longer into the tick in flight, to the
    tick's end.  No launch is late for it, and the newcomer is in the next
    program either way."""
    for poll_s in (2 * MS, 0.0):
        engine = _markov_engine(max_slots=2)
        device, front, t0 = _serve(fake, engine, MARKOV, hold=True,
                                   poll_s=poll_s)
        loop = engine.stats()["loop"]
        assert loop["late_n"] == 0 and loop["hold_n"] >= 10
        assert loop["commit"]["margin_s"] == pytest.approx(poll_s, abs=1e-9)
        assert loop["hold_s"] / loop["hold_n"] == pytest.approx(
            10 * MS - poll_s, abs=1e-4)
        assert device.first_tick("b") == \
            device.tick_at(t0 + MARKOV[1]["at"]) + 1
        engine.close()


def test_a_launch_that_finds_the_tick_over_is_counted_late(fake):
    """The poll suddenly takes 5 ms where the margin, from the 2 ms polls
    before it, left 2: the launch returns after the tick in flight has
    ended.  ``late_n`` counts it, ``ahead_n`` counts it ahead as ever (the
    tick was unfenced), and the margin is 5 ms from then on."""
    engine = _markov_engine(max_slots=2)
    device = Device(fake, engine, 10 * MS, 20 * MS)
    front = Front(engine, fake, [dict(MARKOV[0], at=fake.t)], 2 * MS)
    drain, slow_at = front._drain_requests, fake.t + 0.2

    def slow_once():
        if fake.t >= slow_at and front.poll_s == 2 * MS:
            front.poll_s = 5 * MS
        elif front.poll_s == 5 * MS:
            front.poll_s = 2.001 * MS
        return drain()
    front._drain_requests = slow_once
    assert front.run(ttl_s=1.0) == 0
    loop = engine.stats()["loop"]
    assert loop["late_n"] == 1
    assert loop["ahead_n"] == loop["turnaround_n"]
    gaps = [b[0] - a[1] for a, b in zip(device.ran, device.ran[1:])]
    assert sorted(g for g in gaps if g > 1e-9) == [pytest.approx(3 * MS)]
    assert loop["commit"]["margin_s"] == pytest.approx(5 * MS)
    assert loop["commit"]["estimate_s"]["narrow"] == pytest.approx(10 * MS)
    engine.close()


def test_step_and_flush_never_hold(fake):
    """A caller that drives ``step()`` itself asks for no due time and gets
    no hold: nothing is counted, held or skipped."""
    engine = _markov_engine(max_slots=2)
    Device(fake, engine, 10 * MS, 20 * MS)
    engine.submit([5, 6, 5], 12, req_id="a")
    engine.flush()
    loop = engine.stats()["loop"]
    assert loop["hold_n"] == 0 and loop["late_n"] == 0
    assert not any(loop["hold_skipped_n"].values())
    assert loop["commit"]["margin_s"] is None
    engine.close()


# ------------------------------------------------------ the six families
@pytest.mark.parametrize("name", FAMILIES)
def test_the_hold_moves_a_prompts_first_tick_and_no_token(fake, name):
    """Six arrivals over a family at `tiny`, with and without the hold, the
    device scripted alike: every stream's tokens are the plain greedy
    reference's both times; with the hold the loop held, was never late,
    and no request's first rows came in a later program than without."""
    name, model, cfg, params = _load(name)
    prompts = _prompts(cfg.vocab)
    arrivals = [_arrival(f"r{i}", at * MS, p, 10)
                for i, (at, p) in enumerate(zip(
                    (0.0, 0.0, 83.5, 133.5, 187.0, 231.0), prompts))]
    streams, first, loops = {}, {}, {}
    for hold in (True, False):
        engine = ServeEngine(model, cfg, params,
                             _scfg(name, prefix_cache=False), mesh=_mesh())
        submit, reqs = engine.submit, []

        def kept(*a, **k):
            reqs.append(submit(*a, **k))
            return reqs[-1]
        engine.submit = kept
        device, front, t0 = _serve(fake, engine, arrivals, hold)
        assert len(reqs) == 6 and all(r.state == "done" for r in reqs)
        _assert_reference(model, cfg, params, reqs)
        streams[hold] = {r.req_id: r.out_tokens for r in reqs}
        first[hold] = {r.req_id: device.ran[device.first_tick(r.req_id)][0]
                       - t0 for r in reqs}
        loops[hold] = engine.stats()["loop"]
        engine.close()
    assert streams[True] == streams[False]
    assert loops[True]["hold_n"] > 0 and loops[True]["late_n"] == 0
    assert loops[False]["hold_n"] == 0
    assert all(first[True][rid] <= first[False][rid] + 1e-9
               for rid in first[True])
    assert any(first[True][rid] < first[False][rid] - 5 * MS
               for rid in first[True])
