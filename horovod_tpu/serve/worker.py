"""Serving worker: `hvdrun --serve CKPT_DIR` runs one of these per host.

Bring-up mirrors a training worker — ``hvd.init()`` assembles the same
mesh from the same launcher env — then the engine serves instead of
trains.  Fleet coordination rides the existing rendezvous KV:

  * the router (runner/http_server.py + serve/router.py) enqueues
    requests with dense sequence numbers into scope ``serve_req``;
  * rank 0 learns of them from its arrivals reader (serve/arrivals.py:
    a GET the server holds on the next request's key), drains the
    reader's queue once a tick, publishes a per-tick PLAN (scope
    ``serve_plan`` key ``e<epoch>.tick.N``) carrying the admitted
    requests verbatim,
    and every rank — rank 0 included — applies the same plan to its own
    engine copy.  Engine scheduling and sampling are deterministic
    (serve/engine.py), so the fleet stays in lockstep without any new
    transport: the plan stream is the only coordination channel, and it
    is the same KV the chaos/metrics/timeline planes already exercise;
  * rank 0 publishes results that the router streams to clients — by
    default over ONE persistent direct connection (``POST
    /serve/stream``, serve/stream.py; knob HOROVOD_SERVE_DIRECT), which
    the router mirrors into scope ``serve_out`` in-process so the
    journal's redrive source of truth is unchanged; on connection loss
    each record falls back to a ``serve_out`` KV PUT (per-tick token
    parts + a final ``.done`` record — the pre-scale-out path,
    docs/control-plane.md) — plus a periodic engine-stats snapshot
    (scope ``serve`` key ``stats``) for ``GET /serve/stats``.

Fault tolerance (docs/serving.md#fault-tolerance):

  * **epoch fencing** — plan keys are namespaced by the elastic reset
    round (HOROVOD_ELASTIC_ROUND -> ``epoch``), and every plan carries
    its epoch in-band, so a restarted fleet can neither read nor replay
    a stale ``serve_plan`` key from a previous incarnation;
  * **redrive** — at bring-up, rank 0 scans the request journal
    (serve/journal.py, scope ``serve_journal``) left by the previous
    incarnation, re-admits every unfinished request through the FIRST
    plan of the new epoch, and — greedy decode being deterministic —
    suppresses re-publishing the token prefix the client already
    received, so its ndjson stream resumes from the last token;
  * **stall, don't die** — every worker-side KV leg rides a bounded
    exp-backoff retry (``common/util.backoff_delays``), so a transient
    rendezvous outage (chaos blackout, server restart) stalls the loop
    instead of killing the fleet;
  * **graceful drain** — the router's POST /admin/drain plants a drain
    signal (scope ``serve`` key ``drain``); rank 0 stops admitting new
    work, finishes everything accepted, publishes the ``drained`` ack
    and stops the fleet with exit 0 (preemption-safe rolling restart);
  * **serve-aware chaos** — the loop clocks ``hvd.chaos.step`` on the
    ENGINE's work-tick counter (a spec kill lands mid-decode
    deterministically) and exposes the ``serve_tick`` stall point.

SLO observability is inherited, not added: the engine records
hvd_serve_* metrics (published by MetricsPublisher to /metrics),
per-request spans into the merged timeline, and the loop ticks
``hvd.postmortem.record_step`` every iteration so /health supervision
sees a wedged engine exactly like a wedged train loop — including an
IDLE fleet, which must look alive, not stalled (docs/serving.md).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import Any, Callable, Dict, List, Optional

from ..utils.profiler import PhaseClock
from .arrivals import Arrivals, decode
from .replica import REPLICA_SCOPE, replica_key, scoped
from .router import (DRAIN_KEY, DRAINED_KEY, OUT_SCOPE, PLAN_SCOPE,
                     REQ_SCOPE, STATS_KEY, STATS_SCOPE, req_key)

# Prefill->decode handoff scope (docs/serving.md#replicated-tier): the
# prefill sub-fleet's rank 0 publishes each finished prefill's prompt
# KV + first token here (densely numbered, like serve_req) and the
# decode sub-fleet drains it in order.
KV_SCOPE = "serve_kv"

# How long an idle loop sleeps where nothing can wake it (no KV, the
# decode role's handoff probes, a poll that a subclass or a test has
# replaced), and the cadence at which the arrivals reader probes a server
# that cannot hold a GET.  A loop with a reader beside it does not sleep:
# it blocks on the reader's queue (serve/arrivals.py), at most until its
# next drain probe or stats snapshot is due.
_IDLE_SLEEP_S = 0.02
_STATS_INTERVAL_S = 1.0
# Drain-latch probe cadence: the latch is a driver/human-scale signal,
# but probing it was a KV roundtrip on EVERY engine tick — at serving
# tick rates that roundtrip (two thread handoffs through the rendezvous
# server) was a measurable slice of the tick budget.  A quarter-second
# poll bounds drain pickup latency far below the drain timeout while
# taking the probe off the hot loop.
_DRAIN_POLL_S = 0.25
# Serve-loop KV retry budget: wider than the http_client's own write
# budget because a mid-stream outage should stall serving, not kill it
# (the elastic driver would misread the death as a rank failure).
_KV_RETRIES = 8
_KV_BACKOFF_MS = 50.0


def plan_key(tick: int, epoch: int = 0) -> str:
    """Epoch-namespaced plan key: a reset bumps the epoch, so the new
    fleet's key space is disjoint from every stale plan (fencing)."""
    return f"e{epoch:04d}.tick.{tick:09d}"


class FleetFrontend:
    """Drives one engine in fleet lockstep (see module docstring).
    ``addr``/``port`` empty means standalone (no KV; local submissions
    only — the bench/load-generator path)."""

    def __init__(self, engine, addr: str, port: int, rank: int,
                 nprocs: int, plan_timeout_s: float = 120.0,
                 epoch: int = 0, journal: bool = True,
                 drain_timeout_s: float = 30.0, direct: bool = True,
                 replica_id: int = 0, role: str = "mixed"):
        self.engine = engine
        self.addr = addr
        self.port = int(port or 0)
        self.rank = int(rank)
        self.nprocs = int(nprocs)
        self.plan_timeout_s = float(plan_timeout_s)
        self.epoch = int(epoch)
        self.journal = bool(journal)
        self.drain_timeout_s = float(drain_timeout_s)
        self.direct = bool(direct)
        self.replica_id = int(replica_id)
        self.role = str(role)
        # Per-replica KV scoping (serve/replica.py): replica 0 keeps
        # the unscoped names, replica K suffixes .rKK — N fleets share
        # one rendezvous without collisions.
        self.req_scope = scoped(REQ_SCOPE, self.replica_id)
        self.out_scope = scoped(OUT_SCOPE, self.replica_id)
        self.plan_scope = scoped(PLAN_SCOPE, self.replica_id)
        self.stats_scope = scoped(STATS_SCOPE, self.replica_id)
        self.kv_scope = scoped(KV_SCOPE, self.replica_id)
        self._stats_key = STATS_KEY
        self._drained_key = DRAINED_KEY
        if self.role == "prefill":
            # The prefill sub-fleet runs its own plan stream and stats
            # key beside the decode sub-fleet's — the decode side owns
            # the client-facing ones (it emits the tokens).
            self.plan_scope += ".pf"
            self._stats_key += ".prefill"
            self._drained_key += ".prefill"
        self._dstream = None  # lazy: serve/stream.DirectTokenStream
        self.tick = 0
        self._next_seq = 0
        self._next_handoff = 0  # decode role: serve_kv drain cursor
        self._handoff_seq = 0   # prefill role: serve_kv publish cursor
        self._parts: Dict[str, int] = {}
        self._results: Dict[str, List[int]] = {}
        self._suppress: Dict[str, int] = {}  # rid -> tokens NOT to re-publish
        self._first_pub: Dict[str, float] = {}  # rid -> first part sent at
        # Prefill role only: redriven requests' already-streamed prefixes,
        # forwarded through the handoff so the DECODE publisher (the one
        # that owns the client stream) suppresses them, not us.
        self._resume_info: Dict[str, Dict[str, Any]] = {}
        self._last_stats = 0.0
        # run() on rank 0 of a KV-backed front: the reader that waits on
        # the next request's key, started by the loop's first poll
        self._arrivals: Optional[Arrivals] = None

    # ------------------------------------------------------------ KV I/O
    def _kv(self):
        from ..runner import http_client
        return http_client

    def _kv_op(self, fn: Callable[[], Any], what: str) -> Any:
        """Bounded exp-backoff retry (common/util.backoff_delays) around
        one KV leg: a transient rendezvous outage mid-serve must stall
        the loop, not kill the worker.  Non-transient errors and an
        exhausted budget still raise — an unreachable fleet is a real
        failure, and the elastic driver owns it from there."""
        from ..common.util import backoff_delays
        from ..runner.http_client import _transient
        delays = backoff_delays(_KV_RETRIES, _KV_BACKOFF_MS)
        for attempt in range(len(delays) + 1):
            try:
                return fn()
            except Exception as e:
                if attempt >= len(delays) or not _transient(e):
                    raise
                time.sleep(delays[attempt])

    def _kv_get(self, scope: str, key: str, timeout: float = 0):
        kv = self._kv()
        return self._kv_op(
            lambda: kv.get_kv(self.addr, self.port, scope, key,
                              timeout=timeout),
            f"get {scope}/{key}")

    def _kv_put(self, scope: str, key: str, value: bytes) -> None:
        kv = self._kv()
        self._kv_op(
            lambda: kv.put_kv(self.addr, self.port, scope, key, value),
            f"put {scope}/{key}")

    def _drain_requests(self) -> List[Dict[str, Any]]:
        """Rank 0: consume newly-arrived requests in sequence order (a
        torn PUT is a None that holds its number).  This is the loop's
        poll: inside ``run`` it empties the queue that the arrivals
        reader fills (serve/arrivals.py) and touches no socket; its first
        call starts the reader, so a front whose poll is replaced starts
        none.  Called by hand, outside ``run``, it probes the dense
        numbering itself (nonblocking, no listing)."""
        if self._arrivals is not None:
            if not self._arrivals.started:
                self._arrivals.start()
            reqs = self._arrivals.drain()
            self._next_seq += len(reqs)
            return reqs
        reqs = []
        while True:
            raw = self._kv_get(self.req_scope, req_key(self._next_seq))
            if raw is None:
                return reqs
            reqs.append(decode(raw))
            self._next_seq += 1

    def _request_in_store(self) -> bool:
        """Asked once, when the loop is about to stop: does the store hold
        a request that the reader has not handed over yet (it lags by a
        round trip, and by an outage's length when it rides one out)?
        Everything accepted is finished first, as when the loop probed
        for itself."""
        if self._arrivals is None or not self._arrivals.started:
            return False
        return self._kv_get(self.req_scope,
                            req_key(self._next_seq)) is not None

    def _publish_plan(self, reqs: List[Dict[str, Any]],
                      stop: bool = False) -> None:
        payload = {"tick": self.tick, "epoch": self.epoch,
                   "stop": stop, "reqs": reqs}
        # Scheduling decisions live in the plan stream (docs/serving.md
        # #raw-speed): the engine's rolling digest covers every prefix
        # hit, chunk boundary, draft and CoW copy rank 0 has dispatched
        # so far, so followers prove their engines made the SAME
        # decisions, not just the same tokens.
        digest = getattr(self.engine, "sched_digest", None)
        if digest is not None:
            payload["sched"] = digest
        self._kv_put(self.plan_scope, plan_key(self.tick, self.epoch),
                     json.dumps(payload).encode())

    def _fetch_plan(self) -> Dict[str, Any]:
        # Rides _kv_get like every other serve KV leg (hvdlint
        # serve-kv-retry): a transient rendezvous blip during the
        # long-poll must stall this follower, not kill it — the
        # poll's own timeout still surfaces as the None below.
        raw = self._kv_get(self.plan_scope,
                           plan_key(self.tick, self.epoch),
                           timeout=self.plan_timeout_s)
        if raw is None:
            raise TimeoutError(
                f"rank {self.rank}: no plan "
                f"{plan_key(self.tick, self.epoch)} after "
                f"{self.plan_timeout_s:.0f}s — rank 0 gone?")
        plan = json.loads(raw)
        if int(plan.get("epoch", -1)) != self.epoch:
            # Belt-and-braces under the key namespace: a plan from
            # another incarnation must never drive this engine.
            raise ValueError(
                f"rank {self.rank}: stale plan epoch "
                f"{plan.get('epoch')!r} != {self.epoch} — refusing to "
                "replay a previous incarnation's plan stream")
        sched = plan.get("sched")
        mine = getattr(self.engine, "sched_digest", None)
        if sched is not None and mine is not None \
                and not plan.get("stop") and sched != mine:
            # Divergence is caught at the tick it happens — before this
            # rank dispatches another step off a forked schedule.
            raise ValueError(
                f"rank {self.rank}: lockstep divergence at "
                f"{plan_key(self.tick, self.epoch)} — local scheduling "
                f"digest {mine} != rank 0's {sched} (prefix/chunk/spec "
                "decisions disagree; serve/engine.py sched_digest)")
        return plan

    # ----------------------------------------------------------- redrive
    def resume_from_kv(self) -> List[Dict[str, Any]]:
        """Rank 0 at bring-up: resume the request stream a previous
        incarnation left behind.  With the journal on, returns the
        redrive list (unfinished requests annotated with their already-
        streamed prefix) and fast-forwards the request cursor past every
        journaled sequence number; with it off (degraded mode), only
        fast-forwards — orphaned streams time out at the router."""
        if not self.journal:
            seq = 0
            while self._kv_get(self.req_scope, req_key(seq)) is not None:
                seq += 1
            self._next_seq = seq
            return []
        from .journal import redrive_plan
        # journal.py stays replica-agnostic: the getter rewrites its
        # scope names into this replica's (serve/replica.py scoped()).
        entries, seq = redrive_plan(
            lambda scope, key: self._kv_get(
                scoped(scope, self.replica_id), key))
        self._next_seq = seq
        if entries and self.epoch > 0:
            # Epoch 0 is first bring-up: journal entries there are just
            # requests accepted before the fleet was ready, not replays.
            from ..utils import metrics as M
            M.SERVE_REDRIVES.inc(len(entries))
            # Redrive forensics (doctor --request): every log line that
            # acts on a request names its rid.
            rids = ", ".join(str(e.get("id")) for e in entries)
            print(f"[hvd.serve] rank 0 epoch {self.epoch}: redriving "
                  f"{len(entries)} journaled request(s) [{rids}] "
                  f"({sum(len(e['resume_emitted']) for e in entries)} "
                  "already-streamed tokens suppressed)", flush=True)
        return entries

    def _apply_resume(self, r: Dict[str, Any]) -> None:
        """Seed rank 0's publisher state for one redriven request: the
        emitted prefix is already on the client's wire, so publishing
        resumes at the next part with the regenerated suffix only."""
        emitted = r.get("resume_emitted")
        rid = r.get("id")
        if emitted is None or not rid:
            return
        self._results[rid] = [int(t) for t in emitted]
        self._parts[rid] = int(r.get("resume_part", 0))
        self._suppress[rid] = len(emitted)

    # ----------------------------------------------------------- outputs
    def _direct_send(self, record: Dict[str, Any]) -> bool:
        """Try the persistent direct stream (serve/stream.py;
        docs/control-plane.md#direct-streaming).  False = not delivered
        (direct off, or the connection is down and one reconnect
        failed) — the caller publishes via the KV instead, and the
        router's store sees the same keys either way."""
        if not self.direct or self.rank != 0:
            return False
        if self._dstream is None:
            from .stream import DirectTokenStream
            self._dstream = DirectTokenStream(self.addr, self.port)
        return self._dstream.send(record)

    def _publish_part(self, rid: str, part: int, toks: List[int]) -> None:
        rec = {"rid": rid, "part": part, "tokens": toks}
        if self.replica_id:
            rec["scope"] = self.out_scope
        if self._direct_send(rec):
            return
        self._kv_put(self.out_scope, f"{rid}.part.{part:06d}",
                     json.dumps({"tokens": toks}).encode())

    def _publish_done(self, rid: str, done: Dict[str, Any]) -> None:
        rec = {"rid": rid, "done": done}
        if self.replica_id:
            rec["scope"] = self.out_scope
        if self._direct_send(rec):
            return
        self._kv_put(self.out_scope, f"{rid}.done",
                     json.dumps(done).encode())

    def _publish_report(self, report: Dict[str, Any]) -> None:
        for rid, toks in report["emitted"].items():
            skip = self._suppress.get(rid, 0)
            if skip:
                # Redriven request: these tokens were streamed by the
                # previous incarnation (deterministic replay regenerates
                # them identically) — consume the suppression budget
                # instead of re-publishing.
                take = min(skip, len(toks))
                if take < skip:
                    self._suppress[rid] = skip - take
                else:
                    self._suppress.pop(rid, None)
                toks = toks[take:]
            if not toks:
                continue
            self._results.setdefault(rid, []).extend(toks)
            part = self._parts.get(rid, 0)
            self._publish_part(rid, part, toks)
            self._parts[rid] = part + 1
            self._first_pub.setdefault(rid, time.perf_counter())
        for req in report["finished"]:
            first_pub = self._first_pub.pop(req.req_id, None)
            if req.finish_reason == "prefill_done":
                # Prefill-role completion: the request's life continues
                # on the decode sub-fleet (via the serve_kv handoff) —
                # the decode side owns the client-facing .done.
                continue
            done = {
                "done": True,
                "tokens": self._results.pop(req.req_id, []),
                "finish_reason": req.finish_reason,
                "ttft_s": req.ttft(),
                "tpot_s": req.tpot(),
                "timing": self._req_timing(req),
                "trace": getattr(req, "trace", None),
            }
            ftt = getattr(req, "first_token_t", None)
            if first_pub is not None and ftt is not None:
                # first token sampled -> its part handed to the stream
                done["timing"]["publish"] = max(0.0, first_pub - ftt)
            if getattr(req, "loop", None) is not None:
                done["loop"] = req.loop
            if getattr(req, "steps", None):
                # block denoising (docs/serving.md#block-denoising): the
                # pass, 0-based and counted a block, in which each served
                # token was fixed, and [token, pass] of what the last block
                # holds behind the answer's cut — what a check needs to
                # rebuild the state a token was chosen in
                done["steps"] = req.steps[len(req.steps)
                                          - len(done["tokens"]):]
                done["tail"] = req.tail
            self._publish_done(req.req_id, done)
            self._parts.pop(req.req_id, None)
            self._suppress.pop(req.req_id, None)

    @staticmethod
    def _req_timing(req) -> Dict[str, float]:
        """Engine-measured component durations for the router's SLO
        attribution (serve/trace.py ``attribute``): perf_counter stamps
        are process-local, so the done record ships DURATIONS.  Getattr-
        defensive — scripted test engines finish bare stubs without the
        Request timing fields."""
        sub = getattr(req, "submitted_t", None)
        adm = getattr(req, "admitted_t", None)
        ftt = getattr(req, "first_token_t", None)
        done = getattr(req, "done_t", None)
        up = getattr(req, "upstream", None) or {}
        t: Dict[str, float] = {}
        if up:
            # Disaggregated: the queue/prefill legs ran on the prefill
            # sub-fleet and rode the handoff record; the decode-side
            # import-to-admission wait belongs to the handoff leg.
            if up.get("queue_s") is not None:
                t["queue"] = max(0.0, float(up["queue_s"]))
            if up.get("prefill_s") is not None:
                t["prefill"] = max(0.0, float(up["prefill_s"]))
            hand = float(getattr(req, "handoff_s", 0.0) or 0.0)
            if sub is not None and adm is not None:
                hand += max(0.0, adm - sub)
            if hand > 0.0:
                t["handoff"] = hand
        else:
            if sub is not None and adm is not None:
                t["queue"] = max(0.0, adm - sub)
            if adm is not None and ftt is not None:
                t["prefill"] = max(0.0, ftt - adm)
        if ftt is not None and done is not None:
            t["decode"] = max(0.0, done - ftt)
        if getattr(req, "pickup_s", None) is not None:
            t["pickup"] = req.pickup_s
        return t

    def _publish_stats(self, force: bool = False) -> None:
        now = time.monotonic()
        if not force and now - self._last_stats < _STATS_INTERVAL_S:
            return
        self._last_stats = now
        try:
            payload = dict(self.engine.stats(),
                           replica_id=self.replica_id)
            payload["queue_depth"] = int(payload.get("waiting", 0))
            if not force:
                # the periodic payload is encoded inside ``hvd:publish``
                # and keeps its size; the one at exit has the timeline
                payload.get("loop", {}).pop("timeline", None)
            fps = getattr(self.engine, "prefix_fps", None)
            if fps is not None:
                # Affinity piggyback (serve/replica.py): the router
                # learns this replica's radix-tree fingerprints from the
                # same heartbeat it already reads for liveness.
                fp_list, digest = fps()
                payload["prefix_fps"] = fp_list
                payload["replica_digest"] = digest
            self._kv_put(self.stats_scope, self._stats_key,
                         json.dumps(payload).encode())
        except Exception:
            if force:
                raise
            # periodic stats are best-effort; the next tick retries

    # ------------------------------------------------------------- drain
    def _drain_requested(self) -> bool:
        return self._kv_get(self.stats_scope, DRAIN_KEY) is not None

    def _publish_drained(self) -> None:
        """The ack POST /admin/drain waits on: final engine stats plus
        the completed count, written once everything accepted is done."""
        payload = dict(self.engine.stats(), epoch=self.epoch,
                       t=time.time())
        self._kv_put(self.stats_scope, self._drained_key,
                     json.dumps(payload).encode())

    # ----------------------------------------------------- replica/handoff
    def register_replica(self, info: Optional[Dict[str, Any]] = None) \
            -> None:
        """Rank 0 of a replicated fleet announces itself under the
        ``replicas`` scope so the router can discover and route to it
        (serve/replica.py).  Liveness afterwards is the stats heartbeat,
        not this one-shot registration."""
        payload = {"replica_id": self.replica_id, "epoch": self.epoch,
                   "nprocs": self.nprocs, "role": self.role}
        if info:
            payload.update(info)
        self._kv_put(REPLICA_SCOPE, replica_key(self.replica_id),
                     json.dumps(payload).encode())

    def _publish_handoffs(self, report: Dict[str, Any]) -> None:
        """Prefill-role rank 0: ship each finished prefill's prompt KV
        + first token to the decode sub-fleet via serve_kv (densely
        numbered, so the decode side drains with nonblocking probes)."""
        for h in report.get("handoff", []):
            info = self._resume_info.pop(h.get("req_id"), None)
            if info:
                h = dict(h, **info)
            key = f"handoff.{self._handoff_seq:06d}"
            rec = {"kind": "kvblock", "scope": self.kv_scope,
                   "key": key, "payload": h}
            if not self._direct_send(rec):
                self._kv_put(self.kv_scope, key,
                             json.dumps(h).encode())
            self._handoff_seq += 1

    def _drain_handoffs(self) -> List[Dict[str, Any]]:
        """Decode-role rank 0: consume prefill handoffs in sequence
        order; each becomes a plan entry every decode rank imports."""
        out = []
        while True:
            raw = self._kv_get(self.kv_scope,
                               f"handoff.{self._next_handoff:06d}")
            if raw is None:
                return out
            try:
                out.append({"handoff": json.loads(raw)})
            except (ValueError, TypeError):
                out.append(None)  # torn PUT: hold the dense numbering
            self._next_handoff += 1

    # -------------------------------------------------------------- loop
    def _submit(self, r: Dict[str, Any], kv_backed: bool) -> None:
        """Hand one plan entry to the engine: a prefill handoff to import,
        or a client request (answered at once where the engine refuses
        it)."""
        if "handoff" in r:
            # Prefill->decode import: the prompt KV is in the payload;
            # skips the admission queue.
            h = r["handoff"]
            if self.rank == 0 and kv_backed and \
                    h.get("resume_emitted") is not None:
                self._apply_resume(
                    {"id": h.get("req_id"),
                     "resume_emitted": h["resume_emitted"],
                     "resume_part": h.get("resume_part", 0)})
            self.engine.import_prefill(h)
            return
        if self.rank == 0 and kv_backed:
            self._apply_resume(r)
            if self.role == "prefill" and \
                    r.get("resume_emitted") is not None:
                self._resume_info[r["id"]] = {
                    "resume_emitted": r["resume_emitted"],
                    "resume_part": r.get("resume_part", 0)}
        try:
            req = self.engine.submit(r["tokens"], r["max_new_tokens"],
                                     req_id=r.get("id"),
                                     eos_id=r.get("eos_id"))
        except ValueError as e:
            # invalid per the engine's limits: answer it so the router
            # stream doesn't hang to timeout
            if self.rank == 0 and r.get("id") and kv_backed:
                self._publish_done(r["id"], {"done": True, "tokens": [],
                                             "error": str(e)})
            return
        if req is None:
            return  # scripted test engines return None
        # Guarded attach, not a submit kwarg: scripted engines predate
        # trace.
        if r.get("trace") is not None:
            req.trace = r["trace"]
        if r.get("submitted_t") is not None:
            # The router's wall-clock stamp -> this hand-over: exact on
            # one host, skewed by the clocks' difference across hosts.
            req.pickup_s = max(0.0, time.time() - float(r["submitted_t"]))

    def _hold(self, clock) -> None:
        """The commit point (docs/serving.md#the-loops-order): the tick
        fenced last is published, the one in flight has most of its time
        left, and whatever ``step()`` launches now starts only when that
        one ends.  So wait, for as long as the engine's own measurements
        allow (``ServeEngine.commit_due``), and poll then: a request that
        arrives meanwhile is in the next program instead of the one after.
        Booked as ``idle`` — the loop waiting on purpose, not the host's
        work — and counted (``hold_n``, ``hold_s``)."""
        commit_due = getattr(self.engine, "commit_due", None)
        due = commit_due() if commit_due is not None else None
        if due is None:
            return
        with clock.span("idle") as held:
            time.sleep(max(0.0, due - time.perf_counter()))
        clock.add("hold_n", 1)
        clock.add("hold_s", held.t1 - held.t0)

    def _idle_wait(self, until: float) -> None:
        """Nothing in the engine and nothing polled.  With the arrivals
        reader beside the loop: block on its queue, and wake the moment a
        request is put there; at the latest when the loop has something
        of its own to do — ``until`` (``time.monotonic``: the next drain
        probe, the end of ``ttl_s``) or the next stats snapshot — so that
        an idle loop still looks alive (``PM.record_step``).  Without
        one: sleep, as ever."""
        if self._arrivals is None or not self._arrivals.started:
            time.sleep(_IDLE_SLEEP_S)
            return
        until = min(until, self._last_stats + _STATS_INTERVAL_S)
        self._arrivals.wait(until - time.monotonic())

    def run(self, ttl_s: float = 0.0) -> int:
        """Serve until ``ttl_s`` elapses (0 = until interrupted), or a
        drain completes.  Rank 0 paces the fleet; followers block on the
        plan stream."""
        from .. import chaos as _chaos
        from .. import postmortem as PM
        fleet = self.nprocs > 1 and bool(self.addr and self.port)
        solo_kv = self.nprocs == 1 and bool(self.addr and self.port)
        kv_backed = fleet or solo_kv
        carry: List[Dict[str, Any]] = []
        if self.rank == 0 and kv_backed and self.role != "decode":
            # Decode role never touches serve_req — redrive replays
            # through the prefill sub-fleet, which re-hands-off with the
            # resume prefix attached (byte-identical stream resumption).
            carry = self.resume_from_kv()
        # The engine's phase clock when it has one (scripted test engines
        # do not): the loop's own phases land in the same table.
        clock = getattr(self.engine, "clock", None) or PhaseClock()
        if self.rank == 0 and kv_backed and self.role != "decode":
            # after the redrive has set the cursor; a fleet's followers
            # get their requests from the plan stream
            self._arrivals = Arrivals(
                self._kv().KeyWaiter(self.addr, self.port, self.req_scope),
                self._kv_op, self._next_seq, clock.add, _IDLE_SLEEP_S)
        t0 = time.monotonic()
        stop = False
        drain_t: Optional[float] = None
        drain_check_t = 0.0
        try:
            while True:
                # Loop liveness for /health supervision: an IDLE fleet
                # must look alive; only a wedged loop/engine freezes it.
                PM.record_step(self.tick)
                _chaos.maybe_stall("serve_tick")
                if not fleet:
                    # a fleet's program starts when its last rank has
                    # launched, a plan-stream hop after rank 0, which rank 0
                    # cannot see: it launches at once, as before
                    self._hold(clock)
                with clock.span("poll"):
                    if self.rank == 0:
                        if drain_t is None and kv_backed and \
                                time.monotonic() >= drain_check_t:
                            drain_check_t = time.monotonic() + _DRAIN_POLL_S
                            if self._drain_requested():
                                drain_t = time.monotonic()
                                print("[hvd.serve] rank 0: drain requested "
                                      "— finishing in-flight work",
                                      flush=True)
                        if not kv_backed:
                            reqs = []
                        elif self.role == "decode":
                            # The decode sub-fleet's work arrives as
                            # prefill handoffs, not raw client requests.
                            reqs = self._drain_handoffs()
                        else:
                            reqs = self._drain_requests()
                        if carry:
                            reqs = carry + reqs
                            carry = []
                        done_serving = (
                            (bool(ttl_s)
                             and time.monotonic() - t0 >= ttl_s)
                            or drain_t is not None)
                        stop = bool(done_serving and not reqs
                                    and not self.engine.has_work()
                                    and not self._request_in_store())
                        if drain_t is not None and not stop and \
                                time.monotonic() - drain_t >= \
                                self.drain_timeout_s:
                            # Degraded drain: the budget beats
                            # completeness so a preemption deadline is
                            # never missed.
                            print("[hvd.serve] rank 0: drain budget "
                                  f"({self.drain_timeout_s:.0f}s) exhausted "
                                  "with work in flight — stopping anyway",
                                  flush=True)
                            stop = True
                        if fleet:
                            self._publish_plan(reqs, stop=stop)
                    else:
                        plan = self._fetch_plan()
                        reqs, stop = plan["reqs"], plan["stop"]
                self.tick += 1
                if stop:
                    break
                with clock.span("submit"):
                    for r in reqs:
                        if r is not None:
                            self._submit(r, kv_backed)
                # Chaos step clock = the ENGINE's work-tick counter: it
                # advances only when the fleet is decoding/prefilling,
                # so a spec kill at step K lands mid-stream
                # deterministically (docs/chaos.md).
                _chaos.step(self.engine.tick)
                report = self.engine.step()
                if self.rank == 0 and kv_backed:
                    with clock.span("publish"):
                        self._publish_report(report)
                        if self.role == "prefill":
                            self._publish_handoffs(report)
                        self._publish_stats()
                if not self.engine.has_work() and not reqs:
                    if self.rank == 0:
                        with clock.span("idle"):
                            self._idle_wait(
                                min(drain_check_t, t0 + ttl_s) if ttl_s
                                else drain_check_t)
        except KeyboardInterrupt:
            if self.rank == 0 and fleet:
                # release the followers blocked on the plan stream
                try:
                    self._publish_plan([], stop=True)
                except Exception:
                    pass
            raise
        finally:
            if self._arrivals is not None:
                self._arrivals.stop()
                self._arrivals = None
        if self._dstream is not None:
            # Orderly end of the direct stream: everything sent is
            # already stored router-side, so this only releases the
            # connection (a torn close loses nothing).
            self._dstream.close()
            self._dstream = None
        if self.rank == 0 and kv_backed:
            self._publish_stats(force=True)
            if drain_t is not None:
                self._publish_drained()
        return 0


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m horovod_tpu.serve.worker",
        description="Serving-fleet worker (launched by hvdrun --serve)")
    ap.add_argument("ckpt_dir", help="servable directory: serve.json + "
                                     "checkpoint (docs/serving.md)")
    ap.add_argument("--ttl", type=float, default=0.0,
                    help="seconds to serve before a clean exit "
                         "(0 = until interrupted); bounded CI smokes "
                         "use this")
    args = ap.parse_args(argv)

    import jax
    import horovod_tpu as hvd
    from ..utils.platform import enable_compile_cache, force_cpu
    if os.environ.get("JAX_PLATFORMS") == "cpu" and \
            os.environ.get("HVD_CPU_CHIPS"):
        # CPU-virtual fleets (the test tier): that many devices per
        # process, like the test workers
        force_cpu(virtual_chips=int(os.environ["HVD_CPU_CHIPS"]))
    enable_compile_cache()
    hvd.init()
    rt = __import__("horovod_tpu.runtime", fromlist=["get"]).get()
    from .config import from_knobs
    from .engine import ServeEngine, load_servable
    scfg = from_knobs(rt.knobs)
    model, model_cfg, params = load_servable(args.ckpt_dir, hvd.mesh())
    # The knob default (2048) may exceed a small model's max_seq; clamp
    # rather than fail — the model is the binding constraint.
    if scfg.max_seq_len > model_cfg.max_seq:
        import dataclasses
        scfg = dataclasses.replace(scfg, max_seq_len=model_cfg.max_seq)
    # Prefill/decode disaggregation (docs/serving.md#replicated-tier):
    # HOROVOD_SERVE_PREFILL_RANKS splits the fleet into two sub-fleets,
    # each with its own rank 0 and plan stream; the decode side owns the
    # client-facing output and stats scopes.
    pf = int(scfg.prefill_ranks)
    rank, size = hvd.process_rank(), hvd.process_size()
    if 0 < pf < size:
        if rank < pf:
            role, sub_rank, sub_n = "prefill", rank, pf
        else:
            role, sub_rank, sub_n = "decode", rank - pf, size - pf
    else:
        role, sub_rank, sub_n = "mixed", rank, size
    engine = ServeEngine(model, model_cfg, params, scfg,
                         mesh=hvd.mesh(), role=role)
    epoch = int(rt.knobs["HOROVOD_ELASTIC_ROUND"])
    frontend = FleetFrontend(
        engine,
        rt.knobs["HOROVOD_RENDEZVOUS_ADDR"],
        rt.knobs["HOROVOD_RENDEZVOUS_PORT"],
        sub_rank, sub_n,
        epoch=epoch,
        journal=bool(rt.knobs["HOROVOD_SERVE_JOURNAL"]),
        drain_timeout_s=float(rt.knobs["HOROVOD_SERVE_DRAIN_TIMEOUT"]),
        direct=bool(rt.knobs["HOROVOD_SERVE_DIRECT"]),
        replica_id=scfg.replica_id, role=role)
    dev = jax.devices()[0]
    print(f"SERVE-READY rank {rank} epoch {epoch} "
          f"({type(model_cfg).__name__}, slots={scfg.max_slots}, "
          f"blocks={scfg.cache_blocks}x{scfg.block_size}, role={role}, "
          f"replica={scfg.replica_id}/{scfg.replicas}, "
          f"platform={dev.platform}, device_kind={dev.device_kind}, "
          f"devices={hvd.size()}, cache={engine._cache_shd.spec})",
          flush=True)
    if sub_rank == 0 and frontend.addr and frontend.port:
        if scfg.replicas > 1 and role != "prefill":
            frontend.register_replica({"replicas": scfg.replicas,
                                       "block_size": scfg.block_size})
        frontend._publish_stats(force=True)  # readiness for the router
    try:
        return frontend.run(ttl_s=args.ttl)
    except KeyboardInterrupt:
        return 130
    finally:
        engine.close()  # unregister the memory plane's KV-pool provider


if __name__ == "__main__":
    sys.exit(main())
