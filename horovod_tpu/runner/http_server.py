"""Rendezvous: a threaded HTTP key-value store + the /metrics route.

Direct functional port of the reference's rendezvous server (reference:
horovod/runner/http/http_server.py:35-201): PUT/GET on /scope/key paths
backed by an in-memory dict.  Consumers: worker bootstrap (slot info),
elastic host-change notifications, and anything that needs a tiny shared
blackboard during launch.  The reference's C++ gloo HTTPStore speaks the
same protocol; here the native core uses TCP directly, so this server
serves the Python-side rendezvous and elastic signaling.

``GET /metrics`` is special-cased: workers PUT periodic metric snapshots
into the ``metrics`` scope (``utils/metrics.py`` MetricsPublisher), and
this route renders them — plus the server process's own registry — as one
fleet-wide Prometheus text exposition, each sample labeled with its rank
(``hvdrun --metrics-port`` pins the port; see docs/metrics.md).

Two more special routes serve the distributed tracing plane
(docs/timeline.md):

  * ``GET /clock`` returns this server's wall time — the reference clock
    every rank's NTP-style offset handshake measures against
    (``utils/clocksync.py``);
  * ``GET /timeline`` renders the trace chunks workers PUT into the
    ``timeline`` scope (``utils/timeline.py`` TimelinePublisher) as one
    merged, rank-laned Chrome/Perfetto JSON on the shared aligned epoch.

``GET /health`` serves the postmortem plane's live leg
(docs/postmortem.md): workers PUT heartbeats into the ``health`` scope
(``utils/health.py`` HeartbeatPublisher) and this route renders the
fleet liveness view with per-rank staleness judged from the server's
own receipt times (``?stale_after=SECS`` tunes the patience).

``GET /perf`` serves the perf-attribution plane (docs/profiling.md):
workers PUT step-time decomposition reports into the ``perf`` scope
(``horovod_tpu/perf/ledger.py`` PerfPublisher) and this route renders
the merged fleet view with the bottleneck verdict root-cause-first.

The serving plane (docs/serving.md) adds the front door:

  * ``POST /generate`` enqueues a generation request onto the
    ``serve_req`` scope (journaled to ``serve_journal`` for redrive)
    and streams the engine fleet's tokens back as ndjson
    (``horovod_tpu/serve/router.py`` — watermark shedding, sequence
    numbering, result streaming);
  * ``POST /serve/stream`` is rank 0's persistent direct token stream
    (``horovod_tpu/serve/stream.py``): ndjson records over one chunked
    connection, mirrored into the ``serve_out`` store in-process so the
    journal/redrive source of truth is unchanged
    (docs/control-plane.md#direct-streaming);
  * ``GET /serve/stats`` merges router counters with the engine's
    self-published stats (scope ``serve`` key ``stats``);
  * ``POST /admin/drain`` stops admission and gracefully drains the
    engine fleet to a clean exit 0 (docs/serving.md#fault-tolerance).

Sharding (docs/control-plane.md): with ``shards=N`` the server starts
N-1 additional KV shard servers in this process, each with its own
store, lock and accept loop; scopes are owned per the deterministic
``runner/kvshard.shard_for_scope`` map, clients route per scope, and
the primary's render routes read the owning shard's store directly
in-process (the stores share one process, so no HTTP hop).  A dark
shard therefore stalls only the scopes it owns.
"""

from __future__ import annotations

import itertools
import json
import socket
import sys
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Dict, List, Optional, Tuple

from .kvshard import MAP_KEY, MAP_SCOPE, shard_for_scope

METRICS_SCOPE = "metrics"
TIMELINE_SCOPE = "timeline"
CLOCK_SCOPE = "clock"
HEALTH_SCOPE = "health"
SERVE_SCOPE = "serve"
PERF_SCOPE = "perf"
SERIES_ROUTE = "series"
ALERTS_ROUTE = "alerts"
GENERATE_ROUTE = "generate"
# serve_out writes wake the router's stream drains (serve/router.py
# waits on kv_wakeup instead of busy-polling; docs/control-plane.md);
# serve_kv writes wake the decode sub-fleet's handoff long-polls;
# serve_req writes wake the GET that rank 0's arrivals reader has
# waiting on the next request's key (``?wait=``; serve/arrivals.py).
# Matching is on the base name so per-replica scoped variants
# (serve_out.r01, ...; serve/replica.py) wake the same condition.
_WAKEUP_SCOPES = ("serve_out", "serve_kv", "serve_req")
# A GET ``?wait=SECONDS`` on one of those scopes is held until the key is
# written or the wait, at most this long, runs out; the 404 that ends a
# wait says so in this header, so that a client can tell a server that
# waited from one that does not know the parameter.
MAX_WAIT_S = 30.0
WAITED_HEADER = "X-Hvd-Waited"


def add_stream_waiter(server, scope: str, req_key: str):
    """Register a per-request wakeup condition for one stream drain
    (serve/router.py) and return it — or None on a server without the
    waiter registry (bare test servers), where the caller falls back to
    the broadcast ``kv_wakeup``.  Keyed waiters are the replicated
    tier's scalability fix: the broadcast condition wakes EVERY waiting
    stream on EVERY ingested record, an O(streams x tokens/s) stampede
    that was most of the measured tick budget once N replica fleets
    shared one router process (docs/serving.md#replicated-tier)."""
    waiters = getattr(server, "kv_waiters", None)
    lock = getattr(server, "kv_waiters_lock", None)
    if waiters is None or lock is None:
        return None
    with lock:
        ent = waiters.get((scope, req_key))
        if ent is None:
            ent = waiters[(scope, req_key)] = [threading.Condition(), 0]
        ent[1] += 1  # refcount: a re-dispatched stream may share a key
        return ent[0]


def drop_stream_waiter(server, scope: str, req_key: str) -> None:
    waiters = getattr(server, "kv_waiters", None)
    lock = getattr(server, "kv_waiters_lock", None)
    if waiters is None or lock is None:
        return
    with lock:
        ent = waiters.get((scope, req_key))
        if ent is not None:
            ent[1] -= 1
            if ent[1] <= 0:
                del waiters[(scope, req_key)]


def wake_stream(server, scope: str, key: str) -> None:
    """Wake the stream drain waiting on this record: the per-request
    condition when one is registered (serve_out keys are
    ``req.NNNNNN.part.*`` / ``req.NNNNNN.done``), then the broadcast
    condition for legacy/unkeyed waiters — with keyed streams
    registered, the broadcast usually has no waiters and the notify is
    a few microseconds."""
    if scope.split(".r", 1)[0] not in _WAKEUP_SCOPES:
        return
    waiters = getattr(server, "kv_waiters", None)
    lock = getattr(server, "kv_waiters_lock", None)
    if waiters is not None and lock is not None:
        req = key.split(".part.", 1)[0]
        if req.endswith(".done"):
            req = req[:-len(".done")]
        with lock:
            ent = waiters.get((scope, req))
        if ent is not None:
            cond = ent[0]
            with cond:
                cond.notify_all()
    cond = getattr(server, "kv_wakeup", None)
    if cond is not None:
        with cond:
            cond.notify_all()


def store_for(server, scope: str):
    """The httpd whose in-process store owns ``scope`` — the primary's
    render routes and the router read/write through this so the view is
    correct whichever shard a scope hashes to.  A server started
    without shards is its own (only) store."""
    stores = getattr(server, "kv_stores", None)
    if not stores:
        return server
    return stores[shard_for_scope(scope, len(stores))]


_TRACE_SEQ = itertools.count()


def trace_span(server, lane: str, name: str, start_t: float,
               dur_s: float, args: Optional[Dict] = None) -> None:
    """Router-side request span as a synthetic timeline chunk on rank
    0's process lane (the alert_instant pattern): worker chunks stamp
    absolute aligned µs measured against THIS server, so the server's
    own wall clock is on the same epoch by construction
    (docs/serving.md#request-lifecycle).  Best-effort — tracing must
    never take the front door down."""
    try:
        chunk = {"rank": 0, "seq": -1, "events": [
            {"name": name, "ph": "X", "ts": float(start_t) * 1e6,
             "dur": max(0.0, float(dur_s)) * 1e6, "lane": lane,
             "args": args or {}}]}
        tl = store_for(server, TIMELINE_SCOPE)
        key = f"trace.0.{next(_TRACE_SEQ):06d}"
        with tl.kv_lock:  # type: ignore[attr-defined]
            tl.kv.setdefault(TIMELINE_SCOPE, {})[key] = \
                json.dumps(chunk).encode()  # type: ignore[attr-defined]
            tl.kv_times.setdefault(TIMELINE_SCOPE, {})[key] = \
                time.time()  # type: ignore[attr-defined]
    except Exception:
        pass


def watch_state_for(server):
    """The watch plane's server-side state (series store + alert
    engine; docs/watch.md), installed on the ``metrics``-owning shard
    store at server start — so history piggybacks on the metric PUTs
    that shard already receives and survives elastic resets with the
    driver.  None on servers that predate/skip installation."""
    return getattr(store_for(server, METRICS_SCOPE), "watch_state", None)


class _KVHandler(BaseHTTPRequestHandler):
    server_version = "hvdtpu-rendezvous/1.0"

    def _split(self) -> Tuple[str, str]:
        path, _, self._query = self.path.partition("?")
        parts = path.strip("/").split("/", 1)
        scope = parts[0] if parts else ""
        key = parts[1] if len(parts) > 1 else ""
        return scope, key

    def _count_request(self) -> None:
        """Per-shard request accounting (hvd_kv_shard_requests_total):
        only meaningful when the KV is actually sharded — single-shard
        servers skip the metric so the default path pays nothing."""
        stores = getattr(self.server, "kv_stores", None)
        idx = getattr(self.server, "shard_index", 0)
        with self.server.kv_lock:  # type: ignore[attr-defined]
            self.server.kv_requests = \
                getattr(self.server, "kv_requests", 0) + 1
        if stores and len(stores) > 1:
            try:
                from ..utils import metrics as M
                M.KV_SHARD_REQUESTS.inc(shard=str(idx))
            except Exception:
                pass  # telemetry must never take a KV op down

    def _wake(self, scope: str, key: str) -> None:
        wake_stream(self.server, scope, key)

    def do_PUT(self) -> None:  # noqa: N802
        scope, key = self._split()
        length = int(self.headers.get("Content-Length", 0))
        value = self.rfile.read(length)
        self._count_request()
        with self.server.kv_lock:  # type: ignore[attr-defined]
            self.server.kv.setdefault(scope, {})[key] = value  # type: ignore
            # Receipt stamp: the server-side truth /health staleness is
            # computed from (a worker with a broken clock still ages).
            self.server.kv_times.setdefault(scope, {})[key] = \
                time.time()  # type: ignore[attr-defined]
        self.send_response(200)
        self.end_headers()
        self._wake(scope, key)
        # Watch plane (docs/watch.md): metrics snapshots feed the fleet
        # series store (rate-limited to the series resolution) and each
        # ingest runs an alert-evaluation pass; heartbeats feed the
        # absence-kind liveness series.  Best-effort by contract —
        # telemetry must never fail the KV op that carried it.
        if scope in (METRICS_SCOPE, HEALTH_SCOPE):
            try:
                ws = watch_state_for(self.server)
                if ws is not None:
                    if scope == METRICS_SCOPE:
                        ws.ingest_metrics(key, value)
                    else:
                        ws.note_heartbeat(key)
            except Exception:
                pass

    def do_POST(self) -> None:  # noqa: N802
        scope, key = self._split()
        if scope == GENERATE_ROUTE and not key:
            # Serving front door (docs/serving.md): parse, backpressure,
            # enqueue to the KV, stream the engine's tokens back.
            from ..serve import router as serve_router
            serve_router.handle_generate(self)
            return
        if scope == SERVE_SCOPE and key == "stream":
            # Rank 0's persistent direct token stream: parts/done
            # records off the KV PUT+poll path entirely
            # (docs/control-plane.md#direct-streaming).
            from ..serve import stream as serve_stream
            serve_stream.handle_stream(self)
            return
        if scope == "admin" and key == "drain":
            # Graceful serving drain (docs/serving.md#fault-tolerance):
            # stop admission, let the engine fleet finish in-flight
            # requests, exit 0 — the preemption-safe rolling restart.
            from ..serve import router as serve_router
            serve_router.handle_drain(self)
            return
        self.send_response(404)
        self.end_headers()

    def do_GET(self) -> None:  # noqa: N802
        scope, key = self._split()
        if scope == SERVE_SCOPE and key == "stats":
            import json as _json
            from ..serve import router as serve_router
            self._serve_body(
                _json.dumps(serve_router.render_stats(self.server)
                            ).encode(), "application/json")
            return
        if scope == SERVE_SCOPE and key == "trace":
            # Tail analytics over per-request trace records
            # (docs/serving.md#request-lifecycle): slowest-requests
            # table + per-component p50/p99 fleet rollup.
            import json as _json
            from ..serve import router as serve_router
            self._serve_body(
                _json.dumps(serve_router.render_trace(self.server)
                            ).encode(), "application/json")
            return
        if scope == METRICS_SCOPE and not key:
            self._serve_metrics()
            return
        if scope == CLOCK_SCOPE and not key:
            self._serve_body(repr(time.time()).encode(), "text/plain")
            return
        if scope == TIMELINE_SCOPE and not key:
            self._serve_timeline()
            return
        if scope == HEALTH_SCOPE and not key:
            self._serve_health()
            return
        if scope == PERF_SCOPE and not key:
            self._serve_perf()
            return
        if scope == SERIES_ROUTE and not key:
            self._serve_series()
            return
        if scope == ALERTS_ROUTE and not key:
            self._serve_alerts()
            return
        self._count_request()
        wait = self._wait_s(scope)
        if wait is not None:
            self._serve_waited(scope, key, wait)
            return
        value = self._lookup(scope, key)
        if value is None:
            self.send_response(404)
            self.end_headers()
            return
        self.send_response(200)
        self.send_header("Content-Length", str(len(value)))
        self.end_headers()
        self.wfile.write(value)

    def _lookup(self, scope: str, key: str) -> Optional[bytes]:
        with self.server.kv_lock:  # type: ignore[attr-defined]
            return self.server.kv.get(scope, {}).get(key)  # type: ignore

    def _wait_s(self, scope: str) -> Optional[float]:
        """How long the caller lets this GET be held (``?wait=SECONDS``),
        or None: a scope whose writes wake nobody, a server without the
        wakeup condition, no such parameter or a malformed one — the GET
        is answered at once, as by a server that never knew it."""
        query = getattr(self, "_query", "")
        if "wait=" not in query or \
                scope.split(".r", 1)[0] not in _WAKEUP_SCOPES or \
                getattr(self.server, "kv_wakeup", None) is None:
            return None
        from urllib.parse import parse_qs
        try:
            wait = float(parse_qs(query)["wait"][0])
        except (KeyError, ValueError):
            return None
        return min(max(0.0, wait), MAX_WAIT_S)

    def _serve_waited(self, scope: str, key: str, wait: float) -> None:
        """The held GET: block on the key's wakeup condition until a
        writer has put the key (``wake_stream``) or the wait has run out.
        The caller keeps its connection (``Connection: keep-alive``) and
        asks for the next key over it, so both answers carry their
        length, and the value follows its headers at once."""
        server = self.server
        cond = add_stream_waiter(server, scope, key) or server.kv_wakeup
        deadline = time.monotonic() + wait
        try:
            # registered before the first look: a writer that comes after
            # it finds the waiter, one that came before it left the value
            with cond:
                while True:
                    value = self._lookup(scope, key)
                    left = deadline - time.monotonic()
                    if value is not None or left <= 0:
                        break
                    cond.wait(left)
        finally:
            drop_stream_waiter(server, scope, key)
        keep = self.headers.get("Connection", "").lower() == "keep-alive"
        try:
            self.connection.setsockopt(socket.IPPROTO_TCP,
                                       socket.TCP_NODELAY, 1)
            self.send_response(404 if value is None else 200)
            self.send_header("Content-Length", str(len(value or b"")))
            if value is None:
                self.send_header(WAITED_HEADER, "1")
            if keep:
                self.send_header("Connection", "keep-alive")
            self.end_headers()
            if value:
                self.wfile.write(value)
        except OSError:
            return  # the caller went away while its GET was held
        self.close_connection = not keep

    def _serve_metrics(self) -> None:
        """Fleet Prometheus exposition: local (driver) registry + every
        worker snapshot the ``metrics`` scope holds, rank-labeled."""
        from ..utils import metrics as M
        store = store_for(self.server, METRICS_SCOPE)
        with store.kv_lock:  # type: ignore[attr-defined]
            stored = dict(store.kv.get(METRICS_SCOPE, {}))  # type: ignore
        snaps = [({"rank": "driver"}, M.REGISTRY.snapshot())]
        for key in sorted(stored):
            try:
                snap = json.loads(stored[key])
            except (ValueError, TypeError):
                continue  # a torn PUT must not 500 the whole scrape
            rank = str(snap.get("rank", key.rsplit(".", 1)[-1]))
            snaps.append(({"rank": rank}, snap))
        body = M.render_prometheus(snaps).encode()
        self.send_response(200)
        self.send_header("Content-Type",
                         "text/plain; version=0.0.4; charset=utf-8")
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def _serve_body(self, body: bytes, content_type: str) -> None:
        self.send_response(200)
        self.send_header("Content-Type", content_type)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def _serve_timeline(self) -> None:
        """Merged fleet trace: every chunk the ``timeline`` scope holds,
        rank-laned on the shared aligned epoch (docs/timeline.md)."""
        from ..utils.timeline import merge_timeline_chunks
        store = store_for(self.server, TIMELINE_SCOPE)
        with store.kv_lock:  # type: ignore[attr-defined]
            stored = dict(store.kv.get(TIMELINE_SCOPE, {}))  # type: ignore
        merged = merge_timeline_chunks(stored)
        self._serve_body(json.dumps(merged).encode(), "application/json")

    def _serve_health(self) -> None:
        """Fleet liveness view (postmortem plane, docs/postmortem.md):
        the ``health`` scope's heartbeats as JSON with per-rank
        staleness judged from the server's receipt times.  The staleness
        threshold is tunable per request (``GET /health?stale_after=2``)
        so dashboards and tests pick their own patience."""
        from urllib.parse import parse_qs
        from ..utils.health import fleet_health
        stale_after = 10.0
        try:
            q = parse_qs(getattr(self, "_query", ""))
            if q.get("stale_after"):
                stale_after = float(q["stale_after"][0])
        except (ValueError, TypeError):
            pass  # malformed query: fall back to the default patience
        store = store_for(self.server, HEALTH_SCOPE)
        with store.kv_lock:  # type: ignore[attr-defined]
            stored = dict(store.kv.get(HEALTH_SCOPE, {}))  # type: ignore
            times = dict(store.kv_times.get(  # type: ignore
                HEALTH_SCOPE, {}))
        view = fleet_health(stored, times, stale_after=stale_after)
        shards = kv_shard_health(self.server)
        if shards is not None:
            # Control-plane health rides the same view (docs/
            # control-plane.md): a dark shard is a partial outage the
            # on-call reader must see next to rank liveness.
            view["kv_shards"] = shards
        self._serve_body(json.dumps(view).encode(), "application/json")

    def _serve_series(self) -> None:
        """Fleet time-series view (watch plane, docs/watch.md): the
        bounded per-(rank, family) history the rendezvous server folds
        out of the metric snapshots workers already publish.
        ``GET /series?family=F&rank=N&window=S`` filters; bare
        ``GET /series`` returns everything retained."""
        from urllib.parse import parse_qs
        ws = watch_state_for(self.server)
        if ws is None:
            self._serve_body(json.dumps({"error": "watch plane not "
                                         "installed"}).encode(),
                             "application/json")
            return
        family = rank = window = None
        try:
            q = parse_qs(getattr(self, "_query", ""))
            if q.get("family"):
                family = q["family"][0]
            if q.get("rank"):
                rank = int(q["rank"][0])
            if q.get("window"):
                window = float(q["window"][0])
        except (ValueError, TypeError):
            pass  # malformed query: fall back to the unfiltered view
        view = ws.store.query(family=family, rank=rank, window_s=window)
        self._serve_body(json.dumps(view).encode(), "application/json")

    def _serve_alerts(self) -> None:
        """Live alert view (watch plane, docs/watch.md#rules): one
        evaluation pass over the rules engine — firing alerts first
        (severity-ordered), then the active ruleset and the bounded
        transition history; the payload ``hvdrun doctor --watch``
        renders."""
        ws = watch_state_for(self.server)
        if ws is None:
            self._serve_body(json.dumps({"error": "watch plane not "
                                         "installed"}).encode(),
                             "application/json")
            return
        view = ws.engine.view()
        view["series"] = {"families": len(ws.store.families()),
                          "points": ws.store.point_count(),
                          "dropped_series": ws.store.dropped_series}
        self._serve_body(json.dumps(view).encode(), "application/json")

    def _serve_perf(self) -> None:
        """Merged fleet perf-attribution view (docs/profiling.md): the
        ``perf`` scope's per-rank reports plus the fleet bottleneck
        verdict (straggler-bound / comm-bound / compute-bound /
        input-bound / stall-bound), root cause first — the same payload
        ``hvdrun doctor --perf`` renders."""
        from ..perf.ledger import merge_perf_reports
        store = store_for(self.server, PERF_SCOPE)
        with store.kv_lock:  # type: ignore[attr-defined]
            stored = dict(store.kv.get(PERF_SCOPE, {}))  # type: ignore
        view = merge_perf_reports(stored)
        self._serve_body(json.dumps(view).encode(), "application/json")

    def do_DELETE(self) -> None:  # noqa: N802
        scope, key = self._split()
        self._count_request()
        with self.server.kv_lock:  # type: ignore[attr-defined]
            existed = self.server.kv.get(scope, {}).pop(key, None)  # type: ignore
            self.server.kv_times.get(scope, {}).pop(key, None)  # type: ignore
        self.send_response(200 if existed is not None else 404)
        self.end_headers()

    def log_message(self, *args) -> None:  # silence per-request logging
        pass


def kv_shard_health(server) -> Optional[List[Dict]]:
    """Per-shard control-plane health rows for /health and the doctor
    rendering, or None on an unsharded server: shard index, bound port,
    liveness (stop_shard marks a shard dark), request count, key count
    and the scopes currently resident (docs/control-plane.md)."""
    stores = getattr(server, "kv_stores", None)
    if not stores or len(stores) < 2:
        return None
    rows = []
    for i, store in enumerate(stores):
        with store.kv_lock:
            scopes = sorted(store.kv)
            keys = sum(len(d) for d in store.kv.values())
            requests = getattr(store, "kv_requests", 0)
        rows.append({
            "shard": i,
            "port": store.server_address[1],
            "alive": not getattr(store, "kv_stopped", False),
            "requests": requests,
            "keys": keys,
            "scopes": scopes,
        })
    return rows


class RendezvousServer:
    """Threaded KV server; start() returns the bound port (reference:
    http_server.py:174-201 RendezvousServer.start/init).

    ``shards=N`` (docs/control-plane.md) starts N-1 additional KV shard
    servers in this process (own store/lock/accept loop each, ephemeral
    ports); server-side accessors route per scope through the
    deterministic ``kvshard.shard_for_scope`` map, exactly like the
    workers' clients, so both sides agree by construction."""

    def __init__(self, host: str = "0.0.0.0", port: int = 0,
                 shards: int = 1):
        self._host = host
        self._port = port
        self._shards = max(1, int(shards))
        self._httpd: Optional[ThreadingHTTPServer] = None
        self._shard_httpds: List[ThreadingHTTPServer] = []
        self._threads: List[threading.Thread] = []
        self._final_kv: List[dict] = []
        self._final_kv_times: List[dict] = []

    def start(self) -> int:
        wakeup = threading.Condition()
        # Keyed stream waiters (add_stream_waiter): shared across all
        # shard httpds, like the broadcast condition, so a stream's
        # records wake it no matter which shard its scope hashes to.
        waiters: Dict[Tuple[str, str], list] = {}
        waiters_lock = threading.Lock()
        stores: List[ThreadingHTTPServer] = []
        for i in range(self._shards):
            # Only the primary gets the requested port; shard servers
            # bind ephemeral ports published via the shard map.
            httpd = ThreadingHTTPServer(
                (self._host, self._port if i == 0 else 0), _KVHandler)
            httpd.kv = {}  # type: ignore[attr-defined]
            httpd.kv_times = {}  # type: ignore[attr-defined]
            httpd.kv_lock = threading.Lock()  # type: ignore[attr-defined]
            httpd.kv_requests = 0  # type: ignore[attr-defined]
            httpd.kv_stopped = False  # type: ignore[attr-defined]
            httpd.shard_index = i  # type: ignore[attr-defined]
            httpd.kv_wakeup = wakeup  # type: ignore[attr-defined]
            httpd.kv_waiters = waiters  # type: ignore[attr-defined]
            httpd.kv_waiters_lock = waiters_lock  # type: ignore[attr-defined]
            stores.append(httpd)
        for httpd in stores:
            # Every shard sees the full store list: render routes and
            # the router resolve a scope's owner in-process.
            httpd.kv_stores = stores  # type: ignore[attr-defined]
            t = threading.Thread(target=httpd.serve_forever, daemon=True)
            t.start()
            self._threads.append(t)
        self._httpd = stores[0]
        self._shard_httpds = stores
        self._install_watch_state(stores)
        return self._httpd.server_address[1]

    def _install_watch_state(self, stores) -> None:
        """Watch plane (docs/watch.md): the series store + alert engine
        live on the ``metrics``-owning shard store — history piggybacks
        on the metric PUTs that store already receives and, since every
        shard lives in the driver process, survives elastic resets.
        Firing alerts additionally land as instants in the ``timeline``
        KV scope so the merged Perfetto trace shows incidents on the
        suspect rank's lane."""
        from ..watch import make_watch_state
        seq = itertools.count()

        def alert_instant(rule: str, rank: int, severity: str,
                          now: float) -> None:
            # A synthetic timeline chunk on the suspect rank's lane:
            # worker chunks stamp absolute aligned µs (wall + offset
            # measured against THIS server), so the server's own wall
            # clock is on the same epoch by construction.
            chunk = {"rank": int(rank), "seq": -1, "events": [
                {"name": f"alert.{rule}", "ph": "i", "s": "p",
                 "ts": now * 1e6, "lane": "alerts",
                 "args": {"rule": rule, "severity": severity}}]}
            tl = store_for(stores[0], TIMELINE_SCOPE)
            key = f"alert.{rank}.{next(seq):06d}"
            with tl.kv_lock:  # type: ignore[attr-defined]
                tl.kv.setdefault(TIMELINE_SCOPE, {})[key] = \
                    json.dumps(chunk).encode()  # type: ignore
                tl.kv_times.setdefault(TIMELINE_SCOPE, {})[key] = \
                    time.time()  # type: ignore[attr-defined]

        ws = make_watch_state(
            instant_fn=alert_instant,
            log_fn=lambda m: print(m, file=sys.stderr, flush=True))
        store_for(stores[0], METRICS_SCOPE).watch_state = ws

    @property
    def watch_state(self):
        """The installed watch plane (None before start())."""
        if self._httpd is None:
            return None
        return watch_state_for(self._httpd)

    def install_alert_rules(self, rules) -> None:
        """Merge user alert rules (hvdrun --alerts / HOROVOD_ALERTS)
        over the committed defaults by name and publish the merged set
        to KV scope ``alerts`` key ``rules`` for cross-checking — the
        chaos-spec distribution contract (docs/watch.md#rules)."""
        ws = self.watch_state
        if ws is None:
            return
        ws.engine.set_rules(rules)
        from ..watch import KV_KEY, KV_SCOPE, rules_to_json
        self.put(KV_SCOPE, KV_KEY,
                 rules_to_json(ws.engine.rules).encode())

    @property
    def port(self) -> int:
        assert self._httpd is not None
        return self._httpd.server_address[1]

    @property
    def shard_ports(self) -> List[int]:
        """Bound port per shard, primary first — what the launcher
        stamps into HOROVOD_KV_SHARD_ADDRS and publishes at scope
        ``kvshard`` key ``map``."""
        assert self._shard_httpds
        return [h.server_address[1] for h in self._shard_httpds]

    def publish_shard_map(self, addr: str) -> None:
        """Publish the shard address list to the primary's ``kvshard``
        scope so workers and the router can cross-check the map they
        derived from env (agreement by construction, visible by KV)."""
        self.put(MAP_SCOPE, MAP_KEY, json.dumps({
            "n": self._shards,
            "addrs": [f"{addr}:{p}" for p in self.shard_ports],
        }).encode())

    def _store(self, scope: str):
        assert self._httpd is not None
        return store_for(self._httpd, scope)

    def put(self, scope: str, key: str, value: bytes) -> None:
        """Server-side direct write (launcher publishing slot info,
        reference: http_server.py:134-172 init(host_alloc_plan))."""
        store = self._store(scope)
        with store.kv_lock:  # type: ignore[attr-defined]
            store.kv.setdefault(scope, {})[key] = value  # type: ignore
            store.kv_times.setdefault(scope, {})[key] = \
                time.time()  # type: ignore[attr-defined]
        wake_stream(self._httpd, scope, key)

    def get(self, scope: str, key: str) -> Optional[bytes]:
        if self._httpd is None:
            # Server-side reads stay valid after stop(): the store is
            # retained so drivers can harvest worker-published state
            # (e.g. elastic per-rank results) during teardown.
            return self._final_scope(scope).get(key)
        store = self._store(scope)
        with store.kv_lock:  # type: ignore[attr-defined]
            return store.kv.get(scope, {}).get(key)  # type: ignore

    def _final_scope(self, scope: str) -> dict:
        idx = shard_for_scope(scope, len(self._final_kv) or 1)
        if idx >= len(self._final_kv):
            return {}
        return self._final_kv[idx].get(scope, {})

    def scope_items(self, scope: str) -> Dict[str, bytes]:
        """All key->value pairs of a scope (valid after stop(), like
        get()); used to harvest worker metric snapshots."""
        if self._httpd is None:
            return dict(self._final_scope(scope))
        store = self._store(scope)
        with store.kv_lock:  # type: ignore[attr-defined]
            return dict(store.kv.get(scope, {}))  # type: ignore

    def scope_receipt_times(self, scope: str) -> Dict[str, float]:
        """Wall-clock receipt time of every key in a scope (valid after
        stop(), like scope_items) — the server-side truth heartbeat
        staleness is judged from (utils/health.fleet_health)."""
        if self._httpd is None:
            idx = shard_for_scope(scope, len(self._final_kv_times) or 1)
            if idx >= len(self._final_kv_times):
                return {}
            return dict(self._final_kv_times[idx].get(scope, {}))
        store = self._store(scope)
        with store.kv_lock:  # type: ignore[attr-defined]
            return dict(store.kv_times.get(scope, {}))  # type: ignore

    def clear_scope(self, scope: str) -> None:
        """Drop every key in a scope (round-scoped state like elastic
        worker results)."""
        store = self._store(scope)
        with store.kv_lock:  # type: ignore[attr-defined]
            store.kv.pop(scope, None)  # type: ignore[attr-defined]
            store.kv_times.pop(scope, None)  # type: ignore

    def stop_shard(self, index: int) -> None:
        """Take ONE shard dark (server-side partial outage: connections
        refused, the in-process store retained) — the chaos/test lever
        behind the "one KV shard down" story.  The primary (index 0)
        hosts the HTTP routes and cannot be stopped alone; use stop()."""
        if index == 0:
            raise ValueError("shard 0 is the primary; stop() the server")
        httpd = self._shard_httpds[index]
        if getattr(httpd, "kv_stopped", False):
            return
        httpd.kv_stopped = True  # type: ignore[attr-defined]
        httpd.shutdown()
        httpd.server_close()

    def stop(self) -> None:
        if self._httpd is not None:
            self._final_kv = []
            self._final_kv_times = []
            for httpd in self._shard_httpds:
                with httpd.kv_lock:  # type: ignore[attr-defined]
                    self._final_kv.append(
                        {s: dict(d)
                         for s, d in httpd.kv.items()})  # type: ignore
                    self._final_kv_times.append(
                        {s: dict(d)
                         for s, d in httpd.kv_times.items()})  # type: ignore
                if not getattr(httpd, "kv_stopped", False):
                    httpd.kv_stopped = True  # type: ignore[attr-defined]
                    httpd.shutdown()
                    httpd.server_close()
            self._httpd = None
            self._shard_httpds = []
