"""Tiny HTTP KV client for the rendezvous server (reference:
horovod/runner/http/http_client.py:1-45: read_data_from_kvstore /
put_data_into_kvstore).

Writers retry: a one-shot PUT meant a single transient connection refusal
during slot publish or a metrics PUT killed the worker, while the
wait-loop reader already rode outages out.  Both sides now use the shared
bounded exponential-backoff-with-jitter schedule
(``common/util.backoff_delays``; knobs ``HOROVOD_KV_RETRIES`` /
``HOROVOD_KV_RETRY_BACKOFF_MS``).  The chaos plane's KV blackout fault
injects here (docs/chaos.md), which is what proves the budget is neither
decorative nor unbounded.

Sharding (docs/control-plane.md): when the launcher started shard
servers (``hvdrun --kv-shards N``) it stamps the address list into
``HOROVOD_KV_SHARD_ADDRS`` (primary first).  Every call here routes a
request whose target is the PRIMARY to the scope's owning shard via the
deterministic ``runner/kvshard.shard_for_scope`` map; requests aimed at
any other server (tests talking to ad-hoc servers) pass through
untouched.  The per-op routing is what makes ``_kv_op``-style backoff
ride each shard independently: ops against a dark shard back off and
fail alone while every other scope's traffic proceeds.
"""

from __future__ import annotations

import http.client
import os
import socket
import threading
import time
import urllib.error
import urllib.request
from typing import List, Optional, Tuple

from .http_server import WAITED_HEADER
from .kvshard import parse_shard_addrs, shard_for_scope

# Explicit override (tests, ShardedKVClient): wins over the env map.
_installed_map: Optional[List[Tuple[str, int]]] = None
# Env-map cache keyed on the raw env string (cheap per-op resolve).
_env_map_raw: Optional[str] = None
_env_map: Optional[List[Tuple[str, int]]] = None


def install_shard_map(addrs: Optional[List[Tuple[str, int]]]) -> None:
    """Install (or with None, clear) the process-global shard map,
    overriding HOROVOD_KV_SHARD_ADDRS.  The runtime installs from env at
    hvd.init; tests install explicitly."""
    global _installed_map
    _installed_map = list(addrs) if addrs else None


def _shard_map() -> Optional[List[Tuple[str, int]]]:
    global _env_map_raw, _env_map
    if _installed_map is not None:
        return _installed_map
    raw = os.environ.get("HOROVOD_KV_SHARD_ADDRS", "")
    if not raw:
        return None
    if raw != _env_map_raw:
        _env_map_raw = raw
        try:
            _env_map = parse_shard_addrs(raw)
        except ValueError:
            _env_map = None
    return _env_map


def resolve_kv_addr(addr: str, port: int,
                    scope: str) -> Tuple[str, int, int]:
    """(addr, port, shard index) a KV op for ``scope`` should target.
    Reroutes only when the caller aimed at the fleet primary — any
    other (addr, port) is an ad-hoc server outside the sharded KV."""
    shards = _shard_map()
    if not shards or len(shards) < 2:
        return addr, int(port), 0
    if (addr, int(port)) != (shards[0][0], shards[0][1]):
        return addr, int(port), 0
    idx = shard_for_scope(scope, len(shards))
    a, p = shards[idx]
    return a, p, idx


def _count_shard_unavailable(shard: int) -> None:
    if _shard_map() is None:
        return
    try:  # telemetry must never take the KV op (or its retry) down
        from ..utils import metrics as M
        M.KV_SHARD_UNAVAILABLE.inc(shard=str(shard))
    except Exception:
        pass


def _chaos_kv(op: str, scope: str = "") -> None:
    # Lazy import: chaos resolves its spec through this module's get_kv.
    from .. import chaos
    inj = chaos.active()
    if inj is not None:
        inj.maybe_fail_kv(op, scope)


def _retry_delays(retries: Optional[int]):
    from ..common.knobs import current
    from ..common.util import backoff_delays
    if retries is None:
        retries = int(current("HOROVOD_KV_RETRIES"))
    return backoff_delays(retries, float(current(
        "HOROVOD_KV_RETRY_BACKOFF_MS")))


def _transient(e: Exception) -> bool:
    """Retryable: connection-level failures and 5xx; a 4xx is a caller
    bug and must surface immediately."""
    if isinstance(e, urllib.error.HTTPError):
        return e.code >= 500
    return isinstance(e, (urllib.error.URLError, ConnectionError,
                          TimeoutError))


def put_kv(addr: str, port: int, scope: str, key: str,
           value: bytes, retries: Optional[int] = None) -> None:
    addr, port, shard = resolve_kv_addr(addr, port, scope)
    url = f"http://{addr}:{port}/{scope}/{key}"
    delays = _retry_delays(retries)
    for attempt in range(len(delays) + 1):
        try:
            _chaos_kv("put", scope)
            req = urllib.request.Request(url, data=value, method="PUT")
            with urllib.request.urlopen(req, timeout=10):
                return
        except Exception as e:
            if _transient(e):
                _count_shard_unavailable(shard)
            if attempt >= len(delays) or not _transient(e):
                raise
            time.sleep(delays[attempt])


def get_kv(addr: str, port: int, scope: str, key: str,
           timeout: Optional[float] = None,
           poll_interval: float = 0.2) -> Optional[bytes]:
    """GET with blocking-until-present semantics (workers wait for the
    launcher to publish slot info).  ``timeout=None`` reads
    HOROVOD_GLOO_TIMEOUT_SECONDS (reference: --gloo-timeout-seconds, the
    knob bounding how long workers wait on the rendezvous); pass 0 for
    a non-blocking probe.  Transient connection errors (server restarting,
    chaos blackout) are retried until the deadline like a 404; at the
    deadline they RAISE — an unreachable server is not an absent key."""
    if timeout is None:
        from ..common.knobs import current
        timeout = float(current("HOROVOD_GLOO_TIMEOUT_SECONDS"))
    addr, port, shard = resolve_kv_addr(addr, port, scope)
    url = f"http://{addr}:{port}/{scope}/{key}"
    deadline = time.time() + timeout
    while True:
        try:
            _chaos_kv("get", scope)
            with urllib.request.urlopen(url, timeout=10) as resp:
                return resp.read()
        except urllib.error.HTTPError as e:
            if e.code != 404:
                raise
            if time.time() >= deadline:
                return None
            time.sleep(poll_interval)
        except Exception as e:
            if _transient(e):
                _count_shard_unavailable(shard)
            if not _transient(e) or time.time() >= deadline:
                raise
            time.sleep(poll_interval)


class KeyWaiter:
    """GETs that the server holds (``?wait=SECONDS``; runner/http_server.py
    ``_serve_waited``), over one connection that is kept from key to key:
    how rank 0's arrivals reader learns of the next request
    (serve/arrivals.py).  One thread calls ``wait_kv`` and ``close``;
    ``interrupt`` may come from another."""

    def __init__(self, addr: str, port: int, scope: str):
        self.scope = scope
        self._addr, self._port, self._shard = resolve_kv_addr(addr, port,
                                                              scope)
        self._conn: Optional[http.client.HTTPConnection] = None
        # ``interrupt`` against the sending of a request: a wait that began
        # just after ``interrupt`` had looked for its socket would last
        self._lock = threading.Lock()
        self._closed = False

    def wait_kv(self, key: str, wait: float
                ) -> Tuple[Optional[bytes], bool]:
        """(the value, or None where the key is still absent; whether the
        server held the GET).  A server that does not know the parameter
        answers its 404 at once and the second is False: the caller paces
        itself.  Raises what ``get_kv`` raises at its deadline; retrying
        is the caller's (``_kv_op``), and starts on a new connection."""
        try:
            _chaos_kv("get", self.scope)
            with self._lock:
                if self._closed:
                    raise ConnectionError("KeyWaiter is closed")
                conn = self._conn
                if conn is None:
                    # the held GET answers within ``wait``; the rest is
                    # the slack every KV leg has
                    conn = self._conn = http.client.HTTPConnection(
                        self._addr, self._port, timeout=wait + 10)
                conn.request("GET", f"/{self.scope}/{key}?wait={wait}",
                             headers={"Connection": "keep-alive"})
            resp = conn.getresponse()
            body = resp.read()
        except Exception as e:
            self.close()
            if self._closed:
                return None, True  # interrupted: nothing came, and say so
            if _transient(e):
                _count_shard_unavailable(self._shard)
            if isinstance(e, http.client.HTTPException):
                # a torn or garbled answer is the connection's fault
                raise ConnectionError(f"{type(e).__name__}: {e}") from e
            raise
        if resp.status == 200:
            return body, True
        if resp.status == 404:
            return None, resp.getheader(WAITED_HEADER) is not None
        raise urllib.error.HTTPError(
            f"http://{self._addr}:{self._port}/{self.scope}/{key}",
            resp.status, resp.reason, resp.headers, None)

    def close(self) -> None:
        """Drop the connection; a later wait opens a new one.  For the
        thread that waits, or once none does."""
        with self._lock:
            conn, self._conn = self._conn, None
        if conn is not None:
            conn.close()

    def interrupt(self) -> None:
        """From any thread: the wait in progress, and every later one,
        returns at once with nothing."""
        with self._lock:
            self._closed = True
            sock = self._conn.sock if self._conn is not None else None
            if sock is not None:
                try:
                    sock.shutdown(socket.SHUT_RDWR)
                except OSError:
                    pass


def delete_kv(addr: str, port: int, scope: str, key: str,
              retries: Optional[int] = None) -> bool:
    addr, port, shard = resolve_kv_addr(addr, port, scope)
    url = f"http://{addr}:{port}/{scope}/{key}"
    delays = _retry_delays(retries)
    for attempt in range(len(delays) + 1):
        try:
            _chaos_kv("put", scope)  # a delete is a write for blackouts
            req = urllib.request.Request(url, method="DELETE")
            with urllib.request.urlopen(req, timeout=10):
                return True
        except urllib.error.HTTPError:
            return False
        except Exception as e:
            if _transient(e):
                _count_shard_unavailable(shard)
            if attempt >= len(delays) or not _transient(e):
                raise
            time.sleep(delays[attempt])
    return False


class ShardedKVClient:
    """Scope-routing client bound to one fleet KV (docs/control-plane
    .md): ``(primary addr, primary port, shard address list)`` resolved
    once, then every op targets the owning shard directly.  The
    module-level functions already route via the env map; this class is
    for callers that hold an explicit map (the launcher's own tools,
    tests, the saturation bench) or talk to several fleets at once."""

    def __init__(self, addrs: List[Tuple[str, int]]):
        if not addrs:
            raise ValueError("ShardedKVClient needs at least one shard")
        self.addrs = [(a, int(p)) for a, p in addrs]

    @classmethod
    def from_env(cls, knobs=None) -> Optional["ShardedKVClient"]:
        """Build from HOROVOD_KV_SHARD_ADDRS (or, unsharded, from the
        rendezvous addr/port knobs); None when no rendezvous is known."""
        shards = _shard_map()
        if shards:
            return cls(shards)
        if knobs is None:
            from ..common.knobs import current
            addr = current("HOROVOD_RENDEZVOUS_ADDR")
            port = current("HOROVOD_RENDEZVOUS_PORT")
        else:
            addr = knobs["HOROVOD_RENDEZVOUS_ADDR"]
            port = knobs["HOROVOD_RENDEZVOUS_PORT"]
        if not addr or not port:
            return None
        return cls([(addr, int(port))])

    def _target(self, scope: str) -> Tuple[str, int]:
        return self.addrs[shard_for_scope(scope, len(self.addrs))]

    def put(self, scope: str, key: str, value: bytes,
            retries: Optional[int] = None) -> None:
        a, p = self._target(scope)
        put_kv(a, p, scope, key, value, retries=retries)

    def get(self, scope: str, key: str,
            timeout: Optional[float] = None,
            poll_interval: float = 0.2) -> Optional[bytes]:
        a, p = self._target(scope)
        return get_kv(a, p, scope, key, timeout=timeout,
                      poll_interval=poll_interval)

    def delete(self, scope: str, key: str,
               retries: Optional[int] = None) -> bool:
        a, p = self._target(scope)
        return delete_kv(a, p, scope, key, retries=retries)
