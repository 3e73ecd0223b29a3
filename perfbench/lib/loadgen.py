"""Open-loop load from one process: a request is sent at its due time
whether or not earlier ones finished, streams ``POST /generate`` and notes
when each part arrives.  Stdlib only."""

from __future__ import annotations

import http.client
import json
import threading
import time


class Client:
    def __init__(self, host, port, reqs, timeout_s=120.0):
        self.host, self.port, self.timeout_s = host, port, timeout_s
        self.reqs = reqs
        self.records = [None] * len(reqs)
        self._threads, self._conns = [], []
        self._stop = threading.Event()
        self._lock = threading.Lock()

    def _one(self, i, body, due_abs):
        rec = {"i": i, "due": due_abs, "sent": time.time(), "status": None,
               "part_t": [], "part_n": [], "tokens": [], "done": None,
               "error": None, "end": None,
               "prompt_len": len(body["tokens"]),
               "max_new_tokens": body["max_new_tokens"]}
        self.records[i] = rec
        conn = http.client.HTTPConnection(self.host, self.port,
                                          timeout=self.timeout_s)
        with self._lock:
            self._conns.append(conn)
        try:
            conn.request("POST", "/generate", json.dumps(body),
                         {"Content-Type": "application/json"})
            resp = conn.getresponse()
            rec["status"] = resp.status
            if resp.status != 200:
                rec["error"] = resp.read().decode(errors="replace")[:200]
                return
            while not self._stop.is_set():
                line = resp.readline()
                if not line:
                    break
                now = time.time()
                msg = json.loads(line)
                if msg.get("done"):
                    rec["done"] = msg
                    break
                if "error" in msg:
                    rec["error"] = msg["error"]
                    break
                rec["part_t"].append(now)
                rec["part_n"].append(len(msg["tokens"]))
                rec["tokens"].extend(msg["tokens"])
        except (OSError, ValueError, http.client.HTTPException,
                AttributeError) as e:
            if not self._stop.is_set():      # cut by stop() is no failure
                rec["error"] = repr(e)
        finally:
            rec["end"] = time.time()
            conn.close()
            self._release(i)

    def _release(self, i):
        """Send the turns that waited for request i."""
        rec = self.records[i]
        for j, r in enumerate(self.reqs):
            if r.get("after") == i and not self._stop.is_set():
                body = {"tokens": self.reqs[i]["tokens"] + rec["tokens"]
                        + r["tokens"],
                        "max_new_tokens": r["max_new_tokens"]}
                self.reqs[j] = dict(r, tokens=body["tokens"])
                due = rec["end"] + r.get("think_s", 0.0)
                self._spawn(j, body, due, wait=True)

    def _spawn(self, i, body, due_abs, wait=False):
        def go():
            if wait:
                time.sleep(max(0.0, due_abs - time.time()))
            self._one(i, body, due_abs)
        t = threading.Thread(target=go, daemon=True)
        with self._lock:
            self._threads.append(t)
        t.start()

    def run(self, t0):
        """Send every request with a due time at t0 + due (blocks until the
        last is sent); answers arrive on their own threads."""
        for i, r in enumerate(self.reqs):
            if r["due"] is None:
                continue
            due = t0 + r["due"]
            time.sleep(max(0.0, due - time.time()))
            if self._stop.is_set():
                break
            self._spawn(i, {"tokens": r["tokens"],
                            "max_new_tokens": r["max_new_tokens"]}, due)

    def wait(self, until, pred):
        """Until ``pred(records)`` or the time ``until``."""
        while time.time() < until and not pred(self.records):
            time.sleep(0.05)

    def stop(self):
        """No more sends; streams still open are cut and count as
        unfinished, not as failed."""
        self._stop.set()
        with self._lock:
            conns = list(self._conns)
        for c in conns:
            try:
                if c.sock is not None:
                    c.sock.shutdown(2)
            except OSError:
                pass

    def join(self, timeout_s):
        end = time.time() + timeout_s
        with self._lock:
            threads = list(self._threads)
        for t in threads:
            t.join(max(0.0, end - time.time()))


def http_json(port, path, body, timeout_s):
    """One JSON request to the router; {} when it cannot be parsed."""
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout_s)
    try:
        conn.request("POST" if body is not None else "GET", "/" + path,
                     None if body is None else json.dumps(body),
                     {"Content-Type": "application/json"})
        return json.loads(conn.getresponse().read() or b"{}")
    except (OSError, ValueError, http.client.HTTPException):
        return {}
    finally:
        conn.close()
