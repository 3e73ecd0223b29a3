#!/usr/bin/env python3
"""perfbench: one run of one cell of BENCHMARK.json.

  python3 perfbench/run.py --workload NAME --seed N --seconds S --trace 0|1

This process never touches jax: it finds the cell's files by name, starts
the chip-holding child (and for a serving cell the router and the load),
reduces what comes back through the per-layer readers, and prints the
result as the last line.  ``--dry-run 1`` rehearses the same path on the CPU
at toy width; it prints "platform": "cpu" and is no measurement.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import random
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from perfbench.lib import (  # noqa: E402
    checks, loadgen, peaks, serve_math, spec, stats, traffic as T)
from perfbench.lib.child import TAG  # noqa: E402


def note(**fields):
    print("perfbench: " + json.dumps(fields, default=str), flush=True)


class Child:
    """A child process whose tagged stdout lines are parsed and whose other
    lines are relayed (they come before the last line)."""

    def __init__(self, module, argv, env, stdin=False):
        self.proc = subprocess.Popen(
            [sys.executable, "-m", module] + argv, cwd=ROOT, env=env,
            stdout=subprocess.PIPE, stdin=subprocess.PIPE if stdin else None,
            text=True, start_new_session=True)
        self.events, self._cv = [], threading.Condition()
        self._reader = threading.Thread(target=self._read, daemon=True)
        self._reader.start()

    def _read(self):
        for line in self.proc.stdout:
            if line.startswith(TAG):
                with self._cv:
                    self.events.append(json.loads(line[len(TAG):]))
                    self._cv.notify_all()
            else:
                sys.stdout.write(line)
                sys.stdout.flush()
        with self._cv:
            self.events.append({"kind": "eof"})
            self._cv.notify_all()

    def expect(self, kind, timeout, name=None):
        end = time.time() + timeout
        with self._cv:
            while True:
                for ev in self.events:
                    if ev["kind"] == kind and (name is None
                                               or ev.get("name") == name):
                        self.events.remove(ev)
                        return ev
                    if ev["kind"] == "eof":
                        raise RuntimeError(
                            f"child ended (code {self.proc.poll()}) before "
                            f"{kind} {name or ''}")
                left = end - time.time()
                if left <= 0:
                    raise RuntimeError(f"no {kind} {name or ''} from the "
                                       f"child in {timeout:.0f}s")
                self._cv.wait(left)

    def send(self, line):
        self.proc.stdin.write(line + "\n")
        self.proc.stdin.flush()

    def finish(self, timeout):
        """Wait for the end; whatever is left of its group is stopped."""
        try:
            if self.proc.stdin:
                self.proc.stdin.close()
            self.proc.wait(timeout)
        except (subprocess.TimeoutExpired, OSError):
            pass
        for sig in (signal.SIGTERM, signal.SIGKILL):
            try:
                os.killpg(self.proc.pid, sig)
            except ProcessLookupError:
                break
            try:
                self.proc.wait(5)
            except subprocess.TimeoutExpired:
                continue
        self.proc.wait()
        self._reader.join(5)
        return self.proc.returncode


def child_env(args, chips):
    env = dict(os.environ)
    env.pop("BENCH_RUN", None)
    env["PB_T0"] = repr(T0)
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env["PYTHONUNBUFFERED"] = "1"
    env["TPU_LOG_DIR"] = env.get("TPU_LOG_DIR", "disabled")
    if args.dry_run:
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count={chips}")
    else:
        env["JAX_PLATFORMS"] = "tpu"
    return env


def report(ctx, metrics_e2e, metrics_layer, traced, correct, attempted,
           failed, device, breakdown=None):
    values = {}
    for m in (metrics_layer if traced else metrics_e2e):
        v = spec.metric_reader(m["name"])(ctx)
        if v is not None:
            # a CPU rehearsal proves the path and reports no number
            values[m["name"]] = {"value": None if ctx["dry_run"] else v,
                                 "unit": m["unit"]}
    line = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed), "metrics": values, "device": device}
    if traced and breakdown:
        line["breakdown"] = breakdown
    # each number compared beside its limit: the end of standard error and
    # the last key of the line are what the driver's record keeps of a run
    # that is not correct (a value that JSON cannot spell, as text)
    line["compared"] = {
        name: {"value": value if value is None or math.isfinite(value)
               else repr(value), "limit": limit}
        for name, value, limit in ctx["compared"]}
    for name, c in line["compared"].items():
        print(f"perfbench: compared {name} = {c['value']} "
              f"(limit {c['limit']})", file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)


# ------------------------------------------------------------------ training
def run_train(args, entry, config, traffic):
    argv = ["--workload", args.workload, "--seed", str(args.seed),
            "--seconds", str(args.seconds), "--trace", str(args.trace),
            "--dry", str(args.dry_run), "--break", args.break_]
    ch = Child("perfbench.lib.train_child", argv,
               child_env(args, entry["chips"]))
    try:
        res = ch.expect("train", timeout=args.limit)
    finally:
        rc = ch.finish(30)
    if rc != 0:
        raise RuntimeError(f"train child exit code {rc}")
    device = res["device"]
    tr = res.get("trace")
    if tr:
        device = dict(device, busy_s=tr["busy_s"], window_s=tr["window_s"])
    ctx = {"kind": "train", "dry_run": args.dry_run, "result": res, "config": config,
           "traffic": traffic, "entry": entry, "trace": tr,
           "compared": res["checks"],
           "peaks": None if args.dry_run else peaks.device_peaks(device["kind"])}
    return ctx, res["correct"], res["attempted"], res["failed"], device, (
        {"device_ops": tr["device_ops"], "idle_gaps": tr["idle_gaps"]}
        if tr else None)


# ------------------------------------------------------------------- serving
def pick_sample(records, reqs, seed, k):
    """Indices of k requests that finished, the longest among them."""
    done = [r["i"] for r in records
            if r and r["done"] and not r["done"].get("error")
            and len(r["tokens"]) == r["max_new_tokens"]]
    if not done:
        return []
    size = lambda i: len(reqs[i]["tokens"]) + len(records[i]["tokens"])
    longest = max(done, key=size)
    rest = [i for i in done if i != longest]
    random.Random(int(seed) ^ 0x5EED).shuffle(rest)
    return [longest] + rest[:k - 1]


def build_sample(sample, reqs, records, Tmax):
    """What the served-path check is handed, one entry a sampled request and
    all in the sample's order.  ``seqs``: prompt + streamed tokens,
    zero-padded to Tmax; ``spans``: [first, n], the logits at positions
    first..first+n-1 of a causal pass predict the n served tokens.  Beside
    them what the stream delivered, for a family whose check needs more than
    the tokens (families/__init__.py, ``served_stats``): ``done``, the done
    record as loadgen kept it, and ``part_n``, the tokens a streamed part."""
    out = {"seqs": [], "spans": [], "done": [], "part_n": []}
    for i in sample:
        p, o = reqs[i]["tokens"], records[i]["tokens"]
        out["seqs"].append((p + o + [0] * Tmax)[:Tmax])
        out["spans"].append([len(p) - 1, len(o)])
        out["done"].append(records[i]["done"])
        out["part_n"].append(records[i]["part_n"])
    return out


def run_serve(args, entry, config, traffic):
    env = child_env(args, entry["chips"])
    # the sizes the server runs at: the rehearsal's toy copy on the CPU
    sizes = spec.tiny(config) if args.dry_run else config
    vocab = sizes["vocab_size"]
    mix = traffic
    if args.dry_run:
        mix = dict(traffic,
                   prompt_len=dict(traffic["prompt_len"], median=24, min=8,
                                   max=64),
                   output_len=dict(traffic["output_len"], median=8, min=4,
                                   max=16))
    if args.rate:
        mix = dict(mix, arrivals=dict(mix["arrivals"], rate_per_s=args.rate))
    reqs = T.requests(mix, args.seed, args.seconds, vocab)
    with socket.socket() as sock:      # a free port for the router
        sock.bind(("127.0.0.1", 0))
        port = sock.getsockname()[1]
    router = Child("perfbench.lib.router_child", [str(port)],
                   dict(env, JAX_PLATFORMS="cpu"), stdin=True)
    server = None
    try:
        server = Child("perfbench.lib.serve_child",
                       ["--workload", args.workload, "--seed", str(args.seed),
                        "--trace", str(args.trace), "--dry",
                        str(args.dry_run), "--port", str(port),
                        "--break", args.break_, "--control",
                        str(args.control)], env, stdin=True)
        router.expect("router", 60)
        server.expect("ready", args.limit)

        def mark(name):
            server.send("mark " + name)
            server.expect("mark", 60, name)

        # warm up the one tick shape: prefill over two chunks, then decode
        rng = random.Random(int(args.seed) + 1)
        chunk = sizes["engine"]["prefill_chunk"]
        warm = loadgen.Client("127.0.0.1", port, [
            {"due": 0.0, "after": None, "max_new_tokens": 8,
             "tokens": [rng.randrange(vocab) for _ in range(chunk + 8)]}],
            timeout_s=args.limit)
        warm.run(time.time())
        warm.wait(time.time() + args.limit, lambda rs: rs[0] and rs[0]["end"])
        if not (warm.records[0] and warm.records[0]["done"]):
            raise RuntimeError(f"warm-up request failed: {warm.records[0]}")
        mark("start")
        client = loadgen.Client("127.0.0.1", port, reqs)
        t0 = time.time()
        setup_s = t0 - T0
        tracer = None
        if args.trace:
            def trace_window():
                time.sleep(traffic["trace"]["start_s"] if not args.dry_run
                           else 1.0)
                server.send("trace-start")
                time.sleep(traffic["trace"]["seconds"] if not args.dry_run
                           else 1.0)
                server.send("trace-stop")
            tracer = threading.Thread(target=trace_window, daemon=True)
            tracer.start()
        client.run(t0)
        time.sleep(max(0.0, t0 + args.seconds - time.time()))
        t1 = t0 + args.seconds
        mark("end")
        if tracer:
            tracer.join()
            server.expect("mark", 120, "trace-stop")
        due = [i for i, r in enumerate(reqs) if r["due"] is not None]
        client.wait(t1 + traffic["grace_s"], lambda rs: all(
            rs[i] and (rs[i]["part_t"] or rs[i]["end"]) for i in due))
        t_end = time.time()
        # stop: the router refuses new work, the engine gives up what is
        # still in flight after drain_timeout_s
        drain = loadgen.http_json(port, "admin/drain", {}, 90)
        client.stop()
        client.join(10)
        stopped = server.expect("stopped", 120)
        records = client.records
        sample = [] if args.skip_check else pick_sample(
            records, reqs, args.seed, traffic["check"]["sample_requests"])
        fd, path = tempfile.mkstemp(prefix="pb-sample-", suffix=".json")
        with os.fdopen(fd, "w") as f:
            json.dump(build_sample(sample, reqs, records,
                                   mix["prompt_len"]["max"]
                                   + mix["output_len"]["max"]), f)
        if os.environ.get("PB_DEBUG_DIR"):     # the builder's look at a sample
            import shutil
            os.makedirs(os.environ["PB_DEBUG_DIR"], exist_ok=True)
            shutil.copy(path, os.path.join(os.environ["PB_DEBUG_DIR"],
                                           f"sample-{args.seed}.json"))
            with open(os.path.join(os.environ["PB_DEBUG_DIR"],
                                   f"marks-{args.seed}.json"), "w") as f:
                json.dump(stopped["marks"], f)
        server.send("check " + path)
        checked = server.expect("checked", args.limit)
        os.unlink(path)
    finally:
        rcs = [c.finish(30) for c in (server, router) if c]
    if rcs[0] != 0:
        raise RuntimeError(f"serve child exit code {rcs[0]}")

    numbers = dict(checked["numbers"])
    numbers["protocol_violations"] = float(protocol_violations(records, vocab))
    if "served_gap_share" not in numbers:     # nothing finished: no verdict
        numbers["served_gap_share"] = numbers["served_gap_max"] = None
    marks = stopped["marks"]
    numbers["window_compilations"] = float(
        marks["end"]["lowerings"] - marks["start"]["lowerings"])
    rows, correct = checks.judge(numbers, traffic["check"]["limits"])
    for name, value, limit, ok in rows:
        note(check=name, value=value, limit=limit, ok=ok)
    sent = [r for r in records if r]
    failed = sum(1 for r in sent if r["error"] or r["status"] != 200)
    late = [r["sent"] - r["due"] for r in sent]
    note(requests=len(reqs), sent=len(sent), failed=failed,
         finished=sum(1 for r in sent if r["done"]),
         generator_lateness_ms={"median": 1e3 * stats.median(late),
                                "max": 1e3 * max(late)},
         hbm_peak=stopped["hbm_peak"], cache_hits=stopped["cache_hits"],
         drain=drain.get("drained"), sample=sample,
         no_first_token_at_window_end=sum(
             1 for r in sent if r["due"] < t1
             and not (r["part_t"] and r["part_t"][0] < t1)),
         ttft_s_by_due_order=[round(r["part_t"][0] - r["due"], 2)
                              if r["part_t"] else None for r in sent],
         errors=sorted({str(r["error"] or r["status"])[:120] for r in sent
                        if r["error"] or r["status"] != 200})[:5],
         collected_s=t_end - t0)
    device = checked["device"]
    tr = checked["trace"]
    if tr:
        device = dict(device, busy_s=tr["busy_s"], window_s=tr["window_s"])
    ctx = {"kind": "serve", "dry_run": args.dry_run, "records": sent, "reqs": reqs, "t0": t0, "t1": t1,
           "t_end": t_end, "seconds": args.seconds, "setup_s": setup_s,
           "marks": marks, "config": config, "traffic": traffic,
           "entry": entry, "trace": tr,
           "compared": [row[:3] for row in rows],
           "peaks": None if args.dry_run else peaks.device_peaks(device["kind"])}
    n, qs = serve_math.gap_quantiles_ms(ctx)
    print(f"perfbench: itl samples {n} quantiles_ms {qs}", flush=True)
    return ctx, correct, len(sent), failed, device, (
        {"device_ops": tr["device_ops"], "idle_gaps": tr["idle_gaps"]}
        if tr else None)


def protocol_violations(records, vocab):
    """Finished answers that are not what was asked for: another status
    than 200, another number of tokens, an id out of range, streamed parts
    that differ from the final record."""
    bad = 0
    for r in records:
        if not r or not r["done"]:
            continue
        toks = r["done"].get("tokens") or []
        if (r["status"] != 200 or r["done"].get("error")
                or len(toks) != r["max_new_tokens"]
                or any(not 0 <= t < vocab for t in toks)
                or toks != r["tokens"]):
            bad += 1
    return bad


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--dry-run", type=int, default=0)
    ap.add_argument("--limit", type=float, default=1100.0,
                    help="seconds any one wait for a child may take")
    ap.add_argument("--control", type=int, default=0,
                    help="limits only: also read the lower-precision "
                         "control on the served sample")
    ap.add_argument("--rate", type=float, default=0.0,
                    help="the knee sweep only: another arrival rate than "
                         "the cell's")
    ap.add_argument("--skip-check", type=int, default=0,
                    help="the knee sweep only: no output check, so the "
                         "line says correct: false")
    ap.add_argument("--break", dest="break_", default="",
                    help="tests only: break the timed path underneath")
    args = ap.parse_args()
    if not os.path.isdir(os.path.join(ROOT, "horovod_tpu")):
        print("perfbench: the program (horovod_tpu/) is not in this "
              "checkout", file=sys.stderr)
        return 2
    bench = spec.benchmark()
    entry, config, traffic = spec.cell(args.workload, bench)
    e2e, layer = spec.cell_metrics(args.workload, bench)
    note(workload=args.workload, seed=args.seed, seconds=args.seconds,
         trace=args.trace, dry_run=args.dry_run, kind=traffic["kind"])
    runner = {"train": run_train, "serve": run_serve}[traffic["kind"]]
    try:
        ctx, correct, attempted, failed, device, breakdown = runner(
            args, entry, config, traffic)
    except RuntimeError as e:
        print(f"perfbench: {e}", file=sys.stderr)
        return 1
    report(ctx, e2e, layer, args.trace, correct, attempted, failed, device,
           breakdown)
    return 0


T0 = time.time()

if __name__ == "__main__":
    sys.exit(main())
