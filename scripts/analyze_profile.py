#!/usr/bin/env python
"""Summarize a jax.profiler trace: where does the step time go?

The reference's perf-observability story is the Horovod timeline
(reference: horovod/common/timeline.{h,cc}) plus NVTX op ranges; this
framework emits those (utils/timeline.py, utils/profiler.py) AND the
XLA-level truth via ``jax.profiler.trace`` (``bench.py --profile DIR``).
This script turns the trace's device timeline into the table a human
needs: per-op total time, share of device-busy time, and a category
rollup (matmul / elementwise-fusion / data movement / collectives /
pallas custom calls) — the TPU analog of reading nvprof output.

Usage:
  python bench.py --profile /tmp/prof            # capture
  python scripts/analyze_profile.py /tmp/prof    # analyze
  python scripts/analyze_profile.py /tmp/prof --top 40 --csv out.csv
"""

from __future__ import annotations

import argparse
import collections
import csv
import glob
import gzip
import json
import os
import re
import sys
from typing import Optional

# category -> regexes over XLA op/fusion names (first match wins, in order)
CATEGORIES = [
    ("pallas/custom", re.compile(r"custom-call|pallas|mosaic|_attn_kernel|"
                                 r"_bwd_d(q|kv)_kernel", re.I)),
    ("collective", re.compile(r"all-reduce|all-gather|reduce-scatter|"
                              r"all-to-all|collective-permute|psum", re.I)),
    # 'convolution', not 'conv': XLA's 'convert' (dtype cast) ops must not
    # land in the matmul bucket
    ("matmul/conv", re.compile(r"dot|convolution", re.I)),
    ("data-movement", re.compile(r"copy|transpose|reshape|bitcast|"
                                 r"dynamic-slice|dynamic-update-slice|"
                                 r"gather|scatter|pad|concatenate", re.I)),
    ("infeed/outfeed", re.compile(r"infeed|outfeed|transfer", re.I)),
    ("elementwise/fusion", re.compile(r"fusion|loop|wrapped|add|multiply|"
                                      r"tanh|exp|log|select|compare|reduce",
                                      re.I)),
]


def find_trace(path: str) -> str:
    """Accept a trace .json.gz file, a profile session dir, or the DIR
    passed to ``bench.py --profile`` (newest session wins)."""
    if os.path.isfile(path):
        return path
    hits = sorted(glob.glob(
        os.path.join(path, "plugins", "profile", "*", "*.trace.json.gz")))
    hits = hits or sorted(glob.glob(os.path.join(path, "*.trace.json.gz")))
    if not hits:
        raise FileNotFoundError(
            f"no *.trace.json.gz under {path} — was the profile captured "
            "with jax.profiler.trace / bench.py --profile?")
    return hits[-1]


def load_events(trace_file: str):
    with gzip.open(trace_file, "rt") as f:
        doc = json.load(f)
    events = doc.get("traceEvents", [])
    pid_names = {e["pid"]: e["args"]["name"] for e in events
                 if e.get("ph") == "M" and e.get("name") == "process_name"
                 and "name" in e.get("args", {})}
    tid_names = {(e["pid"], e["tid"]): e["args"]["name"] for e in events
                 if e.get("ph") == "M" and e.get("name") == "thread_name"
                 and "name" in e.get("args", {})}
    return events, pid_names, tid_names


def device_pids(pid_names) -> set:
    """Device planes: TPU/GPU planes when present, else the host-CPU
    device plane (CPU-backend traces).  Python-thread planes never count."""
    dev = {p for p, n in pid_names.items()
           if "/device:" in n or n.startswith("/tpu")}
    if not dev:
        dev = {p for p, n in pid_names.items() if n.startswith("/host:")}
    return dev


_HOST_FRAME = re.compile(
    r"^(\$|end: |PjitFunction|PjRt|PyClient|ExecuteSharded|ParseArguments|"
    r"Handle inputs|CommonPjRt|ThreadpoolListener|TransferTo|CopyTo|"
    r"Tfrt\w*Executable|ThunkExecutor|SlinkyThreadPool)")
# ^ runtime-executor envelope and thread-pool wait spans (jax 0.9 CPU
#   traces) cover the op spans: counting them double-counts


def op_tids(events, pids, tid_names) -> Optional[set]:
    """Device planes carry sibling thread lines ('XLA Modules', 'Steps')
    whose envelope events span the op events — summing the whole plane
    double-counts.  Restrict to the 'XLA Ops' lines when any exist;
    return None (no tid filter) for planes without named op lines (CPU
    fallback traces)."""
    ops = {(p, t) for (p, t), n in tid_names.items()
           if p in pids and "XLA Ops" in n}
    return ops or None


def summarize(events, pids, tids=None):
    per_op = collections.defaultdict(lambda: [0.0, 0])  # name -> [us, count]
    # Span is tracked PER PLANE and summed: planes start/stop at different
    # times (e.g. a late-created device plane), and one global
    # [min ts, max ts] window times len(pids) would understate occupancy
    # on every plane that wasn't alive for the whole window.
    plane_t = {}  # pid -> [t0, t1]
    for e in events:
        if e.get("ph") != "X" or e.get("pid") not in pids:
            continue
        if tids is not None and (e["pid"], e.get("tid")) not in tids:
            continue
        # host-plane fallback (CPU traces) carries python-frame events
        # ("$file.py:123 fn") and runtime dispatch frames; only XLA
        # executable activity counts
        if _HOST_FRAME.match(e["name"]):
            continue
        dur = float(e.get("dur", 0.0))
        ts = float(e.get("ts", 0.0))
        per_op[e["name"]][0] += dur
        per_op[e["name"]][1] += 1
        w = plane_t.setdefault(e["pid"], [ts, ts + dur])
        w[0] = min(w[0], ts)
        w[1] = max(w[1], ts + dur)
    busy = sum(us for us, _ in per_op.values())
    span = sum(max(0.0, t1 - t0) for t0, t1 in plane_t.values())
    return per_op, busy, span


def categorize(name: str) -> str:
    for cat, rx in CATEGORIES:
        if rx.search(name):
            return cat
    return "other"


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("path", help="profile dir or .trace.json.gz file")
    ap.add_argument("--top", type=int, default=25)
    ap.add_argument("--csv", default=None,
                    help="also write the full per-op table as CSV")
    args = ap.parse_args()

    trace_file = find_trace(args.path)
    events, pid_names, tid_names = load_events(trace_file)
    pids = device_pids(pid_names)
    if not pids:
        print(f"no device planes in {trace_file}; planes: "
              f"{sorted(pid_names.values())}", file=sys.stderr)
        return 1
    per_op, busy_us, span_us = summarize(events, pids,
                                         op_tids(events, pids, tid_names))
    if not per_op or busy_us <= 0.0:
        print("no timed device events in trace", file=sys.stderr)
        return 1

    planes = ", ".join(sorted(pid_names[p] for p in pids))
    denom = span_us  # already summed per plane (see summarize)
    print(f"trace:  {trace_file}")
    print(f"planes: {planes}")
    print(f"device busy {busy_us / 1e3:.2f} ms over {span_us / 1e3:.2f} ms "
          f"of summed per-plane span ({100 * busy_us / denom if denom else 0:.0f}% "
          f"occupied per core)")

    cats = collections.defaultdict(float)
    for name, (us, _) in per_op.items():
        cats[categorize(name)] += us
    print("\nby category:")
    for cat, us in sorted(cats.items(), key=lambda kv: -kv[1]):
        print(f"  {cat:<20} {us / 1e3:>10.2f} ms  {100 * us / busy_us:5.1f}%")

    rows = sorted(per_op.items(), key=lambda kv: -kv[1][0])
    print(f"\ntop {min(args.top, len(rows))} ops:")
    print(f"  {'ms':>10} {'%':>6} {'count':>6}  op")
    for name, (us, cnt) in rows[:args.top]:
        print(f"  {us / 1e3:>10.2f} {100 * us / busy_us:>6.1f} {cnt:>6}  "
              f"{name[:90]}")

    if args.csv:
        with open(args.csv, "w", newline="") as f:
            w = csv.writer(f)
            w.writerow(["op", "category", "total_ms", "count"])
            for name, (us, cnt) in rows:
                w.writerow([name, categorize(name), f"{us / 1e3:.3f}", cnt])
        print(f"\nwrote {args.csv} ({len(rows)} ops)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
