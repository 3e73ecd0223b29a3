"""Reduction of a profiler trace to what the per-layer metrics read: device
busy time (the union of the intervals in which an operation ran), idle gaps
and what the host was doing in them, collective time and the part of it no
compute hides, the operations that took most time, and named programs'
device time.  Works on plain interval lists, so the tests feed it synthetic
traces; ``load`` turns an ``.xplane.pb`` into those lists.
"""

from __future__ import annotations

import glob
import os
import re

COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|all-to-all|"
                        r"collective-permute|psum", re.I)


def short_name(hlo):
    """A trace names a device operation by its whole HLO text.  Keep the
    operation, the type of what it makes and the first parameter or
    optimizer leaf it touches, with numbering and layer index dropped, so
    that like operations of all layers add up."""
    m = re.match(r"%?([\w\-]+?)[.\d]*\s*=\s*\(?([a-z0-9]+\[[\d,]*\])", hlo)
    if not m:
        return re.sub(r"[.\d]+$", "", hlo.lstrip("%"))[:80]
    leaf = re.search(r"%((?:params|opt_state)\w*)", hlo)
    hint = ""
    if leaf:
        hint = " " + re.sub(r"_+", "_", re.sub(r"layers___\d+", "layers",
                                               leaf.group(1))).strip("_")
    return f"{m.group(1)} {m.group(2)}{hint}"[:100]


def union(intervals):
    """Sorted, merged copy of [(start, end)]."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def total(intervals):
    return sum(e - s for s, e in intervals)


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def subtract(a, b):
    """Parts of the merged intervals ``a`` that the merged ``b`` leave bare."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def gaps(busy, lo, hi):
    return subtract([(lo, hi)], busy)


def self_times(events):
    """{name: seconds} with each event's time less that of the events nested
    in it (a `while` holds its body's operations).  events: (name, s, e)."""
    out, stack = {}, []
    for name, s, e in sorted(events, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][2] <= s:
            stack.pop()
        if stack:
            out[stack[-1][0]] -= min(e, stack[-1][2]) - s
        out[name] = out.get(name, 0.0) + (e - s)
        stack.append((name, s, e))
    return out


def leaves(events):
    """Events that hold no other event."""
    ev = sorted(events, key=lambda x: (x[1], -x[2]))
    out = []
    for i, (name, s, e) in enumerate(ev):
        if i + 1 < len(ev) and ev[i + 1][1] < e and ev[i + 1][2] <= e:
            continue
        out.append((name, s, e))
    return out


def collective_intervals(ops):
    """Merged intervals during which a collective is in flight: a plain
    collective's own span, and for an asynchronous one the span from its
    -start to the end of its -done."""
    spans, open_ = [], {}
    for name, s, e in sorted(ops, key=lambda x: x[1]):
        if not COLLECTIVE.search(name):
            continue
        base = re.sub(r"[-_](start|done)", "", name)
        if re.search(r"[-_]start", name):
            open_.setdefault(base, []).append(s)
        elif re.search(r"[-_]done", name):
            spans.append((open_[base].pop(0) if open_.get(base) else s, e))
        else:
            spans.append((s, e))
    return union(spans)


def reduce_device(ops, lo, hi, host=(), async_ops=()):
    """One device's numbers over the window [lo, hi] (seconds).
    ops, host, async_ops: (name, start, end); async_ops are the spans of
    operations in flight beside the main stream (the profiler's "Async XLA
    Ops" line), of which only collectives are read."""
    ops = [(n, max(s, lo), min(e, hi)) for n, s, e in ops
           if min(e, hi) > max(s, lo)]
    busy = union([(s, e) for _, s, e in ops])
    leaf = leaves(ops)
    coll = union(collective_intervals(leaf) + clip(
        [(s, e) for n, s, e in async_ops if COLLECTIVE.search(n)], lo, hi))
    compute = union([(s, e) for n, s, e in leaf if not COLLECTIVE.search(n)])
    idle = []
    for s, e in sorted(gaps(busy, lo, hi), key=lambda g: g[0] - g[1])[:10]:
        cover = {}
        for n, hs, he in host:
            o = min(e, he) - max(s, hs)
            if o > 0:
                cover[n] = cover.get(n, 0.0) + o
        idle.append((max(cover, key=cover.get) if cover else "unattributed",
                     e - s))
    by_op = sorted(self_times(ops).items(), key=lambda kv: -kv[1])
    return {"busy_s": total(busy), "window_s": hi - lo,
            "collective_s": total(coll),
            "exposed_collective_s": total(subtract(coll, compute)),
            "collective_count": sum(1 for n, _, _ in leaf
                                    if COLLECTIVE.search(n)
                                    and not re.search(r"[-_]done", n)),
            "idle_gaps": idle,
            # every operation's self time by short name, most first, for a
            # reader that looks a kernel up by its name; the breakdown's ten
            "ops_s": dict(by_op), "device_ops": by_op[:10]}


def combine(per_device):
    """Average over the devices used; breakdown from the first."""
    n = len(per_device)
    out = {k: sum(d[k] for d in per_device) / n
           for k in ("busy_s", "window_s", "collective_s",
                     "exposed_collective_s", "collective_count")}
    out["device_ops"] = [[k, v] for k, v in per_device[0]["device_ops"]]
    out["ops_s"] = per_device[0]["ops_s"]
    out["idle_gaps"] = [[k, v] for k, v in per_device[0]["idle_gaps"]]
    return out


# ------------------------------------------------------------------ xplane
def find_xplane(trace_dir):
    hits = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                         "*", "*.xplane.pb")))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return hits[-1]


def load(trace_dir, marks=("pb:",)):
    """({device id: {"ops": [...], "modules": [...]}}, host spans whose name
    starts with one of ``marks``, outline) of a jax.profiler trace; times in
    seconds on the trace's clock."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(find_xplane(trace_dir))
    devices, host, outline = {}, [], []
    for plane in data.planes:
        m = re.match(r"/device:TPU:(\d+)$", plane.name)
        for line in plane.lines:
            events = [(ev.name, ev.start_ns * 1e-9,
                       (ev.start_ns + ev.duration_ns) * 1e-9)
                      for ev in line.events]
            if m and line.name in ("XLA Ops", "Async XLA Ops"):
                events = [(short_name(n), s, e) for n, s, e in events]
            outline.append((plane.name, line.name, len(events)))
            if m and line.name == "XLA Ops":
                devices.setdefault(int(m.group(1)), {})["ops"] = events
            elif m and line.name == "Async XLA Ops":
                devices.setdefault(int(m.group(1)), {})["async"] = events
            elif m and line.name == "XLA Modules":
                devices.setdefault(int(m.group(1)), {})["modules"] = events
            elif not m and plane.name.startswith("/host"):
                host += [e for e in events if e[0].startswith(tuple(marks))]
    return devices, host, outline


def reduce_trace(trace_dir, module=None, dry=False):
    """The combined reduction over the span the device planes cover; with
    ``module``, also the device seconds and count of the programs whose
    name contains it."""
    devices, host, outline = load(trace_dir)
    devices = {k: v for k, v in devices.items() if v.get("ops")}
    if not devices and dry:
        # the CPU rehearsal has no device plane: stand-in numbers, so that
        # the readers run; the parent prints no value from a dry run
        return {"busy_s": 0.5, "window_s": 1.0, "collective_s": 0.0,
                "exposed_collective_s": 0.0, "collective_count": 0.0,
                "device_ops": [], "ops_s": {}, "idle_gaps": [], "module_s": 0.5,
                "module_count": 1.0, "trace_lo": 0.0, "trace_hi": 1.0,
                "outline": [list(o) for o in outline if o[2]][:40]}
    if not devices:
        raise RuntimeError("the trace holds no device operation: "
                           + "; ".join(f"{p}/{l}:{n}" for p, l, n in outline))
    lo = min(s for d in devices.values() for _, s, _ in d["ops"])
    hi = max(e for d in devices.values() for _, _, e in d["ops"])
    out = combine([reduce_device(d["ops"], lo, hi, host, d.get("async", ()))
                   for _, d in sorted(devices.items())])
    out["trace_lo"], out["trace_hi"] = lo, hi
    if module:
        mods = [(s, e) for d in devices.values()
                for n, s, e in d.get("modules", ()) if module in n]
        out["module_s"] = total(mods) / len(devices)
        out["module_count"] = len(mods) / len(devices)
    out["outline"] = [list(o) for o in outline if o[2]][:40]
    if os.environ.get("PB_DEBUG_DIR"):     # the builder's look at a trace
        import json
        os.makedirs(os.environ["PB_DEBUG_DIR"], exist_ok=True)
        sample = {str(k): {ln: [list(e) for e in evs[:60]]
                           for ln, evs in d.items()}
                  for k, d in devices.items()}
        with open(os.path.join(os.environ["PB_DEBUG_DIR"],
                               f"trace-{os.getpid()}.json"), "w") as f:
            json.dump({"reduced": out, "host": [list(h) for h in host[:200]],
                       "sample": sample}, f)
    return out
