"""The builder's look inside one traced run of a cell (never the driver's):
the ten longest device gaps named by the PROGRAM's own phase (its ``hvd:``
spans, utils/profiler.PhaseClock), and the device seconds of a step or a
tick grouped by the program's named scopes (docs/profiling.md#scopes).

  python3 perfbench/tools/phase_table.py CELL [--seed N] [--seconds S] [--dry 1] [--trace 0]

The harness deletes a run's trace once it has reduced it, so this tool
starts the run itself (perfbench/run.py's own path, ``--trace 1``) with the
cell's child wrapped: the wrapper moves the trace aside where the child
would delete it.  The run compiles into a compile cache of its own (see
``main``), so it starts cold.  The tables go to stdout and to
``chiprun_out/phase_table/<cell>.json``; the kept trace is deleted.
"""

import argparse
import json
import os
import re
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

ME = "perfbench.tools.phase_table"
# innermost first: an op under tick/model/attn/kv_gather is the gather's
SCOPES = ("kv_gather", "kv_write", "grad_sync", "tick/sample",
          "tick/copy_blocks", "attn", "ffn", "head", "embed", "optimizer",
          "tick/model")
SCOPE_RE = re.compile(r"(?<![\w])(" + "|".join(SCOPES) + r")(?![\w])")


# ------------------------------------------------------------ child mode
def child(argv):
    """``python -m perfbench.tools.phase_table --as-child MODULE KEEP ...``:
    the cell's own child, with the trace moved to KEEP instead of deleted."""
    import importlib
    module, keep, rest = argv[0], argv[1], argv[2:]
    rmtree = shutil.rmtree

    def keep_trace(path, *a, **k):
        if os.path.basename(str(path)).startswith("pb-trace-") and \
                not os.path.exists(keep):
            return shutil.move(path, keep)
        return rmtree(path, *a, **k)
    shutil.rmtree = keep_trace
    return importlib.import_module(module).main(rest)


# ----------------------------------------------------------- the tables
def scope_of(path):
    """The table's row for one device op's scope path (its ``tf_op``)."""
    hits = SCOPE_RE.findall(path)
    for s in SCOPES:                  # innermost known scope wins
        if s in hits:
            m = re.search(r"grad_sync/(bucket\d+)", path)
            return f"grad_sync/{m.group(1)}" if m and s == "grad_sync" else s
    return "(no scope)"


def device_lines(trace_dir):
    """{device id: {line name: [(HLO name, scope path, start s, end s)]}}
    from the raw XSpace: the scope path of an op is the ``tf_op`` stat of
    its event METADATA, which jax's ProfileData does not hand out."""
    from tensorflow.tsl.profiler.protobuf import xplane_pb2
    from perfbench.lib import tracered
    space = xplane_pb2.XSpace()
    with open(tracered.find_xplane(trace_dir), "rb") as f:
        space.ParseFromString(f.read())
    out = {}
    for plane in space.planes:
        m = re.match(r"/device:TPU:(\d+)$", plane.name)
        if not m:
            continue
        tf_op = next((k for k, v in plane.stat_metadata.items()
                      if v.name == "tf_op"), None)
        paths = {}
        for k, md in plane.event_metadata.items():
            st = next((x for x in md.stats if x.metadata_id == tf_op), None)
            paths[k] = "" if st is None else (
                st.str_value or plane.stat_metadata[st.ref_value].name)
        for line in plane.lines:
            t0 = line.timestamp_ns * 1e-9
            out.setdefault(int(m.group(1)), {})[line.name] = [
                (plane.event_metadata[ev.metadata_id].name,
                 paths.get(ev.metadata_id, ""), t0 + ev.offset_ps * 1e-12,
                 t0 + (ev.offset_ps + ev.duration_ps) * 1e-12)
                for ev in line.events]
    return out


def tables(trace_dir):
    from perfbench.lib import tracered
    devices, host, _ = tracered.load(trace_dir, marks=("hvd:",))
    devices = {k: v for k, v in devices.items() if v.get("ops")}
    out = {"hvd_spans": {}, "gaps": [], "devices": {}}
    for name, s, e in host:
        row = out["hvd_spans"].setdefault(name, {"n": 0, "s": 0.0})
        row["n"] += 1
        row["s"] += e - s
    if devices:
        first = devices[min(devices)]
        lo = min(s for _, s, _ in first["ops"])
        hi = max(e for _, _, e in first["ops"])
        # the loop's phases nest in nothing, so the phase that covers most
        # of a gap is what the host was doing in it
        out["gaps"] = [[n, 1e3 * t] for n, t in tracered.reduce_device(
            first["ops"], lo, hi, host)["idle_gaps"]]
    for dev, lines in sorted(device_lines(trace_dir).items()):
        if not lines.get("XLA Ops") or not lines.get("XLA Modules"):
            continue
        mods = {}
        for name, _, s, e in lines["XLA Modules"]:
            mods.setdefault(re.sub(r"\(\d+\)$", "", name), []).append((s, e))
        # the step / the tick: the program that holds most device time
        prog = max(mods, key=lambda n: sum(e - s for s, e in mods[n]))
        runs = mods[prog]
        inside, outside, sample, path_of = [], [], None, {}
        for name, path, s, e in lines["XLA Ops"]:
            if any(a <= s and e <= b for a, b in runs):
                inside.append(((scope_of(path), name), s, e))
                path_of[name] = path
                sample = sample or (path and {"name": name[:120],
                                              "tf_op": path})
            else:
                outside.append((tracered.short_name(name), s, e))
        leaf = tracered.leaves(inside)
        by, bare = {}, {}
        for (label, name), s, e in leaf:
            row = by.setdefault(label, {"ms": 0.0, "ops": 0})
            row["ms"] += 1e3 * (e - s) / len(runs)
            row["ops"] += 1
            if label == "(no scope)":
                key = f"{tracered.short_name(name)} <{path_of[name]}>"
                bare[key] = bare.get(key, 0.0) + 1e3 * (e - s) / len(runs)
        total = sum(r["ms"] for r in by.values())
        other = {}
        for name, s, e in tracered.leaves(outside):
            other[name] = other.get(name, 0.0) + 1e3 * (e - s) / len(runs)
        out["devices"][str(dev)] = {
            "program": prog, "runs": len(runs),
            "program_ms": 1e3 * sum(e - s for s, e in runs) / len(runs),
            "leaf_ops_per_run": len(leaf) / len(runs),
            "distinct_leaf_ops": len({k for k, _, _ in leaf}),
            "first_scoped_op": sample,
            "largest_without_scope_ms": dict(sorted(
                bare.items(), key=lambda kv: -kv[1])[:8]),
            "other_programs_ms_per_run": {
                re.sub(r"\(\d+\)$", "", n): 1e3 * sum(e - s for s, e in v)
                / len(runs) for n, v in mods.items() if n != prog},
            "ops_outside_any_run_of_it_ms": dict(sorted(
                other.items(), key=lambda kv: -kv[1])[:8]),
            "by_scope": {k: dict(v, share=100.0 * v["ms"] / total,
                                 ops=v["ops"] / len(runs))
                         for k, v in sorted(by.items(),
                                            key=lambda kv: -kv[1]["ms"])}}
    return out


def show(cell, t):
    print(f"phase_table: {cell}: hvd: spans in the trace "
          + json.dumps({k: {"n": v["n"], "ms_each": 1e3 * v["s"] / v["n"]}
                        for k, v in sorted(t["hvd_spans"].items())}))
    print(f"phase_table: {cell}: ten longest device gaps (ms) by the "
          f"program's phase: {json.dumps(t['gaps'])}")
    for dev, d in sorted(t["devices"].items()):
        print(f"phase_table: {cell}: device {dev}: program {d['program']} "
              f"x{d['runs']}, {d['program_ms']:.3f} ms a run, "
              f"{d['leaf_ops_per_run']:.1f} leaf ops a run "
              f"({d['distinct_leaf_ops']} distinct); other programs ms a "
              f"run {json.dumps(d['other_programs_ms_per_run'])}; ops "
              f"outside it {json.dumps(d['ops_outside_any_run_of_it_ms'])}")
        for label, row in d["by_scope"].items():
            print(f"phase_table:   {label:22s} {row['ms']:9.3f} ms "
                  f"{row['share']:5.1f}%  {row['ops']:7.1f} ops")
        print("phase_table:   largest ops without a scope (ms a run, "
              f"<tf_op>): {json.dumps(d['largest_without_scope_ms'])}")
        if dev == min(t["devices"]):
            print("phase_table:   the scope path is the tf_op stat of the "
                  f"event metadata, e.g. {json.dumps(d['first_scoped_op'])}")


# ----------------------------------------------------------- parent mode
def untraced(R, args):
    """The harness reports per-layer metrics in traced runs only; the
    program's clocks run always.  So: the cell's own untraced run, with
    the readers that print the phase table and the TTFT split called on
    its records before the end-to-end line is written."""
    report = R.report

    def with_phases(ctx, *a, **k):
        if ctx["kind"] == "serve":
            for name in ("engine.loop_host_ms.serve", "front.pickup_ms.serve",
                         "engine.prefill_ticks.serve"):
                R.spec.metric_reader(name)(ctx)
        return report(ctx, *a, **k)
    R.report = with_phases
    sys.argv = [R.__file__, "--workload", args.cell, "--seed",
                str(args.seed), "--seconds", str(args.seconds), "--trace",
                "0", "--dry-run", str(args.dry)]
    return R.main()


def main():
    if len(sys.argv) > 1 and sys.argv[1] == "--as-child":
        return child(sys.argv[2:])
    ap = argparse.ArgumentParser()
    ap.add_argument("cell")
    ap.add_argument("--seed", type=int, default=2400000001)
    ap.add_argument("--seconds", type=float, default=45.0)
    ap.add_argument("--dry", type=int, default=0)
    ap.add_argument("--trace", type=int, default=1,
                    help="0: an untraced run of a serving cell, read by the "
                         "program's own clocks alone (the done records' "
                         "loop and timing), beside its end-to-end line")
    args = ap.parse_args()
    sys.path.insert(0, os.path.join(ROOT, "perfbench"))
    import run as R
    if not args.trace:
        return untraced(R, args)
    tmp = tempfile.mkdtemp(prefix="pb-phase-table-")
    keep = os.path.join(tmp, "trace")
    # jax's persistent compile cache keys a program with its debug info
    # stripped: a cache warmed by another tree hands back that tree's op
    # names.  Compile this tree's programs anew, so the scopes are its own.
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(tmp, "jax-cache")

    class KeepTrace(R.Child):
        def __init__(self, module, argv, env, stdin=False):
            if module in ("perfbench.lib.serve_child",
                          "perfbench.lib.train_child"):
                module, argv = ME, ["--as-child", module, keep] + argv
            super().__init__(module, argv, env, stdin=stdin)
    R.Child = KeepTrace
    sys.argv = [R.__file__, "--workload", args.cell, "--seed",
                str(args.seed), "--seconds", str(args.seconds), "--trace",
                "1", "--dry-run", str(args.dry)]
    try:
        rc = R.main()
        if rc or not os.path.isdir(keep):
            print(f"phase_table: no trace kept (run exit code {rc})",
                  file=sys.stderr)
            return rc or 1
        t = tables(keep)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    show(args.cell, t)
    os.makedirs(os.path.join(ROOT, "chiprun_out", "phase_table"),
                exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "phase_table",
                           args.cell + ".json"), "w") as f:
        json.dump(t, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
