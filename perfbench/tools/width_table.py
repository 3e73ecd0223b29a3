"""phase_table.py for a cell whose tick runs one program at several widths
(serve/engine.py ``tick_width``): the same traced run and tables, and
beside them the device time by named scope of EACH compiled program apart.
phase_table.py adds up all programs of one name, so its table of the tick
is a blend of the chunk-wide and the decode-wide program; the profiler
names them ``jit_step_fn(<id>)`` with an id each.

  python3 perfbench/tools/width_table.py CELL [phase_table.py's options]

The builder's tool, never the driver's.  Output as phase_table.py's, with a
``programs`` table more, in ``chiprun_out/phase_table/<cell>.json`` too.
"""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench.tools import phase_table as PT  # noqa: E402

TICK = "step_fn"        # serve_child.TICK_MODULE


def programs(lines):
    """{program name with its id: {"runs", "program_ms", "by_scope",
    "largest_without_scope_ms"}} of one device's lines
    (phase_table.device_lines), whole runs only: a run cut at the trace's
    edge has lost ops and would shorten the mean."""
    from perfbench.lib import tracered
    ops = lines.get("XLA Ops") or []
    lo = min((s for _, _, s, _ in ops), default=0.0)
    hi = max((e for _, _, _, e in ops), default=0.0)
    mods = {}
    for name, _, s, e in lines.get("XLA Modules") or []:
        if TICK in name and s > lo and e < hi:
            mods.setdefault(name, []).append((s, e))
    out = {}
    for prog, runs in mods.items():
        leaf = tracered.leaves(
            [((PT.scope_of(path), tracered.short_name(name)), s, e)
             for name, path, s, e in ops
             if any(a <= s and e <= b for a, b in runs)])
        by, bare = {}, {}
        for (label, name), s, e in leaf:
            row = by.setdefault(label, {"ms": 0.0, "ops": 0.0})
            row["ms"] += 1e3 * (e - s) / len(runs)
            row["ops"] += 1.0 / len(runs)
            if label == "(no scope)":
                bare[name] = bare.get(name, 0.0) + 1e3 * (e - s) / len(runs)
        out[prog] = {
            "runs": len(runs),
            "program_ms": 1e3 * sum(e - s for s, e in runs) / len(runs),
            "by_scope": dict(sorted(by.items(), key=lambda kv: -kv[1]["ms"])),
            "largest_without_scope_ms": dict(sorted(
                bare.items(), key=lambda kv: -kv[1])[:6])}
    return dict(sorted(out.items(), key=lambda kv: kv[1]["program_ms"]))


def tables(trace_dir, _tables=PT.tables):
    t = _tables(trace_dir)
    t["programs"] = {str(dev): programs(lines) for dev, lines in
                     sorted(PT.device_lines(trace_dir).items())}
    return t


def show(cell, t, _show=PT.show):
    _show(cell, t)

    def ms(p, scope):
        return p["by_scope"].get(scope, {}).get("ms", 0.0)
    for dev, progs in t["programs"].items():
        scopes = sorted({s for p in progs.values() for s in p["by_scope"]},
                        key=lambda s: -max(ms(p, s) for p in progs.values()))
        print(f"width_table: {cell}: device {dev}: whole runs of each "
              "program, ms a run by scope: "
              + "; ".join(f"{name} x{p['runs']} {p['program_ms']:.3f} ms"
                          for name, p in progs.items()))
        for s in scopes:
            print(f"width_table:   {s:22s} " + " ".join(
                f"{ms(p, s):9.3f}" for p in progs.values()))
        for name, p in progs.items():
            print(f"width_table:   {name} largest ops without a scope (ms a "
                  f"run): {json.dumps(p['largest_without_scope_ms'])}")


if __name__ == "__main__":
    PT.tables, PT.show = tables, show
    sys.exit(PT.main())
