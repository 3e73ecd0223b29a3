"""Least seconds the chip needs for the routed experts' work in the traced
ticks — reading each touched expert's three matrices once a layer a tick,
multiplying each held assignment's row by them (the family's
``expert_required_seconds``) — over the device self-time the experts' loops
cost there (``ctx["trace"]["ops_s"]``): the ops that are one expert's tile of
rows (inside the loops no parameter's name reaches an op's text, so they are
found by the tile's output types, the family's ``expert_op_types``, which
reads the program's tile size) PLUS the self-time of the tick's ``while``
ops, the loops' own starts and steps.  A short name does not tell an
expert's loop from the attention's loop over blocks of slots, so every
``while`` is counted and the share errs low.  The counts are the window's
means a tick (``stats()["moe"]`` between the marks) times the traced ticks:
the harness snapshots no counter at the trace's edges.  None without a
trace, the counters or such ops."""
from perfbench.lib import spec


def read(ctx):
    tr = ctx["trace"]
    fam = spec.family(ctx["config"])
    d = fam.window_counts(ctx)
    if not tr or not tr.get("module_count") or not ctx["peaks"] or not d:
        return None
    types = fam.expert_op_types(ctx["config"])
    ops = tr.get("ops_s", {})
    tiles_s = sum(s for name, s in ops.items() if any(t in name for t in types))
    if not tiles_s:
        return None
    loops_s = sum(s for name, s in ops.items() if name.startswith("while"))
    per_tick = tr["module_count"] / d["ticks"]
    touched = d["experts_touched"] * per_tick
    rows = d["assignments_held"] * per_tick
    need, bound = fam.expert_required_seconds(ctx["config"], ctx["peaks"],
                                              touched, rows)
    print(f"perfbench: expert roofline bound={bound} required_s={need} "
          f"expert_tile_ops_s={tiles_s} while_self_s={loops_s} "
          f"experts_read={touched:.1f} rows={rows:.1f} "
          f"ticks={tr['module_count']}", flush=True)
    return 100.0 * need / (tiles_s + loops_s)
