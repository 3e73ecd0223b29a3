"""Least seconds the chip needs for the window layers' attention in the
traced ticks — every live slot's window (K and V of the positions a query
may see) read once a layer a tick, the new tokens' score and value FLOPs
over those keys (the family's ``window_attn_required_seconds``) — over the
device self-time of its ops there (``ctx["trace"]["ops_s"]``, found by the
family's ``window_attn_op_types``: scores and probabilities by the ring's
length, the ring's gathers, and the value product's output, whose type the
global layers' share, so the share errs low).  The counts are the window's
means a tick (the window kind's ``*_ticks`` counters between the marks)
times the traced ticks: the harness snapshots no counter at the trace's
edges.  None without a trace, the counters or such ops."""
from perfbench.lib import spec


def read(ctx):
    tr = ctx["trace"]
    fam = spec.family(ctx["config"])
    if not tr or not tr.get("module_count") or not ctx["peaks"] \
            or not hasattr(fam, "ring_counts"):
        return None
    d = fam.ring_counts(ctx)
    if not d:
        return None
    types = fam.window_attn_op_types(ctx["config"])
    hits = {n: s for n, s in tr.get("ops_s", {}).items()
            if any(t in n for t in types)}
    if not hits:
        return None
    a, b = ctx["marks"]["start"], ctx["marks"]["end"]
    per_tick = tr["module_count"] / d["ticks"]
    tokens = (b["tokens_prefill"] + b["tokens_decode"]
              - a["tokens_prefill"] - a["tokens_decode"]) * per_tick
    need, bound = fam.window_attn_required_seconds(
        ctx["config"], ctx["peaks"], d["slot_ticks"] * per_tick, tokens,
        d["window_position_ticks"] * per_tick)
    top = sorted(hits.items(), key=lambda kv: -kv[1])[:5]
    print(f"perfbench: window attention roofline bound={bound} "
          f"required_s={need} ops_s={sum(hits.values())} "
          f"slot_ticks={d['slot_ticks'] * per_tick:.1f} "
          f"new_tokens={tokens:.1f} ticks={tr['module_count']}; "
          + "; ".join(f"{n}={s:.6f}" for n, s in top), flush=True)
    return 100.0 * need / sum(hits.values())
