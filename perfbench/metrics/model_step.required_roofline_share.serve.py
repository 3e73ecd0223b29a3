"""Least time the chip needs for what the traced ticks were asked to do
(lib/peaks.serve_required_seconds: new tokens through every matmul, weights
read once a tick, KV of the live contexts) over the tick program's device
time in the trace.  Prints which bound binds."""
from perfbench.lib import peaks, serve_math


def read(ctx):
    tr = ctx["trace"]
    if not tr or not tr.get("module_count") or not ctx["peaks"]:
        return None
    lo, hi = tr["t0"], tr["t1"]
    toks = sum(n for r in ctx["records"]
               for t, n in zip(r["part_t"], r["part_n"]) if lo <= t < hi)
    # prompts prefilled in the traced span: those whose first token fell in it
    toks += sum(r["prompt_len"] for r in ctx["records"]
                if r["part_t"] and lo <= r["part_t"][0] < hi)
    ticks = tr["module_count"]
    ctx_tokens = (serve_math.context_token_seconds(ctx["records"], lo, hi)
                  / (hi - lo) * ticks)
    need, bound = peaks.serve_required_seconds(
        ctx["config"], ctx["peaks"], toks, ctx_tokens, ticks)
    print(f"perfbench: roofline bound={bound} required_s={need} "
          f"tick_device_s={tr['module_s']} ticks={ticks}", flush=True)
    return 100.0 * need / tr["module_s"]
