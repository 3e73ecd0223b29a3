"""Median over finished requests of the client's time to first token less
the engine's own queue and prefill durations (done record ``timing``): what
router, KV, stream and HTTP add."""
from perfbench.lib import stats


def read(ctx):
    vals = []
    for r in ctx["records"]:
        t = (r["done"] or {}).get("timing") or {}
        if r["part_t"] and "queue" in t and "prefill" in t:
            vals.append(r["part_t"][0] - r["sent"] - t["queue"] - t["prefill"])
    return 1e3 * stats.median(vals) if vals else None
