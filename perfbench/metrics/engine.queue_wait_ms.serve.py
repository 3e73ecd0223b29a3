"""90th percentile of the engine's admission wait (done record
``timing.queue``) over finished requests."""
from perfbench.lib import stats


def read(ctx):
    vals = [r["done"]["timing"]["queue"] for r in ctx["records"]
            if r["done"] and "queue" in (r["done"].get("timing") or {})]
    v = stats.percentile(vals, 90)
    return None if v is None else 1e3 * v
