"""Median over finished requests of the done record's ``timing.publish``:
the request's first token sampled to its first part handed to the stream
(serve/worker.py ``_publish_report``).  None where no record has it."""
from perfbench.lib import stats


def read(ctx):
    vals = [r["done"]["timing"]["publish"] for r in ctx["records"]
            if r["done"] and "publish" in (r["done"].get("timing") or {})]
    return 1e3 * stats.median(vals) if vals else None
