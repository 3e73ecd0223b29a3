"""Median over finished requests of the done record's ``loop.prefill_ticks``:
engine ticks harvested from the request's submit up to and including the
one that gave its first token.  Prints how many requests took how many.
None where no record has it."""
import collections

from perfbench.lib import stats


def read(ctx):
    vals = [r["done"]["loop"]["prefill_ticks"] for r in ctx["records"]
            if r["done"] and (r["done"].get("loop") or {}).get(
                "prefill_ticks") is not None]
    if not vals:
        return None
    print("perfbench: prefill_ticks -> requests "
          f"{dict(sorted(collections.Counter(vals).items()))}", flush=True)
    return float(stats.median(vals))
