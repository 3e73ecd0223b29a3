"""Median over finished requests of the done record's ``timing.pickup``:
the router's stamp on the request to the instant the serving loop hands it
to the engine (serve/worker.py ``_submit``).  Prints the split of the
client's time to first token into the program's hops, each a median over
the same requests, ``rest`` taken request by request.  None where no
record has the field."""
from perfbench.lib import stats

HOPS = ("pickup", "queue", "prefill", "publish")


def read(ctx):
    rows = []
    for r in ctx["records"]:
        t = (r["done"] or {}).get("timing") or {}
        if "pickup" not in t:
            continue
        row = {h: 1e3 * t.get(h, 0.0) for h in HOPS}
        if r.get("part_t"):
            row["client_ttft"] = 1e3 * (r["part_t"][0] - r["sent"])
            row["rest"] = row["client_ttft"] - sum(row[h] for h in HOPS)
        rows.append(row)
    if not rows:
        return None
    split = {k: stats.median([row[k] for row in rows if k in row])
             for k in ("client_ttft",) + HOPS + ("rest",)}
    print("perfbench: ttft split ms (medians) " + " ".join(
        f"{k}={v:.2f}" for k, v in split.items() if v is not None)
        + f" requests={len(rows)}", flush=True)
    return split["pickup"]
