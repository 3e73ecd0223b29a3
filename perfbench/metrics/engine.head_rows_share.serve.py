"""Share of a chunk-wide tick's rows that its output head ran on: delta
between the window's marks of ``stats()["loop"]``'s ``head_rows`` over that of
``packed_rows`` (serve/engine.py ``_count_wide``: summed over the WIDE ticks a
fence has seen, host arithmetic; ``packed_rows`` is ``min(max_slots *
prefill_chunk, max_batch_tokens)`` a tick, ``head_rows`` ``max_slots * (1 +
spec_k)`` where the module samples on the columns the tick reads —
``greedy_cached(.., read)`` —, every packed row where it does not), in
percent.  A narrow tick is all rows either way and is not counted.  Prints
both deltas.  None where the marks lack the fields (a program whose head runs
on every row and does not count them) or the window held no wide tick."""


def read(ctx):
    a, b = (ctx["marks"][m].get("stats", {}).get("loop") or {}
            for m in ("start", "end"))
    if "head_rows" not in a or "head_rows" not in b:
        return None
    rows = b["packed_rows"] - a["packed_rows"]
    if not rows:
        return None
    head = b["head_rows"] - a["head_rows"]
    print(f"perfbench: wide ticks' rows head={head} of {rows}", flush=True)
    return 100.0 * head / rows
