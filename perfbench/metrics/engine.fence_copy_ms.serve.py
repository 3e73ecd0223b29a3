"""The device idle behind the tokens' copy, a tick: delta between the
window's marks of ``stats()["loop"]``'s ``fence_copy_s`` (serve/engine.py
``_harvest``: from the stamp after ``block_until_ready`` to the end of the
``hvd:harvest_wait`` span, the D2H copy of the tick's tokens and counters)
over that of ``ticks``.  None where the marks lack the field."""


def read(ctx):
    a, b = (ctx["marks"][m].get("stats", {}).get("loop") or {}
            for m in ("start", "end"))
    if "fence_copy_s" not in a or "fence_copy_s" not in b:
        return None
    ticks = b["ticks"] - a["ticks"]
    if not ticks:
        return None
    ready = 1e3 * (b["fence_ready_s"] - a["fence_ready_s"]) / ticks
    print(f"perfbench: fence ms/tick ready={ready:.3f} ticks={ticks}",
          flush=True)
    return 1e3 * (b["fence_copy_s"] - a["fence_copy_s"]) / ticks
