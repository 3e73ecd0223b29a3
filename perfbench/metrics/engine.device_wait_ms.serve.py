"""The loop's wait on the device a tick: sum over finished requests of the
done record's ``loop.phase_s.harvest_wait`` (the D2H fence of
serve/engine.py ``_harvest``) over the sum of their ``loop.ticks``.  None
where no record has the fields."""


def read(ctx):
    loops = [r["done"]["loop"] for r in ctx["records"]
             if r["done"] and (r["done"].get("loop") or {}).get("ticks")
             and "phase_s" in r["done"]["loop"]]
    ticks = sum(lp["ticks"] for lp in loops)
    if not ticks:
        return None
    return 1e3 * sum(lp["phase_s"].get("harvest_wait", 0.0)
                     for lp in loops) / ticks
