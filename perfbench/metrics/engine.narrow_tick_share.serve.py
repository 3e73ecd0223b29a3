"""Share of the engine's ticks run at the decode width: sum over finished
requests of the done record's ``loop.narrow_ticks`` (serve/engine.py
``tick_width``, counted in ``_harvest``) over the sum of their
``loop.ticks``, in percent.  Prints the narrow and the wide ticks' wait on
the device a tick.  None where no record has the field."""


def read(ctx):
    loops = [r["done"]["loop"] for r in ctx["records"]
             if r["done"] and (r["done"].get("loop") or {}).get("ticks")
             and "narrow_ticks" in r["done"]["loop"]]
    ticks = sum(lp["ticks"] for lp in loops)
    if not ticks:
        return None
    narrow = sum(lp["narrow_ticks"] for lp in loops)
    narrow_s = sum(lp.get("narrow_wait_s", 0.0) for lp in loops)
    wide_s = sum(lp.get("phase_s", {}).get("harvest_wait", 0.0)
                 for lp in loops) - narrow_s
    wide = ticks - narrow
    print("perfbench: device wait ms/tick "
          f"narrow={1e3 * narrow_s / narrow if narrow else 0.0:.3f} "
          f"wide={1e3 * wide_s / wide if wide else 0.0:.3f} "
          f"narrow_ticks={narrow} wide_ticks={wide} requests={len(loops)}",
          flush=True)
    return 100.0 * narrow / ticks
