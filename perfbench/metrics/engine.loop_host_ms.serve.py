"""The loop's own host time a tick: sum over finished requests of every
phase of the done record's ``loop.phase_s`` but ``harvest_wait`` (the wait
on the device) and ``idle``, over the sum of their ``loop.ticks``.  Prints
every phase in ms a tick and what is left of ``engine.tick_ms.serve``.
None where no record has the fields."""
from perfbench.lib import spec

NOT_HOST = ("harvest_wait", "idle")


def read(ctx):
    loops = [r["done"]["loop"] for r in ctx["records"]
             if r["done"] and (r["done"].get("loop") or {}).get("ticks")
             and "phase_s" in r["done"]["loop"]]
    ticks = sum(lp["ticks"] for lp in loops)
    if not ticks:
        return None
    phases = {}
    for lp in loops:
        for name, s in lp["phase_s"].items():
            phases[name] = phases.get(name, 0.0) + 1e3 * s / ticks
    tick_ms = spec.metric_reader("engine.tick_ms.serve")(ctx)
    line = " ".join(f"{k}={v:.3f}" for k, v in sorted(phases.items()))
    left = "" if tick_ms is None else (
        f" tick_ms={tick_ms:.3f} "
        f"residual={tick_ms - sum(phases.values()):.3f}")
    print(f"perfbench: loop phases ms/tick {line}{left} ticks={ticks} "
          f"requests={len(loops)}", flush=True)
    return sum(v for k, v in phases.items() if k not in NOT_HOST)
