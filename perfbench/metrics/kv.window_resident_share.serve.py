"""Positions the window cache kind holds over positions a full-context cache
would hold for the same slots, in %: both summed over every tick dispatched
between the window's marks and every slot of it
(``stats()["kv_pool"]["kinds"]["window"]``: ``resident_position_ticks`` /
``full_position_ticks``; a slot holds at most its ring, the window plus one
chunk).  None where the program has no such kind or no tick ran."""
from perfbench.lib import spec


def read(ctx):
    fam = spec.family(ctx["config"])
    d = fam.ring_counts(ctx) if hasattr(fam, "ring_counts") else None
    if not d or not d["full_position_ticks"]:
        return None
    print(f"perfbench: window kind holds {d['resident_position_ticks']} of "
          f"{d['full_position_ticks']} position-ticks over {d['ticks']} "
          f"ticks, {d['slot_ticks'] / d['ticks']:.2f} slots a tick",
          flush=True)
    return 100.0 * d["resident_position_ticks"] / d["full_position_ticks"]
