"""Bytes the state cache kind holds over bytes a key-value cache of the same
layers would hold for the same slots at their lengths, in %: both summed
over every tick dispatched between the window's marks and every slot of it
(``stats()["kv_pool"]["kinds"]["conv"]``: ``state_bytes_ticks`` /
``kv_bytes_ticks``).  CONTEXT: what a fixed state a slot saves of a cache
that grows with the context.  None where the program has no such kind (the
parent commit) or no tick ran."""
from perfbench.lib import spec


def read(ctx):
    fam = spec.family(ctx["config"])
    d = fam.state_counts(ctx) if hasattr(fam, "state_counts") else None
    if not d or not d["kv_bytes_ticks"]:
        return None
    print(f"perfbench: state kind holds {d['state_bytes_ticks']} of "
          f"{d['kv_bytes_ticks']} byte-ticks over {d['ticks']} ticks, "
          f"{d['slot_ticks'] / d['ticks']:.2f} slots a tick", flush=True)
    return 100.0 * d["state_bytes_ticks"] / d["kv_bytes_ticks"]
