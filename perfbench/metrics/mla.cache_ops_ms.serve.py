"""Device self-time a tick of the ops shaped like the latent pool or a
gather of it (writes of new positions, copies, the gather of every slot's
context: the family's ``pool_op_types``), in ms: sum over
``ctx["trace"]["ops_s"]`` / traced tick programs.  None without a trace or
such ops (a model whose cache is per-head K/V)."""
from perfbench.lib import spec


def read(ctx):
    tr = ctx["trace"]
    fam = spec.family(ctx["config"])
    if not tr or not tr.get("module_count") or not hasattr(fam, "pool_op_types"):
        return None
    types = fam.pool_op_types(ctx["config"])
    hits = {n: s for n, s in tr.get("ops_s", {}).items()
            if any(t in n for t in types)}
    if not hits:
        return None
    top = sorted(hits.items(), key=lambda kv: -kv[1])[:5]
    print("perfbench: latent pool ops ms/tick "
          + "; ".join(f"{n}={1e3 * s / tr['module_count']:.3f}"
                      for n, s in top), flush=True)
    return 1e3 * sum(hits.values()) / tr["module_count"]
