"""Device self-time a tick of the ops shaped like the WINDOW kind's pool or a
gather of it (the new positions' scatter into the rings, the gather of every
slot's ring: the family's ``pool_op_types(config, "window")``), in ms: sum
over ``ctx["trace"]["ops_s"]`` / traced tick programs.  Prints the five
costliest.  None without a trace or such ops, or for a family whose cache
is of one kind."""
from perfbench.lib import spec


def read(ctx):
    fam = spec.family(ctx["config"])
    return fam.pool_ops_ms(ctx, "window") if hasattr(fam, "pool_ops_ms") \
        else None
