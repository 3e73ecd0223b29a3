"""Device self-time a tick of the ops shaped like the GLOBAL kind's pool or a
gather of it (the new positions' scatter, the gather of every slot's whole
context, ``max_seq_len`` positions a slot whatever it holds: the family's
``pool_op_types(config, "global")``), in ms: sum over
``ctx["trace"]["ops_s"]`` / traced tick programs.  Prints the five costliest.
None without a trace or such ops, or for a family whose cache is of one
kind."""
from perfbench.lib import spec


def read(ctx):
    fam = spec.family(ctx["config"])
    return fam.pool_ops_ms(ctx, "global") if hasattr(fam, "pool_ops_ms") \
        else None
