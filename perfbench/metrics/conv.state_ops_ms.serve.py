"""Device self-time a tick of the ops that make or move the conv layers'
STATE kind's pool (the scatter of a tick's ``u`` into ``[conv layers, slots,
columns, d]``, which returns the pool, and the gathers of the columns a row
reads back: the family's ``pool_op_types(config, "conv")`` /
``pool_ops_ms``), in ms: sum over ``ctx["trace"]["ops_s"]`` / traced tick
programs.  Prints the five costliest.  None without a trace or such ops, or
for a family that has no such kind."""
from perfbench.lib import spec


def read(ctx):
    fam = spec.family(ctx["config"])
    # ``state_counts`` marks a family with a state kind: another family's
    # ``pool_ops_ms`` knows other kinds
    return fam.pool_ops_ms(ctx, "conv") if hasattr(fam, "state_counts") \
        else None
