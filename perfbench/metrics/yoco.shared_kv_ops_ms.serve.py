"""Device self-time a tick of the ops that fetch the ONE full attention
layer's pool, which that layer writes and it and every cross layer read (a
tile's gather by table, the new positions' scatter: the family's
``shared_kv_op_types`` / ``shared_kv_ops_ms``), in ms: sum over
``ctx["trace"]["ops_s"]`` / traced tick programs.  Eight layers fetch the
same blocks a tick: what a later change can save is in this number.  Prints
the five costliest.  None without a trace or such ops, or for a family whose
layers each keep their own keys."""
from perfbench.lib import spec


def read(ctx):
    fam = spec.family(ctx["config"])
    return fam.shared_kv_ops_ms(ctx) if hasattr(fam, "shared_kv_ops_ms") \
        else None
