"""Device self-time a tick of the ops that make or move the state-space
layers' two STATE kinds' pools (the read of the one carry a slot, the
scatter of the carries after a tick's last rows, which returns ``[layers,
slots, columns, d_state, d_inner]``, the buffer of the carry after every row
that this scatter reads (``[1 + spec_k, slots, d_state, d_inner]``: a scan
alone would keep the last), and the conv inputs' columns: the
family's ``state_op_types`` / ``state_ops_ms``), in ms: sum over
``ctx["trace"]["ops_s"]`` / traced tick programs.  Prints the five costliest.
None without a trace or such ops, or for a family that keeps no carry."""
from perfbench.lib import spec


def read(ctx):
    fam = spec.family(ctx["config"])
    return fam.state_ops_ms(ctx) if hasattr(fam, "state_ops_ms") else None
