"""Device self-time a tick of the ops that move the gated delta-rule layers'
state kinds (``gdn/state``): the stacked committed states ``[layers, slots,
.., heads, dv, dk]`` that the commit's scatter returns, the ring of the rows
to replay, the positions the states stand after, and the conv inputs'
columns — told by the type an op makes and the configuration's sizes (the
family's ``state_op_group``) —, in ms: sum over ``ctx["trace"]["ops_s"]`` /
traced tick programs.  Prints the five costliest.  None without a trace,
for a family that keeps no such state, or where one of the three pools has
no op in the trace."""
from perfbench.lib import spec


def read(ctx):
    fam = spec.family(ctx["config"])
    # (another family's ``state_ops_ms`` is another metric's: this one is
    # the family's that has the gated delta rule's ``mix_share``)
    return fam.state_ops_ms(ctx) if hasattr(fam, "mix_share") else None
