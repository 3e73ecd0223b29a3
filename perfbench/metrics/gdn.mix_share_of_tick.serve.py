"""Device self-time of the ops that are the gated delta rule's own — a
verify row's one chunk a slot with the rows it replays before it
(``gdn/step``, ``gdn/replay``), the part of a prompt's chunks that is free of
the state (``gdn/chunk``) and the state passed along them (``gdn/pass``) —
over the tick programs' device time in the trace, in %.  The harness's
reduction of a trace (lib/tracered.py) keeps an op's name and the type it
makes and drops its scope, so the ops are told by that type and the model's
sizes alone (the family's ``mix_op_group``: heads, dk, dv; no chunk length
or grouping of the program's); the states' and the ring's moves are apart
(``gdn.state_ops_ms.serve``).  The projections, the convolution, the gates
and the output's norm around the recurrence are NOT counted.  The cell
traces a prompt mid-prefill, so the ticks are the WIDE program's.  Prints
the five costliest.  None without a trace, for a family that has no such
layer, or where the trace lacks one of the kinds of op the rule expects."""
from perfbench.lib import spec


def read(ctx):
    fam = spec.family(ctx["config"])
    return fam.mix_share(ctx) if hasattr(fam, "mix_share") else None
