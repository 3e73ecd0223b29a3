"""Device self-time of the ops that the family can tell as the short
convolution's own by their types and parameters — the input projection's
``[rows, 3 d]`` product and its split, the gate-and-tap fusions, the output
projection (the family's ``mixer_share``) — over the tick program's device
time in the trace, in %.  What the family cannot tell apart it leaves out
and names, so the share errs LOW.  Prints the five costliest.  None without
a trace or such ops, or for a family that has no such operator."""
from perfbench.lib import spec


def read(ctx):
    fam = spec.family(ctx["config"])
    return fam.mixer_share(ctx) if hasattr(fam, "mixer_share") else None
