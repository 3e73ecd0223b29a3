"""Device self-time of the ops that are the selective scans' own — the
discretisation, the recurrence and the readout, whatever is ``[.., d_state,
d_inner]`` outside the carries' pool and the buffer of the carry after every
row that the snapshots are taken from (the family's ``scan_share``) — over
the tick program's device time in the trace, in %.  The projections, the
convolution and the gate around a scan are NOT counted.  Prints the five
costliest.  None without a trace or such ops, or for a family that scans
nothing."""
from perfbench.lib import spec


def read(ctx):
    fam = spec.family(ctx["config"])
    return fam.scan_share(ctx) if hasattr(fam, "scan_share") else None
