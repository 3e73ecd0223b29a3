"""The busiest held expert's assignments over the mean held expert's, a
routed layer a tick: what ``stats()["moe"]["load_max"]`` (the largest count
on one held expert, summed over layers and ticks) grew by in the window over
the growth of ``assignments_held`` / experts held.  1 is even; the expert
loop's longest tile run follows the numerator.  CONTEXT, not a lever: the
router's skew on this traffic, which no optimisation of the program moves.
None where the program counts no such thing, or nothing was routed here."""
from perfbench.lib import spec


def read(ctx):
    d = spec.family(ctx["config"]).window_counts(ctx)
    if not d or not d["assignments_held"]:
        return None
    return (d["load_max"] * ctx["config"]["n_routed_experts"]
            / d["assignments_held"])
