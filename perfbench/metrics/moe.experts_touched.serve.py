"""Held experts with at least one token, a routed layer a tick: what the
engine's ``stats()["moe"]["experts_touched"]`` (summed over the expert
layers by the tick itself, horovod_tpu/models/latent_moe.py) grew by between
the window's marks, over ticks x routed layers.  Prints what the
configuration's family expects for the window's mean tokens a tick.
CONTEXT, not a lever: a property of the traffic and the router (how many
tokens a tick holds, where they are routed) that says how much of the held
experts' weights a tick must read; no optimisation of the program moves it.
None where the program counts no such thing (the parent commit)."""
from perfbench.lib import spec


def read(ctx):
    fam = spec.family(ctx["config"])
    d = fam.window_counts(ctx)
    if not d:
        return None
    per = d["ticks"] * fam.layer_kinds(ctx["config"]).count("routed")
    touched = d["experts_touched"] / per
    tokens = d["assignments"] / (per * ctx["config"]["num_experts_per_tok"])
    print(f"perfbench: experts touched a layer a tick {touched:.3f} at "
          f"{tokens:.2f} valid tokens a tick; evenly routed tokens would "
          f"touch {fam.experts_touched(ctx['config'], tokens):.3f}",
          flush=True)
    return touched
