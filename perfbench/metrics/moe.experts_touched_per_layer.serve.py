"""Experts with at least one token, a layer a tick, of the layer's 64: what
the engine's ``stats()["moe"]["experts_touched"]`` (summed over the layers
by the tick itself, horovod_tpu/models/swa_moe.py) grew by between the
window's marks, over ticks x layers.  Prints what evenly routed tokens would
touch at the window's mean tokens a tick.  CONTEXT, not a lever, as
``moe.experts_touched.serve`` says of itself: a property of the traffic and
the router that says how much of the experts' weights a tick must read.
None where the program counts no such thing (the parent commit)."""
from perfbench.lib import spec


def read(ctx):
    fam = spec.family(ctx["config"])
    d = fam.window_counts(ctx) if hasattr(fam, "window_counts") else None
    if not d:
        return None
    config = ctx["config"]
    per = d["ticks"] * config["num_hidden_layers"]
    touched = d["experts_touched"] / per
    tokens = d["assignments"] / (
        per * config["moe_num_active_primary_experts"])
    print(f"perfbench: experts touched a layer a tick {touched:.3f} of "
          f"{config['moe_num_primary_experts']} at {tokens:.2f} valid tokens "
          f"a tick; evenly routed tokens would touch "
          f"{fam.experts_touched(config, tokens):.3f}", flush=True)
    return touched
