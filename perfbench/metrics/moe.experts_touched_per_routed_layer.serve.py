"""Experts with at least one token, a ROUTED layer a tick, of the layer's
``num_experts``: what the engine's ``stats()["moe"]["experts_touched"]``
(summed over the routed layers by the tick itself,
horovod_tpu/models/conv_moe.py) grew by between the window's marks, over
ticks x routed layers (the layers past ``num_dense_layers``).  Prints what
evenly routed tokens would touch at the window's mean tokens a tick.
CONTEXT, not a lever: a property of the traffic and the router that says
how much of the experts' weights a tick must read.  None where the program
counts no such thing (the parent commit) or the configuration has no such
keys."""
from perfbench.lib import spec


def read(ctx):
    fam = spec.family(ctx["config"])
    config = ctx["config"]
    d = fam.window_counts(ctx) if hasattr(fam, "window_counts") else None
    if not d or "num_dense_layers" not in config:
        return None
    per = d["ticks"] * (config["num_hidden_layers"]
                        - config["num_dense_layers"])
    touched = d["experts_touched"] / per
    tokens = d["assignments"] / (per * config["num_experts_per_tok"])
    print(f"perfbench: experts touched a routed layer a tick {touched:.3f} "
          f"of {config['num_experts']} at {tokens:.2f} valid tokens a tick; "
          f"evenly routed tokens would touch "
          f"{fam.experts_touched(config, tokens):.3f}", flush=True)
    return touched
