"""Positions a denoising pass fixed, a block row a tick: what the engine's
``stats()["diffusion"]["tokens_fixed"]`` grew by between the window's marks
over the growth of ``slot_passes - commit_passes`` (the block rows that came
in with a position masked; horovod_tpu/serve/engine.py ``_emit_block``).  1
is the rule's floor (a pass fixes its surest position and no other), the
block's length its ceiling.  Prints the share of the fixed positions that
the threshold fixed.  None where the program counts no such thing (the
parent commit, a model that decodes a token after another) or no pass
ran."""


def window_delta(ctx):
    """{name: growth of ``stats()["diffusion"][name]`` between the window's
    marks}, or None."""
    a, b = (ctx["marks"][k]["stats"].get("diffusion")
            for k in ("start", "end"))
    return {k: b[k] - a[k] for k in b} if a and b else None


def read(ctx):
    d = window_delta(ctx)
    passes = d["slot_passes"] - d["commit_passes"] if d else 0
    if not passes:
        return None
    print(f"perfbench: denoising passes {passes} fixed {d['tokens_fixed']} "
          f"positions, {d['fixed_by_threshold'] / d['tokens_fixed']:.3f} of "
          f"them by the threshold; {d['blocks_done']} blocks filled",
          flush=True)
    return d["tokens_fixed"] / passes
