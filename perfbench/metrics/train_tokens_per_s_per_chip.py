"""Tokens of all optimizer steps completed in the window (fenced by
fetching their losses) over the window's seconds over the chips."""


def read(ctx):
    r = ctx["result"]
    return r["tokens"] / r["window_s"] / r["chips"]
