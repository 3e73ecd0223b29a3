"""Process start to the first measured step or the first request due:
start-up, weights, warm-up and compilation.  The plain reference's time is
not part of it."""


def read(ctx):
    return ctx["result"]["setup_s"] if ctx["kind"] == "train" else ctx["setup_s"]
