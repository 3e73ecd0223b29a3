"""Output tokens delivered to clients in the window over its seconds."""
from perfbench.lib import serve_math


def read(ctx):
    return serve_math.window_tokens(ctx) / ctx["seconds"]
