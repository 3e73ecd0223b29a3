"""Window seconds over the engine ticks it made (delta of the engine's
``tick`` counter between the window's marks)."""


def read(ctx):
    a, b = ctx["marks"]["start"], ctx["marks"]["end"]
    ticks = b["tick"] - a["tick"]
    return 1e3 * (b["t"] - a["t"]) / ticks if ticks else None
