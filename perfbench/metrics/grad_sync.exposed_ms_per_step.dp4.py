"""The part of the collectives' time during which no compute operation ran
on that device, per step."""


def read(ctx):
    tr = ctx["trace"]
    return 1e3 * tr["exposed_collective_s"] / tr["steps"] if tr else None
