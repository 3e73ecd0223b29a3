"""Device time during which a collective was in flight, per step, from the
trace (averaged over the chips)."""


def read(ctx):
    tr = ctx["trace"]
    return 1e3 * tr["collective_s"] / tr["steps"] if tr else None
