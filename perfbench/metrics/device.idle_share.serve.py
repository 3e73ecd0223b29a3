"""1 - union of the device-op intervals over the traced span."""


def read(ctx):
    tr = ctx["trace"]
    return 100.0 * (1 - tr["busy_s"] / tr["window_s"]) if tr else None
