"""memory_stats()['peak_bytes_in_use'] of the fullest device, after the
window."""


def read(ctx):
    peak = ctx["result"]["device"]["memory_peak_bytes"]
    return peak / 1e9 if peak else None
