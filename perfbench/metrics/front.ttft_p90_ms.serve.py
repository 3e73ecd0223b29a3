"""90th percentile of the time from due to first token over the requests
due in the window (the third largest of 25: recorded, not judged)."""
from perfbench.lib import serve_math, stats


def read(ctx):
    got, _, late = serve_math.ttfts(ctx)
    v = stats.percentile(got + late, 90)
    return None if v is None else 1e3 * v
