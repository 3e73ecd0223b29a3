"""Gap between successive output tokens as the client receives them (tokens
of one streamed part share its gap equally); 99th percentile over all
gaps whose token arrived in the window.  A tenth of the gaps span a
prefill-chunk tick, so this is that tick as the client sees it, and a
time to first token holds two of them.  Recorded, not judged: in one
process of three to seven every tick of the window's first seconds takes
3 ms more (PERF.md), so its runs fall on two values."""
from perfbench.lib import serve_math, stats


def read(ctx):
    v = stats.percentile(serve_math.window_gaps(ctx), 99)
    return None if v is None else 1e3 * v
