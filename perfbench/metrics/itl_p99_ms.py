"""Gap between successive output tokens as the client receives them (tokens
of one streamed part share its gap equally); 99th percentile over all
gaps whose token arrived in the window."""
from perfbench.lib import serve_math, stats


def read(ctx):
    gaps = serve_math.window_gaps(ctx)
    qs = {q: round(1e3 * (stats.percentile(gaps, q) or 0), 1)
          for q in (50, 90, 95, 98, 99, 99.5, 100)}
    print(f"perfbench: itl samples {len(gaps)} quantiles_ms {qs}", flush=True)
    v = stats.percentile(gaps, 99)
    return None if v is None else 1e3 * v
