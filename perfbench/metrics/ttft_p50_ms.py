"""Time from the instant a request was due to its first streamed token at
the client; median over the requests due in the window.  One that failed,
was refused or had no first token when collection ended is placed above
every finished one, at the time waited.  The median and not a tail: the
cell sends 25 requests, and a 90th percentile over 25 is the third largest
(its runs spread by 13%, PERF.md); the tail stands beside it as
front.ttft_p90_ms.serve."""
from perfbench.lib import serve_math, stats


def read(ctx):
    got, missing, late = serve_math.ttfts(ctx)
    qs = {q: round(1e3 * (stats.percentile(got + late, q) or 0), 1)
          for q in (50, 75, 90, 100)}
    print(f"perfbench: ttft samples {len(got)} missing {missing} "
          f"quantiles_ms {qs}", flush=True)
    v = stats.percentile(got + late, 50)
    return None if v is None else 1e3 * v
