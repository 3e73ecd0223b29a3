"""Client-side arithmetic of a serving window (stdlib only)."""

from __future__ import annotations

from . import stats


def ttfts(ctx):
    """(seconds from due to first token for every request due in the window
    that got one, how many got none, and for those the time they had waited
    when collection ended: they sort above every finished one)."""
    got, late = [], []
    for r in ctx["records"]:
        if not ctx["t0"] <= r["due"] < ctx["t1"]:
            continue
        if r["part_t"]:
            got.append(r["part_t"][0] - r["due"])
        else:
            late.append(max(ctx["t_end"] - r["due"], max(got, default=0.0)))
    worst = max(got, default=0.0)
    return got, len(late), sorted(max(x, worst) for x in late)


def window_gaps(ctx):
    """Per-token gaps whose token arrived inside the window."""
    out = []
    for r in ctx["records"]:
        out += [g for t, g in stats.token_gaps(r["part_t"], r["part_n"])
                if ctx["t0"] <= t < ctx["t1"]]
    return out


def gap_quantiles_ms(ctx, qs=(50, 90, 95, 98, 99, 99.5, 100)):
    """(how many gaps, {quantile: ms}) of a window, for the run's notes."""
    gaps = window_gaps(ctx)
    return len(gaps), {q: round(1e3 * (stats.percentile(gaps, q) or 0), 1)
                       for q in qs}


def window_tokens(ctx):
    return sum(n for r in ctx["records"]
               for t, n in zip(r["part_t"], r["part_n"])
               if ctx["t0"] <= t < ctx["t1"])


def context_token_seconds(records, lo, hi):
    """Integral over [lo, hi] of the cached positions of the requests in
    decode: a request holds its prompt from its first token on and grows
    by what it has been sent."""
    total = 0.0
    for r in records:
        if not r["part_t"]:
            continue
        times = r["part_t"] + [r["end"] or hi]
        ctx = r["prompt_len"]
        for k in range(len(r["part_t"])):
            ctx += r["part_n"][k]
            a, b = max(times[k], lo), min(times[k + 1], hi)
            if b > a:
                total += ctx * (b - a)
    return total
