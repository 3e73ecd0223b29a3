"""The one general generator of serving traffic.  A mix is a data file of
parameters.  The file and the window's length fix the whole schedule: which
(prompt, output) lengths arrive when.  The run's seed chooses the token ids
and nothing else, so that two runs differ in content and never in work: with
25 requests in a window, another order alone moved the tokens delivered by
12% (PERF.md, PR 23).

Mix keys (``traffic/<name>.json``, kind "serve"):
  arrivals      {"rate_per_s": r}  open loop; gaps are the stratified
                quantiles of an exponential with mean 1/r, shuffled
  prompt_len, output_len
                {"median", "sigma", "min", "max"}  log-normal, clipped;
                stratified quantiles, paired by a shuffle fixed in the file
  pairing_seed  fixes that pairing
  schedule_seed fixes the order of the requests and of the gaps
  shared_prefix optional {"tokens": n, "groups": g}: each request starts
                with one of g seeded prefixes of n tokens (inside its
                prompt length)
  sessions      optional {"turns": [lo, hi], "think_s": t}: requests are
                grouped into sessions; a turn is sent ``think_s`` after
                the turn before it finished, and its prompt is that
                turn's prompt, its served answer and the new tokens
"""

from __future__ import annotations

import math
import random
from statistics import NormalDist


def _lognormal_quantiles(spec, n):
    mu, nd = math.log(spec["median"]), NormalDist()
    out = []
    for k in range(n):
        x = math.exp(mu + spec["sigma"] * nd.inv_cdf((k + 0.5) / n))
        out.append(int(min(spec["max"], max(spec["min"], round(x)))))
    return out


def lengths(mix, n):
    """The fixed multiset of (prompt, output) lengths of n requests."""
    prompts = _lognormal_quantiles(mix["prompt_len"], n)
    outputs = _lognormal_quantiles(mix["output_len"], n)
    random.Random(mix.get("pairing_seed", 0)).shuffle(outputs)
    return list(zip(prompts, outputs))


def gaps(rate, n):
    """The fixed multiset of gaps between arrivals; sums to about n/rate."""
    return [-math.log(1.0 - (k + 0.5) / n) / rate for k in range(n)]


def requests(mix, seed, seconds, vocab):
    """[{"due", "tokens", "max_new_tokens", "after", "think_s", "carry"}]
    in order of arrival.  ``due`` is seconds from the window's start (None
    for a turn that waits for turn ``after``)."""
    rate = mix["arrivals"]["rate_per_s"]
    n = max(1, round(rate * seconds))
    order = random.Random(mix.get("schedule_seed", 0))
    rng = random.Random(int(seed))
    pairs = lengths(mix, n)
    order.shuffle(pairs)
    gs = gaps(rate, n)
    order.shuffle(gs)
    # the first request is due half a gap in, so that the sum of the gaps
    # leaves the last one inside the window
    shift = gs[0] / 2.0
    prefix = mix.get("shared_prefix")
    prefixes = ([[rng.randrange(vocab) for _ in range(prefix["tokens"])]
                 for _ in range(prefix["groups"])] if prefix else [])
    out, t = [], -shift
    for (p, o), g in zip(pairs, gs):
        t += g
        head = prefixes[rng.randrange(len(prefixes))][:p] if prefixes else []
        toks = head + [rng.randrange(vocab) for _ in range(p - len(head))]
        out.append({"due": min(t, seconds * (1 - 1e-9)), "tokens": toks,
                    "max_new_tokens": o, "after": None})
    sess = mix.get("sessions")
    if sess:
        i = 0
        while i < len(out):
            turns = order.randint(*sess["turns"])
            for j in range(i + 1, min(i + turns, len(out))):
                out[j].update(due=None, after=j - 1,
                              think_s=sess["think_s"])
            i += turns
    return out
