"""The comparisons that decide ``correct``.  Each number compared has a
limit of its own, read from the cell's traffic file (``check``), where the
readings it was set from are recorded beside it.  Pure arithmetic."""

from __future__ import annotations

import math
import statistics


def worst_leaf_gap(prog, ref):
    """Largest gap between the program's norm of a leaf and the
    reference's, against the reference's norm of that leaf or of the
    median leaf, whichever is larger.  Returns (gap, leaf)."""
    floor = statistics.median(ref.values())
    worst = max(ref, key=lambda k: abs(prog[k] - ref[k]) / max(ref[k], floor))
    return abs(prog[worst] - ref[worst]) / max(ref[worst], floor), worst


def train_numbers(prog, ref):
    """{name: value} of a training cell's compared numbers.  prog/ref:
    {"loss": [..], "mnorm": {leaf: norm after the first call}, "dnorm":
    {leaf: norm of the parameters' change}}."""
    loss = max(abs(a - b) / abs(b) for a, b in zip(prog["loss"], ref["loss"]))
    mom, mom_leaf = worst_leaf_gap(prog["mnorm"], ref["mnorm"])
    dlt, dlt_leaf = worst_leaf_gap(prog["dnorm"], ref["dnorm"])
    return ({"loss_gap": loss, "first_moment_norm_gap": mom,
             "param_change_norm_gap": dlt},
            {"first_moment_norm_gap": mom_leaf,
             "param_change_norm_gap": dlt_leaf})


GAP_COUNTS_FROM = 0.05


def serve_numbers(stats):
    """{name: value} from the teacher-forced statistics of the sampled
    served tokens (lib/reference.generated_logit_stats): the share of them
    that lie more than GAP_COUNTS_FROM standard deviations of the
    reference's logits below its best (a flip between near-ties does not;
    a lower precision does at a quarter of the positions), and the widest
    such gap (a token from a wrong block or position lies about 4 below)."""
    gaps = stats["gap"]
    return {"served_gap_share": sum(g > GAP_COUNTS_FROM for g in gaps)
            / len(gaps),
            "served_gap_max": max(gaps)}


# a serving cell's compared numbers that the harness computes itself
HARNESS_NUMBERS = ("served_gap_share", "served_gap_max",
                   "protocol_violations", "window_compilations")


def family_numbers(stats):
    """{name: value} of the compared numbers a family's own ``served_stats``
    adds under ``numbers`` (families/__init__.py); each needs a limit in the
    cell's traffic file, as ``judge`` demands.  One named as a number of the
    harness's own would be overwritten unseen, so it is refused."""
    own = dict(stats.get("numbers") or {})
    taken = sorted(set(own) & set(HARNESS_NUMBERS))
    if taken:
        raise ValueError(f"a family's served_stats may not name {taken}: "
                         "the harness computes them")
    return own


def judge(numbers, limits):
    """[(name, value, limit, ok)] and the verdict; a number without a
    finite value fails, a limit that is missing is an error."""
    rows = []
    for name, value in numbers.items():
        limit = limits[name]
        ok = value is not None and math.isfinite(value) and value <= limit
        rows.append((name, value, limit, ok))
    return rows, all(r[3] for r in rows)
