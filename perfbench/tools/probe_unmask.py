"""Readings for ``unmask_margin`` and the limit of ``early_unmask_share``:
the reference's confidences at the served tokens of a SAVED sample (a run
with ``PB_DEBUG_DIR`` set keeps ``sample-SEED.json``), with no program and no
load, the reference alone on the chip.  Prints how long the check's forward
took, the served tokens' reference confidences by tenth, and for a row of
margins the share of served tokens that ``families/blockdiff_moe.early``
would call early.

  python3 perfbench/tools/probe_unmask.py SAMPLE.json SEED [--dry 1]
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("sample")
    ap.add_argument("seed", type=int)
    ap.add_argument("--cell", default="serve-moe-blockdiff-gen")
    ap.add_argument("--dry", type=int, default=0)
    args = ap.parse_args()
    if args.dry:
        os.environ["JAX_PLATFORMS"] = "cpu"
    from perfbench.lib import spec
    _, config, _ = spec.cell(args.cell)
    if args.dry:
        config = spec.tiny(config)
    fam = spec.family(config)
    with open(args.sample) as f:
        sample = json.load(f)
    import jax
    print("probe_unmask:", jax.devices()[0].device_kind, flush=True)
    for again in range(2):      # the second pass finds its programs compiled
        t = time.perf_counter()
        reads = fam.token_reads(config, args.seed, sample, "served")
        print(f"probe_unmask: token_reads {time.perf_counter() - t:.1f}s "
              f"({'warm' if again else 'cold'}), {len(reads)} served tokens, "
              f"{len(fam.states(config, sample)[1])} states", flush=True)
    g = fam.gen(config)
    print("probe_unmask: the served tokens' reference confidences, by "
          "tenth: " + str([sum(int(10 * min(r['conf'], 0.999)) == k
                               for r in reads.values()) for k in range(10)]),
          flush=True)
    for margin in (0.0, 0.02, 0.05, 0.1, 0.15, 0.2, 0.3):
        trial = dict(config, assumed=dict(config["assumed"],
                                          unmask_margin=margin))
        n = fam.early(reads.values(), trial)
        print(f"probe_unmask: margin {margin}: early {n} of {len(reads)} = "
              f"{n / len(reads):.4f} (threshold {g['tau']})", flush=True)
    flips = sum(r["flip"] for r in reads.values())
    print(f"probe_unmask: flips {flips}, gaps over 0.05: "
          f"{sum(r['gap'] > 0.05 for r in reads.values())}, max "
          f"{max(r['gap'] for r in reads.values()):.3f}", flush=True)


if __name__ == "__main__":
    main()
