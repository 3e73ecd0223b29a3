#!/usr/bin/env python
"""Perf regression gate over bench artifacts (docs/profiling.md).

Compares bench JSON artifacts (bench.py's one printed line, or JSONL of
such lines) against a committed baseline ledger with the
median±MAD statistic in ``horovod_tpu/perf/gate.py``: a key regresses
when its current median moves in the worse direction past BOTH the
4×scaled-MAD band and the 10% relative floor — noise-tolerant, but a 2×
slowdown always trips.

Usage:
  python scripts/perf_gate.py check  --baseline PERF_BASELINE.json a.json...
  python scripts/perf_gate.py update --baseline PERF_BASELINE.json a.json...
  python scripts/perf_gate.py --smoke          # self-contained CI leg

``check`` exits 1 on any regression (improvements and keys without
baseline history pass, loudly).  ``update`` folds artifact values into
the rolling per-key windows (run it to adopt a new bench mode or refresh
the baseline after an accepted change).  ``--smoke`` is the acceptance
experiment: run ``bench.py --cpu`` three times, baseline the first two,
assert the unmodified re-run PASSES, then inject a synthetic 2×
regression (half the printed value — on the CPU that value is a token
count, so this exercises the gate, not the host's speed) and assert the
gate TRIPS (with a noise-tolerant smoke floor — see ``SMOKE_MIN_REL``).

Stdlib-only: the gate module is loaded by file path, so this script runs
in CI steps without jax importable.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_BASELINE = os.path.join(REPO, "PERF_BASELINE.json")


def _gate_mod():
    """Load horovod_tpu/perf/gate.py standalone (no package import: the
    package __init__ pulls jax, which this script must not require)."""
    mod = sys.modules.get("horovod_tpu.perf.gate")
    if mod is None:
        import importlib.util
        path = os.path.join(REPO, "horovod_tpu", "perf", "gate.py")
        spec = importlib.util.spec_from_file_location(
            "horovod_tpu.perf.gate", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        sys.modules["horovod_tpu.perf.gate"] = mod
    return mod


def _print_results(res: dict) -> None:
    for key, r in sorted(res["results"].items()):
        status = r["status"]
        if status == "no-baseline":
            print(f"  NO-BASELINE  {key}  (median "
                  f"{r['current_median']:.6g}; run `update` to adopt)")
            continue
        ratio = ("n/a" if r["ratio"] is None  # zero baseline median
                 else f"{r['ratio']:.3f}")
        print(f"  {status.upper():<12} {key}  baseline "
              f"{r['baseline_median']:.6g}±{r['baseline_mad']:.2g} -> "
              f"current {r['current_median']:.6g} "
              f"(ratio {ratio}, threshold ±{r['threshold']:.2g})")


def cmd_check(gate, args) -> int:
    doc = gate.load_baseline(args.baseline)
    artifacts = gate.load_artifacts(args.artifacts)
    if not artifacts:
        print("perf_gate: no artifacts to check", file=sys.stderr)
        return 2
    res = gate.check_artifacts(doc, artifacts, mad_k=args.mad_k,
                               min_rel_delta=args.min_rel_delta)
    _print_results(res)
    if res["failed"]:
        print("perf_gate: REGRESSION detected", file=sys.stderr)
        return 1
    print("perf_gate: pass")
    return 0


def cmd_update(gate, args) -> int:
    doc = (gate.load_baseline(args.baseline)
           if os.path.exists(args.baseline) else gate.empty_baseline())
    artifacts = gate.load_artifacts(args.artifacts)
    touched = gate.update_baseline(doc, artifacts)
    gate.save_baseline(args.baseline, doc)
    print(f"perf_gate: updated {len(touched)} key(s) in {args.baseline}")
    for key in sorted(set(touched)):
        print(f"  {key}")
    return 0


# Smoke-only relative floor: the CPU smoke bench on a loaded CI host
# shows ~15% run-to-run throughput noise (far above a quiet TPU host),
# while the injected 2x slowdown is a 50% drop — 0.25 separates the two
# deterministically.  Real gate runs keep the 10% default: their
# baselines hold rolling windows whose MAD band absorbs host noise.
SMOKE_MIN_REL = 0.25


def cmd_smoke(gate, args) -> int:
    """The self-contained acceptance experiment (CI leg): three real
    bench runs — two baseline the host's noise, the unmodified third
    must pass; a synthetic 2× slowdown of it must trip.  Exit 0 iff
    BOTH behaviors hold."""
    def run_bench() -> dict:
        cmd = [sys.executable, os.path.join(REPO, "bench.py"), "--cpu"]
        proc = subprocess.run(cmd, capture_output=True, text=True,
                              timeout=600, cwd=REPO)
        line = ""
        for ln in (proc.stdout or "").strip().splitlines():
            if ln.startswith("{"):
                line = ln
        if proc.returncode != 0 or not line:
            print(proc.stdout[-2000:], file=sys.stderr)
            print(proc.stderr[-2000:], file=sys.stderr)
            raise RuntimeError(f"bench --cpu failed rc={proc.returncode}")
        return json.loads(line)

    doc = gate.empty_baseline()
    for i in (1, 2):
        print(f"perf_gate --smoke: bench run {i} (baseline)...")
        gate.update_baseline(doc, [run_bench()])

    print("perf_gate --smoke: bench run 3 (unmodified re-run)...")
    second = run_bench()
    res = gate.check_artifacts(doc, [second], min_rel_delta=SMOKE_MIN_REL)
    _print_results(res)
    if res["failed"]:
        print("perf_gate --smoke: FAIL — unmodified re-run tripped the "
              "gate (baseline too tight for this host's noise)",
              file=sys.stderr)
        return 1

    # Injected 2× regression: the printed value halves.
    slowed = dict(second)
    slowed["value"] = float(second["value"]) / 2.0
    res2 = gate.check_artifacts(doc, [slowed], min_rel_delta=SMOKE_MIN_REL)
    _print_results(res2)
    if not res2["failed"]:
        print("perf_gate --smoke: FAIL — injected 2x slowdown did NOT "
              "trip the gate", file=sys.stderr)
        return 1
    print("perf_gate --smoke: pass (re-run clean, 2x slowdown caught)")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="median±MAD perf regression gate over bench "
                    "artifacts (docs/profiling.md)")
    ap.add_argument("--smoke", action="store_true",
                    help="self-contained CI smoke: bench twice, pass the "
                         "re-run, trip on an injected 2x slowdown")
    sub = ap.add_subparsers(dest="cmd")
    for name, fn in (("check", cmd_check), ("update", cmd_update)):
        p = sub.add_parser(name)
        p.add_argument("artifacts", nargs="+",
                       help="bench JSON artifact file(s) or JSONL sweeps")
        p.add_argument("--baseline", default=DEFAULT_BASELINE,
                       help=f"baseline ledger (default {DEFAULT_BASELINE})")
        p.add_argument("--mad-k", type=float, default=4.0)
        p.add_argument("--min-rel-delta", type=float, default=0.10)
        p.set_defaults(fn=fn)
    args = ap.parse_args(argv)
    gate = _gate_mod()
    if args.smoke:
        return cmd_smoke(gate, args)
    if not getattr(args, "cmd", None):
        ap.print_help()
        return 2
    return args.fn(gate, args)


if __name__ == "__main__":
    sys.exit(main())
