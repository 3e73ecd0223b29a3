"""Readings for the limits of a training cell, many seeds in one process
on the chip (set-up is long): the program's numbers against the reference
("sound"), and the control's: the reference computed in int8 (W8A8), the
nearest precision below the configuration's bfloat16, put in the program's
place.  The benchmark's own runs never run this.

  python3 perfbench/tools/control.py CELL --sound 101,102 --control 201,202 [--dry 1]

A serving cell's control runs inside a short run of its own load:
  python3 perfbench/run.py --workload CELL --seed N --seconds S --trace 0 --control 1
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
    ap.add_argument("cell")
    ap.add_argument("--sound", default="")
    ap.add_argument("--control", default="")
    ap.add_argument("--dry", type=int, default=0)
    args = ap.parse_args()
    os.environ.setdefault("PB_T0", repr(time.time()))
    if args.dry:
        os.environ["JAX_PLATFORMS"] = "cpu"
    from perfbench.lib import checks, child, spec, train_child
    entry, config, traffic = spec.cell(args.cell)
    if args.dry:
        config, traffic = spec.tiny(config), spec.tiny_train(traffic)
        os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                                   f"{entry['chips']}")
    child.bring_up(entry["chips"], args.dry)
    seeds = lambda s: [int(x) for x in s.split(",") if x]
    program = None
    for seed in seeds(args.sound):
        if program is None:
            program = train_child.Program(config, traffic, seed)
        else:
            program.reseed(seed)
        prog = program.first_steps()
        program.free()
        ref = train_child.reference_numbers(config, traffic, seed)
        numbers, where = checks.train_numbers(prog, ref)
        print("READING " + json.dumps({"cell": args.cell, "kind": "sound",
                                       "seed": seed, **numbers,
                                       "where": where,
                                       "ref_step_s": ref["step_s"]}),
              flush=True)
    for seed in seeds(args.control):
        ref = train_child.reference_numbers(config, traffic, seed)
        low = train_child.reference_numbers(config, traffic, seed,
                                            quant="int8")
        numbers, where = checks.train_numbers(low, ref)
        print("READING " + json.dumps({"cell": args.cell, "kind": "control",
                                       "seed": seed, **numbers,
                                       "where": where}), flush=True)


if __name__ == "__main__":
    main()
