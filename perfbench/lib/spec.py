"""Finds a cell's files by the names in BENCHMARK.json.

A cell is one entry of ``workloads``: a configuration (``configs/<name>.json``)
under a traffic mix (``traffic/<name>.json``).  Per-layer metrics are readers
(``metrics/<name>.py``), and a configuration's model family is a file
(``families/<family>.py``).  Nothing here imports jax or the program.
"""

from __future__ import annotations

import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
# where family files are looked for, in order; the benchmark's tests add the
# directory of the family that only they use
FAMILY_DIRS = [os.path.join(BENCH_DIR, "families")]
_families = {}


def load_json(path):
    with open(path) as f:
        return json.load(f)


def benchmark():
    return load_json(os.path.join(ROOT, "BENCHMARK.json"))


def cell(name, bench=None):
    """(workload entry, configuration, traffic) of the cell ``name``."""
    bench = bench or benchmark()
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SystemExit(f"perfbench: no workload {name!r} in BENCHMARK.json "
                         f"({', '.join(w['name'] for w in bench['workloads'])})")
    conf = next(c for c in bench["configs"] if c["name"] == entry["config"])
    config = load_json(os.path.join(ROOT, conf["file"]))
    traffic = load_json(
        os.path.join(BENCH_DIR, "traffic", entry["traffic"] + ".json"))
    return entry, config, traffic


def cell_metrics(name, bench=None):
    """(end-to-end, per-layer) metric entries that the cell reports."""
    bench = bench or benchmark()

    def mine(m):
        return "workloads" not in m or name in m["workloads"]
    return ([m for m in bench["end_to_end"] if mine(m)],
            [m for m in bench["per_layer"] if mine(m)])


def _load(path, module_name):
    spec = importlib.util.spec_from_file_location(module_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name):
    """The ``read(ctx)`` of ``metrics/<name>.py``; returns a number, or None
    when there is nothing to read."""
    return _load(os.path.join(BENCH_DIR, "metrics", name + ".py"),
                 "perfbench_metric_" + name.replace(".", "_").replace("-", "_")
                 ).read


def family(config):
    """The module ``families/<family>.py`` of a configuration (its ``family``
    key; a file without the key is a dense GQA decoder).  What it gives is
    set out in families/__init__.py."""
    name = config.get("family", "dense_gqa")
    if name not in _families:
        path = next((p for p in (os.path.join(d, name + ".py")
                                 for d in FAMILY_DIRS) if os.path.isfile(p)),
                    None)
        if path is None:
            raise SystemExit(f"perfbench: no family file {name}.py under "
                             + ", ".join(FAMILY_DIRS))
        _families[name] = _load(path, "perfbench_family_" + name)
    return _families[name]


def tiny(config):
    """The CPU rehearsal's copy of a configuration: the family's toy sizes in
    float32 (the limits are read at the published widths in bfloat16; a toy
    in bfloat16 rounds coarser than they allow) under a toy engine.  Never
    used on a chip."""
    out = family(config).tiny(config)
    if "engine" in out:
        out["engine"] = dict(out["engine"], max_slots=4, prefill_chunk=16,
                             max_batch_tokens=64, block_size=4,
                             max_seq_len=128, cache_blocks=256)
    return out


def tiny_train(traffic):
    """A training mix at the rehearsal's sequence length."""
    return dict(traffic, seq=traffic.get("dry_seq", 64))
