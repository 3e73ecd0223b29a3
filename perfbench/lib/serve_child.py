"""The chip-holding child of a serving cell: the program's own serving
stack (ServeEngine under FleetFrontend, fed by the router over the
rendezvous KV and the direct stream) wired as serve/worker.py:main wires
it, with load_servable replaced by weights made on the device from the
seed.  The parent drives it over stdin:

  mark NAME          snapshot the engine's counters
  trace-start / trace-stop
  check PATH         after the run loop ended: the served-path check on the
                     sample in PATH, then exit
"""

from __future__ import annotations

import argparse
import json
import shutil
import sys
import tempfile
import threading
import time

from . import checks, child, reference, spec, tracered

TICK_MODULE = "step_fn"


def _annotate(obj, method, label):
    import jax
    fn = getattr(obj, method, None)
    if fn is None:
        return

    def wrapped(*a, **k):
        with jax.profiler.TraceAnnotation(label):
            return fn(*a, **k)
    setattr(obj, method, wrapped)


def served_check(config, seed, sample, tokens_of, quant=None):
    """(teacher-forced statistics of the served sample that run.build_sample
    wrote, the compared numbers read from them).  The statistics are the
    family's own where it brings them, handed the whole sample, and else
    ``generated_logit_stats`` on the sample's ``seqs`` and ``spans`` alone;
    the numbers are the two every cell has and the family's own beside
    them."""
    stats_of = reference.served_stats_for(config)
    if stats_of is reference.generated_logit_stats:
        stats = stats_of(config, seed, sample["seqs"],
                         [tuple(s) for s in sample["spans"]], tokens_of,
                         quant=quant)
    else:
        stats = stats_of(config, seed, sample, tokens_of, quant=quant)
    return stats, {**checks.serve_numbers(stats),
                   **checks.family_numbers(stats)}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--dry", type=int, default=0)
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--break", dest="broken", default="")
    ap.add_argument("--control", type=int, default=0)
    args = ap.parse_args(argv)
    entry, config, traffic = spec.cell(args.workload)
    if args.dry:
        config = spec.tiny(config)
    device, counter = child.bring_up(entry["chips"], args.dry)

    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    import horovod_tpu as hvd
    from horovod_tpu.serve.config import ServeConfig
    from horovod_tpu.serve.engine import ServeEngine
    from horovod_tpu.serve.worker import FleetFrontend
    from . import weights

    child.say(phase="devices up", at_s=child.since_start())
    hvd.init()
    mesh = hvd.mesh()
    child.say(phase="hvd.init done", at_s=child.since_start())
    model, cfg = spec.family(config).program(config)
    params = jax.jit(lambda key: weights.make(config, key,
                                              weights.dtype_of(config)),
                     out_shardings=NamedSharding(mesh, P()))(
        weights.seed_key(args.seed))
    jax.block_until_ready(params)
    child.say(phase="weights made", at_s=child.since_start())
    scfg = ServeConfig(**config["engine"])
    engine = ServeEngine(model, cfg, params, scfg, mesh=mesh)
    frontend = FleetFrontend(engine, "127.0.0.1", args.port, 0, 1,
                             drain_timeout_s=traffic["drain_timeout_s"])
    if args.trace:
        for obj, method, label in (
                (engine, "_dispatch", "pb:dispatch"),
                (engine, "_harvest", "pb:harvest"),
                (frontend, "_publish_report", "pb:publish"),
                (frontend, "_drain_requests", "pb:poll-requests")):
            _annotate(obj, method, label)
    if args.broken == "wrong-token":    # tests: a token altered at its source
        harvest = engine._harvest

        def bad():
            rep = harvest()
            for toks in rep["emitted"].values():
                toks[:] = [(t + 1) % config["vocab_size"] for t in toks]
            return rep
        engine._harvest = bad
    jax.block_until_ready(params)
    child.say(phase="engine built", at_s=child.since_start())
    pool = engine.kv_pool()
    child.say(hbm_in_use_after_init=child.memory("bytes_in_use"),
              pool_bytes=pool["pool_bytes"], cache_blocks=scfg.cache_blocks,
              slots=scfg.max_slots, chunk=scfg.prefill_chunk)

    marks, trace = {}, {}

    def control():
        for line in sys.stdin:
            cmd = line.split()
            if not cmd:
                continue
            if cmd[0] == "mark":
                st = engine.stats()
                marks[cmd[1]] = {
                    "t": time.time(), "tick": st["tick"],
                    "tokens_prefill": st["tokens_prefill"],
                    "tokens_decode": st["tokens_decode"],
                    "spec": st["spec"], "prefix_cache": st["prefix_cache"],
                    "completed": st["completed"],
                    # the whole record, for readers that arrive later
                    "stats": st,
                    "lowerings": counter.lowerings,
                    "hbm_peak": child.memory("peak_bytes_in_use")}
                child.emit("mark", name=cmd[1])
            elif cmd[0] == "trace-start":
                trace["dir"] = tempfile.mkdtemp(prefix="pb-trace-")
                trace["t0"] = time.time()
                jax.profiler.start_trace(
                    trace["dir"], profiler_options=child.trace_options())
                child.emit("mark", name="trace-start")
            elif cmd[0] == "trace-stop":
                jax.profiler.stop_trace()
                trace["t1"] = time.time()
                child.emit("mark", name="trace-stop")
            elif cmd[0] == "check":
                trace["check"] = cmd[1]
                return

    ctl = threading.Thread(target=control, daemon=True)
    ctl.start()
    frontend._publish_stats(force=True)     # readiness, as worker.main does
    child.emit("ready")
    child.say(loop_thread_before=child.thread_placement())
    try:
        frontend.run()
    finally:
        engine.close()
    child.say(loop_thread_after=child.thread_placement())
    peak = child.memory("peak_bytes_in_use")
    child.emit("stopped", marks=marks, hbm_peak=peak,
               cache_hits=counter.cache_hits)

    red = None
    if trace.get("dir"):
        red = tracered.reduce_trace(trace["dir"], module=TICK_MODULE,
                                    dry=args.dry)
        red["t0"], red["t1"] = trace["t0"], trace["t1"]
        shutil.rmtree(trace["dir"], ignore_errors=True)

    # the program's state goes before the reference comes
    engine.cache = engine.params = frontend = None
    del params
    ctl.join()
    with open(trace["check"]) as f:
        sample = json.load(f)
    numbers = {}
    if sample["seqs"]:
        t = time.perf_counter()
        stats, numbers = served_check(config, args.seed, sample, "served")
        where = [(r, k, first + k) for r, (first, n) in
                 enumerate(sample["spans"]) for k in range(n)]
        worst = sorted(range(len(where)), key=lambda i: -stats["gap"][i])[:8]
        child.say(reference_s=time.perf_counter() - t,
                  served_tokens_checked=len(stats["gap"]),
                  flips=sum(stats["flip"]),
                  served_gap_mean=sum(stats["gap"]) / len(stats["gap"]),
                  gaps_over_0p1=sum(g > 0.1 for g in stats["gap"]),
                  worst_gaps=[{"row": where[i][0], "nth_token": where[i][1],
                               "position": where[i][2],
                               "gap": stats["gap"][i]} for i in worst])
    if args.control and sample["seqs"]:
        # the control: the reference in int8 in the program's place, at the
        # same prompts and tokens (never in the benchmark's own runs)
        _, low = served_check(config, args.seed, sample, "quant", "int8")
        print("READING " + json.dumps({"cell": args.workload, "seed": args.seed,
                                       "sound": numbers, "control": low}),
              flush=True)
    child.emit("checked", numbers=numbers, trace=red,
               device=dict(device, memory_peak_bytes=peak))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
