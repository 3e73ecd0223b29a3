#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the repo's main path once, through the entry points a user calls,
at the full width of llama ``1b`` (models/llama.py CONFIGS: vocab 128,256,
dim 2048, 16 layers, 32/8 heads, ffn 8192; random weights from a seed):

  device  platform is ``tpu`` and ``device_kind`` is in the peaks table
  train   hvd.init(), the fused-psum DistributedOptimizer inside
          make_scanned_train_step (what ``python bench.py`` runs), two
          calls of three steps, then serve.engine.save_servable
  serve   ``hvdrun --serve`` on that servable: launcher, worker, router;
          a few POST /generate, /serve/stats, POST /admin/drain
  kernel  ops/flash_attention.py compiled (never interpreted) against
          layers.causal_attention, then train steps with it

Each phase is a child process that owns every chip from start to exit;
this parent never imports jax (a parent that touched jax would hold the
chip).  Any phase that exits non-zero, prints no result line or runs out
of time fails the smoke, named, with a non-zero exit and no ``ok`` line.
Every line printed is a SMOKE OBSERVATION from one run — seconds include
compilation — and none is a benchmark number.

``--dry-run`` is the one switch: the ``tiny`` config on the CPU with
Pallas interpreted, same phases, same code, and result lines that say
``"platform": "cpu", "dry_run": true``.  Without it, no TPU means exit 1.

Last stdout line on success:
  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
WORK = os.path.join(HERE, ".chip_smoke")   # servable + phase logs
RESULT = "SMOKE-RESULT "
SEED = 0
BUDGET_S = 1150.0   # all phases, compilation included

# What each mode runs.  chip: the one 1b training setting that has run on
# a v5e before (per-chip batch 8, seq 1024, per-layer remat, ce_chunks 8),
# the bench's flash geometries plus 1b's, and a cache sized for the width.
SIZES = {
    "chip": dict(
        model="1b", vocab=128256, seq=1024, batch=8, ce_chunks=8,
        # (q heads, kv heads, head dim, seq)
        flash_geoms=[(16, 8, 64, 1024), (16, 8, 64, 2048),
                     (32, 8, 64, 1024), (32, 8, 64, 2048)],
        flash_block=256, flash_model="bench", flash_seq=1024, flash_batch=16,
        prefix=128, tails=(128, 384, 384), new_tokens=32),
    "dry": dict(
        model="tiny", vocab=256, seq=64, batch=4, ce_chunks=8,
        flash_geoms=[(4, 2, 16, 128)],
        flash_block=64, flash_model="tiny", flash_seq=64, flash_batch=4,
        prefix=16, tails=(16, 48, 48), new_tokens=8),
}
STEPS = 3
SERVE_ENV = {"HOROVOD_SERVE_CACHE_BLOCKS": "1024",
             "HOROVOD_SERVE_MAX_BATCH_TOKENS": "512",
             "HOROVOD_SERVE_PREFILL_CHUNK": "128"}
SERVE_TTL_S = 900   # upper bound; the smoke drains the fleet when done
LOSS_TOL = 1e-3     # |distributed first-step loss - one-device loss|
FLASH_TOL = {"out": 2e-2, "dq": 4e-2, "dk": 4e-2, "dv": 4e-2}
BALANCE = 1.25      # max/min of per-device bytes across the mesh


class SmokeFailure(Exception):
    pass


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


# ===================================================================
# Children: everything below this line until `run_child` imports jax.
# ===================================================================
def _setup(dry):
    """Common child bring-up: the compile cache, the backend, and the
    fields every result line carries."""
    import importlib.metadata

    import jax
    import jaxlib

    from horovod_tpu.perf import costmodel
    from horovod_tpu.utils.platform import enable_compile_cache
    from horovod_tpu.utils.profiler import compile_counts
    cache_dir = enable_compile_cache()
    compile_counts()   # the program's own counter, listening from here on
    dev = jax.devices()[0]
    check(dev.platform == ("cpu" if dry else "tpu"),
          f"platform is {dev.platform!r}")
    if not dry:
        costmodel.device_peaks(dev.device_kind)   # unknown kind raises
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = None
    fields = {
        "platform": dev.platform, "device_kind": dev.device_kind,
        "device_count": jax.device_count(), "dry_run": dry,
        "versions": {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                     "libtpu": libtpu},
        "compile_cache_dir": cache_dir,
        "compile_cache_from_env": bool(
            cache_dir and os.environ.get("JAX_COMPILATION_CACHE_DIR")),
    }
    return fields, compile_counts


def _memory(key):
    """``key`` of device.memory_stats() per local device (None where the
    backend reports none, i.e. the CPU)."""
    import jax
    stats = [d.memory_stats() for d in jax.local_devices()]
    return [int(s[key]) if s and key in s else None for s in stats]


def _balanced(values, what):
    if len(values) > 1 and all(v is not None for v in values):
        check(max(values) <= BALANCE * max(min(values), 1),
              f"{what} is not balanced across devices: {values}")


def phase_device(size, dry):
    fields, _ = _setup(dry)
    return fields


def _hlo_collectives(hlo, n):
    """{op kind: count} of the collectives in compiled HLO text, and the
    group sizes of its cross-device reductions (a replica_groups text
    this does not know is returned as written)."""
    import re
    kinds, sizes = {}, set()
    pat = re.compile(r"\s(all-reduce|all-reduce-start|reduce-scatter|"
                     r"all-gather|all-gather-start|collective-permute|"
                     r"collective-permute-start|all-to-all)\(")
    for line in hlo.splitlines():
        m = pat.search(line)
        if not m:
            continue
        kind = m.group(1).replace("-start", "")
        kinds[kind] = kinds.get(kind, 0) + 1
        if kind not in ("all-reduce", "reduce-scatter"):
            continue
        groups = re.search(r"replica_groups=(\{\{[0-9,]*\}|\[\d+,\d+\]|\{\})",
                           line)
        text = groups.group(1) if groups else "absent"
        if text.startswith("{{"):        # {{0,1,2,3}}
            sizes.add(len(text[2:-1].split(",")))
        elif text.startswith("["):       # [groups,size]<=[...]
            sizes.add(int(text[1:-1].split(",")[1]))
        elif text == "{}":               # every device
            sizes.add(n)
        else:
            sizes.add(text)
    return kinds, sorted(sizes, key=str)


def _train_setup(cfg, loss_fn, batch, seq):
    """What `python bench.py` builds: hvd.init(), adamw inside the
    fused-psum DistributedOptimizer of make_scanned_train_step, state
    replicated over every chip, and STEPS copies of one seeded batch."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import horovod_tpu as hvd
    from horovod_tpu.models import llama
    from horovod_tpu.parallel.data_parallel import (
        make_scanned_train_step, replicate, shard_batch)
    hvd.init()
    mesh, n = hvd.mesh(), hvd.size()
    check(n == jax.device_count(),
          f"hvd.size() {n} != jax.device_count() {jax.device_count()}")
    opt = optax.adamw(3e-4, weight_decay=0.01)
    run = make_scanned_train_step(loss_fn, opt, mesh)
    params = replicate(llama.init(jax.random.PRNGKey(SEED), cfg), mesh)
    opt_state = replicate(opt.init(params), mesh)
    ids = np.random.RandomState(SEED).randint(
        0, cfg.vocab, (batch * n, seq + 1), dtype=np.int32)
    batches = shard_batch(
        jnp.asarray(np.broadcast_to(ids, (STEPS,) + ids.shape)), mesh, axis=1)
    return n, run, params, opt_state, ids, batches


def phase_train(size, dry):
    t_start = time.perf_counter()
    fields, counter = _setup(dry)
    import functools

    import jax
    import numpy as np

    from horovod_tpu import runtime
    from horovod_tpu.models import layers, llama
    from horovod_tpu.serve.engine import save_servable

    cfg = llama.CONFIGS[size["model"]]
    check(cfg.vocab == size["vocab"], "SIZES vocab out of date")
    attn_fn = functools.partial(layers.causal_attention, score_dtype=None)

    def loss_fn(p, ids):
        return llama.loss_fn(p, ids, cfg, attn_fn=attn_fn, remat=True,
                             ce_chunks=size["ce_chunks"])

    batch = size["batch"]
    n, run, params, opt_state, ids, batches = _train_setup(
        cfg, loss_fn, batch, size["seq"])
    jax.block_until_ready((params, opt_state))
    in_use_after_init = _memory("bytes_in_use")

    # Equivalence probe (the verify skill's): the distributed first-step
    # loss at global batch G must equal ONE device's loss on the same G
    # rows, here taken per-chip-batch rows at a time on one device.
    one = jax.tree_util.tree_map(
        lambda x: x.addressable_shards[0].data, params)
    one_dev = jax.tree_util.tree_leaves(one)[0].devices().pop()
    one_loss = jax.jit(loss_fn)
    ref = float(np.mean([
        float(one_loss(one, jax.device_put(ids[i * batch:(i + 1) * batch],
                                           one_dev)))
        for i in range(n)]))
    del one

    losses, lowered, first_call_s = [], [], None
    for _ in range(2):
        before = counter()["compiles"]
        params, opt_state, out = run(params, opt_state, batches)
        losses.append(np.asarray(out).tolist())   # D2H fence
        lowered.append(counter()["compiles"] - before)
        if first_call_s is None:
            first_call_s = time.perf_counter() - t_start
    flat = [x for call in losses for x in call]
    check(all(np.isfinite(flat)), f"non-finite loss: {losses}")
    check(len(set(flat)) > 1, f"loss constant: {losses}")
    check(flat[-1] < flat[0], f"loss did not fall: {losses}")
    check(lowered[1] == 0,
          f"second call lowered {lowered[1]} new programs")
    check(abs(flat[0] - ref) <= LOSS_TOL,
          f"first-step loss {flat[0]} vs one-device {ref}")
    peak = _memory("peak_bytes_in_use")
    _balanced(in_use_after_init, "bytes_in_use after init")
    _balanced(peak, "peak_bytes_in_use")

    # The compiled step's cross-device reductions, against the bucket plan.
    rt = runtime.get()
    leaves = jax.tree_util.tree_leaves(params)
    buckets = rt.plan_cache.get([x.shape for x in leaves],
                                [x.dtype for x in leaves],
                                rt.fusion_threshold()).num_buckets
    abstract = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding),
        (params, opt_state, batches))
    kinds, group_sizes = _hlo_collectives(
        run.lower(*abstract).compile().as_text(), n)
    if n > 1:
        check(kinds.get("all-reduce", 0) + kinds.get("reduce-scatter", 0) > 0,
              f"no cross-device reduction in the compiled step: {kinds}")
        check(group_sizes == [n],
              f"reductions over {group_sizes} participants, mesh has {n}")

    save_servable(size["servable"], "llama", cfg, params)
    files = [os.path.getsize(os.path.join(root, f))
             for root, _, names in os.walk(size["servable"]) for f in names]
    return {
        **fields, "hvd_size": n, "model": size["model"], "seq": size["seq"],
        "per_chip_batch": batch, "losses": losses,
        "one_device_first_loss": ref, "loss_tol": LOSS_TOL,
        "first_call_s": round(first_call_s, 1),
        "first_call_is": f"child start to the first {STEPS} steps on the "
                         "host, compile included",
        "lowerings_per_call": lowered,
        "cache_hits": counter()["cache_hits"],
        "bucket_plan_buckets": buckets, "hlo_collectives": kinds,
        "hlo_reduction_group_sizes": group_sizes,
        "bytes_in_use_after_init": in_use_after_init,
        "peak_bytes_in_use": peak,
        "servable_files": len(files), "servable_bytes": sum(files),
        "servable_max_file_bytes": max(files),
    }


def phase_kernel(size, dry):
    t_start = time.perf_counter()
    fields, counter = _setup(dry)
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    import bench
    from horovod_tpu.models import layers, llama
    from horovod_tpu.ops.flash_attention import flash_attention

    # Compiled on the chip, explicitly; interpreted only in the dry run.
    flash = functools.partial(flash_attention, block_q=size["flash_block"],
                              block_k=size["flash_block"], interpret=dry)

    def fwd_bwd(attn, q, k, v, w):
        out, vjp = jax.vjp(attn, q, k, v)
        return (out,) + vjp(w)

    rows, first_s = [], None
    for H, KV, D, S in size["flash_geoms"]:
        keys = jax.random.split(jax.random.PRNGKey(SEED), 4)
        q, w = (jax.random.normal(kk, (2, S, H, D), jnp.bfloat16)
                for kk in keys[:2])
        k, v = (jax.random.normal(kk, (2, S, KV, D), jnp.bfloat16)
                for kk in keys[2:])
        got = jax.jit(functools.partial(fwd_bwd, flash))(q, k, v, w)
        jax.block_until_ready(got)
        if first_s is None:
            first_s = time.perf_counter() - t_start
        f32 = [x.astype(jnp.float32) for x in (q, k, v, w)]
        with jax.default_matmul_precision("highest"):
            want = jax.jit(functools.partial(
                fwd_bwd, layers.causal_attention))(*f32)
        errs = {}
        for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
            a, b = np.asarray(a, np.float32), np.asarray(b)
            check(np.all(np.isfinite(a)), f"flash {name} not finite")
            errs[name] = float(np.max(np.abs(a - b)) / np.max(np.abs(b)))
            check(errs[name] <= FLASH_TOL[name],
                  f"flash {name} off by {errs[name]:.3g} of max "
                  f"(tol {FLASH_TOL[name]}) at H{H} KV{KV} D{D} S{S}")
        rows.append({"H": H, "KV": KV, "D": D, "S": S,
                     "err_over_max": {k: round(e, 5)
                                      for k, e in errs.items()}})

    # What `bench.py --flash` runs: the bench model through the same step
    # builder with attn_fn=flash_attention.
    cfg = (bench.bench_config(size["flash_seq"])
           if size["flash_model"] == "bench"
           else llama.CONFIGS[size["flash_model"]])
    _, run, params, opt_state, _, batches = _train_setup(
        cfg, lambda p, ids: llama.loss_fn(p, ids, cfg, attn_fn=flash),
        size["flash_batch"], size["flash_seq"])
    _, _, out = run(params, opt_state, batches)
    losses = np.asarray(out).tolist()
    check(all(np.isfinite(losses)), f"non-finite flash loss: {losses}")
    check(losses[-1] < losses[0], f"flash loss did not fall: {losses}")
    return {
        **fields, "interpret": dry, "block": size["flash_block"],
        "tolerance_over_max": FLASH_TOL, "geometries": rows,
        "first_kernel_s": round(first_s, 1),
        "first_kernel_is": "child start to the first forward+backward "
                           "on the host, compile included",
        "train_model": size["flash_model"], "train_losses": losses,
        "cache_hits": counter()["cache_hits"],
        "peak_bytes_in_use": _memory("peak_bytes_in_use"),
    }


CHILD_PHASES = {"device": phase_device, "train": phase_train,
                "kernel": phase_kernel}


def mode_size(dry):
    return dict(SIZES["dry" if dry else "chip"],
                servable=os.path.join(WORK, "servable"))


def child_main(phase, dry):
    size = mode_size(dry)
    try:
        result = CHILD_PHASES[phase](size, dry)
    except SmokeFailure as e:
        print(f"chip_smoke[{phase}]: {e}", file=sys.stderr)
        return 1
    print(RESULT + json.dumps(result), flush=True)
    return 0


# ===================================================================
# Parent: no jax from here on.
# ===================================================================
def child_env(dry):
    """The environment every child gets.  The platform is set here and
    not inherited: ``tpu`` makes jax raise where it would otherwise fall
    back to the CPU, and shells (this sandbox's too) export ``cpu``."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu" if dry else "tpu"
    env["PYTHONPATH"] = HERE + os.pathsep + env.get("PYTHONPATH", "")
    env["PYTHONUNBUFFERED"] = "1"
    return env


def kill_group(proc):
    """Stop a child and everything it started."""
    if proc.poll() is None:
        try:
            os.killpg(proc.pid, signal.SIGTERM)
            proc.wait(timeout=10)
        except (ProcessLookupError, subprocess.TimeoutExpired):
            pass
    try:
        os.killpg(proc.pid, signal.SIGKILL)
    except ProcessLookupError:
        pass
    proc.wait()


def log_lines(path):
    try:
        with open(path, errors="replace") as f:
            return f.read().splitlines()
    except OSError:
        return []


def log_tail(path):
    return "\n".join(log_lines(path)[-30:])


def run_child(phase, dry, env, timeout):
    """One child phase to its result line, or SmokeFailure."""
    log = os.path.join(WORK, f"{phase}.log")
    cmd = [sys.executable, os.path.abspath(__file__), "--phase", phase]
    if dry:
        cmd.append("--dry-run")
    with open(log, "w") as out:
        proc = subprocess.Popen(cmd, stdout=out, stderr=subprocess.STDOUT,
                                env=env, cwd=HERE, start_new_session=True)
        try:
            rc = proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            raise SmokeFailure(f"no result after {timeout:.0f}s\n"
                               + log_tail(log))
        finally:
            kill_group(proc)
    lines = [ln for ln in log_lines(log) if ln.startswith(RESULT)]
    check(rc == 0, f"exit code {rc}\n" + log_tail(log))
    check(lines, "exit code 0 but no result line\n" + log_tail(log))
    return json.loads(lines[-1][len(RESULT):])


def http(port, path, body=None, timeout=300):
    """(status, parsed ndjson lines) of one request to the router."""
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/{path}",
        data=None if body is None else json.dumps(body).encode(),
        headers={"Content-Type": "application/json"},
        method="GET" if body is None else "POST")
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, [json.loads(ln) for ln in r.read().splitlines()]
    except urllib.error.HTTPError as e:
        return e.code, [{"error": e.read().decode(errors="replace")}]
    except (OSError, ValueError) as e:
        raise SmokeFailure(f"{path}: {e!r}")


def phase_serve(size, dry, env, timeout, device):
    """hvdrun --serve on the trained servable, one local slot: launcher,
    worker, and the router on the rendezvous server."""
    import random
    t_start = time.monotonic()
    deadline = t_start + timeout
    log = os.path.join(WORK, "serve.log")
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    rng = random.Random(SEED)

    def toks(k):
        return [rng.randrange(size["vocab"]) for _ in range(k)]

    prefix = toks(size["prefix"])
    a, b, c = (toks(k) for k in size["tails"])
    prompts = {"p1": prefix + a, "p2": b, "p3": prefix + c}
    new = size["new_tokens"]

    cmd = [sys.executable, "-m", "horovod_tpu.runner.launch", "-np", "1",
           "--serve", size["servable"], "--serve-port", str(port),
           "--serve-ttl", str(SERVE_TTL_S)]
    with open(log, "w") as out:
        proc = subprocess.Popen(cmd, stdout=out, stderr=subprocess.STDOUT,
                                env=dict(env, **SERVE_ENV), cwd=HERE,
                                start_new_session=True)
        try:
            ready = None
            while ready is None:
                check(proc.poll() is None,
                      f"launcher exited {proc.poll()} before SERVE-READY\n"
                      + log_tail(log))
                check(time.monotonic() < deadline,
                      "no SERVE-READY in time\n" + log_tail(log))
                ready = next((ln for ln in log_lines(log)
                              if "SERVE-READY" in ln), None)
                time.sleep(0.2)
            ready_s = time.monotonic() - t_start
            check(f"platform={device['platform']}," in ready and
                  f"device_kind={device['device_kind']}," in ready and
                  f"devices={device['device_count']}," in ready,
                  f"server came up on another device: {ready}")
            if device["device_count"] > 1:
                # models/paged.py shardings: blocks over the data axis
                check("cache=PartitionSpec(None, '" in ready,
                      f"paged cache is not sharded over the mesh: {ready}")
            while "engine" not in http(port, "serve/stats", timeout=10)[1][0]:
                check(time.monotonic() < deadline, "engine stats never "
                      "published\n" + log_tail(log))
                time.sleep(0.2)

            answers, first_wave_s = {}, None

            def generate(name, prompt):
                answers[name] = http(
                    port, "generate",
                    {"tokens": prompt, "max_new_tokens": new},
                    timeout=max(1.0, deadline - time.monotonic()))

            # p1 + p2 together, then p3 (shares p1's prefix) + p1 again.
            for wave in (("p1", "p2"), ("p3", "p1_again")):
                threads = [threading.Thread(
                    target=generate,
                    args=(name, prompts[name.split("_")[0]]))
                    for name in wave]
                for t in threads:
                    t.start()
                for t in threads:
                    t.join()
                if first_wave_s is None:
                    first_wave_s = time.monotonic() - t_start
            for name, (status, lines) in answers.items():
                check(status == 200, f"{name}: HTTP {status} {lines}")
                done = lines[-1]
                check(done.get("done") is True and not done.get("error"),
                      f"{name}: {done}")
                check(len(done["tokens"]) == new and
                      all(0 <= t < size["vocab"] for t in done["tokens"]),
                      f"{name}: wrong tokens {done['tokens']}")
                streamed = [t for ln in lines[:-1]
                            for t in ln.get("tokens", [])]
                check(streamed == done["tokens"],
                      f"{name}: streamed parts differ from the done record")
            check(answers["p1"][1][-1]["tokens"]
                  == answers["p1_again"][1][-1]["tokens"],
                  "the repeated prompt did not repeat its tokens")
            # the engine publishes its stats on a period: wait for the
            # snapshot that has seen all four requests finish
            stats = {}
            while stats.get("engine", {}).get("completed", 0) < len(answers):
                check(time.monotonic() < deadline, "engine stats never "
                      f"showed {len(answers)} completed: {stats}")
                time.sleep(0.2)
                stats = http(port, "serve/stats", timeout=10)[1][0]
            pc = stats["engine"]["prefix_cache"]
            check(pc["hits"] >= 2 and pc["hit_tokens"] >= 2 * size["prefix"],
                  f"no prefix hit: {pc}")
            # the program's own clocks (docs/serving.md#request-lifecycle)
            loop = stats["engine"].get("loop") or {}
            check(loop.get("ticks", 0) >= 1 and loop.get("phase_s", {}).get(
                "harvest_wait", 0) > 0 and loop.get("compiles", 0) >= 1,
                f"/serve/stats has no loop table: {loop}")
            for name, (_, lines) in answers.items():
                done = lines[-1]
                check({"pickup", "publish"} <= set(done["timing"]) and
                      done.get("loop", {}).get("ticks", 0) >= 1,
                      f"{name}: the done record lacks its hops: {done}")
            status, drained = http(port, "admin/drain", {}, timeout=120)
            check(status == 200 and drained[0].get("drained"),
                  f"drain failed: {status} {drained}")
            try:
                rc = proc.wait(timeout=max(1.0, deadline - time.monotonic()))
            except subprocess.TimeoutExpired:
                raise SmokeFailure("launcher still running after the "
                                   "drain\n" + log_tail(log))
            check(rc == 0, f"launcher exit code {rc}\n" + log_tail(log))
        finally:
            kill_group(proc)
    return {
        # the worker names its device in the READY line (checked above) and
        # places its cache with the same function the other children use
        **{k: device[k] for k in (
            "platform", "device_kind", "device_count", "dry_run", "versions",
            "compile_cache_dir", "compile_cache_from_env")},
        "ready_line": ready.strip(),
        "serve_env": SERVE_ENV,
        "prompt_tokens": {k: len(v) for k, v in prompts.items()},
        "new_tokens": new,
        "ready_s": round(ready_s, 1),
        "first_wave_s": round(first_wave_s, 1),
        "first_wave_is": "launcher start to the first two answers "
                         "complete, load and compile included",
        "ttft_s": {k: v[1][-1].get("ttft_s") for k, v in answers.items()},
        "timing": {k: v[1][-1]["timing"] for k, v in answers.items()},
        "loop": {k: v[1][-1]["loop"] for k, v in answers.items()},
        "stats_loop": loop,
        "prefix_cache": pc,
        "engine_ticks": stats["engine"]["tick"],
        "router": drained[0].get("router"),
    }


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--dry-run", action="store_true",
                    help="tiny config on the CPU, Pallas interpreted; "
                         "never a chip pass")
    ap.add_argument("--phase", choices=sorted(CHILD_PHASES),
                    help=argparse.SUPPRESS)   # child mode
    args = ap.parse_args()
    if args.phase:
        return child_main(args.phase, args.dry_run)

    dry = args.dry_run
    size = mode_size(dry)
    env = child_env(dry)
    shutil.rmtree(WORK, ignore_errors=True)
    os.makedirs(WORK)
    t_start = time.monotonic()
    device = None
    try:
        for phase in ("device", "train", "serve", "kernel"):
            t0 = time.monotonic()
            left = BUDGET_S - (t0 - t_start)
            try:
                check(left > 0, "the smoke's time budget is spent")
                if phase == "serve":
                    result = phase_serve(size, dry, env, left, device)
                else:
                    result = run_child(phase, dry, env, left)
            except SmokeFailure as e:
                print(f"chip_smoke: FAILED phase={phase}: {e}",
                      file=sys.stderr)
                return 1
            device = device or result
            print(json.dumps({"phase": phase, "smoke_observation": True,
                              "wall_s": round(time.monotonic() - t0, 1),
                              **result}), flush=True)
    finally:
        shutil.rmtree(size["servable"], ignore_errors=True)
    final = {"ok": True, "device": {"platform": device["platform"],
                                    "kind": device["device_kind"],
                                    "count": device["device_count"]}}
    if dry:
        final["dry_run"] = True
    print(json.dumps(final), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
