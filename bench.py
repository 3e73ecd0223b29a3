"""Benchmark: flagship training throughput on the available accelerator.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": "tokens/sec/chip", "vs_baseline": N,
   "platform": ..., "device_kind": ..., "device_count": N, "metrics": {...}}

One process that measures or fails: without ``--cpu`` it needs an
accelerator whose ``device_kind`` is in the peaks table, and exits
non-zero before compiling anything when jax found none (jax falls back
to the CPU silently; this program does not).  ``--cpu`` is the
schema/contract smoke: it prints counts and no utilization.

The "metrics" field embeds a condensed hvd.metrics_snapshot() (plan-cache
hit rate, controller cycles/cache rate, collective op/byte counts, stall
warnings — docs/metrics.md) so BENCH rows carry controller-level evidence
alongside MFU.

Protocol mirrors the reference's synthetic benchmarks (reference:
examples/pytorch/pytorch_synthetic_benchmark.py:104-109 — timed iterations
of a full train step on synthetic data):

  * All timed steps run inside ONE compiled ``lax.scan`` program
    (make_scanned_train_step), so per-dispatch latency is amortized and
    cannot dominate or vanish from the measurement.
  * The timer stops only after the per-step losses are fetched to the HOST
    (device-to-host transfer), which fences every step of the scan.
  * Sanity gates: every loss must be finite, losses must CHANGE across
    steps (params are actually updating), and computed MFU must lie in
    (0, 1).  Violations print an error JSON and exit non-zero rather than
    recording garbage.

``vs_baseline`` is model-FLOPs utilization (MFU) against the chip's bf16
peak — the hardware-normalized analog of the reference's
scaling-efficiency metric (BASELINE.md: >=90% scaling efficiency target).
MFU uses 6*N_params FLOPs/token (attention FLOPs excluded — the standard,
conservative MFU convention).  The constants (the ``PEAKS`` table keyed by
``device_kind``, the FLOPs conventions) live in
``horovod_tpu/perf/costmodel.py`` — the perf plane's single source of
truth — and the artifact also carries the attention-FLOPs-included
``mfu_attn`` variant (docs/profiling.md).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np


def bench_config(seq: int = 1024):
    """The default cell's model: a 193M-param decoder, big enough to keep
    the MXU busy on one chip, small enough to compile fast and fit HBM
    with optimizer state."""
    import jax.numpy as jnp
    from horovod_tpu.models import llama
    return llama.LlamaConfig(
        vocab=32768, dim=1024, n_layers=8, n_heads=16, n_kv_heads=8,
        ffn_dim=4096, max_seq=max(2048, seq), dtype=jnp.bfloat16)


def init_backend(cpu: bool) -> dict:
    """``hvd.init()``, then the device this run landed on as every bench
    JSON names it.  Without ``--cpu`` a CPU backend or a ``device_kind``
    outside the peaks table ends the run: the measurement path has no
    fallback (jax itself falls back to the CPU when it finds no chip)."""
    import jax
    import horovod_tpu as hvd
    from horovod_tpu.perf import costmodel
    hvd.init()
    dev = jax.devices()[0]
    if not cpu:
        if dev.platform == "cpu":
            raise SystemExit(fail(
                "no accelerator: jax initialized the CPU backend "
                f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS', '')!r}); "
                "bench.py measures on the chip or fails — pass --cpu for "
                "the schema smoke", cause="no-accelerator"))
        costmodel.device_peaks(dev.device_kind)
    return {"platform": dev.platform, "device_kind": dev.device_kind,
            "device_count": len(jax.devices())}


def maybe_profile(args):
    """Context manager: a jax.profiler trace into ``args.profile`` when
    set, else a no-op.  One definition so every bench path opens the
    trace the same way."""
    import contextlib
    if not args.profile:
        return contextlib.nullcontext()
    import jax
    return jax.profiler.trace(args.profile)


def fail(reason: str, cause: str, **extra) -> int:
    """Emit the error JSON.  ``cause`` is a closed taxonomy:
    no-accelerator | invalid-result | sanitized-lib.  (A crash is a
    traceback and a non-zero exit, not a JSON row.)"""
    print(json.dumps({"metric": "BENCH_INVALID", "value": 0,
                      "unit": "error", "vs_baseline": 0,
                      "cause": cause, "error": reason, **extra}))
    return 1


def metrics_summary() -> dict:
    """Condensed `hvd.metrics_snapshot()` embedded in every bench JSON so
    artifact rows carry controller-level evidence (plan-cache hit rate,
    cycles, stall warnings) alongside MFU.  Best-effort: a bench number
    must never be lost to a telemetry hiccup."""
    try:
        import horovod_tpu as hvd
        fams = hvd.metrics_snapshot().get("families", {})

        def total(name):
            return sum(s.get("value", 0)
                       for s in fams.get(name, {}).get("samples", []))

        def rate(hit, miss):
            h, m = total(hit), total(miss)
            return round(h / (h + m), 4) if h + m else None

        # Watch plane (docs/watch.md): every alert that FIRED during the
        # run rides the artifact as (rule, severity, count), so a sweep
        # row records its in-flight incidents beside its MFU — a number
        # produced while `sentinel-nonfinite` fired reads differently.
        fired_alerts = []
        for s in fams.get("hvd_alerts_total", {}).get("samples", []):
            labels = s.get("labels", {})
            if s.get("value") and labels.get("rule"):
                fired_alerts.append({
                    "rule": labels["rule"],
                    "severity": labels.get("severity", "warning"),
                    "count": int(s["value"])})
        summary = {
            "schema": "hvd-metrics-summary-v1",
            "plan_cache_hit_rate": rate("hvd_fusion_plan_cache_hits_total",
                                        "hvd_fusion_plan_cache_misses_total"),
            "controller_cycles": int(total("hvd_controller_cycles_total")),
            "controller_cache_hit_rate": rate(
                "hvd_controller_cache_hits_total",
                "hvd_controller_cache_misses_total"),
            "collective_ops": int(total("hvd_collective_ops_total")),
            "collective_bytes": int(total("hvd_collective_bytes_total")),
            "stall_warnings": int(total("hvd_stall_warnings_total")),
            "fired_alerts": sorted(fired_alerts,
                                   key=lambda a: (a["rule"],
                                                  a["severity"])),
        }
        # When the run traced (HOROVOD_TIMELINE / --timeline-merge), the
        # artifact points at the evidence (docs/timeline.md).
        from horovod_tpu import runtime as _hvd_rt
        if _hvd_rt.is_initialized():
            tl = _hvd_rt.get().timeline
            if tl is not None:
                summary["timeline"] = tl.path
        return summary
    except Exception as e:
        return {"schema": "hvd-metrics-summary-v1", "error": str(e)}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=30,
                    help="timed steps (all inside one scan)")
    ap.add_argument("--batch", type=int, default=None,
                    help="per-chip batch (default: 16 llama / 64 resnet)")
    ap.add_argument("--seq", type=int, default=1024)
    ap.add_argument("--model", default="bench",
                    choices=["bench", "tiny", "mini", "1b", "8b"])
    ap.add_argument("--resnet", action="store_true",
                    help="ResNet images/sec/chip instead of the llama "
                         "tokens/sec (the reference's headline metric: "
                         "docs/benchmarks.rst ResNet img/sec)")
    ap.add_argument("--cnn", default=None,
                    choices=["resnet50", "resnet101", "vgg16", "inception3"],
                    help="CNN images/sec family — the reference's full "
                         "headline-table trio (docs/benchmarks.rst:12-13 "
                         "Inception V3 / ResNet / VGG-16); --resnet is the "
                         "back-compat spelling of resnet{--depth}")
    ap.add_argument("--depth", type=int, default=50, choices=[50, 101],
                    help="ResNet depth; 101 matches the reference's "
                         "1656.82 img/s 16-GPU headline row exactly")
    ap.add_argument("--remat", action="store_true",
                    help="rematerialize the forward pass (bigger batches)")
    ap.add_argument("--fuse", action="store_true",
                    help="enable the fused qkv/gate-up projections "
                         "(the bench default is unfused)")
    ap.add_argument("--no-fuse", action="store_true",
                    help="back-compat no-op: unfused is the default")
    ap.add_argument("--scan-unroll", type=int, default=1,
                    help="lax.scan unroll factor for the timed step loop "
                         "(unrolled iterations drop loop overhead and let "
                         "XLA overlap across step boundaries; program "
                         "size grows proportionally)")
    ap.add_argument("--ce-chunks", type=int, default=0,
                    help="stream the lm_head+cross-entropy over N sequence "
                         "chunks under jax.checkpoint (0 = whole-sequence "
                         "logits); cuts the ~1 GB logits slab to 1/N live")
    ap.add_argument("--dim", type=int, default=0,
                    help="override model width (with --layers/--ffn, scans "
                         "custom shapes; 0 = use --model's config)")
    ap.add_argument("--layers", type=int, default=0)
    ap.add_argument("--ffn", type=int, default=0)
    ap.add_argument("--score-dtype", default=None,
                    choices=["f32", "input"],
                    help="dtype the attention score tensor materializes "
                         "in (XLA attention path).  'input' (the "
                         "default) halves the score-slab HBM traffic "
                         "for bf16 models; f32 keeps full logit "
                         "precision")
    ap.add_argument("--flash", action="store_true",
                    help="use the pallas flash-attention kernel "
                         "(ops/flash_attention.py) instead of the XLA "
                         "attention path")
    ap.add_argument("--block-q", type=int, default=256,
                    help="flash attention q-block (VMEM tuning)")
    ap.add_argument("--block-k", type=int, default=256,
                    help="flash attention k-block (VMEM tuning)")
    ap.add_argument("--scaling", action="store_true",
                    help="weak-scaling efficiency over mesh prefixes "
                         "{1,2,4,...} — the reference's headline metric "
                         "(docs/benchmarks.rst 90%% at 512 GPUs); needs "
                         "multi-chip (or the CPU-virtual mesh) to be "
                         "non-trivial")
    ap.add_argument("--autotune", action="store_true",
                    help="HOROVOD_AUTOTUNE end-to-end: tune (fusion "
                         "threshold, cycle) on the live fused gradient "
                         "sync, log the trajectory to "
                         "HOROVOD_AUTOTUNE_LOG, report before/after "
                         "sync throughput")
    ap.add_argument("--wire", action="store_true",
                    help="wire-policy sweep (ops/wire.py): run the fused "
                         "sync under each wire policy on a model-like "
                         "bucket mix and emit a per-policy {wire_bytes/"
                         "step, step_time, residual_norm} comparison "
                         "artifact with decode-determinism asserted")
    ap.add_argument("--overlap", action="store_true",
                    help="overlap-plane sweep (ops/overlap.py): run the "
                         "microbatch-pipelined step at each depth and "
                         "the bucket-interleaved ZeRO-1 step, emitting "
                         "per-depth {step_time, exposed_comm_bytes "
                         "(analytical), overlapped_fraction} with the "
                         "pipelined ≡ sequential params guard asserted")
    ap.add_argument("--zero", action="store_true",
                    help="ZeRO weight-update sharding sweep "
                         "(parallel/zero.py; docs/zero.md): run the "
                         "chain at levels 0-3 on the quadratic toy and "
                         "llama-tiny, emitting per-level {analytical "
                         "peak params+grads+opt-state bytes, step_time, "
                         "exposed_comm_bytes, ledger model drift} with "
                         "level 1/2/3 bit-near equivalence asserted")
    ap.add_argument("--layout", action="store_true",
                    help="3D layout sweep (parallel/layout.py + "
                         "perf/costmodel solver; docs/parallelism.md): "
                         "solve the (dp, tp, pp) candidate table for "
                         "llama-tiny, then RUN every candidate mesh "
                         "through the composed TP x PP x ZeRO chain, "
                         "emitting per-layout {measured step_time, "
                         "measured peak bytes, solver-predicted step + "
                         "memory, predicted-vs-measured drift} with "
                         "cross-layout bit-near equivalence asserted")
    ap.add_argument("--serve", action="store_true",
                    help="serving load-generator sweep (serve/engine.py; "
                         "docs/serving.md): drive the continuous-"
                         "batching engine closed-loop (fixed concurrent "
                         "users) and with Poisson arrivals, emitting "
                         "{throughput_tok_s, ttft_p50/p99, tpot_p50/p99, "
                         "batch_fill} per mode, CPU-virtual labeled")
    ap.add_argument("--users", nargs="?", const="1,2,4,8,16,24",
                    default=None, metavar="N,N,...",
                    help="with --serve: control-plane saturation sweep "
                         "(docs/control-plane.md) — closed-loop user "
                         "pools of each size drive POST /generate "
                         "through the REAL router + rendezvous KV with "
                         "a scripted fixed-cost engine, locating the "
                         "router/KV throughput knee for the single-"
                         "process baseline vs the sharded + direct-"
                         "stream control plane (default sweep "
                         "1,2,4,8,16,24)")
    ap.add_argument("--replicas", nargs="?", const="1,2,4",
                    default=None, metavar="N,N,...",
                    help="with --serve --users: replica scale-out sweep "
                         "(docs/serving.md#replicated-tier) — repeat the "
                         "user-count sweep against N independent replica "
                         "fleets registered behind one router with "
                         "prefix-affinity routing, locating the knee per "
                         "replica count plus the affinity hit rate vs "
                         "the least-loaded-only baseline (default sweep "
                         "1,2,4)")
    ap.add_argument("--scenario", metavar="SPEC_YAML", default=None,
                    help="deterministic scenario replay "
                         "(horovod_tpu/scenario; docs/scenarios.md): "
                         "run the spec's trace + fault storm against "
                         "the real router/watch planes on a virtual "
                         "clock, twice — byte-identical SLO rows are "
                         "the validity gate — then once against a live "
                         "rendezvous server whose GET /alerts is "
                         "checked against the spec's expect_alerts; "
                         "per-scenario rows ride the artifact as "
                         "sub_rows for perf/gate.py")
    ap.add_argument("--cpu", action="store_true",
                    help="force CPU (smoke mode)")
    ap.add_argument("--profile", metavar="DIR", default=None,
                    help="capture a jax.profiler trace of the timed scan "
                         "into DIR (inspect with xprof/tensorboard to see "
                         "where step time goes)")
    args = ap.parse_args()
    # Resolve the score-dtype default BEFORE any mode dispatch so every
    # mode (throughput, --scaling, ...) sees the same resolved protocol.
    # Explicitness is remembered for the --flash conflict warning below.
    args.score_dtype_explicit = args.score_dtype is not None
    if args.score_dtype is None:
        args.score_dtype = "input"

    # Sanitizer guard (docs/static-analysis.md): a TSan/ASan/UBSan build
    # of the native core is 5-20x slower — its numbers are correctness
    # evidence, never performance evidence, so every bench artifact path
    # refuses it outright rather than emitting a poisoned row the perf
    # gate would later baseline against.  Checked only when
    # HOROVOD_NATIVE_LIB overrides the default: the default library is
    # always a plain build, so the common case pays nothing.
    if os.environ.get("HOROVOD_NATIVE_LIB", ""):
        from horovod_tpu.common.basics import native_build_info
        san = native_build_info().get("sanitizer", "none")
        if san != "none":
            return fail(
                f"HOROVOD_NATIVE_LIB is a {san} sanitizer build; bench "
                "artifacts from a sanitized library are invalid by "
                "construction (docs/static-analysis.md)",
                cause="sanitized-lib")

    # The flash kernel never materializes a score tensor, so an EXPLICIT
    # --score-dtype (either value) cannot combine with --flash; labeling
    # such a row with a score dtype would record a measurement of
    # nothing.  Hoisted above the mode dispatch so --scaling runs warn
    # too; the resolved default stays silent.
    if args.flash and not args.cpu and args.score_dtype_explicit:
        print(f"--score-dtype {args.score_dtype} is ignored under --flash "
              "(the kernel has no score tensor)", file=sys.stderr)

    if args.cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
    if args.scenario:
        # Virtual-clock replay: no jax import unless the spec says
        # engine: real, and even then the replay is CPU by construction.
        os.environ["JAX_PLATFORMS"] = "cpu"
        return scenario_bench(args)
    if (args.wire or args.overlap or args.zero or args.layout) \
            and args.cpu and \
            "xla_force_host_platform_device_count" \
            not in os.environ.get("XLA_FLAGS", ""):
        # The wire/overlap/zero/layout sweeps are about collectives:
        # virtualize an 8-device CPU mesh (the test harness's topology)
        # so the rings actually ring.  Scoped here: the other cpu
        # smokes keep their 1-device runs.
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                                   " --xla_force_host_platform_device_"
                                   "count=8").strip()
    import jax
    import jax.numpy as jnp
    import optax
    from horovod_tpu.utils.platform import enable_compile_cache
    enable_compile_cache()

    if args.scaling:
        return scaling_bench(args)
    if args.wire:
        if args.profile:
            print("--profile is not supported with --wire (one trace per "
                  "policy would overwrite itself); ignoring",
                  file=sys.stderr)
        return wire_bench(args)
    if args.overlap:
        if args.profile:
            print("--profile is not supported with --overlap (one trace "
                  "per depth would overwrite itself); ignoring",
                  file=sys.stderr)
        return overlap_bench(args)
    if args.zero:
        if args.profile:
            print("--profile is not supported with --zero (one trace per "
                  "level would overwrite itself); ignoring",
                  file=sys.stderr)
        return zero_bench(args)
    if args.layout:
        if args.profile:
            print("--profile is not supported with --layout (one trace "
                  "per candidate mesh would overwrite itself); ignoring",
                  file=sys.stderr)
        return layout_bench(args)
    if args.serve:
        if args.profile:
            print("--profile is not supported with --serve (the tick "
                  "loop is not one scanned program); ignoring",
                  file=sys.stderr)
        if args.users:
            # Control-plane saturation sweep: scripted engine, no jax
            # compute — the measurement is the router+KV, not decode.
            if args.replicas:
                return serve_replicas_bench(args)
            return serve_users_bench(args)
        if args.replicas:
            print("--replicas needs --users (the replica sweep rides "
                  "the control-plane saturation harness)",
                  file=sys.stderr)
            return 2
        return serve_bench(args)
    if args.autotune:
        if args.profile:
            print("--profile is not supported with --autotune (its timing "
                  "loop re-traces per threshold); ignoring", file=sys.stderr)
        return autotune_bench(args)
    if args.resnet or args.cnn:
        return resnet_bench(args)
    if args.batch is None:
        args.batch = 16

    import horovod_tpu as hvd
    from horovod_tpu.models import llama
    from horovod_tpu.parallel.data_parallel import (make_scanned_train_step,
                                                    replicate, shard_batch)

    import dataclasses
    cfg = dataclasses.replace(
        bench_config(args.seq) if args.model == "bench"
        else llama.CONFIGS[args.model],
        fuse_proj=args.fuse and not args.no_fuse)
    if args.dim:
        cfg = dataclasses.replace(
            cfg, dim=args.dim,
            n_layers=args.layers or cfg.n_layers,
            n_heads=max(1, args.dim // 64),
            n_kv_heads=max(1, args.dim // 128),
            ffn_dim=args.ffn or 4 * args.dim)
    if args.cpu:
        cfg = llama.CONFIGS["tiny"]
        args.batch, args.seq, args.steps = 4, 64, 4

    device = init_backend(args.cpu)
    mesh = hvd.mesh()
    n_chips = hvd.size()

    params = llama.init(jax.random.PRNGKey(0), cfg)
    n_params = sum(int(np.prod(l.shape))
                   for l in jax.tree_util.tree_leaves(params))
    opt = optax.adamw(3e-4, weight_decay=0.01)
    # Pallas flash attention on TPU (ops/flash_attention.py): blockwise
    # online softmax on the MXU.
    attn_fn = None
    if args.flash and not args.cpu:
        import functools
        from horovod_tpu.ops.flash_attention import flash_attention
        attn_fn = functools.partial(flash_attention,
                                    block_q=args.block_q,
                                    block_k=args.block_k)
    elif args.score_dtype == "input":
        import functools
        from horovod_tpu.models import layers as L
        attn_fn = functools.partial(L.causal_attention, score_dtype=None)

    # --remat uses the model's PER-LAYER checkpointing (the standard TPU
    # memory lever); whole-loss jax.checkpoint wouldn't reduce the peak.
    run = make_scanned_train_step(
        lambda p, ids: llama.loss_fn(p, ids, cfg, attn_fn=attn_fn,
                                     remat=args.remat,
                                     ce_chunks=args.ce_chunks),
        opt, mesh, unroll=args.scan_unroll)
    params = replicate(params, mesh)
    opt_state = replicate(opt.init(params), mesh)

    global_batch = args.batch * n_chips
    rng = np.random.RandomState(0)

    def make_batches(k: int):
        ids = rng.randint(0, cfg.vocab, (k, global_batch, args.seq + 1),
                          dtype=np.int32)
        return shard_batch(jnp.asarray(ids), mesh, axis=1)

    # Warmup: compile + one real run at the SAME scan length as the timed
    # call (a different K would retrace, putting XLA compilation inside the
    # timed window), fenced by a host fetch.
    wparams, wopt, wlosses = run(params, opt_state, make_batches(args.steps))
    warm = np.asarray(wlosses)  # D2H fence
    if not np.all(np.isfinite(warm)):
        return fail("non-finite warmup loss", cause="invalid-result",
                    losses=warm.tolist())
    params, opt_state = wparams, wopt

    batches = make_batches(args.steps)
    with maybe_profile(args):
        t0 = time.perf_counter()
        params, opt_state, losses = run(params, opt_state, batches)
        losses_host = np.asarray(losses)  # D2H fence — timer is honest
        dt = time.perf_counter() - t0

    # --- sanity gates ---------------------------------------------------
    if losses_host.shape != (args.steps,):
        return fail("loss shape mismatch", cause="invalid-result",
                    shape=list(losses_host.shape))
    if not np.all(np.isfinite(losses_host)):
        return fail("non-finite loss in timed run", cause="invalid-result",
                    losses=losses_host.tolist())
    if args.steps > 1 and float(np.ptp(losses_host)) == 0.0:
        return fail("loss constant across steps — params not updating",
                    cause="invalid-result",
                    loss=float(losses_host[0]))

    tokens = args.steps * global_batch * args.seq
    loss_span = (f"loss {float(losses_host[0]):.3f}->"
                 f"{float(losses_host[-1]):.3f}")
    attn = ("flash" if (args.flash and not args.cpu)
            else f"xla-score-{args.score_dtype}")
    if args.cpu:
        # A CPU run proves the path and the schema; it yields counts,
        # never a rate or a utilization (there is no peak to hold it to).
        print(json.dumps({
            "metric": f"llama-{n_params/1e6:.0f}M train smoke (cpu, "
                      f"{jnp.dtype(cfg.dtype).name}, seq={args.seq}, "
                      f"{loss_span})",
            "value": tokens,
            "unit": "tokens",
            "vs_baseline_is": "not measured (cpu)",
            "vs_baseline": None,
            "attn": attn,
            **device,
            "metrics": metrics_summary(),
        }))
        return 0

    tok_per_sec_chip = tokens / dt / n_chips
    from horovod_tpu.perf import costmodel as cm
    chip = device["device_kind"]
    peak = cm.peak_flops(chip)
    # The conservative 6N convention headlines; the attention-inclusive
    # variant rides beside it (mfu_attn — convention documented in
    # horovod_tpu/perf/costmodel.py train_flops_per_token).
    train_flops_per_token = cm.train_flops_per_token(n_params)
    mfu = (tok_per_sec_chip * train_flops_per_token) / peak
    mfu_attn = (tok_per_sec_chip * cm.train_flops_per_token(
        n_params, attention=dict(n_layers=cfg.n_layers, dim=cfg.dim,
                                 seq=args.seq, causal=True))) / peak

    if not (0.0 < mfu < 1.0):
        return fail(
            f"MFU {mfu:.4f} outside (0,1) — timing or peak detection broken",
            cause="invalid-result",
            chip=chip, tok_per_sec_chip=tok_per_sec_chip,
            loss_first=float(losses_host[0]), loss_last=float(losses_host[-1]))

    print(json.dumps({
        "metric": f"llama-{n_params/1e6:.0f}M train tokens/sec/chip "
                  f"({chip}, {jnp.dtype(cfg.dtype).name}, seq={args.seq}, "
                  f"{loss_span})",
        "value": round(tok_per_sec_chip, 1),
        "unit": "tokens/sec/chip",
        # One schema, one meaning: vs_baseline IS the MFU for model
        # benches; mfu/vs_baseline_is make that explicit in the artifact
        # (a multiple-of-peak artifact can never masquerade as MFU).
        "mfu": round(mfu, 4),
        # Attention-FLOPs-included MFU (6N + 6·L·seq·dim causal term,
        # perf/costmodel.py): higher than `mfu` by construction; the
        # conservative 6N number stays the headline/vs_baseline.
        "mfu_attn": round(mfu_attn, 4),
        "vs_baseline_is": "mfu",
        "vs_baseline": round(mfu, 4),
        # Self-describing protocol: which attention path actually ran,
        # so an artifact row never depends on remembering what the
        # bench default was the day it was recorded.
        "attn": attn,
        **device,
        # Controller-level evidence riding the artifact (docs/metrics.md).
        "metrics": metrics_summary(),
    }))
    return 0


def scaling_bench(args) -> int:
    """Weak-scaling efficiency over mesh prefixes — the REFERENCE'S
    headline metric (docs/benchmarks.rst:12-43 publishes 90%/90%/68%
    scaling efficiency at 512 GPUs; BASELINE.md targets >=90% on
    v5p-128).  Per-chip batch is held fixed while the data mesh grows
    over device prefixes {1, 2, 4, ...}; efficiency(k) = per-chip
    throughput at k chips / per-chip throughput at 1 chip.  On one chip
    this degenerates to k=1 (the mode exists for multi-chip hardware;
    the CPU-virtual harness proves the machinery)."""
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import Mesh

    import horovod_tpu as hvd
    from horovod_tpu.models import llama
    from horovod_tpu.parallel.data_parallel import (make_scanned_train_step,
                                                    replicate, shard_batch)

    device = init_backend(args.cpu)
    devices = jax.devices()
    sizes = [k for k in (1, 2, 4, 8, 16, 32, 64, 128, 256)
             if k <= len(devices)]
    import dataclasses
    if args.cpu:
        cfg = llama.CONFIGS["tiny"]
        batch, seq, steps = 4, 64, 6
    else:
        cfg = llama.CONFIGS[args.model] if args.model != "bench" else \
            llama.LlamaConfig(vocab=32768, dim=1024, n_layers=8,
                              n_heads=16, n_kv_heads=8, ffn_dim=4096,
                              max_seq=max(2048, args.seq),
                              dtype=jnp.bfloat16)
        batch, seq, steps = (args.batch or 16), args.seq, args.steps
    # The perf levers mean the same thing here as in the throughput
    # bench: an efficiency labeled with a flag must have run it.
    cfg = dataclasses.replace(cfg, fuse_proj=args.fuse and not args.no_fuse)
    attn_fn = None
    if args.flash and not args.cpu:
        import functools
        from horovod_tpu.ops.flash_attention import flash_attention
        attn_fn = functools.partial(flash_attention, block_q=args.block_q,
                                    block_k=args.block_k)
    elif args.score_dtype == "input":
        import functools
        from horovod_tpu.models import layers as L
        attn_fn = functools.partial(L.causal_attention, score_dtype=None)
    if args.profile:
        print("--profile is ignored under --scaling (one trace per mesh "
              "size would overwrite itself)", file=sys.stderr)
    opt = optax.adamw(3e-4, weight_decay=0.01)
    base_params = llama.init(jax.random.PRNGKey(0), cfg)
    rng = np.random.RandomState(0)

    rates = {}
    axis = hvd.mesh().axis_names[0]  # train step syncs over this name
    for k in sizes:
        mesh = Mesh(np.asarray(devices[:k]), (axis,))
        run = make_scanned_train_step(
            lambda p, ids: llama.loss_fn(p, ids, cfg, attn_fn=attn_fn,
                                         remat=args.remat,
                                         ce_chunks=args.ce_chunks),
            opt, mesh, axis_name=axis, unroll=args.scan_unroll)
        params = replicate(base_params, mesh)
        opt_state = replicate(opt.init(params), mesh)

        def make_batches():
            ids = rng.randint(0, cfg.vocab, (steps, batch * k, seq + 1),
                              dtype=np.int32)
            return shard_batch(jnp.asarray(ids), mesh,
                               axis_name=axis, axis=1)

        # compile + warm outside the timed window, fenced by a host fetch
        params, opt_state, wl = run(params, opt_state, make_batches())
        if not np.all(np.isfinite(np.asarray(wl))):
            return fail(f"non-finite warmup loss at {k} chips",
                        cause="invalid-result")
        batches = make_batches()
        t0 = time.perf_counter()
        params, opt_state, losses = run(params, opt_state, batches)
        losses_host = np.asarray(losses)  # D2H fence — timer is honest
        dt = time.perf_counter() - t0
        if not np.all(np.isfinite(losses_host)):
            return fail(f"non-finite loss at {k} chips",
                        cause="invalid-result")
        if steps > 1 and float(np.ptp(losses_host)) == 0.0:
            return fail(f"loss constant across steps at {k} chips — "
                        "params not updating", cause="invalid-result")
        # per-chip tok/s (global tokens / dt / k == steps*batch*seq/dt)
        rates[k] = steps * batch * seq / dt

    top = sizes[-1]
    eff = rates[top] / rates[1] if top > 1 else 1.0
    if not (0.0 < eff <= 1.5):  # >1 = measurement noise beyond sanity
        return fail(f"scaling efficiency {eff:.3f} implausible",
                    cause="invalid-result", rates=rates)
    chip = "cpu" if args.cpu else device["device_kind"]
    per_size = ", ".join(f"{k}: {rates[k]:,.0f}" for k in sizes)
    print(json.dumps({
        "metric": (f"llama weak-scaling efficiency at {top} chips vs 1 "
                   f"({chip}, per-chip batch {batch}, seq {seq}; "
                   f"per-chip tok/s by size: {per_size})"),
        "value": round(eff, 4),
        "unit": "scaling_efficiency",
        "vs_baseline_is": "weak_scaling_efficiency_vs_1chip",
        "vs_baseline": round(eff, 4),
        "rates_tok_s_chip": {str(k): round(v, 1)
                             for k, v in rates.items()},
        "attn": ("flash" if (args.flash and not args.cpu)
                 else f"xla-score-{args.score_dtype}"),
        "metrics": metrics_summary(),
    }))
    return 0


def autotune_bench(args) -> int:
    """Autotune proven end to end (reference: parameter_manager.{h,cc}
    scoring loop): the fused gradient sync runs under the live autotuner,
    every accepted (threshold, cycle) sample re-traces the bucket plan,
    the trajectory lands in HOROVOD_AUTOTUNE_LOG, and the JSON reports
    the tuned threshold plus after/before sync-throughput ratio."""
    os.environ["HOROVOD_AUTOTUNE"] = "1"
    log_path = os.environ.setdefault("HOROVOD_AUTOTUNE_LOG",
                                     "autotune_log.csv")
    os.environ.setdefault("HOROVOD_AUTOTUNE_WARMUP_SAMPLES", "1")
    os.environ.setdefault("HOROVOD_AUTOTUNE_STEPS_PER_SAMPLE", "2")
    import jax
    from jax.sharding import PartitionSpec as P

    import horovod_tpu as hvd
    from jax import shard_map
    from horovod_tpu.optimizer import sync_gradients

    device = init_backend(args.cpu)
    mesh = hvd.mesh()
    axis = mesh.axis_names[0]
    n = hvd.size()
    tuner = hvd.autotuner()
    if tuner is None:
        return fail("HOROVOD_AUTOTUNE=1 did not enable the autotuner",
                    cause="invalid-result")

    # A model-like gradient set: a few big tensors + a tail of small ones
    # (what makes bucketing matter).  ~100 MB on TPU, ~2 MB on CPU.
    rng = np.random.RandomState(0)
    per = 128 if args.cpu else 8192
    gs = ([rng.randn(n, per * 16).astype(np.float32) for _ in range(12)] +
          [rng.randn(n, per).astype(np.float32) for _ in range(24)] +
          [rng.randn(n, 16).astype(np.float32) for _ in range(24)])
    total = sum(g.nbytes // n for g in gs)

    compiled = {}

    def step_fn(threshold: int):
        fn = compiled.get(threshold)
        if fn is None:
            def body(*leaves):
                return tuple(sync_gradients(
                    list(leaves), axis,
                    fusion_threshold_bytes=threshold))
            fn = jax.jit(shard_map(
                body, mesh=mesh, in_specs=(P(axis),) * len(gs),
                out_specs=(P(axis),) * len(gs), check_vma=False))
            compiled[threshold] = fn
        return fn

    def timed_sync(threshold: int, steps: int = 5) -> float:
        """bytes/sec of the fused sync at a given threshold."""
        fn = step_fn(threshold)
        jax.block_until_ready(fn(*gs))  # compile outside the timing
        t0 = time.perf_counter()
        for _ in range(steps):
            out = fn(*gs)
        jax.block_until_ready(out)
        return steps * total / (time.perf_counter() - t0)

    initial = tuner.fusion_threshold
    steps = 0
    while not tuner.done and steps < 120:
        thr = tuner.fusion_threshold
        fresh = thr not in compiled
        fn = step_fn(thr)
        if fresh:
            # compile OUTSIDE the measurement: a candidate scored with
            # its one-time trace+compile cost inside the window would
            # always lose to the warmed-up incumbent
            jax.block_until_ready(fn(*gs))
        with tuner.measure(nbytes=total):
            jax.block_until_ready(fn(*gs))
        steps += 1
    if not tuner.done:
        return fail(f"autotune did not converge in {steps} steps",
                    cause="invalid-result")
    tuned = tuner.fusion_threshold

    before = timed_sync(initial)
    after = timed_sync(tuned)
    print(json.dumps({
        "metric": f"autotune fused-sync GB/s (tuned threshold "
                  f"{tuned / (1 << 20):.1f} MiB vs initial "
                  f"{initial / (1 << 20):.0f} MiB, {steps} steps, "
                  f"log={log_path})",
        "value": round(after / 1e9, 3),
        "unit": "GB/s",
        "vs_baseline_is": "speedup_vs_initial_threshold",
        "vs_baseline": round(after / max(before, 1e-9), 4),
        "metrics": metrics_summary(),
    }))
    return 0


def wire_bench(args) -> int:
    """Wire-policy sweep (ops/wire.py; docs/tensor-fusion.md): the fused
    gradient sync runs under each wire policy on a model-like bucket mix
    (a few big tensors + a long small tail), with EF residuals carried
    step to step.  Per policy the artifact records the MODELED per-chip
    wire bytes/step (the analytical ring model — on the CPU-virtual
    harness there is no physical wire to count), the measured step time,
    and the per-bucket EF residual norms; every policy's decode is
    asserted bit-identical across ranks.  A second section re-initializes
    a two-level (dcn, ici) mesh and compares dcn_int8's DCN-leg bytes
    against the flat int8 ring's."""
    import jax
    from jax.sharding import PartitionSpec as P

    import horovod_tpu as hvd
    from horovod_tpu.common.reduce_op import Average
    from horovod_tpu.ops import wire
    from jax import shard_map
    from horovod_tpu.ops.fusion import make_plan
    from horovod_tpu.optimizer import sync_gradients_ef, \
        wire_residual_report

    device = init_backend(args.cpu)
    n = hvd.size()
    timed_steps = 5 if args.cpu else 20

    # Model-like gradient mix: bucket sizes must straddle the auto
    # policy's thresholds so 'auto' demonstrably picks PER-BUCKET formats
    # (big buckets -> int8 ring, the mid tail -> bf16).
    rng = np.random.RandomState(0)
    per = 8192
    gs = [rng.randn(n, per * 16).astype(np.float32) for _ in range(12)] + \
         [rng.randn(n, per).astype(np.float32) for _ in range(24)] + \
         [rng.randn(n, 16).astype(np.float32) for _ in range(24)]
    threshold = 4 * 1024 * 1024
    # The per-rank leaf shapes the sync sees inside shard_map.
    shard_shapes = [(1, g.shape[1]) for g in gs]
    dtypes = [g.dtype for g in gs]
    plan = make_plan(shard_shapes, dtypes, threshold)
    exact = [g.mean(axis=0) for g in gs]

    def modeled_bytes(policy_name, axis_name, axis_sizes):
        pol = wire.get_policy(policy_name)
        total, per_fmt = 0.0, {}
        for b in plan.buckets:
            fmt = wire.resolve_format(pol(b.nbytes, b.dtype, axis_name),
                                      b.dtype, axis_name, Average)
            m = wire.modeled_wire_bytes(sum(b.sizes),
                                        np.dtype(b.dtype).itemsize,
                                        fmt, axis_sizes)
            total += m["bottleneck"]
            per_fmt[fmt] = per_fmt.get(fmt, 0.0) + m["bottleneck"]
        return int(total), {k: int(v) for k, v in sorted(per_fmt.items())}

    def run_policy(policy_name, mesh, axis_name, axis_spec):
        specs = (tuple(P(*axis_spec) for _ in gs),) * 2

        def body(leaves, res):
            s, r = sync_gradients_ef(list(leaves), list(res), axis_name,
                                     fusion_threshold_bytes=threshold,
                                     wire_policy=policy_name)
            return tuple(s), tuple(r)

        fn = jax.jit(shard_map(body, mesh=mesh, in_specs=specs,
                               out_specs=specs, check_vma=False))
        res = tuple(np.zeros_like(g) for g in gs)
        leaves = tuple(gs)
        out, res = fn(leaves, res)   # compile + warm outside the timing
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        for _ in range(timed_steps):
            out, res = fn(leaves, res)
        jax.block_until_ready(out)
        dt = (time.perf_counter() - t0) / timed_steps
        # decode determinism: every rank must hold identical values
        for o in out:
            rows = np.asarray(o)
            for r in range(1, rows.shape[0]):
                if not np.array_equal(rows[r], rows[0]):
                    raise AssertionError(
                        f"policy {policy_name}: rank {r} decoded "
                        "different values than rank 0")
        # accuracy guard: still a mean within the formats' noise
        err = max(float(np.abs(np.asarray(o)[0] - e).max())
                  for o, e in zip(out, exact))
        if err > 0.1:
            raise AssertionError(
                f"policy {policy_name}: error {err} vs exact mean")
        norms = wire_residual_report([np.asarray(r) for r in res],
                                     plan=plan)
        return dt, err, {k: round(v, 6) for k, v in norms.items()
                         if v > 0.0}

    mesh = hvd.mesh()
    axis = mesh.axis_names[0]
    policies = ["none", "bf16", "fp16", "int8_ring", "auto"]
    results = {}
    try:
        for name in policies:
            wire_bytes, per_fmt = modeled_bytes(name, axis, {"flat": n})
            dt, err, norms = run_policy(name, mesh, axis, (axis,))
            results[name] = {
                "wire_bytes_per_step": wire_bytes,
                "wire_bytes_by_format": per_fmt,
                "step_time_s": round(dt, 6),
                "max_abs_err": round(err, 6),
                "residual_norm": norms,
                "decode_deterministic": True,
            }
    except AssertionError as e:
        return fail(str(e), cause="invalid-result")

    # Acceptance ratios on the bucket mix (ISSUE 3): int8 carries <= 1/2
    # the modeled wire bytes of bf16, <= 1/4 of uncompressed fp32.
    b_none = results["none"]["wire_bytes_per_step"]
    b_bf16 = results["bf16"]["wire_bytes_per_step"]
    b_int8 = results["int8_ring"]["wire_bytes_per_step"]
    if not (b_int8 * 2 <= b_bf16 and b_int8 * 4 <= b_none):
        return fail(f"int8 wire bytes {b_int8} not <= bf16/2 "
                    f"({b_bf16}) and fp32/4 ({b_none})",
                    cause="invalid-result")

    # Two-level section: dcn_int8 quantizes only the slow leg.  The CPU
    # harness re-initializes the same 8 virtual devices as a 2x4
    # (dcn, ici) mesh; on hardware this needs a multi-slice mesh.
    two_level = {}
    if n % 2 == 0 and n >= 4:
        hvd.shutdown()
        hvd.init(mesh_spec=f"dcn.data=2,ici.data={n // 2}")
        mesh2 = hvd.mesh()
        axis2 = ("dcn.data", "ici.data")
        sizes2 = {"dcn": 2, "ici": n // 2}
        try:
            for name in ("int8_ring", "dcn_int8"):
                wire_bytes, per_fmt = modeled_bytes(name, axis2, sizes2)
                dt, err, norms = run_policy(name, mesh2, axis2, (axis2,))
                two_level[name] = {
                    "dcn_wire_bytes_per_step": wire_bytes,
                    "step_time_s": round(dt, 6),
                    "max_abs_err": round(err, 6),
                    "residual_norm": norms,
                    "decode_deterministic": True,
                }
        except AssertionError as e:
            return fail(str(e), cause="invalid-result")
        d_flat = two_level["int8_ring"]["dcn_wire_bytes_per_step"]
        d_sel = two_level["dcn_int8"]["dcn_wire_bytes_per_step"]
        if d_sel >= d_flat:
            return fail(f"dcn_int8 DCN bytes {d_sel} not below the flat "
                        f"int8 ring's {d_flat}", cause="invalid-result")

    chip = "cpu" if args.cpu else device["device_kind"]
    label = (f"CPU-virtual ({n} XLA host devices, loopback; no chip, no "
             "host<->device — wire bytes are the analytical ring model)"
             if chip == "cpu" else chip)
    print(json.dumps({
        "metric": f"wire-policy sweep: int8 ring carries "
                  f"{b_int8 / b_none:.3f}x the modeled wire bytes of "
                  f"fp32 ({b_int8 / b_bf16:.3f}x bf16) on the "
                  f"{plan.num_buckets}-bucket mix [{label}]",
        "value": round(b_int8 / b_none, 4),
        "unit": "wire_bytes_ratio_int8_vs_fp32",
        "vs_baseline_is": "modeled_wire_bytes_int8_over_fp32",
        "vs_baseline": round(b_int8 / b_none, 4),
        "label": label,
        "policies": results,
        "two_level": two_level,
        "metrics": metrics_summary(),
    }))
    return 0


def overlap_bench(args) -> int:
    """Overlap-plane sweep (ops/overlap.py; docs/overlap.md): the
    microbatch-pipelined train step runs at depth 0 (the sequential
    issue order of the same per-microbatch syncs), 1 and 2, plus the
    legacy accumulate-k-then-sync baseline ('off'); the bucket-
    interleaved ZeRO-1 step runs against the monolithic chain.  Per row
    the artifact records the measured step time and the ANALYTICAL
    {exposed_comm_bytes, overlapped_fraction} split (the hvd_overlap_*
    gauge model — on the CPU-virtual harness there is no latency-hiding
    scheduler, so wall-clock parity is expected and only the schedule
    is being proven; wins need a real TPU).  The pipelined ≡ sequential
    params guarantee is asserted per depth before anything is printed."""
    import jax
    import jax.numpy as jnp
    import optax

    import horovod_tpu as hvd
    from horovod_tpu.parallel.data_parallel import (
        make_microbatched_train_step, replicate, shard_batch)
    from horovod_tpu.parallel.zero import (init_sharded_opt_state,
                                           make_zero1_train_step)
    from horovod_tpu.utils import metrics as M

    device = init_backend(args.cpu)
    mesh = hvd.mesh()
    n = hvd.size()
    k = 4
    timed_steps = 5 if args.cpu else 20
    dim = 64 if args.cpu else 1024

    rng = np.random.RandomState(0)
    params = {"w1": jnp.asarray(rng.randn(dim, dim) / np.sqrt(dim),
                                jnp.float32),
              "b1": jnp.asarray(np.zeros(dim), jnp.float32),
              "w2": jnp.asarray(rng.randn(dim, 1) / np.sqrt(dim),
                                jnp.float32)}

    def loss_fn(p, batch):
        x, y = batch
        h = jnp.tanh(x @ p["w1"] + p["b1"])
        return jnp.mean((h @ p["w2"] - y) ** 2)

    xs = rng.randn(k, 8 * n, dim).astype(np.float32)
    ys = rng.randn(k, 8 * n, 1).astype(np.float32)
    batch = (shard_batch(jnp.asarray(xs), mesh, axis=1),
             shard_batch(jnp.asarray(ys), mesh, axis=1))

    grad_bytes = sum(int(np.prod(l.shape)) * 4
                     for l in jax.tree_util.tree_leaves(params))
    from horovod_tpu.ops.wire import modeled_wire_bytes
    per_sync = modeled_wire_bytes(grad_bytes // 4, 4, "none",
                                  {"flat": n})["bottleneck"]

    def run_mode(overlap, depth):
        opt = optax.sgd(0.05)
        step = make_microbatched_train_step(
            loss_fn, opt, mesh, backward_passes_per_step=k,
            overlap=overlap, overlap_depth=depth, donate=False)
        from horovod_tpu.optimizer import distributed_optimizer
        dopt = distributed_optimizer(opt, axis_name="hvd",
                                     backward_passes_per_step=k,
                                     overlap=overlap, overlap_depth=depth)
        p = replicate(params, mesh)
        s = replicate(dopt.init(params), mesh)
        p, s, loss = step(p, s, batch)          # compile + warm
        jax.block_until_ready(loss)
        t0 = time.perf_counter()
        for _ in range(timed_steps):
            p, s, loss = step(p, s, batch)
        jax.block_until_ready(loss)
        dt = (time.perf_counter() - t0) / timed_steps
        return dt, p, float(loss)

    results = {}
    ref_params = None
    try:
        for label, overlap, depth in (("off", False, None),
                                      ("0", True, 0),
                                      ("1", True, 1),
                                      ("2", True, 2)):
            dt, p, loss = run_mode(overlap, depth)
            if not overlap:
                # legacy baseline: one sync after microbatch k — the
                # whole sync is exposed, by construction.
                exposed, frac = float(k * per_sync), 0.0
            else:
                exposed = M.OVERLAP_EXPOSED_BYTES.value(plane="microbatch")
                frac = M.OVERLAP_FRACTION.value(plane="microbatch")
            if label == "0":
                ref_params = p
            elif overlap:
                # the numerical-equivalence guarantee: scheduling only
                for key in params:
                    err = float(np.abs(np.asarray(p[key]) -
                                       np.asarray(ref_params[key])).max())
                    if err > 1e-5:
                        raise AssertionError(
                            f"depth {label}: params diverge from the "
                            f"sequential schedule by {err}")
            results[label] = {
                "step_time_s": round(dt, 6),
                "exposed_comm_bytes": int(exposed),
                "overlapped_fraction": round(float(frac), 4),
                "loss": round(loss, 6),
            }
    except AssertionError as e:
        return fail(str(e), cause="invalid-result")

    # ZeRO-1 section: monolithic flat chain vs the bucket-interleaved
    # pipeline (a small threshold forces multiple buckets on the toy).
    zthresh = dim * 4  # bytes: w1 alone spans several buckets
    zero1 = {}
    try:
        opt = optax.adamw(1e-2, weight_decay=0.01)
        zbatch = (shard_batch(jnp.asarray(xs[0]), mesh),
                  shard_batch(jnp.asarray(ys[0]), mesh))
        finals = {}
        for label, inter in (("monolithic", False), ("interleaved", True)):
            step = make_zero1_train_step(
                loss_fn, opt, mesh, interleaved=inter,
                fusion_threshold_bytes=zthresh if inter else None,
                donate=False)
            p = replicate(params, mesh)
            s = init_sharded_opt_state(
                opt, p, mesh, interleaved=inter,
                fusion_threshold_bytes=zthresh if inter else None)
            p, s, loss = step(p, s, zbatch)
            jax.block_until_ready(loss)
            t0 = time.perf_counter()
            for _ in range(timed_steps):
                p, s, loss = step(p, s, zbatch)
            jax.block_until_ready(loss)
            dt = (time.perf_counter() - t0) / timed_steps
            finals[label] = p
            row = {"step_time_s": round(dt, 6)}
            if inter:
                row["exposed_comm_bytes"] = int(
                    M.OVERLAP_EXPOSED_BYTES.value(plane="zero1"))
                row["overlapped_fraction"] = round(float(
                    M.OVERLAP_FRACTION.value(plane="zero1")), 4)
            zero1[label] = row
        for key in params:
            err = float(np.abs(np.asarray(finals["interleaved"][key]) -
                               np.asarray(finals["monolithic"][key])).max())
            if err > 1e-5:
                raise AssertionError(
                    f"interleaved zero-1 diverges from monolithic by {err}")
    except AssertionError as e:
        return fail(str(e), cause="invalid-result")

    chip = "cpu" if args.cpu else device["device_kind"]
    label = (f"CPU-virtual ({n} XLA host devices, loopback; no chip, no "
             "latency-hiding scheduler — exposed bytes are the "
             "analytical model, wall-clock parity expected)"
             if chip == "cpu" else chip)
    frac1 = results["1"]["overlapped_fraction"]
    print(json.dumps({
        "metric": f"overlap sweep: depth-1 microbatch pipeline hides "
                  f"{frac1:.2f} of modeled sync bytes behind compute "
                  f"(k={k}, {n} ranks) [{label}]",
        "value": frac1,
        "unit": "overlapped_fraction",
        "vs_baseline_is": "overlapped_fraction_depth1_vs_sequential",
        "vs_baseline": frac1,
        "label": label,
        "depths": results,
        "zero1": zero1,
        "equivalence_asserted": True,
        "metrics": metrics_summary(),
    }))
    return 0


def zero_bench(args) -> int:
    """ZeRO weight-update sharding sweep (parallel/zero.py;
    docs/zero.md): the chain runs at levels 1/2/3 (plus the level-0
    plain-DP baseline) on the quadratic toy with
    backward_passes_per_step=2, and at levels 1/2/3 on llama-tiny.  Per
    level the artifact records the ANALYTICAL per-rank peak
    {params, grads, opt-state, total} bytes
    (perf/costmodel.zero_memory_bytes) beside the MEASURED peak from
    the memory plane (``measured_peak_bytes`` + ``mem_drift_ratio``,
    perf/memstats.py — on the CPU-virtual harness the live-buffer
    aggregate, labeled by ``measured_source``), the modeled
    exposed_comm_bytes, the measured step_time and the ledger's
    model-drift ratio (the prediction confronted with the wall clock).  Level 1/2/3 bit-near
    parameter equivalence is asserted before anything is printed; on
    the CPU-virtual harness wall-clock parity is expected (no
    latency-hiding scheduler, loopback fabric) and the row is labeled
    accordingly — the memory columns are the headline, the step-time
    ratios the regression gate."""
    import jax
    import jax.numpy as jnp
    import optax

    import horovod_tpu as hvd
    from horovod_tpu.parallel import zero as Z
    from horovod_tpu.parallel.data_parallel import (make_train_step,
                                                    replicate, shard_batch)
    from horovod_tpu.perf import costmodel as cm
    from horovod_tpu.perf import memstats
    from horovod_tpu.utils import metrics as M

    device = init_backend(args.cpu)
    mesh = hvd.mesh()
    n = hvd.size()
    k = 2
    timed_steps = 5 if args.cpu else 20
    dim = 64 if args.cpu else 1024
    thresh = dim * 4  # several buckets on the toy
    opt_slots = 2     # adamw: mu + nu

    rng = np.random.RandomState(0)
    params = {"w1": jnp.asarray(rng.randn(dim, dim) / np.sqrt(dim),
                                jnp.float32),
              "b1": jnp.asarray(np.zeros(dim), jnp.float32),
              "w2": jnp.asarray(rng.randn(dim, 1) / np.sqrt(dim),
                                jnp.float32)}
    n_params = sum(int(np.prod(l.shape))
                   for l in jax.tree_util.tree_leaves(params))

    def loss_fn(p, batch):
        x, y = batch
        h = jnp.tanh(x @ p["w1"] + p["b1"])
        return jnp.mean((h @ p["w2"] - y) ** 2)

    xs = rng.randn(k, 8 * n, dim).astype(np.float32)
    ys = rng.randn(k, 8 * n, 1).astype(np.float32)
    kbatch = (shard_batch(jnp.asarray(xs), mesh, axis=1),
              shard_batch(jnp.asarray(ys), mesh, axis=1))
    # level 0 consumes the same samples as ONE merged batch (gradient of
    # the merged mean == mean of per-microbatch gradients: same update)
    mbatch = (shard_batch(jnp.asarray(xs.reshape(-1, dim)), mesh),
              shard_batch(jnp.asarray(ys.reshape(-1, 1)), mesh))

    def run_toy_level(level):
        import horovod_tpu.perf as perf
        opt = optax.adamw(1e-2, weight_decay=0.01)
        if level == 0:
            step = make_train_step(loss_fn, opt, mesh, donate=False)
            p = replicate(params, mesh)
            s = replicate(opt.init(params), mesh)
            batch = mbatch
        else:
            step = Z.make_zero_train_step(
                loss_fn, opt, mesh, zero_level=level,
                backward_passes_per_step=k,
                fusion_threshold_bytes=thresh, params_template=params,
                donate=False)
            s = Z.init_zero_state(opt, replicate(params, mesh), mesh,
                                  zero_level=level,
                                  fusion_threshold_bytes=thresh)
            p = (Z.shard_zero3_params(replicate(params, mesh), mesh,
                                      fusion_threshold_bytes=thresh)
                 if level == 3 else replicate(params, mesh))
            batch = kbatch
        comm = cm.zero_comm_bytes(n_params, n, level, k=k)
        perf.reset()
        memstats.reset()  # per-level measured peak, not the sweep's max
        perf.configure(comm_bytes_per_step=comm["total_bytes"],
                       zero_model={"n_params": n_params, "world": n,
                                   "level": level, "k": k,
                                   "opt_slots": opt_slots})
        p, s, loss = step(p, s, batch)          # compile + warm
        jax.block_until_ready(loss)
        t0 = time.perf_counter()
        for _ in range(timed_steps):
            with perf.timed_step():
                p, s, loss = step(p, s, batch)
                jax.block_until_ready(loss)
        dt = (time.perf_counter() - t0) / timed_steps
        rep = hvd.perf_report()
        # The measured side of the analytical peak_bytes column
        # (perf/memstats.py; docs/memory.md): live-buffer residency
        # after the level's steps, reconciled against zero_memory_bytes.
        mrow = memstats.sample(force=True) or {}
        if level == 3:
            p = Z.gather_zero3_params(p, params, mesh,
                                      fusion_threshold_bytes=thresh)
        return dt, p, float(loss), comm, rep, mrow

    toy = {}
    finals = {}
    try:
        for level in (0, 1, 2, 3):
            dt, p, loss, comm, rep, mrow = run_toy_level(level)
            finals[level] = p
            mem = cm.zero_memory_bytes(level, n_params, n,
                                       opt_slots=opt_slots)
            row = {
                "step_time_s": round(dt, 6),
                "exposed_comm_bytes": int(comm["total_bytes"]),
                "peak_bytes": mem,
                "measured_peak_bytes": mrow.get("peak_bytes_in_use"),
                "measured_source": mrow.get("source"),
                "mem_drift_ratio": mrow.get("model_drift_ratio"),
                "loss": round(loss, 6),
                "model_drift_ratio": rep.get("model_drift_ratio"),
            }
            if level >= 1:
                row["traced_exposed_comm_bytes"] = int(
                    M.OVERLAP_EXPOSED_BYTES.value(plane=f"zero{level}"))
            toy[str(level)] = row
        # the equivalence guarantee: levels 1/2/3 bit-near, level 0
        # within psum-linearity tolerance of the merged batch
        for level in (2, 3):
            for key in params:
                err = float(np.abs(np.asarray(finals[level][key]) -
                                   np.asarray(finals[1][key])).max())
                if err > 1e-5:
                    raise AssertionError(
                        f"level {level} diverges from level 1 by {err}")
        for key in params:
            err = float(np.abs(np.asarray(finals[1][key]) -
                               np.asarray(finals[0][key])).max())
            if err > 1e-4:
                raise AssertionError(
                    f"level 1 diverges from the plain-DP baseline by "
                    f"{err}")
    except AssertionError as e:
        return fail(str(e), cause="invalid-result")

    # ---- llama-tiny leg: the model-shaped workload (levels 1-3, k=1)
    from horovod_tpu.models import llama as llama_mod
    cfg = llama_mod.CONFIGS["tiny"]
    lbatch_rows, lseq, lsteps = 2 * n, 32, (2 if args.cpu else 10)
    lthresh = 32 * 1024
    lparams = llama_mod.init(jax.random.PRNGKey(0), cfg)
    ln_params = sum(int(np.prod(l.shape))
                    for l in jax.tree_util.tree_leaves(lparams))
    ids = np.random.RandomState(1).randint(
        0, cfg.vocab, (lbatch_rows, lseq + 1), dtype=np.int32)
    lids = shard_batch(jnp.asarray(ids), mesh)

    def run_llama_level(level):
        import horovod_tpu.perf as perf
        opt = optax.adamw(3e-4, weight_decay=0.01)
        step = Z.make_zero_train_step(
            lambda p, b: llama_mod.loss_fn(p, b, cfg),
            opt, mesh, zero_level=level, fusion_threshold_bytes=lthresh,
            params_template=lparams, donate=False)
        s = Z.init_zero_state(opt, replicate(lparams, mesh), mesh,
                              zero_level=level,
                              fusion_threshold_bytes=lthresh)
        p = (Z.shard_zero3_params(replicate(lparams, mesh), mesh,
                                  fusion_threshold_bytes=lthresh)
             if level == 3 else replicate(lparams, mesh))
        perf.reset()
        memstats.reset()
        perf.configure(zero_model={"n_params": ln_params, "world": n,
                                   "level": level,
                                   "opt_slots": opt_slots})
        p, s, loss = step(p, s, lids)
        jax.block_until_ready(loss)
        t0 = time.perf_counter()
        for _ in range(lsteps):
            p, s, loss = step(p, s, lids)
        jax.block_until_ready(loss)
        dt = (time.perf_counter() - t0) / lsteps
        mrow = memstats.sample(force=True) or {}
        if level == 3:
            p = Z.gather_zero3_params(p, lparams, mesh,
                                      fusion_threshold_bytes=lthresh)
        return dt, p, float(loss), mrow

    llama_rows = {}
    lfinals = {}
    try:
        for level in (1, 2, 3):
            dt, p, loss, mrow = run_llama_level(level)
            lfinals[level] = p
            mem = cm.zero_memory_bytes(level, ln_params, n,
                                       opt_slots=opt_slots)
            llama_rows[str(level)] = {
                "step_time_s": round(dt, 6),
                "tokens_per_s": round(lbatch_rows * lseq / dt, 1),
                "exposed_comm_bytes": int(cm.zero_comm_bytes(
                    ln_params, n, level)["total_bytes"]),
                "peak_bytes": mem,
                "measured_peak_bytes": mrow.get("peak_bytes_in_use"),
                "measured_source": mrow.get("source"),
                "mem_drift_ratio": mrow.get("model_drift_ratio"),
                "loss": round(loss, 6),
            }
        for level in (2, 3):
            for a, b in zip(jax.tree_util.tree_leaves(lfinals[level]),
                            jax.tree_util.tree_leaves(lfinals[1])):
                err = float(np.abs(np.asarray(a) - np.asarray(b)).max())
                if err > 1e-4:
                    raise AssertionError(
                        f"llama level {level} diverges from level 1 by "
                        f"{err}")
    except AssertionError as e:
        return fail(str(e), cause="invalid-result")

    # ---- gate rows: the analytical memory reductions (deterministic)
    # and the same-run step-time ratios (correlated noise cancels)
    def _sg(level, N):
        m = cm.zero_memory_bytes(level, N, n, opt_slots=opt_slots)
        return m["grads_bytes"] + m["opt_state_bytes"]

    red2 = _sg(0, n_params) / _sg(2, n_params)
    red3p = (cm.zero_memory_bytes(0, n_params, n)["params_bytes"]
             / cm.zero_memory_bytes(3, n_params, n)["params_bytes"])
    t1 = toy["1"]["step_time_s"]
    chip = "cpu" if args.cpu else device["device_kind"]
    label = (f"CPU-virtual ({n} XLA host devices, loopback; no chip, no "
             "latency-hiding scheduler — memory columns are the "
             "analytical model, wall-clock parity expected)"
             if chip == "cpu" else chip)
    sub_rows = [
        {"metric": "zero level2 state+grad memory reduction",
         "value": round(red2, 3), "unit": "x", "label": label},
        {"metric": "zero level3 param memory reduction",
         "value": round(red3p, 3), "unit": "x", "label": label},
        {"metric": "zero level2 step overhead vs level1",
         "value": round(toy["2"]["step_time_s"] / t1, 4),
         "unit": "ratio", "label": label},
        {"metric": "zero level3 step overhead vs level1",
         "value": round(toy["3"]["step_time_s"] / t1, 4),
         "unit": "ratio", "label": label},
    ]
    print(json.dumps({
        "metric": f"zero sweep: level 2 cuts per-rank state+grad memory "
                  f"{red2:.1f}x, level 3 cuts params {red3p:.1f}x "
                  f"(n={n}, levels 1/2/3 bit-near asserted) [{label}]",
        "value": round(red2, 3),
        "unit": "x",
        "label": label,
        "world": n,
        "k": k,
        "toy": toy,
        "llama": llama_rows,
        "equivalence_asserted": True,
        "sub_rows": sub_rows,
        "metrics": metrics_summary(),
    }))
    return 0


def layout_bench(args) -> int:
    """3D layout sweep (parallel/layout.py + the perf/costmodel solver;
    docs/parallelism.md): solve the (dp, tp, pp) candidate table for
    llama-tiny at the live world size, then RUN every candidate mesh
    through the composed TP x PP x ZeRO chain.  Per layout the artifact
    records the MEASURED step time and peak bytes beside the solver's
    PREDICTED step decomposition and per-chip memory, the raw roofline
    drift AND a compute-calibrated drift (the dp-only row anchors the
    calibration — on the CPU-virtual harness the absolute roofline is
    fiction: 0.5 TFLOP/s "chips" on a loopback "fabric", so the
    calibrated ratio is the one the 2x gate judges), plus the ledger's
    own predicted-vs-measured ratio for the ACTIVE row
    (perf_report()["layout"], the same table doctor --perf renders).
    Cross-layout bit-near parameter equivalence is asserted before
    anything is printed — the sweep is invalid if the composition is
    not the same optimizer."""
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import Mesh

    import horovod_tpu as hvd
    from horovod_tpu.models import llama as llama_mod
    from horovod_tpu.parallel import layout as L
    from horovod_tpu.perf import costmodel as cm
    from horovod_tpu.perf import memstats

    device = init_backend(args.cpu)
    n = hvd.size()
    chip = "cpu" if args.cpu else device["device_kind"]
    link = "loopback" if chip == "cpu" else "ici"

    cfg = llama_mod.CONFIGS["tiny"]
    batch_rows, seq = n, 16
    n_micro = 2
    lthresh = 32 * 1024
    timed_steps = 3 if args.cpu else 10
    level = 1  # params stay replicated -> directly comparable finals

    model = cm.llama_layout_model(
        vocab=cfg.vocab, dim=cfg.dim, n_layers=cfg.n_layers,
        n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
        ffn_dim=cfg.ffn_dim, batch=batch_rows, seq=seq)
    sol = cm.solve_layout(model, n, levels=(level,), n_micro=n_micro,
                          chip=chip, link=link)
    # dp-only first: it is the equivalence reference AND the
    # calibration anchor for the relative-drift column.
    cands = sorted(sol["candidates"],
                   key=lambda r: (r["layout"]["tp"] * r["layout"]["pp"],
                                  r["rank"]))
    assert cands[0]["layout"] == {"dp": n, "tp": 1, "pp": 1}

    params = llama_mod.init(jax.random.PRNGKey(0), cfg)
    ids = np.random.RandomState(1).randint(
        0, cfg.vocab, (batch_rows, seq + 1), dtype=np.int32)
    jids = jnp.asarray(ids)

    def run_layout(dp, tp, pp):
        import horovod_tpu.perf as perf
        mesh = Mesh(np.array(jax.devices()).reshape(dp, tp, pp),
                    ("dp", "tp", "pp"))
        stacked = L.llama_layout_params(params, pp)
        opt = optax.adamw(3e-4, weight_decay=0.01)
        specs = L.llama_layout_specs(stacked)
        st = L.init_layout_state(opt, stacked, specs, mesh,
                                 zero_level=level,
                                 fusion_threshold_bytes=lthresh)
        step = L.make_llama_layout_train_step(
            cfg, opt, mesh, n_micro=n_micro, zero_level=level,
            fusion_threshold_bytes=lthresh, donate=False)
        perf.reset()
        memstats.reset()  # per-layout measured peak, not the sweep max
        perf.configure(layout_model=dict(
            model, world=n, levels=(level,), n_micro=n_micro,
            active={"dp": dp, "tp": tp, "pp": pp, "zero_level": level}))
        p, s, loss = step(stacked, st, jids)    # compile + warm
        jax.block_until_ready(loss)
        t0 = time.perf_counter()
        for _ in range(timed_steps):
            with perf.timed_step():
                p, s, loss = step(p, s, jids)
                jax.block_until_ready(loss)
        dt = (time.perf_counter() - t0) / timed_steps
        rep = hvd.perf_report()
        mrow = memstats.sample(force=True) or {}
        return dt, p, float(loss), rep.get("layout") or {}, mrow

    def flat(p):
        # stage leaves [pp, L/pp, ...] -> [L, ...]: different-pp
        # layouts compare leaf-for-leaf
        stages = jax.tree_util.tree_map(
            lambda a: np.asarray(a).reshape((-1,) + a.shape[2:]),
            p["stages"])
        return jax.tree_util.tree_leaves(
            {"embed": p["embed"], "final_norm": p["final_norm"],
             "lm_head": p["lm_head"], "stages": stages})

    rows = {}
    finals = {}
    try:
        for cand in cands:
            lay = cand["layout"]
            key = f"{lay['dp']}x{lay['tp']}x{lay['pp']}"
            dt, p, loss, lrep, mrow = run_layout(
                lay["dp"], lay["tp"], lay["pp"])
            finals[key] = p
            pvm = lrep.get("predicted_vs_measured") or {}
            rows[key] = {
                "rank": cand["rank"],
                "zero_level": cand["zero_level"],
                "n_micro": cand["n_micro"],
                "step_time_s": round(dt, 6),
                "tokens_per_s": round(batch_rows * seq / dt, 1),
                "predicted_step_s": cand["step_s"],
                "bubble_fraction": round(cand["bubble_fraction"], 4),
                "predicted_peak_bytes": cand["memory"],
                "measured_peak_bytes": mrow.get("peak_bytes_in_use"),
                "measured_source": mrow.get("source"),
                "loss": round(loss, 6),
                "raw_drift_ratio": round(dt / cand["step_s"], 3),
                "ledger_step_ratio": pvm.get("step_ratio"),
            }
        # Calibrated drift: ONE scale factor for the whole table — the
        # geometric mean of measured/predicted — then judge each row's
        # residual.  This cancels the CPU-virtual roofline fiction and
        # leaves exactly the solver's RELATIVE story — the thing the
        # ranking runs on — confronted with the wall clock.
        base = f"{n}x1x1"
        calib = float(np.exp(np.mean([
            np.log(r["step_time_s"] / r["predicted_step_s"])
            for r in rows.values()])))
        for key, row in rows.items():
            r = row["step_time_s"] / (row["predicted_step_s"] * calib)
            row["calibrated_drift_ratio"] = round(max(r, 1.0 / r), 3)
        # The equivalence guarantee: every layout's composed chain is
        # the SAME optimizer as the dp-only chain, bit-near (float32
        # psum-ordering noise only; tests/test_layout.py proves the
        # full level matrix — the bench re-proves it on every artifact).
        ref = flat(finals[base])
        for key, p in finals.items():
            for a, b in zip(flat(p), ref):
                err = float(np.abs(a - b).max())
                if err > 1e-4:
                    raise AssertionError(
                        f"layout {key} diverges from dp-only by {err} "
                        "after the timed steps")
        chosen = sol["chosen"]["layout"]
        ckey = f"{chosen['dp']}x{chosen['tp']}x{chosen['pp']}"
        cdrift = rows[ckey]["calibrated_drift_ratio"]
        if cdrift >= 2.0:
            raise AssertionError(
                f"chosen layout {ckey} calibrated predicted-vs-measured "
                f"drift {cdrift}x >= 2x (docs/parallelism.md#cpu-virtual)")
    except AssertionError as e:
        return fail(str(e), cause="invalid-result")

    label = (f"CPU-virtual ({n} XLA host devices, loopback; no chip, no "
             "latency-hiding scheduler — the solver's RANKING and the "
             "calibrated drift are the product here, the absolute "
             "roofline is not)" if chip == "cpu" else chip)
    base_t = rows[base]["step_time_s"]
    sub_rows = [
        {"metric": "layout solver candidates (llama-tiny)",
         "value": sol["n_candidates"], "unit": "count", "label": label},
        {"metric": "layout chosen calibrated step drift",
         "value": cdrift, "unit": "x", "higher_is_better": False,
         "label": label},
    ]
    for cand in cands:
        lay = cand["layout"]
        if lay["tp"] == 1 and lay["pp"] == 1:
            continue
        key = f"{lay['dp']}x{lay['tp']}x{lay['pp']}"
        sub_rows.append(
            {"metric": f"layout {key} step overhead vs dp-only",
             "value": round(rows[key]["step_time_s"] / base_t, 4),
             "unit": "ratio", "label": label})
    print(json.dumps({
        "metric": f"layout sweep: solver ranked {sol['n_candidates']} "
                  f"(dp, tp, pp) candidates at world={n}, chose {ckey}; "
                  f"every candidate ran the composed chain bit-near the "
                  f"dp-only reference [{label}]",
        "value": cdrift,
        "unit": "x",
        "higher_is_better": False,
        "label": label,
        "world": n,
        "chip": chip,
        "link": link,
        "chosen": ckey,
        "calibration_factor": round(calib, 3),
        "layouts": rows,
        "equivalence_asserted": True,
        "sub_rows": sub_rows,
        "metrics": metrics_summary(),
    }))
    return 0


def scenario_bench(args) -> int:
    """Deterministic scenario replay (horovod_tpu/scenario;
    docs/scenarios.md): execute the spec's workload trace + fault storm
    against the real router/watch planes on a virtual clock.  Validity
    gates before an artifact prints: (1) two independent harness runs
    must produce byte-identical canonical SLO rows AND event digests
    (the determinism contract the corpus is committed under); (2) a
    third run feeds a LIVE rendezvous server's watch plane and the
    spec's ``expect_alerts`` must all appear in ``GET /alerts``
    ``fired_total`` — alert expectations are checked over the same HTTP
    surface operators read, not an in-process shortcut.  Per-scenario
    rows ride the one artifact line as ``sub_rows`` (perf/gate.py
    expands them into standalone baseline keys).  Virtual-clock
    latencies measure queueing/scheduling/recovery under the declared
    load, not chip decode — labeled accordingly."""
    from horovod_tpu.scenario import (ScenarioHarness, canonical_rows,
                                      load_scenario, rows_jsonl)
    try:
        spec = load_scenario(args.scenario)
    except (OSError, ValueError) as e:
        return fail(f"scenario spec {args.scenario!r}: {e}",
                    cause="invalid-result")
    # Knob overrides (common/knobs.py; validated at hvd.init — here the
    # same parse, tolerant of the empty-string default).
    vranks = int(os.environ.get("HOROVOD_SCENARIO_RANKS", "0") or 0) \
        or None
    tick_ms = float(os.environ.get("HOROVOD_SCENARIO_TICK_MS", "0")
                    or 0.0)
    if tick_ms > 0:
        import dataclasses as _dc
        spec = _dc.replace(spec, tick_ms=tick_ms)

    t0 = time.perf_counter()
    first = ScenarioHarness(spec, virtual_ranks=vranks).run()
    second = ScenarioHarness(spec, virtual_ranks=vranks).run()
    rows = canonical_rows(first)
    if first["digest"] != second["digest"]:
        return fail(
            f"scenario {spec.name}: event digest differs across two "
            f"runs of one seed ({first['digest'][:12]} vs "
            f"{second['digest'][:12]}) — the trace generator is "
            "nondeterministic", cause="invalid-result")
    if rows_jsonl(rows) != rows_jsonl(canonical_rows(second)):
        return fail(
            f"scenario {spec.name}: SLO rows differ across two runs of "
            "one seed — the replay harness is nondeterministic",
            cause="invalid-result")

    # Live-server leg: the watch plane under a real RendezvousServer,
    # alerts read back over HTTP like an operator would.
    from horovod_tpu.runner.http_server import RendezvousServer
    server = RendezvousServer(port=0)
    port = server.start()
    try:
        if spec.alert_rules:
            from horovod_tpu.watch import parse_rules
            server.install_alert_rules(parse_rules(spec.alert_rules))
        live = ScenarioHarness(spec, watch=server.watch_state,
                               virtual_ranks=vranks).run()
        import urllib.request
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/alerts", timeout=30) as resp:
            alerts_view = json.loads(resp.read().decode())
    finally:
        server.stop()
    wall = time.perf_counter() - t0
    if rows_jsonl(canonical_rows(live)) != rows_jsonl(rows):
        return fail(
            f"scenario {spec.name}: SLO rows differ between the "
            "private and live watch sinks — the watch feed leaked "
            "into the replay", cause="invalid-result")
    fired = sorted({f["rule"]
                    for f in alerts_view.get("fired_total", [])
                    if f.get("count", 0) > 0})
    missing = [r for r in spec.expect_alerts if r not in fired]
    if missing:
        return fail(
            f"scenario {spec.name}: expect_alerts never fired: "
            f"{missing} (GET /alerts fired_total: {fired})",
            cause="invalid-result")

    slo = first["slo"]
    req = first["requests"]
    label = ("CPU-virtual clock (tick arithmetic — queueing/"
             "scheduling/recovery under the declared load, not chip "
             "decode)")
    print(json.dumps({
        "sub_rows": rows,
        "metric": f"scenario {spec.name} replay "
                  f"({req['completed']}/{req['arrived']} reqs, "
                  f"{first['virtual_ranks']} vranks, "
                  f"{first['restarts']} restart(s), ttft p99 "
                  f"{slo['ttft_p99_s'] * 1e3:.1f} ms) [{label}]",
        "value": slo["throughput_tok_s"],
        "unit": "tokens/sec",
        "vs_baseline_is": "completed_over_arrived",
        "vs_baseline": round(req["completed"] / max(1, req["arrived"]),
                             4),
        "label": label,
        "wall_s": round(wall, 3),
        "scenario": os.path.basename(args.scenario),
        "digest": first["digest"],
        "slo": slo,
        "requests": req,
        "per_rank": first["per_rank"],
        "phases": first["phases"],
        "storms": first["storms"],
        "restarts": first["restarts"],
        "alerts": {"fired": fired,
                   "expected": list(spec.expect_alerts),
                   "missing": missing, "ok": not missing},
        "metrics": metrics_summary(),
    }))
    return 0


def serve_bench(args) -> int:
    """Serving load-generator sweep (serve/engine.py; docs/serving.md):
    the continuous-batching engine under two canonical load shapes —
    CLOSED-LOOP (a fixed pool of concurrent users, each resubmitting on
    completion: the throughput ceiling) and POISSON arrivals (open-loop
    at ~60%% of the measured closed-loop request rate: the latency-
    under-load view).  Per mode the artifact records {throughput_tok_s,
    ttft_p50/p99, tpot_p50/p99, batch_fill}; on the CPU-virtual harness
    the absolute numbers measure the host scheduler + XLA-CPU decode,
    not chip serving — the mode exists to prove the machinery and give
    the trajectory, and is labeled accordingly."""
    import jax

    import horovod_tpu as hvd
    from horovod_tpu.models import llama
    from horovod_tpu.serve.config import ServeConfig
    from horovod_tpu.serve.engine import ServeEngine

    device = init_backend(args.cpu)
    if args.cpu:
        cfg = llama.CONFIGS["tiny"]
        prompt_len, max_new, total, users = 12, 8, 16, 4
        scfg = ServeConfig(max_slots=4, block_size=4, cache_blocks=64,
                           max_seq_len=64, max_batch_tokens=32,
                           prefill_chunk=16)
    else:
        cfg = llama.CONFIGS[args.model if args.model != "bench"
                            else "mini"]
        prompt_len, max_new, total, users = 128, 64, 64, 8
        scfg = ServeConfig(max_slots=16, block_size=16,
                           cache_blocks=1024,
                           max_seq_len=min(1024, cfg.max_seq),
                           max_batch_tokens=512, prefill_chunk=128)
    params = llama.init(jax.random.PRNGKey(0), cfg)
    engine = ServeEngine(llama, cfg, params, scfg, mesh=hvd.mesh())
    rng = np.random.RandomState(0)

    def new_prompt():
        # +/-25% length jitter so slots genuinely desynchronize
        n = max(2, int(prompt_len * (0.75 + 0.5 * rng.rand())))
        return rng.randint(0, cfg.vocab, n).tolist()

    def drain(arrival_times):
        """Run the engine against an arrival schedule (None = closed
        loop: resubmit on completion).  Returns the mode's SLO row."""
        t0 = time.perf_counter()
        tok0 = engine._tokens_prefill + engine._tokens_decode
        submitted = 0
        done = []
        fills = []

        def submit_one():
            nonlocal submitted
            engine.submit(new_prompt(), max_new,
                          req_id=f"lg-{submitted}")
            submitted += 1

        if arrival_times is None:
            for _ in range(min(users, total)):
                submit_one()
        while len(done) < total:
            now = time.perf_counter() - t0
            if arrival_times is not None:
                while submitted < total and \
                        arrival_times[submitted] <= now:
                    submit_one()
                if not engine.has_work() and submitted < total:
                    time.sleep(min(0.005,
                                   arrival_times[submitted] - now))
            rep = engine.step()
            if rep["processed"]:
                fills.append(rep["processed"] / scfg.max_batch_tokens)
            for req in rep["finished"]:
                done.append(req)
                if arrival_times is None and submitted < total:
                    submit_one()
        wall = time.perf_counter() - t0
        tokens = engine._tokens_prefill + engine._tokens_decode - tok0
        ttfts = [r.ttft() for r in done]
        tpots = [r.tpot() for r in done if r.tpot() is not None]
        # Per-component TTFT breakdown through serve/trace.py
        # ``attribute`` — the request-lifecycle components, summing
        # exactly to each request's TTFT.  Engine-direct (no router),
        # so placement/handoff/stream are structurally zero and the
        # queue and prefill legs carry the whole story.
        from horovod_tpu.serve import trace as serve_trace
        comp_vals = {c: [] for c in serve_trace.COMPONENTS}
        for r in done:
            measured = {}
            if r.admitted_t is not None:
                measured["queue"] = r.admitted_t - r.submitted_t
                if r.first_token_t is not None:
                    measured["prefill"] = \
                        r.first_token_t - r.admitted_t
            comps, _ = serve_trace.attribute(r.ttft() or 0.0, measured)
            for c, v in comps.items():
                comp_vals[c].append(v)
        breakdown = {c: round(float(np.percentile(vs, 50)), 5)
                     for c, vs in comp_vals.items() if vs}
        return {
            "ttft_breakdown": breakdown,
            "requests": len(done),
            "wall_s": round(wall, 4),
            "throughput_tok_s": round(tokens / wall, 2),
            "requests_per_s": round(len(done) / wall, 3),
            "ttft_p50_s": round(float(np.percentile(ttfts, 50)), 5),
            "ttft_p99_s": round(float(np.percentile(ttfts, 99)), 5),
            "tpot_p50_s": round(float(np.percentile(tpots, 50)), 5),
            "tpot_p99_s": round(float(np.percentile(tpots, 99)), 5),
            "batch_fill": round(float(np.mean(fills)), 4),
        }

    closed = drain(None)
    # Open-loop Poisson at ~60% of the measured closed-loop request
    # rate: under the saturation knee, so the row shows latency, not
    # queue blow-up.  The schedule comes from the scenario trace
    # machinery's named built-in (scenario/trace.py BUILTIN_TRACES) so
    # --serve and --scenario draw arrivals from ONE seeded generator.
    from horovod_tpu.scenario import builtin_arrivals
    arrivals = builtin_arrivals("serve-bench-poisson",
                                closed_loop_rps=closed["requests_per_s"],
                                n=total)
    poisson = drain(arrivals)

    for mode, row in (("closed_loop", closed), ("poisson", poisson)):
        if row["requests"] != total or row["ttft_p50_s"] <= 0 or \
                row["tpot_p50_s"] <= 0:
            return fail(f"serve {mode} row implausible: {row}",
                        cause="invalid-result")
    chip = "cpu" if args.cpu else device["device_kind"]
    label = (f"CPU-virtual ({hvd.size()} XLA host devices; no chip — "
             "latencies measure the host scheduler + XLA-CPU decode, "
             "not chip serving)" if chip == "cpu" else chip)

    legs = serve_speed_legs(llama, cfg, params, hvd.mesh(), label)
    if isinstance(legs, int):
        return legs  # a leg failed its byte-identity contract
    # Gate-able per-leg rows ride the ONE artifact line as sub_rows;
    # perf/gate.py load_artifacts expands them into standalone rows.
    sub_rows = legs.pop("gate_rows")
    # Per-component TTFT breakdown rides the same artifact as gate-able
    # sub_rows: the gate watches the queue and prefill legs of the
    # closed-loop TTFT independently (a scheduler regression can hide
    # in one leg while the blended p50 stays flat).
    for comp in ("queue", "prefill"):
        sub_rows.append({
            "metric": f"serve closed-loop ttft {comp} p50",
            "value": round(
                closed["ttft_breakdown"].get(comp, 0.0) * 1e3, 3),
            "unit": "ms",
            "higher_is_better": False,
            "label": label})

    print(json.dumps({
        "sub_rows": sub_rows,
        "metric": f"serve load-gen closed-loop throughput "
                  f"({closed['throughput_tok_s']:.0f} tok/s at batch "
                  f"fill {closed['batch_fill']:.2f}, Poisson ttft p99 "
                  f"{poisson['ttft_p99_s'] * 1e3:.1f} ms, "
                  f"{total} reqs, prompt~{prompt_len}, gen {max_new}) "
                  f"[{label}]",
        "value": closed["throughput_tok_s"],
        "unit": "tokens/sec",
        "vs_baseline_is": "closed_loop_batch_fill",
        "vs_baseline": closed["batch_fill"],
        "label": label,
        "closed_loop": closed,
        "poisson": poisson,
        "serve_config": {"max_slots": scfg.max_slots,
                         "block_size": scfg.block_size,
                         "cache_blocks": scfg.cache_blocks,
                         "max_batch_tokens": scfg.max_batch_tokens,
                         "prefill_chunk": scfg.prefill_chunk},
        "legs": legs,
        "metrics": metrics_summary(),
    }))
    return 0


def serve_users_bench(args) -> int:
    """Control-plane saturation sweep (docs/control-plane.md): a
    closed-loop user-count sweep through the REAL front door — POST
    /generate on the rendezvous server, KV enqueue, FleetFrontend
    drain/publish, ndjson stream back — with a scripted fixed-cost
    engine (1 ms/tick, one token per request per tick) so the knee the
    sweep locates is the ROUTER+KV's, not the model's.  Run twice:

      * ``single`` — 1 KV shard, direct streaming OFF (every token a
        serve_out KV PUT polled by the router: the pre-scale-out path);
      * ``sharded_direct`` — ``--kv-shards 3`` + the persistent direct
        token stream (the scale-out control plane).

    Knee = smallest user count whose throughput reaches 90%% of the
    config's max.  The artifact gates the per-config knee throughput
    and the scaled/baseline ratio via PERF_BASELINE.json sub_rows.
    CPU-virtual: loopback HTTP in one process — absolute numbers
    measure the host's scheduler + GIL, the COMPARISON is the claim."""
    import threading
    import urllib.request

    from horovod_tpu.runner import http_client as hc
    from horovod_tpu.runner.http_server import RendezvousServer
    from horovod_tpu.serve.router import RouterState
    from horovod_tpu.serve.worker import FleetFrontend

    user_counts = [int(x) for x in str(args.users).split(",")]
    tick_s = 0.001
    max_new = 16
    warmup_s, window_s = 0.4, 1.5

    class TickEngine:
        """FleetFrontend-contract engine with a fixed 1 ms tick: one
        token per active request per step, deterministic content."""

        def __init__(self):
            self.tick = 0
            self.active = {}
            self.completed = 0

        def submit(self, tokens, max_new_tokens, req_id=None,
                   eos_id=None):
            base = sum(int(t) for t in tokens)
            self.active[req_id] = [(base + i) % 1000
                                   for i in range(max_new_tokens)]

        def has_work(self):
            return bool(self.active)

        def step(self):
            time.sleep(tick_s)  # the modeled decode tick
            emitted, finished = {}, []
            for rid in sorted(self.active):
                emitted[rid] = [self.active[rid].pop(0)]
                if not self.active[rid]:
                    del self.active[rid]
                    finished.append(_UserDone(rid))
                    self.completed += 1
            if emitted:
                self.tick += 1
            return {"tick": self.tick, "processed": len(emitted),
                    "emitted": emitted, "finished": finished}

        def stats(self):
            return {"tick": self.tick, "completed": self.completed,
                    "active": len(self.active)}

    class _UserDone:
        def __init__(self, rid):
            self.req_id = rid
            self.finish_reason = "completed"

        def ttft(self):
            return tick_s

        def tpot(self):
            return tick_s

    def run_config(shards, direct):
        server = RendezvousServer(host="127.0.0.1", shards=shards)
        port = server.start()
        addrs = [("127.0.0.1", p) for p in server.shard_ports]
        if shards > 1:
            hc.install_shard_map(addrs)
        # No shedding: saturation must hit the transport, not admission.
        server._httpd.serve_router = RouterState(
            max_pending=1 << 20, shed_high=1 << 20, journal=True)
        frontend = FleetFrontend(TickEngine(), "127.0.0.1", port, 0, 1,
                                 direct=direct)
        ft = threading.Thread(target=frontend.run, daemon=True)
        ft.start()
        done = {"requests": 0, "tokens": 0}
        done_lock = threading.Lock()
        counting = threading.Event()
        stop = threading.Event()

        def user_loop(uid):
            body = json.dumps({"tokens": [uid + 1, uid + 2],
                               "max_new_tokens": max_new}).encode()
            while not stop.is_set():
                req = urllib.request.Request(
                    f"http://127.0.0.1:{port}/generate", data=body,
                    method="POST")
                try:
                    with urllib.request.urlopen(req, timeout=60) as r:
                        lines = r.read().splitlines()
                except OSError:
                    continue
                rec = json.loads(lines[-1]) if lines else {}
                if rec.get("done") and counting.is_set():
                    with done_lock:
                        done["requests"] += 1
                        done["tokens"] += len(rec.get("tokens") or ())

        rows = []
        try:
            for n in user_counts:
                stop.clear()
                counting.clear()
                users = [threading.Thread(target=user_loop, args=(u,),
                                          daemon=True)
                         for u in range(n)]
                for u in users:
                    u.start()
                time.sleep(warmup_s)
                with done_lock:
                    done["requests"] = done["tokens"] = 0
                counting.set()
                time.sleep(window_s)
                counting.clear()
                with done_lock:
                    reqs, toks = done["requests"], done["tokens"]
                stop.set()
                for u in users:
                    u.join(timeout=90)
                rows.append({"users": n,
                             "requests_per_s": round(reqs / window_s, 2),
                             "tok_s": round(toks / window_s, 1)})
        finally:
            # graceful exit: the drain signal stops the frontend loop
            try:
                urllib.request.urlopen(urllib.request.Request(
                    f"http://127.0.0.1:{port}/admin/drain", data=b"{}",
                    method="POST"), timeout=30).read()
            except OSError:
                pass
            ft.join(timeout=30)
            if shards > 1:
                hc.install_shard_map(None)
            server.stop()
        peak = max(r["tok_s"] for r in rows)
        knee = next((r for r in rows if r["tok_s"] >= 0.9 * peak),
                    rows[-1])
        return {"rows": rows, "peak_tok_s": peak,
                "knee_users": knee["users"],
                "knee_tok_s": knee["tok_s"]}

    single = run_config(shards=1, direct=False)
    scaled = run_config(shards=3, direct=True)
    for tag, res in (("single", single), ("sharded_direct", scaled)):
        if res["peak_tok_s"] <= 0:
            return fail(f"serve --users {tag} sweep moved no tokens: "
                        f"{res}", cause="invalid-result")
    gain = scaled["knee_tok_s"] / max(single["knee_tok_s"], 1e-9)
    label = ("CPU-virtual control plane (loopback HTTP, scripted 1 ms "
             "engine tick — measures router+KV, not decode)")
    sub_rows = [
        {"metric": "serve ctrl-plane single knee throughput "
                   f"(knee at {single['knee_users']} users)",
         "value": single["knee_tok_s"], "unit": "tokens/sec",
         "higher_is_better": True, "label": label},
        {"metric": "serve ctrl-plane sharded-direct knee throughput "
                   f"(knee at {scaled['knee_users']} users)",
         "value": scaled["knee_tok_s"], "unit": "tokens/sec",
         "higher_is_better": True, "label": label},
        {"metric": "serve ctrl-plane scale-out gain "
                   "(sharded+direct vs single, knee tok/s)",
         "value": round(gain, 3), "unit": "x",
         "higher_is_better": True, "label": label},
    ]
    print(json.dumps({
        "sub_rows": sub_rows,
        "metric": "serve ctrl-plane saturation sweep "
                  f"(single knee {single['knee_tok_s']:.0f} tok/s at "
                  f"{single['knee_users']} users; sharded+direct "
                  f"{scaled['knee_tok_s']:.0f} tok/s at "
                  f"{scaled['knee_users']} users; gain {gain:.2f}x) "
                  f"[{label}]",
        "value": scaled["knee_tok_s"], "unit": "tokens/sec",
        "vs_baseline_is": "single_knee_tok_s",
        "vs_baseline": single["knee_tok_s"],
        "label": label,
        "user_counts": user_counts,
        "tick_ms": tick_s * 1e3, "max_new_tokens": max_new,
        "window_s": window_s,
        "single": single, "sharded_direct": scaled,
    }))
    return 0


def serve_replicas_bench(args) -> int:
    """Replica scale-out sweep (docs/serving.md#replicated-tier): the
    ``--users`` saturation harness repeated against N independent
    replica fleets — each a FleetFrontend + slot-capped scripted tick
    engine — registered behind ONE router process with prefix-affinity
    routing.
    The workload is grouped shared-prefix traffic (each closed-loop
    user belongs to one of a few hot prefix groups), so the sweep
    measures the two replicated-tier claims at once:

      * the saturation knee scales with the replica count (the single
        lockstep fleet was the ceiling the tier removes);
      * affinity routing pins each prefix group to one replica — hit
        rate measured from the ``X-Serve-Affinity-Blocks`` response
        header — where the least-loaded-only baseline (affinity knob
        off) scatters it (hit rate 0 by construction).

    CPU-virtual: every replica is a thread in this process, so the
    scale-out gain measures overlap of control-plane waits (loopback
    HTTP, KV locks, the 1 ms engine sleep) under the GIL — the
    COMPARISON across replica counts is the claim, not the absolute
    tok/s.  Artifact gates per-replica-count knee throughput, the
    1->2 scale-out gain, and the affinity hit rate via
    PERF_BASELINE.json sub_rows."""
    import threading
    import urllib.request

    import horovod_tpu.serve.worker as worker_mod
    from horovod_tpu.runner import http_client as hc
    from horovod_tpu.runner.http_server import RendezvousServer
    from horovod_tpu.serve.replica import (ReplicaRouter, fold_digest,
                                           prompt_fingerprints)
    from horovod_tpu.serve.router import RouterState
    from horovod_tpu.serve.worker import FleetFrontend

    replica_counts = sorted({int(x)
                             for x in str(args.replicas).split(",")})
    user_counts = [int(x) for x in str(args.users).split(",")]
    tick_s = 0.025
    slots = 2           # modeled decode slots per replica fleet
    chunk = 8           # tokens emitted per scheduled request per tick
    block = 4           # fingerprint block size (registered with router)
    n_groups = 8        # hot shared-prefix groups (lcm-friendly for 1/2/4)
    prefix_blocks = 3   # full blocks of shared prefix per group
    max_new = 32
    warmup_s, window_s = 0.5, 1.5

    # Deterministic per-group shared prefixes: 3 full blocks each, so
    # the router sees 3 matchable fingerprints per prompt.
    prefixes = [[(17 * g + 3 * i + 1) % 251 for i in range(
        block * prefix_blocks)] for g in range(n_groups)]

    class TickEngine:
        """Scripted slot-capped engine: each 25 ms tick (a GIL-released
        sleep — the modeled decode fleet) serves the first ``slots``
        queued requests FCFS, emitting a ``chunk``-token part each, so
        ONE replica's ceiling is slots*chunk/tick = 640 tok/s by
        construction — far below the router process's own CPU cap and the sweep observes the tier scale until the
        shared router process saturates.  The replica affinity contract
        rides on top: submitted prompts' rolling block fingerprints
        accumulate as the advertised 'radix tree', and stats carry the
        queue depth the least-loaded fallback reads."""

        def __init__(self):
            self.tick = 0
            self.active = {}
            self.order = []  # FCFS arrival order
            self.completed = 0
            self._fps = set()

        def submit(self, tokens, max_new_tokens, req_id=None,
                   eos_id=None):
            base = sum(int(t) for t in tokens)
            self.active[req_id] = [(base + i) % 1000
                                   for i in range(max_new_tokens)]
            self.order.append(req_id)
            self._fps.update(prompt_fingerprints(tokens, block))

        def prefix_fps(self):
            fps = sorted(self._fps)[:64]
            return fps, fold_digest(fps)

        def has_work(self):
            return bool(self.active)

        def step(self):
            time.sleep(tick_s)  # the modeled decode tick
            emitted, finished = {}, []
            for rid in self.order[:slots]:
                emitted[rid] = self.active[rid][:chunk]
                del self.active[rid][:chunk]
                if not self.active[rid]:
                    del self.active[rid]
                    finished.append(_ReplicaDone(rid))
                    self.completed += 1
            self.order = [r for r in self.order if r in self.active]
            if emitted:
                self.tick += 1
            return {"tick": self.tick, "processed": len(emitted),
                    "emitted": emitted, "finished": finished}

        def stats(self):
            return {"tick": self.tick, "completed": self.completed,
                    "active": len(self.active),
                    "waiting": len(self.active)}

    class _ReplicaDone:
        def __init__(self, rid):
            self.req_id = rid
            self.finish_reason = "completed"

        def ttft(self):
            return tick_s

        def tpot(self):
            return tick_s

    def run_config(n_replicas, affinity):
        """One (replica count, affinity) config: fresh server, N
        registered replica fleets, the full user-count sweep.  Returns
        the per-user-count rows, the knee, and the measured affinity
        hit rate over every counted request."""
        server = RendezvousServer(host="127.0.0.1", shards=3)
        port = server.start()
        hc.install_shard_map([("127.0.0.1", p)
                              for p in server.shard_ports])
        # No shedding (saturation must hit the transport, not
        # admission), and an explicit affinity switch per config.
        server._httpd.serve_routers = {
            k: RouterState(max_pending=1 << 20, shed_high=1 << 20,
                           journal=True) for k in range(n_replicas)}
        server._httpd.serve_router = server._httpd.serve_routers[0]
        server._httpd.serve_replicas = ReplicaRouter(
            block_size=block, affinity=affinity, dead_after_s=30.0)
        frontends = [FleetFrontend(TickEngine(), "127.0.0.1", port, 0, 1,
                                   direct=True, replica_id=k)
                     for k in range(n_replicas)]
        for fe in frontends:
            fe.register_replica({"replicas": n_replicas,
                                 "block_size": block})
            fe._publish_stats(force=True)
        threads = [threading.Thread(target=fe.run, daemon=True)
                   for fe in frontends]
        for t in threads:
            t.start()

        done = {"requests": 0, "tokens": 0, "hits": 0, "routed": 0}
        done_lock = threading.Lock()
        counting = threading.Event()
        stop = threading.Event()

        def user_loop(uid):
            toks = prefixes[uid % n_groups] + [uid + 1, uid + 2]
            body = json.dumps({"tokens": toks,
                               "max_new_tokens": max_new}).encode()
            while not stop.is_set():
                req = urllib.request.Request(
                    f"http://127.0.0.1:{port}/generate", data=body,
                    method="POST")
                try:
                    with urllib.request.urlopen(req, timeout=60) as r:
                        hit = int(r.headers.get(
                            "X-Serve-Affinity-Blocks", 0) or 0)
                        lines = r.read().splitlines()
                except (OSError, ValueError):
                    continue
                rec = json.loads(lines[-1]) if lines else {}
                if rec.get("done") and counting.is_set():
                    with done_lock:
                        done["requests"] += 1
                        done["tokens"] += len(rec.get("tokens") or ())
                        done["routed"] += 1
                        done["hits"] += 1 if hit > 0 else 0

        rows = []
        try:
            for n in user_counts:
                stop.clear()
                counting.clear()
                users = [threading.Thread(target=user_loop, args=(u,),
                                          daemon=True)
                         for u in range(n)]
                for u in users:
                    u.start()
                time.sleep(warmup_s)
                with done_lock:
                    done["requests"] = done["tokens"] = 0
                counting.set()
                time.sleep(window_s)
                counting.clear()
                with done_lock:
                    reqs, toks = done["requests"], done["tokens"]
                stop.set()
                for u in users:
                    u.join(timeout=90)
                rows.append({"users": n,
                             "requests_per_s": round(reqs / window_s, 2),
                             "tok_s": round(toks / window_s, 1)})
        finally:
            # graceful exit: ONE drain fans out to every replica fleet
            try:
                urllib.request.urlopen(urllib.request.Request(
                    f"http://127.0.0.1:{port}/admin/drain", data=b"{}",
                    method="POST"), timeout=30).read()
            except OSError:
                pass
            for t in threads:
                t.join(timeout=30)
            hc.install_shard_map(None)
            server.stop()
        peak = max(r["tok_s"] for r in rows)
        knee = next((r for r in rows if r["tok_s"] >= 0.9 * peak),
                    rows[-1])
        with done_lock:
            routed, hits = done["routed"], done["hits"]
        return {"replicas": n_replicas, "affinity": affinity,
                "rows": rows, "peak_tok_s": peak,
                "knee_users": knee["users"], "knee_tok_s": knee["tok_s"],
                "affinity_hit_rate": round(hits / max(routed, 1), 4),
                "routed": routed}

    # Fleet stats must beat the router's load/affinity staleness at
    # bench time scales: 1 Hz heartbeats against 1.5 s windows would
    # measure the heartbeat, not the tier.
    old_interval = worker_mod._STATS_INTERVAL_S
    worker_mod._STATS_INTERVAL_S = 0.05
    try:
        results = {n: run_config(n, affinity=True)
                   for n in replica_counts}
        # The hit-rate control: the biggest tier again with the
        # affinity knob off — pure least-loaded placement scatters the
        # prefix groups (hit rate 0 by construction; the row documents
        # the comparison, the gate rides the affinity-on rate).
        control = run_config(max(replica_counts), affinity=False)
    finally:
        worker_mod._STATS_INTERVAL_S = old_interval

    for n, res in results.items():
        if res["peak_tok_s"] <= 0:
            return fail(f"serve --replicas {n} sweep moved no tokens: "
                        f"{res}", cause="invalid-result")
    label = ("CPU-virtual replica tier (loopback HTTP, slot-capped "
             "scripted engine ticks, N replica threads in one process "
             "— measures router+KV overlap, not decode)")
    sub_rows = []
    for n in replica_counts:
        res = results[n]
        sub_rows.append(
            {"metric": f"serve replica tier knee throughput r{n} "
                       f"(knee at {res['knee_users']} users)",
             "value": res["knee_tok_s"], "unit": "tokens/sec",
             "higher_is_better": True, "label": label})
    gain2 = None
    if 1 in results and 2 in results:
        gain2 = results[2]["knee_tok_s"] / max(
            results[1]["knee_tok_s"], 1e-9)
        sub_rows.append(
            {"metric": "serve replica scale-out gain 1to2 "
                       "(knee tok/s, 2 vs 1 replicas)",
             "value": round(gain2, 3), "unit": "x",
             "higher_is_better": True, "label": label})
    top = max(replica_counts)
    if 1 in results and top > 2:
        sub_rows.append(
            {"metric": f"serve replica scale-out gain 1to{top} "
                       f"(knee tok/s, {top} vs 1 replicas)",
             "value": round(results[top]["knee_tok_s"] / max(
                 results[1]["knee_tok_s"], 1e-9), 3),
             "unit": "x", "higher_is_better": True, "label": label})
    sub_rows.append(
        {"metric": f"serve replica affinity hit rate r{top} "
                   f"({n_groups} prefix groups; least-loaded control "
                   f"{control['affinity_hit_rate']:.2f})",
         "value": results[top]["affinity_hit_rate"], "unit": "ratio",
         "higher_is_better": True, "label": label})
    gain_txt = f"{gain2:.2f}x" if gain2 is not None else "n/a"
    print(json.dumps({
        "sub_rows": sub_rows,
        "metric": "serve replica scale-out sweep "
                  f"(knees {[results[n]['knee_tok_s'] for n in replica_counts]} "
                  f"tok/s at replicas {replica_counts}; 1->2 gain "
                  f"{gain_txt}; affinity hit rate "
                  f"{results[top]['affinity_hit_rate']:.2f} vs control "
                  f"{control['affinity_hit_rate']:.2f}) [{label}]",
        "value": results[top]["knee_tok_s"], "unit": "tokens/sec",
        "label": label,
        "replica_counts": replica_counts, "user_counts": user_counts,
        "tick_ms": tick_s * 1e3, "max_new_tokens": max_new,
        "window_s": window_s, "prefix_groups": n_groups,
        "results": {str(n): results[n] for n in replica_counts},
        "least_loaded_control": control,
    }))
    return 0


def serve_speed_legs(model, cfg, params, mesh, label):
    """The raw-speed acceptance experiments (docs/serving.md#raw-speed),
    each leg independently toggled off vs on over the SAME deterministic
    workload with byte-identity asserted between the runs:

      * prefix — shared-prefix traffic; TTFT p50 drops because repeated
        prefills become radix-cache hits;
      * chunked — one long prompt landing amid short decode streams;
        the victims' worst inter-token gap stays bounded because the
        prompt is split across ticks (and the verify row stays narrow);
      * spec — n-gram-friendly decode; tok/s rises because accepted
        drafts emit several verified tokens per tick.

    Returns {leg: row, "gate_rows": [...]} or fail()'s rc on a broken
    identity contract.  CPU-virtual caveats apply (the caller labels)."""
    from horovod_tpu.serve.config import ServeConfig
    from horovod_tpu.serve.engine import ServeEngine

    def run(scfg, reqs, warm=()):
        """Fresh engine; warm requests complete first — they absorb the
        jit compile (and prime the prefix cache where one is on) so the
        measured wall is serving, not XLA compilation.  Then ``reqs``
        run closed-loop.  Returns (per-request Request objects, wall
        seconds, max inter-token gap seconds per request, engine)."""
        engine = ServeEngine(model, cfg, params, scfg, mesh=mesh)
        for rid, toks, n in list(warm) + [("leg-warmup", [1, 2, 3], 2)]:
            engine.submit(toks, n, req_id=rid)
        engine.flush()
        handles = [engine.submit(toks, n, req_id=rid)
                   for rid, toks, n in reqs]
        gaps = {rid: 0.0 for rid, _, _ in reqs}
        last = {}
        t0 = time.perf_counter()
        while engine.has_work():
            rep = engine.step()
            now = time.perf_counter()
            for rid in rep["emitted"]:
                if rid in gaps:
                    if rid in last:
                        gaps[rid] = max(gaps[rid], now - last[rid])
                    last[rid] = now
        wall = time.perf_counter() - t0
        return handles, wall, gaps, engine

    def identity(tag, off_handles, on_handles):
        for a, b in zip(off_handles, on_handles):
            if a.out_tokens != b.out_tokens:
                return fail(
                    f"serve {tag} leg broke greedy byte-identity: "
                    f"{a.req_id} {a.out_tokens} != {b.out_tokens}",
                    cause="invalid-result")
        return None

    def p50(values):
        return float(np.percentile(values, 50))

    base = dict(max_slots=4, block_size=4, cache_blocks=256,
                max_seq_len=min(128, cfg.max_seq), max_batch_tokens=32,
                prefill_chunk=16)
    rng = np.random.RandomState(42)
    legs = {}
    gate_rows = []

    # --- leg 1: radix prefix cache on shared-prefix traffic ----------
    prefix_toks = rng.randint(0, cfg.vocab, 112).tolist()
    shared = [(f"px-{i}",
               prefix_toks + rng.randint(0, cfg.vocab, 8).tolist(), 8)
              for i in range(8)]
    warm = [("px-warm", prefix_toks + [1, 2, 3], 4)]
    rows = {}
    for mode, on in (("off", False), ("on", True)):
        scfg = ServeConfig(prefix_cache=on, spec_decode=False, **base)
        handles, wall, _, engine = run(scfg, shared, warm=warm)
        st = engine.stats()
        rows[mode] = {
            "ttft_p50_s": round(p50([r.ttft() for r in handles]), 5),
            "wall_s": round(wall, 4),
            "prefill_chunks": st["prefill_chunks"],
            "prefix_hit_rate": st["prefix_cache"].get("hit_rate"),
            "blocks_shared": st["prefix_cache"].get("blocks_shared"),
            "handles": handles,
        }
    rc = identity("prefix", rows["off"]["handles"], rows["on"]["handles"])
    if rc is not None:
        return rc
    speedup = rows["off"]["ttft_p50_s"] / max(rows["on"]["ttft_p50_s"],
                                              1e-9)
    legs["prefix"] = {m: {k: v for k, v in r.items() if k != "handles"}
                      for m, r in rows.items()}
    legs["prefix"]["ttft_p50_speedup"] = round(speedup, 2)
    legs["prefix"]["byte_identical"] = True
    gate_rows.append({
        "metric": "serve prefix ttft p50 speedup (shared-prefix "
                  "workload, off->on)",
        "value": round(speedup, 3), "unit": "x",
        "higher_is_better": True, "label": label})

    # --- leg 2: chunked prefill vs one-shot under interference -------
    victims = [(f"v-{i}", rng.randint(0, cfg.vocab, 8).tolist(), 24)
               for i in range(2)]
    intruder = [("long", rng.randint(0, cfg.vocab, 120).tolist(), 4)]
    rows = {}
    for mode, chunk in (("unchunked", 128), ("chunked", 16)):
        scfg = ServeConfig(prefix_cache=False, spec_decode=False,
                           **dict(base, prefill_chunk=chunk,
                                  max_batch_tokens=160))
        handles, wall, gaps, _ = run(scfg, victims + intruder)
        rows[mode] = {
            "victim_max_gap_s": round(max(gaps[rid]
                                          for rid, _, _ in victims), 5),
            "victim_tpot_p99_s": round(float(np.percentile(
                [h.tpot() for h in handles[:len(victims)]], 99)), 5),
            "wall_s": round(wall, 4),
            "prefill_chunk": chunk,
            "handles": handles,
        }
    rc = identity("chunked", rows["unchunked"]["handles"],
                  rows["chunked"]["handles"])
    if rc is not None:
        return rc
    bound = rows["unchunked"]["victim_max_gap_s"] / \
        max(rows["chunked"]["victim_max_gap_s"], 1e-9)
    legs["chunked"] = {m: {k: v for k, v in r.items() if k != "handles"}
                       for m, r in rows.items()}
    legs["chunked"]["gap_bound_ratio"] = round(bound, 2)
    legs["chunked"]["byte_identical"] = True
    gate_rows.append({
        "metric": "serve chunked prefill interference bound "
                  "(victim max-gap, unchunked/chunked)",
        "value": round(bound, 3), "unit": "x",
        "higher_is_better": True, "label": label})

    # --- leg 3: speculative decoding on n-gram-friendly decode -------
    # Cyclic prompts: a random-init greedy trajectory falls into short
    # cycles, exactly what prompt-lookup drafts (and what production
    # extraction/quote-heavy traffic looks like).
    cyc = [(f"sp-{i}",
            (rng.randint(0, cfg.vocab, 3).tolist() * 8)[:24], 24)
           for i in range(4)]
    rows = {}
    for mode, on in (("off", False), ("on", True)):
        scfg = ServeConfig(prefix_cache=False, spec_decode=on,
                           spec_k=4, **base)
        handles, wall, _, engine = run(scfg, cyc)
        st = engine.stats()
        decode_toks = sum(len(h.out_tokens) for h in handles)
        rows[mode] = {
            "decode_tok_s": round(decode_toks / wall, 2),
            "wall_s": round(wall, 4),
            "spec_accept_rate": st["spec"].get("accept_rate"),
            "drafted": st["spec"].get("drafted_tokens"),
            "accepted": st["spec"].get("accepted_tokens"),
            "handles": handles,
        }
    rc = identity("spec", rows["off"]["handles"], rows["on"]["handles"])
    if rc is not None:
        return rc
    speedup = rows["on"]["decode_tok_s"] / \
        max(rows["off"]["decode_tok_s"], 1e-9)
    legs["spec"] = {m: {k: v for k, v in r.items() if k != "handles"}
                    for m, r in rows.items()}
    legs["spec"]["decode_speedup"] = round(speedup, 2)
    legs["spec"]["byte_identical"] = True
    gate_rows.append({
        "metric": "serve spec decode speedup (n-gram-friendly "
                  "workload, off->on)",
        "value": round(speedup, 3), "unit": "x",
        "higher_is_better": True, "label": label})

    legs["gate_rows"] = gate_rows
    return legs


# Forward GFLOPs are the standard published numbers (torchvision/tf-slim).
# family -> (module, init/loss kwargs, fwd GFLOP/img, canonical size,
# cpu-smoke size, sgd lr).  VGG's BN-less classifier diverges at the
# resnet-calibrated 0.1 (the original paper trained at 0.01).
CNN_FAMILIES = {
    "resnet50":   ("resnet", {"depth": 50}, 4.089e9, 224, 64, 0.1),
    "resnet101":  ("resnet", {"depth": 101}, 7.80e9, 224, 64, 0.1),
    "vgg16":      ("vgg", {"depth": 16}, 15.47e9, 224, 64, 0.01),
    "inception3": ("inception", {}, 5.73e9, 299, 139, 0.1),
}


def resnet_bench(args) -> int:
    """CNN synthetic images/sec — the reference's headline metric family
    (docs/benchmarks.rst:12-43: Inception V3 / ResNet-101 / VGG-16
    scaling rows; the img/sec table's `--model resnet101`, 1656.82 img/s
    over 16 Pascal GPUs ≈ 103.6 img/s/GPU, batch-64 synthetic protocol —
    matched exactly by ``--cnn resnet101``; ``--resnet --depth N`` is the
    back-compat spelling).

    Data-parallel over the whole mesh: per-chip batch shards, gradient
    pmean + cross-chip sync-BN statistics inside the scanned program, so
    images/sec/chip measures real scaled throughput."""
    import functools
    import importlib

    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import PartitionSpec as P

    import horovod_tpu as hvd
    from jax import shard_map
    from horovod_tpu.parallel.data_parallel import replicate, shard_batch

    family = args.cnn or f"resnet{args.depth}"
    mod_name, loss_kw, fwd_gflop, canonical_hw, cpu_hw, lr = \
        CNN_FAMILIES[family]
    model = importlib.import_module(f"horovod_tpu.models.{mod_name}")
    model_loss = functools.partial(model.loss_fn, **loss_kw)

    device = init_backend(args.cpu)
    mesh = hvd.mesh()
    n_chips = hvd.size()
    default_batch = 32 if family == "vgg16" else 64  # VGG: 138M params
    batch = args.batch if args.batch is not None else default_batch
    steps = args.steps
    if args.cpu:
        batch, steps = 2, 3

    dtype = jnp.float32 if args.cpu else jnp.bfloat16
    params = replicate(model.init(jax.random.PRNGKey(0), dtype=dtype,
                                  **loss_kw), mesh)
    opt = optax.sgd(lr, momentum=0.9)
    opt_state = replicate(opt.init(params), mesh)

    rng = np.random.RandomState(0)
    size_hw = cpu_hw if args.cpu else canonical_hw
    x = shard_batch(jnp.asarray(
        rng.randn(batch * n_chips, size_hw, size_hw, 3), dtype), mesh)
    y = shard_batch(jnp.asarray(
        rng.randint(0, 1000, (batch * n_chips,)), jnp.int32), mesh)

    @jax.jit
    @functools.partial(shard_map, mesh=mesh,
                       in_specs=(P(), P(), P("hvd"), P("hvd")),
                       out_specs=(P(), P(), P()), check_vma=False)
    def run(params, opt_state, x, y):
        def one_step(carry, _):
            params, opt_state = carry
            (loss, new_params), g = jax.value_and_grad(
                model_loss, has_aux=True)(params, x, y,
                                          axis_name="hvd")
            g = jax.lax.pmean(g, "hvd")
            updates, opt_state = opt.update(g, opt_state)
            # new_params carries the BN running stats the forward
            # produced (already cross-chip via axis_name); gradient
            # updates for those leaves are zero, so applying on top
            # keeps both effects.
            params = optax.apply_updates(new_params, updates)
            return (params, opt_state), jax.lax.pmean(loss, "hvd")
        (params, opt_state), losses = jax.lax.scan(
            one_step, (params, opt_state), None, length=steps)
        return params, opt_state, losses

    params, opt_state, warm = run(params, opt_state, x, y)
    warm = np.asarray(warm)  # D2H fence
    if not np.all(np.isfinite(warm)):
        return fail("non-finite warmup loss", cause="invalid-result",
                    losses=warm.tolist())

    with maybe_profile(args):
        t0 = time.perf_counter()
        params, opt_state, losses = run(params, opt_state, x, y)
        losses_host = np.asarray(losses)
        dt = time.perf_counter() - t0

    if not np.all(np.isfinite(losses_host)):
        return fail("non-finite loss", cause="invalid-result",
                    losses=losses_host.tolist())
    # Params-not-updating shows as a constant loss WITHIN each scan; a
    # constant timed scan alone can be legitimate saturation (the tiny
    # cpu smoke memorizes its fixed batch to exactly 0.0 during warmup,
    # so the warm scan still shows movement).  Both scans internally
    # flat — even at different levels — means no training happened
    # inside the scans.
    if steps > 1 and float(np.ptp(losses_host)) == 0.0 and \
            float(np.ptp(warm)) == 0.0:
        return fail("loss constant across steps — params not updating",
                    cause="invalid-result",
                    losses=losses_host.tolist(), warmup=warm.tolist())

    loss_span = (f"loss {float(losses_host[0]):.3f}->"
                 f"{float(losses_host[-1]):.3f}")
    if args.cpu:
        # counts only: see the llama path
        print(json.dumps({
            "metric": f"{family} train smoke (cpu, batch={batch}, "
                      f"{size_hw}x{size_hw}, {loss_span})",
            "value": steps * batch * n_chips,
            "unit": "images",
            "vs_baseline_is": "not measured (cpu)",
            "vs_baseline": None,
            **device,
            "metrics": metrics_summary(),
        }))
        return 0

    # batch is PER CHIP: global throughput / n_chips == steps*batch/dt.
    img_per_sec_chip = steps * batch / dt
    from horovod_tpu.perf import costmodel
    chip = device["device_kind"]
    peak = costmodel.peak_flops(chip)
    scale_flops = (size_hw / canonical_hw) ** 2
    train_flops_per_img = 3.0 * fwd_gflop * scale_flops
    mfu = img_per_sec_chip * train_flops_per_img / peak
    if not (0.0 < mfu < 1.0):
        return fail(f"MFU {mfu:.4f} outside (0,1)",
                    cause="invalid-result", chip=chip,
                    img_per_sec_chip=img_per_sec_chip)

    print(json.dumps({
        "metric": f"{family} train images/sec/chip ({chip}, "
                  f"batch={batch}, {size_hw}x{size_hw}, {loss_span})",
        "value": round(img_per_sec_chip, 1),
        "unit": "images/sec/chip",
        "mfu": round(mfu, 4),
        "vs_baseline_is": "mfu",
        "vs_baseline": round(mfu, 4),
        **device,
        "metrics": metrics_summary(),
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
