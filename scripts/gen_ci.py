#!/usr/bin/env python
"""CI pipeline generator — the TPU-native analog of the reference's
matrix generator (reference: .buildkite/gen-pipeline.sh, golden-tested by
test/single/test_buildkite.py against expected_buildkite_pipeline.yaml).

The reference varies a baseline docker image one dimension at a time
(python x framework-versions x {gloo,openmpi,mpich,oneccl} x {cpu,gpu})
and emits a Buildkite YAML.  Here the axes that exist on a TPU-native
stack are different — there is ONE data-plane backend (XLA collectives)
and no docker matrix — so the generated pipeline varies:

  * frontend suites (jax core / native controller / torch / tf / keras /
    mxnet-shim / spark+ray contract fakes / data+checkpoint+elastic),
    each an independent step so CI fans out;
  * runtime knobs, one dimension at a time off the baseline
    (hierarchical allreduce, response-cache off, stream-pool width,
    donation off, negotiated TF join) on exactly the suites that consume
    the knob;
  * process topology: the integration tier under the real launcher at
    np=2 and np=4, and the 8-device multi-chip dryrun.

Usage:
  python scripts/gen_ci.py            # rewrite .ci/pipeline.yaml
  python scripts/gen_ci.py --check    # exit 1 if the committed file is stale

The golden test (tests/test_ci_pipeline.py) regenerates the pipeline and
compares it to the committed file, and cross-checks every HOROVOD_* env
var against the knob registry and every pytest target against the tree.
"""

from __future__ import annotations

import argparse
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT = os.path.join(REPO, ".ci", "pipeline.yaml")

# Suite groups: label -> pytest files (relative to repo root).  Grouped so
# each step is big enough to amortize interpreter+jax startup but small
# enough to pinpoint a red area from the step name alone.
SUITES = {
    "jax-core": [
        "tests/test_basics.py", "tests/test_collectives.py",
        "tests/test_optimizer.py", "tests/test_fsdp.py",
        "tests/test_zero.py", "tests/test_adasum.py",
        "tests/test_hierarchical.py", "tests/test_quantized.py",
        "tests/test_wire.py", "tests/test_overlap.py",
        "tests/test_tracing.py",
    ],
    # The 3D-parallelism unit tier (docs/parallelism.md): mesh/knob
    # resolution, the TP/PP realizations' bit-near composition proofs
    # against pure dp, the layout cost model and the solver's ranking.
    "layout": ["tests/test_layout.py"],
    "models-kernels": [
        "tests/test_models.py", "tests/test_flash_attention.py",
        "tests/test_sequence_parallel.py", "tests/test_pipeline.py",
        "tests/test_expert.py",
    ],
    "native-controller": [
        "tests/test_native_core.py", "tests/test_negotiated.py",
        "tests/test_autotune.py", "tests/test_aux.py",
        "tests/test_metrics.py", "tests/test_chaos.py",
        "tests/test_postmortem.py", "tests/test_native_sanitize.py",
        "tests/test_watch.py",
    ],
    "torch": ["tests/test_torch.py"],
    "tensorflow-keras": ["tests/test_tensorflow.py", "tests/test_keras.py"],
    "mxnet-shim": ["tests/test_mxnet.py"],
    "cluster": [
        "tests/test_spark_ray.py", "tests/test_spark_estimator_depth.py",
        "tests/test_spark_prepare.py",
        "tests/test_real_backend_fakes.py", "tests/test_runner.py",
        "tests/test_ci_pipeline.py", "tests/test_docs_refs.py",
        "tests/test_hvdlint.py",
    ],
    "state-elastic-data": [
        "tests/test_data.py", "tests/test_checkpoint.py",
        "tests/test_elastic.py", "tests/test_tune.py",
        "tests/test_platform_utils.py",
    ],
    "serving": ["tests/test_serve.py", "tests/test_serve_ft.py",
                "tests/test_serve_speed.py", "tests/test_serve_replica.py",
                "tests/test_serve_trace.py", "tests/test_serve_phases.py",
                "tests/test_serve_chain.py",
                "tests/test_serve_chain_families.py",
                "tests/test_serve_commit.py",
                "tests/test_serve_arrivals.py",
                "tests/test_greedy_read.py",
                "tests/test_kv_shard.py", "tests/test_scenario.py",
                "tests/test_latent_moe.py", "tests/test_paged.py",
                "tests/test_decoder.py",
                "tests/test_swa_moe.py", "tests/test_conv_moe.py",
                "tests/test_blockdiff_moe.py",
                "tests/test_serve_blockdiff.py",
                "tests/test_sambay.py", "tests/test_serve_sambay.py",
                "tests/test_gdn_hybrid.py",
                "tests/test_serve_gdn_hybrid.py",
                "tests/test_tpu_compile.py"],
    "perf": ["tests/test_perf.py", "tests/test_memstats.py",
             "tests/test_perfbench_families.py"],
    "bench-examples": ["tests/test_bench.py", "tests/test_chip_smoke.py",
                       "tests/test_examples_smoke.py",
                       "tests/test_profile_analyzer.py"],
}

# Knob variations: (dimension-label, {env}, suite labels to re-run).
# One dimension at a time off the baseline, on the suites that consume the
# knob — the reference's vary-the-baseline pattern.
KNOB_DIMS = [
    ("hierarchical", {"HOROVOD_HIERARCHICAL_ALLREDUCE": "1",
                      "HOROVOD_HIERARCHICAL_ALLGATHER": "1"},
     ["jax-core"]),
    ("cache-off", {"HOROVOD_CACHE_CAPACITY": "0"},
     ["native-controller"]),
    ("bypass-off", {"HOROVOD_BYPASS": "0"},
     ["native-controller"]),
    ("streams-4", {"HOROVOD_NUM_STREAMS": "4"},
     ["torch"]),
    ("no-donate", {"HOROVOD_TPU_DONATE_BUFFERS": "0"},
     ["jax-core"]),
    ("wire-auto", {"HOROVOD_WIRE_POLICY": "auto"},
     ["jax-core"]),
    ("overlap", {"HOROVOD_OVERLAP": "1", "HOROVOD_OVERLAP_DEPTH": "2"},
     ["jax-core"]),
    # ZeRO default level flipped to 3 (docs/zero.md): tests that pin
    # zero_level explicitly are unaffected; everything resolving the
    # knob (the chain's defaults, the resolution tests) must stay green
    # with params sharded and a deeper AG prefetch window.
    ("zero-3", {"HOROVOD_ZERO_LEVEL": "3", "HOROVOD_ZERO_AG_PREFETCH": "4"},
     ["jax-core"]),
    # The session mesh resolved as a 3-axis (dp,tp,pp) layout instead of
    # the legacy single axis (docs/parallelism.md): the core suites must
    # stay green when init hands back a layout mesh — unit tests that
    # pin the legacy DP path's semantics build their own ("hvd",) mesh,
    # and tests claiming an explicit mesh spec clear these knobs.
    ("layout-tp-pp", {"HOROVOD_LAYOUT": "auto", "HOROVOD_TP": "2",
                      "HOROVOD_PP": "2"},
     ["jax-core", "layout"]),
    ("tf-join", {"HOROVOD_TF_JOIN": "1"},
     ["tensorflow-keras"]),
    # serve-redrive off = degraded mode: the router stops journaling,
    # redrive fast-forwards instead of replaying — the serving suite
    # must stay green either way (docs/serving.md#fault-tolerance).
    ("serve-journal-off", {"HOROVOD_SERVE_JOURNAL": "0"},
     ["serving"]),
    # raw-speed legs off = the slow-but-simple paths (every prompt
    # recomputes / one token per tick): the serving suite must stay
    # green with each leg disabled (docs/serving.md#raw-speed).
    ("serve-prefix-off", {"HOROVOD_SERVE_PREFIX_CACHE": "0"},
     ["serving"]),
    ("serve-spec-off", {"HOROVOD_SERVE_SPEC": "0"},
     ["serving"]),
    # control-plane scale-out off/on (docs/control-plane.md): the
    # serving suite must stay green over a 3-shard KV with direct
    # streaming disabled (every token back on the KV PUT+poll path) —
    # the degraded/pre-scale-out combination.
    ("kv-shards-3", {"HOROVOD_KV_SHARDS": "3",
                     "HOROVOD_SERVE_DIRECT": "0"},
     ["serving"]),
    # replicated tier on by default (docs/serving.md#replicated-tier):
    # a 2-replica config with this process as replica 0 must keep the
    # serving suite green — replica 0 keeps the unscoped KV names, so
    # everything pre-replica stays byte-compatible under the knob.
    ("serve-replicas-2", {"HOROVOD_SERVE_REPLICAS": "2"},
     ["serving"]),
    # host-RAM spill tier armed: cold radix blocks migrate to host RAM
    # at eviction and reload on hit — outputs must stay reference-greedy
    # byte-identical through the migration.
    ("serve-spill", {"HOROVOD_SERVE_SPILL_BLOCKS": "64"},
     ["serving"]),
    # memory plane off (docs/memory.md): the perf suite must stay green
    # with sampling disabled — reports lose their memory section, the
    # hvd_mem_* gauges stay unset, and nothing downstream may assume
    # the section exists (tests that exercise sampling itself re-enable
    # the knob explicitly).
    ("mem-off", {"HOROVOD_MEM": "0"},
     ["perf"]),
]


def _step(label, command, env=None, timeout=30):
    s = {"label": label, "command": command,
         "timeout_in_minutes": timeout}
    if env:
        s["env"] = dict(sorted(env.items()))
    return s


def build_steps():
    py = "python"
    steps = []
    # -m "": CI runs the FULL tiers — the repo's pytest addopts default
    # to the fast pre-commit selection (not slow, not integration),
    # which would silently hollow these steps out.
    full = "-q -m \"\""
    for name, files in SUITES.items():
        steps.append(_step(
            f"unit: {name}",
            f"{py} -m pytest {' '.join(files)} {full}"))
    for dim, env, suites in KNOB_DIMS:
        for name in suites:
            steps.append(_step(
                f"knob {dim}: {name}",
                f"{py} -m pytest {' '.join(SUITES[name])} {full}",
                env=env))
    steps.append(_step(
        "integration: real launcher np=2/np=4",
        f"{py} -m pytest tests/integration {full}", timeout=45))
    steps.append(_step(
        # chaos smoke: the resilience claims as experiments — a 2-process
        # kill-and-recover dryrun plus the transport/fastcommit/straggler
        # injections (docs/chaos.md), all CPU-virtual.
        "chaos: 2-process kill-and-recover smoke",
        f"{py} -m pytest tests/integration/test_chaos_integration.py {full}",
        env={"JAX_PLATFORMS": "cpu"}, timeout=20))
    steps.append(_step(
        # postmortem doctor smoke: a chaos-killed (and separately a
        # chaos-stalled) 2-process run under hvdrun --postmortem must
        # produce a postmortem.json attributing the injected fault to
        # the right rank and cause, with the stalled rank's SIGABRT
        # flight record parseable and span-bearing, and `hvdrun doctor`
        # rendering it root-cause-first (docs/postmortem.md).
        "postmortem: chaos-killed 2-process doctor smoke",
        f"{py} -m pytest tests/integration/test_postmortem_integration.py "
        f"{full}",
        env={"JAX_PLATFORMS": "cpu"}, timeout=20))
    steps.append(_step(
        # timeline-merge smoke: a 2-process loopback run under the real
        # launcher with --timeline-merge + an injected chaos stall must
        # produce ONE valid Chrome/Perfetto JSON — both rank lanes on a
        # common clock-aligned epoch, native controller-cycle and
        # transport spans present, the stall a named event on the
        # faulted rank (docs/timeline.md).
        "timeline: 2-process merged-trace smoke",
        f"{py} -m pytest tests/integration/test_tracing_integration.py "
        f"{full}",
        env={"JAX_PLATFORMS": "cpu"}, timeout=20))
    steps.append(_step(
        # serving smoke: the full front door on a 2-process CPU-virtual
        # fleet — hvdrun --serve restores a checkpoint.py servable,
        # completes concurrent POST /generate requests with streamed
        # tokens, exports nonzero hvd_serve_ttft at /metrics, leaves
        # per-request spans in the merged timeline, and the plan-stream
        # lockstep digests match across ranks (docs/serving.md).
        # Loopback TCP + XLA-CPU decode only.
        "serve: 2-process hvdrun --serve /generate smoke",
        f"{py} -m pytest tests/integration/test_serve_integration.py "
        f"{full}",
        env={"JAX_PLATFORMS": "cpu"}, timeout=20))
    steps.append(_step(
        # elastic-serve chaos smoke: the fault-tolerant serving
        # acceptance experiment — a 2-proc fleet under the elastic
        # serve driver has rank 1 chaos-killed MID-DECODE; the fleet
        # resets, journaled requests redrive past their streamed
        # prefix, every client stream completes byte-identical to an
        # unfaulted fleet's, and POST /admin/drain exits both fleets 0
        # (docs/serving.md#fault-tolerance).  Bounded runtime: tiny
        # model, 2 requests, loopback only.
        "chaos: elastic-serve kill-mid-stream smoke",
        f"{py} -m pytest "
        f"tests/integration/test_elastic_serve_integration.py {full}",
        env={"JAX_PLATFORMS": "cpu"}, timeout=25))
    steps.append(_step(
        # sharded-serve chaos smoke: the control-plane scale-out
        # acceptance experiment — two 2-proc fleets over a 3-shard KV
        # with direct token streaming; fleet B's chaos spec blacks out
        # the serve and plan shards MID-RUN (op-offset windows) and
        # every accepted /generate stream must complete byte-identical
        # to the unfaulted fleet's, with per-shard health at /health
        # (docs/control-plane.md).
        "chaos: sharded-serve partial-outage smoke",
        f"{py} -m pytest "
        f"tests/integration/test_kv_shard_integration.py {full}",
        env={"JAX_PLATFORMS": "cpu"}, timeout=20))
    steps.append(_step(
        # replica-tier acceptance: the replicated front door's claims
        # as experiments — prefix-affinity placement and per-replica
        # scoping units, the host-RAM spill migration and the
        # prefill/decode disaggregation handoff each byte-identical to
        # reference greedy, and a 2-replica kill-one-replica run
        # through the REAL router whose re-dispatched stream completes
        # byte-identical to the unfaulted single-fleet reference
        # (docs/serving.md#replicated-tier).
        "serve: 2-replica affinity + kill-one-replica redispatch",
        f"{py} -m pytest tests/test_serve_replica.py {full}",
        env={"JAX_PLATFORMS": "cpu"}, timeout=20))
    steps.append(_step(
        # request-trace smoke: the causal tracing plane end to end —
        # deterministic span ids (the hvdlint trace-context contract),
        # the sums-exactly SLO attribution, a /generate request through
        # the real router leaving a serve_trace record + timeline spans,
        # GET /serve/trace analytics, shed-rid 429 forensics, and
        # `hvdrun doctor --request` byte-consistent from live route and
        # post-exit KV (docs/serving.md#request-lifecycle).
        "serve: request-lifecycle trace + doctor --request smoke",
        f"{py} -m pytest tests/test_serve_trace.py {full}",
        env={"JAX_PLATFORMS": "cpu"}, timeout=20))
    steps.append(_step(
        # watch-plane alerts smoke: hvdrun --alerts (user rules merged
        # over the committed defaults) on 2-proc runs — a
        # chaos-scheduled 40 ms stall must fire the straggler-suspect
        # rule at GET /alerts naming rank 1 AND land as a timeline
        # instant on rank 1's lane, and a NaN-injected gradient must
        # fire the sentinel-nonfinite CRITICAL alert plus a parseable
        # reason-nan flight dump (docs/watch.md).
        "watch: 2-process alerts + sentinel smoke (hvdrun --alerts)",
        f"{py} -m pytest tests/integration/test_watch_integration.py "
        f"{full}",
        env={"JAX_PLATFORMS": "cpu"}, timeout=20))
    steps.append(_step(
        # perf-attribution smoke: a 2-process CPU-virtual fleet records
        # steps through the decomposition ledger; the components sum to
        # the measured step time within 10%, the merged GET /perf view
        # serves the same numbers, and `hvdrun doctor --perf` renders
        # that exact payload (docs/profiling.md).
        "perf: 2-process attribution /perf + doctor smoke",
        f"{py} -m pytest tests/integration/test_perf_integration.py "
        f"{full}",
        env={"JAX_PLATFORMS": "cpu"}, timeout=20))
    steps.append(_step(
        # memory-plane smoke: a 2-process CPU-virtual fleet's measured
        # hvd_mem_* families land in GET /series for both ranks, the
        # GET /perf reconciliation carries bounded drift + the fleet
        # worst-watermark rollup, a synthetic near-cap fires the
        # committed mem-pressure-high rule at GET /alerts in flight,
        # and the sentinel's reason-mem flight dump parses
        # (docs/memory.md).
        "mem: 2-process memory ledger + pressure-alert smoke",
        f"{py} -m pytest tests/integration/test_mem_integration.py "
        f"{full}",
        env={"JAX_PLATFORMS": "cpu"}, timeout=20))
    steps.append(_step(
        # scenario distribution smoke: hvdrun --chaos + --scenario on a
        # 2-proc run — the spec rides the rendezvous KV as JSON and
        # both ranks regenerate the SAME trace digest, the embedded
        # storm arrives as part of the MERGED chaos spec, the embedded
        # alert rule lands in the published ruleset, and a
        # contradictory --chaos seed refuses to launch
        # (docs/scenarios.md).
        "scenario: 2-process spec/storm/rules distribution smoke",
        f"{py} -m pytest tests/integration/test_scenario_integration.py "
        f"{full}",
        env={"JAX_PLATFORMS": "cpu"}, timeout=15))
    steps.append(_step(
        "dryrun: 8-chip multichip shardings",
        f'{py} -c "import __graft_entry__ as g; g.dryrun_multichip(8)"',
        env={"JAX_PLATFORMS": "cpu",
             "XLA_FLAGS": "--xla_force_host_platform_device_count=8"},
        timeout=20))
    steps.append(_step(
        "bench: cpu smoke",
        f"{py} bench.py --cpu", timeout=15))
    steps.append(_step(
        # eager fast-path smoke: the steady-state plan epoch must lock
        # at np=2 under the real launcher and hold the <1.2 cycles/op
        # bound with a sub-ms locked negotiation round trip — the
        # docs/benchmarks.md steady-state claim as a gate
        # (scripts/bench_eager.py; docs/tensor-fusion.md#steady-state).
        "bench: eager fast-path smoke (np=2, cycles/op bound)",
        f"{py} -m pytest tests/integration/test_multiprocess.py "
        f"-q -m \"\" -k eager_bench_bounds",
        env={"JAX_PLATFORMS": "cpu"}, timeout=15))
    steps.append(_step(
        # wire-policy sweep smoke: every wire format round-trips on the
        # 8-device virtual mesh, int8 carries <= 1/2 bf16's modeled
        # bytes, EF residuals and decode determinism asserted
        # (docs/tensor-fusion.md#wire-policies) — all CPU-virtual.
        "bench: wire-policy sweep smoke",
        f"{py} bench.py --wire --cpu", timeout=15))
    steps.append(_step(
        # overlap-plane sweep smoke: the microbatch pipeline at each
        # depth lands the same params as the sequential schedule, the
        # interleaved ZeRO-1 matches monolithic, and the analytical
        # exposed/overlapped split rides the artifact
        # (docs/overlap.md) — all CPU-virtual.
        "bench: overlap sweep smoke",
        f"{py} bench.py --overlap --cpu", timeout=15))
    steps.append(_step(
        # ZeRO-level equivalence smoke: the bucket-interleaved chain at
        # levels 1/2/3 (int8 wire + EF + microbatching) under the real
        # launcher — every leg rides real cross-process collectives and
        # params land bit-near across levels, bit-identical across
        # chips (docs/zero.md) — all CPU-virtual.
        "zero: 2-process zero2/zero3 equivalence smoke",
        f"{py} -m pytest tests/integration/test_zero_integration.py "
        f"{full}",
        env={"JAX_PLATFORMS": "cpu"}, timeout=15))
    steps.append(_step(
        # auto-layout smoke: HOROVOD_LAYOUT=auto under the real launcher
        # at np=2 resolves the constrained (2,2,2) mesh on both
        # processes; the composed TP+PP+ZeRO chain lands bit-near the
        # dp-only reference across REAL cross-process collectives, and
        # the solver's candidate table rides GET /perf with the chosen
        # layout's predicted-vs-measured ratio (docs/parallelism.md).
        "layout: 2-process auto-layout (2,2,2) smoke",
        f"{py} -m pytest tests/integration/test_layout_integration.py "
        f"{full}",
        env={"JAX_PLATFORMS": "cpu"}, timeout=15))
    steps.append(_step(
        # ZeRO sweep smoke: levels 0-3 on the quadratic toy +
        # llama-tiny with level 1/2/3 equivalence asserted in-bench,
        # the analytical memory columns and the ledger drift riding
        # the artifact for the perf gate (docs/zero.md) — all
        # CPU-virtual.
        "bench: zero sweep smoke",
        f"{py} bench.py --zero --cpu", timeout=15))
    steps.append(_step(
        # layout sweep smoke: the solver's candidate table measured on
        # llama-tiny — every feasible (dp,tp,pp) trains with params
        # equivalence-asserted against dp-only in-bench, and the chosen
        # layout's calibrated predicted-vs-measured drift gates the run
        # and rides the artifact for the perf gate
        # (docs/parallelism.md) — all CPU-virtual.
        "bench: layout sweep smoke",
        f"{py} bench.py --layout --cpu", timeout=15))
    steps.append(_step(
        # serving load-gen + raw-speed smoke: closed-loop and Poisson
        # load emit plausible SLO rows, AND the three speed legs
        # (radix prefix cache, chunked prefill, speculative decoding)
        # each run off->on over the same workload with byte-identical
        # greedy output — a broken identity contract fails the bench
        # itself, the speedup rows ride the artifact for the perf gate
        # (docs/serving.md#raw-speed) — all CPU-virtual.
        "bench: serve load-gen + speed-legs smoke",
        f"{py} bench.py --serve --cpu", timeout=15))
    steps.append(_step(
        # control-plane saturation smoke: the closed-loop user sweep
        # drives POST /generate through the REAL router + KV for the
        # single-process baseline AND the sharded+direct config; the
        # knee rows ride the artifact for the perf gate
        # (docs/control-plane.md) — all CPU-virtual.
        "bench: serve control-plane saturation smoke",
        f"{py} bench.py --serve --users 1,2,4 --cpu", timeout=15))
    steps.append(_step(
        # replica scale-out smoke: the --replicas sweep drives POST
        # /generate through the REAL prefix-affinity router over 1- and
        # 2-replica tiers; the per-count knees, the 1->2 scale-out gain
        # and the affinity hit rate (vs a least-loaded control) ride
        # the artifact for the perf gate
        # (docs/serving.md#replicated-tier) — all CPU-virtual.
        "bench: serve replica scale-out smoke",
        f"{py} bench.py --serve --users 2,4,8,16 --replicas 1,2 --cpu",
        timeout=15))
    steps.append(_step(
        # scenario replay smoke: one committed corpus spec replayed
        # against the REAL router/engine/watch planes on the virtual
        # clock — two same-seed runs must produce byte-identical SLO
        # rows (the bench fails itself otherwise), the expected alerts
        # are verified against a live GET /alerts, and the rows ride
        # the artifact for the perf gate (docs/scenarios.md) — all
        # CPU-virtual.
        "bench: scenario trace-replay smoke (burst-serve)",
        f"{py} bench.py --scenario scenarios/burst-serve.yaml --cpu",
        timeout=15))
    steps.append(_step(
        # perf regression gate smoke: bench.py --cpu runs three times —
        # two baseline the host's noise, the unmodified re-run must
        # PASS the median±MAD gate, and an injected synthetic 2x
        # slowdown must TRIP it (docs/profiling.md#regression-gate).
        "perf: regression-gate smoke (re-run passes, 2x trips)",
        f"{py} scripts/perf_gate.py --smoke", timeout=20))
    steps.append(_step(
        # repo-invariant linter (docs/static-analysis.md#hvdlint):
        # knob-registry, metrics-docs coverage + exposition, serve
        # lockstep determinism, serve KV-retry discipline, unique test
        # basenames, postmortem signal-safety — conventions every PR
        # used to re-verify by hand, now a standing gate.
        "lint: hvdlint repo invariants",
        f"{py} scripts/hvdlint.py", timeout=10))
    steps.append(_step(
        # clang-tidy over csrc with the committed concurrency/bugprone
        # config (csrc/.clang-tidy, WarningsAsErrors).  Gated on
        # availability like run_real_backends: without clang-tidy the
        # leg exits 0 with an explicit impossibility note.
        "lint (gated): clang-tidy csrc concurrency/bugprone",
        f"{py} scripts/run_clang_tidy.py", timeout=15))
    steps.append(_step(
        # native race harness under ThreadSanitizer: build the SAN=tsan
        # library, then run every stress scenario (submit storms, epoch
        # lock/break/relock churn, trace drain-while-record, chaos
        # reconnect storms, flight dumps mid-cycle) with zero
        # unsuppressed reports as the assertion
        # (docs/static-analysis.md#sanitizers).
        "sanitize: TSan native race harness",
        "make -C csrc SAN=tsan && "
        f"{py} -m pytest tests/test_native_sanitize.py -q -m \"\" "
        f"-k \"tsan\"", timeout=30))
    steps.append(_step(
        # the same harness under ASan (memory errors; leak checking is
        # a documented non-goal under a Python driver) and UBSan
        # (-fno-sanitize-recover: any UB aborts the scenario).
        "sanitize: ASan + UBSan native harness",
        "make -C csrc SAN=asan && make -C csrc SAN=ubsan && "
        f"{py} -m pytest tests/test_native_sanitize.py -q -m \"\" "
        f"-k \"asan or ubsan\"", timeout=30))
    steps.append(_step(
        # promtool-check-metrics-style gate, pure Python (no external
        # dep): renders a populated fleet /metrics snapshot through the
        # server's own code path and lints the exposition format so
        # drift fails here, not in someone's Prometheus scrape.
        "metrics: exposition-format lint",
        f"{py} scripts/check_metrics_format.py", timeout=10))
    steps.append(_step(
        # Gated on availability: with real pyspark/ray installed this
        # validates the contract fakes against reality (reference:
        # Dockerfile.test.cpu:57-86); without them it exits 0 with an
        # explicit impossibility note, never a silent skip.
        "real-backends (gated): contract tests vs real pyspark/ray",
        f"{py} scripts/run_real_backends.py", timeout=30))
    return steps


def validate(steps):
    """Every pytest target must exist — a renamed test file must break the
    generator, not silently shrink CI."""
    for s in steps:
        for tok in s["command"].split():
            if tok in ("tests/integration", "bench.py") or (
                    tok.startswith("tests/") and tok.endswith(".py")):
                if not os.path.exists(os.path.join(REPO, tok)):
                    raise FileNotFoundError(
                        f"step '{s['label']}' references missing {tok}")
    dirs = [t for s in steps for t in s["command"].split()
            if t == "tests/integration"]
    assert dirs, "integration tier missing from pipeline"
    assert os.path.exists(os.path.join(REPO, "__graft_entry__.py")), \
        "dryrun step target __graft_entry__.py missing"


def render(steps) -> str:
    """Hand-rendered YAML: deterministic byte-for-byte output (a yaml-lib
    version bump must not dirty the golden file)."""
    lines = ["# Generated by scripts/gen_ci.py — do not edit by hand.",
             "# Regenerate: python scripts/gen_ci.py", "steps:"]
    for s in steps:
        lines.append(f"  - label: {_q(s['label'])}")
        lines.append(f"    command: {_q(s['command'])}")
        lines.append(f"    timeout_in_minutes: {s['timeout_in_minutes']}")
        if "env" in s:
            lines.append("    env:")
            for k, v in s["env"].items():
                lines.append(f"      {k}: {_q(v)}")
    return "\n".join(lines) + "\n"


def _q(v: str) -> str:
    return '"' + str(v).replace("\\", "\\\\").replace('"', '\\"') + '"'


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--check", action="store_true",
                    help="verify the committed pipeline is current")
    args = ap.parse_args()
    steps = build_steps()
    validate(steps)
    text = render(steps)
    if args.check:
        if not os.path.exists(OUT):
            print(f"{OUT} missing; run scripts/gen_ci.py", file=sys.stderr)
            return 1
        with open(OUT) as f:
            if f.read() != text:
                print(f"{OUT} is stale; run scripts/gen_ci.py",
                      file=sys.stderr)
                return 1
        print("pipeline up to date")
        return 0
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    with open(OUT, "w") as f:
        f.write(text)
    print(f"wrote {OUT} ({len(steps)} steps)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
