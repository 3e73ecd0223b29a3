"""bench.py smoke: the driver's benchmark entry must keep producing its
one-line JSON contract in CPU mode for both metrics (llama tokens/sec and
resnet images/sec).  Subprocess-isolated — bench.py owns process-global
jax config."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:  # `import bench` must work under bare `pytest`
    sys.path.insert(0, REPO)


def _run_bench(*flags, env=None, timeout=420):
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), "--cpu", *flags],
        capture_output=True, text=True, timeout=timeout, cwd=REPO, env=env)
    assert out.returncode == 0, out.stdout + out.stderr
    line = out.stdout.strip().splitlines()[-1]
    return json.loads(line)


@pytest.mark.slow
def test_bench_llama_cpu_contract():
    rec = _run_bench()
    assert set(rec) >= {"metric", "value", "unit", "vs_baseline"}
    # a CPU run prints counts and names its device; no rate, no MFU
    assert (rec["platform"], rec["device_kind"]) == ("cpu", "cpu")
    assert rec["unit"] == "tokens"  # steps x global batch x seq
    assert rec["value"] == 4 * (4 * rec["device_count"]) * 64
    assert rec["vs_baseline"] is None and "mfu" not in rec
    # The headline protocol guard: a plain run must resolve the
    # score-dtype default to 'input' (bf16 score slab) and say so in
    # the self-describing `attn` field, so a silent default drift fails
    # here rather than in a bench artifact.
    assert rec["attn"] == "xla-score-input"


@pytest.mark.slow
def test_bench_resnet_cpu_contract():
    rec = _run_bench("--resnet")
    assert rec["unit"] == "images" and rec["value"] > 0
    assert rec["vs_baseline"] is None and "mfu" not in rec
    assert rec["platform"] == "cpu"


@pytest.mark.slow
def test_bench_scaling_cpu_contract():
    """--scaling: the reference's headline metric (scaling efficiency,
    docs/benchmarks.rst) measured over mesh prefixes.  On the 8-device
    virtual CPU mesh the absolute value reflects shared-core contention,
    but the contract — efficiency in (0, 1.5], a rate per size, sizes
    doubling from 1 — must hold."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    rec = _run_bench("--scaling", env=env)
    assert rec["unit"] == "scaling_efficiency"
    assert 0 < rec["value"] <= 1.5
    rates = rec["rates_tok_s_chip"]
    assert sorted(map(int, rates)) == [1, 2, 4, 8]
    assert all(v > 0 for v in rates.values())
    assert rec["vs_baseline_is"] == "weak_scaling_efficiency_vs_1chip"


@pytest.mark.slow
def test_bench_autotune_cpu_contract(tmp_path):
    env = dict(os.environ)
    env["HOROVOD_AUTOTUNE_LOG"] = str(tmp_path / "traj.csv")
    rec = _run_bench("--autotune", env=env, timeout=400)
    assert rec["unit"] == "GB/s"
    assert rec["value"] > 0
    assert rec["vs_baseline"] > 0
    # the trajectory artifact must exist with >= 2 samples
    lines = (tmp_path / "traj.csv").read_text().strip().splitlines()
    assert lines[0].startswith("threshold_bytes")
    assert len(lines) >= 3


@pytest.mark.slow
def test_bench_wire_cpu_contract():
    """--wire: the wire-policy sweep artifact (ISSUE 3 acceptance): int8
    policies at <= 1/2 bf16's (<= 1/4 fp32's) modeled wire bytes on the
    bucket mix, per-bucket EF residual norms for every lossy policy,
    decode determinism flagged per policy, 'auto' mixing formats across
    buckets, and the explicit CPU-virtual labeling."""
    env = dict(os.environ)
    rec = _run_bench("--wire", env=env, timeout=400)
    assert rec["unit"] == "wire_bytes_ratio_int8_vs_fp32"
    assert "CPU-virtual" in rec["label"]
    pol = rec["policies"]
    assert pol["int8_ring"]["wire_bytes_per_step"] * 2 <= \
        pol["bf16"]["wire_bytes_per_step"]
    assert pol["int8_ring"]["wire_bytes_per_step"] * 4 <= \
        pol["none"]["wire_bytes_per_step"]
    assert all(p["decode_deterministic"] for p in pol.values())
    for lossy in ("bf16", "fp16", "int8_ring"):
        assert pol[lossy]["residual_norm"], lossy
    # auto demonstrably picks per-bucket formats on the mix
    assert len(pol["auto"]["wire_bytes_by_format"]) >= 2
    two = rec["two_level"]
    assert two["dcn_int8"]["dcn_wire_bytes_per_step"] < \
        two["int8_ring"]["dcn_wire_bytes_per_step"]


@pytest.mark.slow
def test_bench_overlap_cpu_contract():
    """--overlap: the overlap-plane sweep artifact (docs/overlap.md):
    per-depth {step_time, exposed_comm_bytes (analytical),
    overlapped_fraction}, the legacy baseline fully exposed, depth 1
    hiding the largest fraction, a zero1 section with the interleaved
    pipeline's split, the pipelined ≡ sequential equivalence asserted
    inside the bench, and the explicit CPU-virtual labeling."""
    env = dict(os.environ)
    rec = _run_bench("--overlap", env=env, timeout=400)
    assert rec["unit"] == "overlapped_fraction"
    assert "CPU-virtual" in rec["label"]
    assert rec["equivalence_asserted"] is True
    depths = rec["depths"]
    assert set(depths) >= {"off", "0", "1", "2"}
    for row in depths.values():
        assert row["step_time_s"] > 0
        assert row["exposed_comm_bytes"] >= 0
        assert 0.0 <= row["overlapped_fraction"] <= 1.0
    # the baseline and the sequential schedule hide nothing; the
    # shallowest pipeline hides the most (deeper buffers drain more at
    # the flush)
    assert depths["off"]["overlapped_fraction"] == 0.0
    assert depths["0"]["overlapped_fraction"] == 0.0
    assert depths["1"]["overlapped_fraction"] >= \
        depths["2"]["overlapped_fraction"] > 0.0
    assert depths["1"]["exposed_comm_bytes"] < \
        depths["off"]["exposed_comm_bytes"]
    zero1 = rec["zero1"]
    assert zero1["monolithic"]["step_time_s"] > 0
    assert 0.0 < zero1["interleaved"]["overlapped_fraction"] <= 1.0


@pytest.mark.slow
def test_bench_zero_cpu_contract():
    """--zero: the ZeRO sweep artifact (docs/zero.md): per-level
    {analytical peak bytes, MEASURED peak bytes + mem drift
    (perf/memstats.py; docs/memory.md), step_time, exposed_comm_bytes,
    ledger drift}, the acceptance reductions (>= 2x state+grad at
    level 2, >= n/2 x params at level 3), levels 1/2/3 equivalence
    asserted in-bench, the gate-able sub_rows, and the CPU-virtual
    labeling."""
    env = dict(os.environ)
    rec = _run_bench("--zero", env=env, timeout=400)
    assert rec["unit"] == "x"
    assert "CPU-virtual" in rec["label"]
    assert rec["equivalence_asserted"] is True
    n = rec["world"]
    assert n >= 2
    toy = rec["toy"]
    assert set(toy) == {"0", "1", "2", "3"}
    for row in toy.values():
        assert row["step_time_s"] > 0
        assert row["exposed_comm_bytes"] >= 0
        assert row["peak_bytes"]["total_bytes"] > 0
    # the acceptance reductions, from the artifact's own analytical rows
    def _sg(lv):
        m = toy[lv]["peak_bytes"]
        return m["grads_bytes"] + m["opt_state_bytes"]
    assert _sg("0") >= 2 * _sg("2")
    assert toy["0"]["peak_bytes"]["params_bytes"] >= \
        (n / 2) * toy["3"]["peak_bytes"]["params_bytes"]
    # memory monotonically non-increasing with level; level-2 wire bytes
    # strictly below level-1's at k>1 (the ZeRO-2 claim)
    totals = [toy[lv]["peak_bytes"]["total_bytes"]
              for lv in ("0", "1", "2", "3")]
    assert totals == sorted(totals, reverse=True)
    assert rec["k"] > 1
    assert toy["2"]["exposed_comm_bytes"] < toy["1"]["exposed_comm_bytes"]
    # the ledger ran against the costmodel prediction: drift recorded
    # and inside the (documented, CPU-virtual-loose) bound
    for lv in ("1", "2", "3"):
        drift = toy[lv]["model_drift_ratio"]
        assert drift is not None and 0.0 < drift < 50.0, (lv, drift)
    # the memory plane's measured side rode along: a peak measurement
    # per row (CPU-virtual live-buffer aggregate, labeled as such) with
    # a finite reconciliation against the analytical prediction
    for lv in ("0", "1", "2", "3"):
        row = toy[lv]
        assert row["measured_peak_bytes"] is not None \
            and row["measured_peak_bytes"] >= 0, (lv, row)
        assert row["measured_source"] in ("device", "live_buffers")
        mdrift = row["mem_drift_ratio"]
        assert mdrift is not None and 0.0 < mdrift < 1e4, (lv, mdrift)
    llama = rec["llama"]
    assert set(llama) == {"1", "2", "3"}
    for row in llama.values():
        assert row["tokens_per_s"] > 0
        assert row["peak_bytes"]["total_bytes"] > 0
        assert row["measured_peak_bytes"] is not None \
            and row["measured_peak_bytes"] >= 0
        mdrift = row["mem_drift_ratio"]
        assert mdrift is not None and 0.0 < mdrift < 1e4
    subs = {r["metric"]: r for r in rec["sub_rows"]}
    assert subs["zero level2 state+grad memory reduction"]["value"] >= 2
    assert subs["zero level3 param memory reduction"]["value"] >= n / 2
    for key in ("zero level2 step overhead vs level1",
                "zero level3 step overhead vs level1"):
        assert subs[key]["unit"] == "ratio" and subs[key]["value"] > 0


@pytest.mark.slow
def test_bench_layout_cpu_contract():
    """--layout: the 3D layout sweep artifact (docs/parallelism.md) —
    the solver's ranked candidate table actually RAN: a measured row
    per (dp, tp, pp) candidate with predicted step + memory beside the
    wall clock and the live-buffer peak, drift both raw and calibrated
    (the chosen row's calibrated drift is the headline value and must
    sit under the 2x ledger-validation gate), cross-layout bit-near
    equivalence asserted in-bench, the gate-able sub_rows, and the
    CPU-virtual labeling."""
    env = dict(os.environ)
    rec = _run_bench("--layout", env=env, timeout=400)
    assert rec["unit"] == "x"
    assert rec["higher_is_better"] is False
    assert "CPU-virtual" in rec["label"]
    assert rec["equivalence_asserted"] is True
    n = rec["world"]
    assert n == 8  # the sweep virtualizes the 8-device harness mesh
    layouts = rec["layouts"]
    assert len(layouts) >= 2 and f"{n}x1x1" in layouts
    ranks = set()
    for key, row in layouts.items():
        dp, tp, pp = map(int, key.split("x"))
        assert dp * tp * pp == n
        ranks.add(row["rank"])
        assert row["step_time_s"] > 0 and row["tokens_per_s"] > 0
        assert row["predicted_step_s"] > 0
        assert row["predicted_peak_bytes"]["total_bytes"] > 0
        assert row["measured_peak_bytes"] is not None \
            and row["measured_peak_bytes"] > 0, (key, row)
        assert row["measured_source"] in ("device", "live_buffers")
        # pipeline rows carry the bubble the model priced
        assert (row["bubble_fraction"] > 0) == (pp > 1), (key, row)
        # every row's chain ran against the ledger's layout table: the
        # active-row prediction was judged against the wall clock
        assert row["ledger_step_ratio"] is not None \
            and row["ledger_step_ratio"] > 0, (key, row)
        assert row["raw_drift_ratio"] > 0
        assert row["calibrated_drift_ratio"] >= 1.0
    assert ranks == set(range(1, len(layouts) + 1))
    # the ledger-validation gate the bench itself asserts pre-print:
    # re-check it from the artifact (chosen row, calibrated)
    assert rec["chosen"] in layouts
    assert 1.0 <= layouts[rec["chosen"]]["calibrated_drift_ratio"] < 2.0
    assert rec["value"] == layouts[rec["chosen"]]["calibrated_drift_ratio"]
    subs = {r["metric"]: r for r in rec["sub_rows"]}
    assert len(subs) >= 4  # the committed PERF_BASELINE.json keys
    assert subs["layout solver candidates (llama-tiny)"]["value"] \
        == len(layouts)
    assert subs["layout chosen calibrated step drift"][
        "higher_is_better"] is False
    for key, sub in subs.items():
        if "overhead vs dp-only" in key:
            assert sub["unit"] == "ratio" and sub["value"] > 0


@pytest.mark.slow
def test_bench_serve_users_cpu_contract():
    """--serve --users: the control-plane saturation sweep
    (docs/control-plane.md) — per-user-count rows for the single-shard
    baseline AND the sharded+direct config, a knee per config, the
    gate-able sub_rows (knee throughputs + scale-out gain), and the
    explicit measures-router-not-decode labeling."""
    env = dict(os.environ)
    rec = _run_bench("--serve", "--users", "1,2,4", env=env, timeout=400)
    assert rec["unit"] == "tokens/sec"
    assert "CPU-virtual" in rec["label"] and "router" in rec["label"]
    assert rec["user_counts"] == [1, 2, 4]
    for cfg in ("single", "sharded_direct"):
        res = rec[cfg]
        assert [r["users"] for r in res["rows"]] == [1, 2, 4]
        assert all(r["tok_s"] > 0 for r in res["rows"]), res
        assert res["knee_users"] in (1, 2, 4)
        assert res["knee_tok_s"] >= 0.9 * res["peak_tok_s"]
    subs = {r["metric"].split(" (")[0]: r for r in rec["sub_rows"]}
    assert "serve ctrl-plane scale-out gain" in subs
    assert subs["serve ctrl-plane scale-out gain"]["unit"] == "x"
    assert subs["serve ctrl-plane single knee throughput"]["value"] == \
        rec["single"]["knee_tok_s"]
    assert subs["serve ctrl-plane sharded-direct knee throughput"][
        "value"] == rec["sharded_direct"]["knee_tok_s"]


@pytest.mark.slow
def test_bench_serve_replicas_cpu_contract():
    """--serve --users --replicas: the replica scale-out sweep
    (docs/serving.md#replicated-tier) — one knee row per replica count,
    the gated sub_rows (per-count knees, 1->2 scale-out gain, affinity
    hit rate vs the least-loaded control), and the explicit
    measures-router-not-decode labeling.  The 1->2 gain floor here is
    the acceptance criterion's, minus gate-style noise headroom."""
    env = dict(os.environ)
    rec = _run_bench("--serve", "--users", "2,4,8,16", "--replicas",
                     "1,2", env=env, timeout=500)
    assert rec["unit"] == "tokens/sec"
    assert "CPU-virtual" in rec["label"] and "router" in rec["label"]
    assert rec["replica_counts"] == [1, 2]
    for n in (1, 2):
        res = rec["results"][str(n)]
        assert res["replicas"] == n
        assert all(r["tok_s"] > 0 for r in res["rows"]), res
        assert res["knee_tok_s"] >= 0.9 * res["peak_tok_s"]
    subs = {r["metric"].split(" (")[0]: r for r in rec["sub_rows"]}
    gain = subs["serve replica scale-out gain 1to2"]
    assert gain["unit"] == "x" and gain["higher_is_better"]
    # Acceptance floor is 1.7x; the sweep lands ~2x with keyed stream
    # wakeups, so 1.5 here keeps the contract test noise-tolerant while
    # still catching a tier that stopped scaling out.
    assert gain["value"] >= 1.5, rec
    hit = subs["serve replica affinity hit rate r2"]
    assert hit["unit"] == "ratio" and hit["value"] >= 0.9
    assert rec["least_loaded_control"]["affinity_hit_rate"] <= 0.5


@pytest.mark.slow
def test_bench_serve_cpu_contract():
    """--serve: the serving load-generator artifact (docs/serving.md):
    a closed-loop row (fixed user pool, the throughput ceiling) and a
    Poisson open-loop row, each carrying {throughput_tok_s,
    ttft_p50/p99, tpot_p50/p99, batch_fill}, every request completing,
    and the explicit CPU-virtual labeling."""
    env = dict(os.environ)
    rec = _run_bench("--serve", env=env, timeout=400)
    assert rec["unit"] == "tokens/sec"
    assert "CPU-virtual" in rec["label"]
    assert rec["vs_baseline_is"] == "closed_loop_batch_fill"
    for mode in ("closed_loop", "poisson"):
        row = rec[mode]
        assert row["requests"] == 16, row
        assert row["throughput_tok_s"] > 0
        assert 0 < row["ttft_p50_s"] <= row["ttft_p99_s"]
        assert 0 < row["tpot_p50_s"] <= row["tpot_p99_s"]
        assert 0.0 < row["batch_fill"] <= 1.0
    # the closed loop keeps slots fuller than the sub-saturation
    # Poisson arrivals (60% of its measured request rate)
    assert rec["closed_loop"]["batch_fill"] >= \
        rec["poisson"]["batch_fill"]
    assert rec["serve_config"]["max_batch_tokens"] > 0
    # raw-speed legs (docs/serving.md#raw-speed): each independently
    # toggled off->on over the same workload, byte-identical output,
    # and the leg's mechanism verifiably fired.  Thresholds are
    # deliberately below the measured wins (prefix ~3-5x, chunk ~2-6x,
    # spec ~1.3-1.5x) — this is a contract smoke, the perf gate's
    # median±MAD rows track the actual trajectory.
    legs = rec["legs"]
    for leg in ("prefix", "chunked", "spec"):
        assert legs[leg]["byte_identical"] is True, leg
    assert legs["prefix"]["ttft_p50_speedup"] > 1.5
    assert legs["prefix"]["on"]["prefix_hit_rate"] > 0
    assert legs["prefix"]["on"]["prefill_chunks"] < \
        legs["prefix"]["off"]["prefill_chunks"]
    assert legs["chunked"]["gap_bound_ratio"] > 1.0
    assert legs["spec"]["on"]["spec_accept_rate"] > 0
    assert legs["spec"]["on"]["accepted"] >= 1
    # the gate-able sub-rows ride the one artifact line
    assert {r["metric"].split(" (")[0] for r in rec["sub_rows"]} == {
        "serve prefix ttft p50 speedup",
        "serve chunked prefill interference bound",
        "serve spec decode speedup"}


# ------------------------------------------- one process, no fallback
def test_bench_without_cpu_fails_before_compiling_on_a_cpu_host():
    """`python bench.py` measures on the chip or fails: on a CPU-only
    host it prints the error JSON and exits non-zero straight after
    backend init — one process (no child to retry at fewer steps), and
    nothing trained on the CPU under a chip metric's name."""
    import time
    t0 = time.monotonic()
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        capture_output=True, text=True, timeout=120, cwd=REPO,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 1, out.stdout + out.stderr
    (line,) = out.stdout.strip().splitlines()
    rec = json.loads(line)
    assert rec["metric"] == "BENCH_INVALID"
    assert rec["cause"] == "no-accelerator"
    assert time.monotonic() - t0 < 60
    src = open(os.path.join(REPO, "bench.py")).read()
    assert "subprocess" not in src and '"--steps", "10"' not in src


def test_unknown_device_kind_is_an_error():
    """One peaks table keyed by device_kind; a kind outside it raises
    (nothing is priced as a v5e by default any more)."""
    from horovod_tpu.perf import costmodel as cm
    assert cm.device_peaks("TPU v5 lite")["bf16_tflops"] == 197.0
    assert all(row["source"] for row in cm.PEAKS.values())
    for fn in (cm.device_peaks, cm.peak_flops):
        with pytest.raises(ValueError, match="not in the peaks table"):
            fn("TPU v9 imaginary")
    assert "cpu" not in cm.PEAKS  # the CPU has no peak to hold a run to


@pytest.mark.slow
def test_bench_score_dtype_f32_selectable():
    """`--score-dtype f32` must still select the full-precision score
    path and label the artifact accordingly (the default-run assertion
    lives in test_bench_llama_cpu_contract to avoid a third identical
    bench subprocess in the slow tier)."""
    rec_f32 = _run_bench("--score-dtype", "f32")
    assert rec_f32["attn"] == "xla-score-f32"
