"""Perf-attribution plane (horovod_tpu/perf/; docs/profiling.md):

  * cost-model golden numbers — param counts for the llama / moe_llama
    bench shapes pinned against the analytical formulas (and the
    formulas pinned against real init() for the tiny configs), the 6N /
    attention FLOPs conventions, the roofline decomposition;
  * the ledger's decomposition-sums-to-step-time invariant, including
    the over-prediction path (components rescaled, drift observable);
  * the native op-stats C API round trip (hvd_core_op_stats), name
    collapse and the cardinality bound's __other__ overflow;
  * the regression gate's pass/fail matrix (median±MAD semantics);
  * the fleet merge verdicts and the doctor --perf rendering.
"""

import json

import numpy as np
import pytest

from horovod_tpu.perf import costmodel as cm
from horovod_tpu.perf import gate
from horovod_tpu.perf.ledger import (PerfLedger, local_verdict,
                                     merge_perf_reports, native_op_stats)


# ------------------------------------------------------------- cost model
def test_llama_param_count_golden():
    # bench.py's default "bench" config (dim 1024, 8 layers, ffn 4096)
    assert cm.llama_param_count(32768, 1024, 8, 16, 8, 4096) == 192955392
    # CONFIGS["tiny"] / ["mini"], pinned against actual init() below
    assert cm.llama_param_count(256, 64, 2, 4, 2, 128) == 106816
    assert cm.llama_param_count(4096, 512, 4, 8, 4, 1024) == 13636096


def test_moe_llama_param_count_golden():
    assert cm.moe_llama_param_count(256, 64, 2, 4, 2, 128, 4) == 189248
    assert cm.moe_llama_param_count(256, 64, 2, 4, 2, 128, 8) == 320832
    # CONFIGS["mini"]: total vs top-1-active
    assert cm.moe_llama_param_count(4096, 256, 4, 8, 4, 512, 8) == 11282688
    assert cm.moe_llama_active_param_count(
        4096, 256, 4, 8, 4, 512, 8, 1) == 3942656
    # active == total when every expert fires
    assert cm.moe_llama_active_param_count(
        4096, 256, 4, 8, 4, 512, 8, 8) == 11282688


def test_llama_param_count_matches_real_init():
    import jax
    from horovod_tpu.models import llama
    cfg = llama.CONFIGS["tiny"]
    actual = sum(int(np.prod(l.shape)) for l in
                 jax.tree_util.tree_leaves(
                     llama.init(jax.random.PRNGKey(0), cfg)))
    assert actual == cm.llama_param_count(
        cfg.vocab, cfg.dim, cfg.n_layers, cfg.n_heads, cfg.n_kv_heads,
        cfg.ffn_dim)


def test_moe_param_count_matches_real_init():
    import jax
    from horovod_tpu.models import moe_llama
    cfg = moe_llama.CONFIGS["tiny"]
    actual = sum(int(np.prod(l.shape)) for l in
                 jax.tree_util.tree_leaves(
                     moe_llama.init(jax.random.PRNGKey(0), cfg)))
    assert actual == cm.moe_llama_param_count(
        cfg.vocab, cfg.dim, cfg.n_layers, cfg.n_heads, cfg.n_kv_heads,
        cfg.moe_hidden, cfg.n_experts)


def test_flops_conventions():
    # the conservative headline convention bench.py's MFU is defined by
    assert cm.train_flops_per_token(1000) == 6000.0
    # attention term: 12·L·s·d, halved causal (the documented convention)
    full = cm.train_flops_per_token(
        0, attention=dict(n_layers=2, dim=64, seq=128, causal=False))
    assert full == 12.0 * 2 * 128 * 64
    causal = cm.train_flops_per_token(
        0, attention=dict(n_layers=2, dim=64, seq=128))
    assert causal == full / 2
    # additive with the 6N term
    assert cm.train_flops_per_token(
        1000, attention=dict(n_layers=2, dim=64, seq=128)) == \
        6000.0 + causal


def test_bench_constants_are_the_cost_model():
    """bench.py must consume THIS table (the unification satellite) —
    a fork of the constants is exactly the drift this plane removes."""
    import bench
    import inspect
    assert "costmodel" in inspect.getsource(bench.init_backend)
    assert not hasattr(bench, "PEAK_TFLOPS")  # no second table
    assert cm.peak_flops("TPU v5 lite") == 197.0e12
    assert cm.peak_flops("cpu") == cm.CPU_MODEL_TFLOPS * 1e12


def test_predicted_step_time_roofline():
    pred = cm.predicted_step_time(1e9, 1e6, chip="cpu", link="loopback")
    assert pred["compute_s"] == pytest.approx(1e9 / 0.5e12)
    assert pred["exposed_comm_s"] == pytest.approx(1e6 / 10e9)
    assert pred["step_s"] == pytest.approx(
        pred["compute_s"] + pred["exposed_comm_s"])
    # overlap hides comm; full overlap leaves only compute
    full = cm.predicted_step_time(1e9, 1e6, overlap_fraction=1.0)
    assert full["exposed_comm_s"] == 0.0
    # DCN is the slow fabric: same bytes take longer than ICI
    dcn = cm.predicted_step_time(0, 1e9, link="dcn")
    ici = cm.predicted_step_time(0, 1e9, link="ici")
    assert dcn["exposed_comm_s"] > ici["exposed_comm_s"]
    with pytest.raises(ValueError, match="link"):
        cm.predicted_step_time(1, 1, link="carrier-pigeon")
    with pytest.raises(ValueError, match="overlap_fraction"):
        cm.predicted_step_time(1, 1, overlap_fraction=1.5)


def test_plan_comm_bytes_matches_wire_model():
    """The cost model's comm leg is the plan cache × wire policy × ring
    model — cross-checked against ops/wire.modeled_wire_bytes directly."""
    from horovod_tpu.ops.fusion import make_plan
    from horovod_tpu.ops.wire import modeled_wire_bytes
    shapes = [(1 << 20,), (256,), (64,)]
    dtypes = [np.float32] * 3
    plan = make_plan(shapes, dtypes, 4 << 20)
    out = cm.plan_comm_bytes(plan, "none", {"flat": 8})
    expect = sum(modeled_wire_bytes(sum(b.sizes), 4, "none",
                                    {"flat": 8})["bottleneck"]
                 for b in plan.buckets)
    assert out["bottleneck"] == int(expect)
    # int8 carries 1/4 the bytes of fp32 on every bucket
    out8 = cm.plan_comm_bytes(plan, "int8_ring", {"flat": 8})
    assert out8["bottleneck"] * 4 <= out["bottleneck"] + 4 * len(
        plan.buckets)
    # auto on a two-level mesh routes the big bucket's bytes to DCN
    two = cm.plan_comm_bytes(plan, "auto", {"dcn": 2, "ici": 4})
    assert "dcn" in two["per_fabric"]


# ------------------------------------------------------- ZeRO what-if model
def test_zero_memory_bytes_goldens():
    """The docs/zero.md memory math, exact: N=1000 fp32 params, n=4,
    adam (2 slots)."""
    lv = {l: cm.zero_memory_bytes(l, 1000, 4) for l in (0, 1, 2, 3)}
    assert lv[0] == {"params_bytes": 4000, "grads_bytes": 4000,
                     "opt_state_bytes": 8000, "ef_residual_bytes": 0,
                     "total_bytes": 16000}
    assert lv[1]["total_bytes"] == 4000 + 4000 + 2000
    assert lv[2]["total_bytes"] == 4000 + 1000 + 2000
    assert lv[3]["total_bytes"] == 1000 + 1000 + 2000
    # the acceptance ratios: state+grads >= 2x down at level 2 vs the
    # unsharded baseline on any n >= 2; params n-fold down at level 3
    for n in (2, 4, 8):
        l0 = cm.zero_memory_bytes(0, 1000, n)
        l2 = cm.zero_memory_bytes(2, 1000, n)
        l3 = cm.zero_memory_bytes(3, 1000, n)
        sg0 = l0["grads_bytes"] + l0["opt_state_bytes"]
        sg2 = l2["grads_bytes"] + l2["opt_state_bytes"]
        assert sg0 >= 2 * sg2, (n, sg0, sg2)
        assert l0["params_bytes"] >= (n / 2) * l3["params_bytes"]
    # EF adds a full-size residual per rank (inherent to EF-on-RS)
    assert cm.zero_memory_bytes(2, 1000, 4, ef=True)[
        "ef_residual_bytes"] == 4000
    with pytest.raises(ValueError, match="zero level"):
        cm.zero_memory_bytes(5, 1000, 4)


def test_zero_comm_bytes_wire_claims():
    """RS+AG == AR at k=1 (the arXiv:2004.13336 equal-bytes claim),
    level 2 strictly below level 1 at k>1, level 3 == level 2, and the
    RS leg priced at the wire format's itemsize with exact AG legs."""
    n, N = 8, 1 << 20
    at_k1 = [cm.zero_comm_bytes(N, n, l)["total_bytes"]
             for l in (0, 1, 2, 3)]
    assert len(set(at_k1)) == 1  # all equal
    k = 4
    l1 = cm.zero_comm_bytes(N, n, 1, k=k)
    l2 = cm.zero_comm_bytes(N, n, 2, k=k)
    l3 = cm.zero_comm_bytes(N, n, 3, k=k)
    assert l2["total_bytes"] < l1["total_bytes"]
    assert l3 == l2
    # per-microbatch RS at int8 is 1/4 the fp32 leg; AG stays exact
    q = cm.zero_comm_bytes(N, n, 2, k=k, wire_format="int8_ring")
    assert q["rs_bytes"] * 4 == l2["rs_bytes"]
    assert q["ag_bytes"] == l2["ag_bytes"]
    # single member axis moves nothing
    assert cm.zero_comm_bytes(N, 1, 3)["total_bytes"] == 0.0


def test_zero_level_table_rows():
    rows = cm.zero_level_table(1000, 4, k=2, wire_format="bf16",
                               flops_per_step=1e9, chip="cpu",
                               link="ici")
    assert [r["level"] for r in rows] == [0, 1, 2, 3]
    for r in rows:
        assert r["memory"]["total_bytes"] > 0
        assert r["comm"]["total_bytes"] > 0
        assert r["exposed_comm_s"] == pytest.approx(
            r["comm"]["total_bytes"] / cm.link_bandwidth("ici"))
        assert r["predicted"]["step_s"] > 0
    # memory monotonically non-increasing with level
    mems = [r["memory"]["total_bytes"] for r in rows]
    assert mems == sorted(mems, reverse=True)


def test_ledger_zero_section_and_drift_bound():
    """configure(zero_model=...) makes the report carry the per-level
    what-if table, and on a workload whose step time matches the model
    the ledger's drift ratio sits inside the tested bound — the
    "ledger confirms the prediction" closure (docs/zero.md)."""
    led = PerfLedger()
    comm = cm.zero_comm_bytes(1 << 16, 8, 2, k=2)["total_bytes"]
    led.configure(flops_per_step=1e7, comm_bytes_per_step=comm,
                  chip="cpu", link="loopback",
                  zero_model={"n_params": 1 << 16, "world": 8,
                              "level": 2, "k": 2, "opt_slots": 2})
    assert led.report()["zero"]["levels"]  # table rides steps=0 reports
    pred_t = (1e7 / cm.peak_flops("cpu")
              + comm / cm.link_bandwidth("loopback"))
    for dt in (pred_t * 1.02, pred_t * 0.98, pred_t):
        led.record_step(dt)
    rep = led.report()
    assert rep["zero"]["active_level"] == 2
    levels = rep["zero"]["levels"]
    assert [r["level"] for r in levels] == [0, 1, 2, 3]
    # the active level's table row IS the configured comm model
    assert levels[2]["comm"]["total_bytes"] == int(comm)
    # drift bound: modeled/measured within 5% when the workload matches
    assert 0.95 <= rep["model_drift_ratio"] <= 1.05
    with pytest.raises(ValueError, match="n_params"):
        led.configure(zero_model={"world": 8})


def test_doctor_renders_zero_table():
    from horovod_tpu.runner.doctor import render_perf
    led = PerfLedger()
    led.configure(flops_per_step=1e7, comm_bytes_per_step=1e5,
                  zero_model={"n_params": 1000, "world": 4, "level": 3})
    led.record_step(0.01)
    rep = led.report()
    rep["rank"] = 0
    view = merge_perf_reports({"rank.0": json.dumps(rep).encode()})
    text = render_perf(view)
    assert "ZeRO memory-vs-comm what-if" in text
    assert "active level: 3" in text
    assert text.count("\n  ") >= 4  # the four level rows render


# ----------------------------------------------------------------- ledger
def test_decomposition_sums_to_step_time_exactly():
    led = PerfLedger()
    led.configure(flops_per_step=1e8, comm_bytes_per_step=1e6,
                  chip="cpu", link="loopback")
    led.add_input_wait(0.002)
    for dt in (0.01, 0.02, 0.015):
        row = led.record_step(dt)
        parts = (row["compute_s"] + row["exposed_comm_s"]
                 + row["host_input_s"] + row["stall_s"])
        assert parts == pytest.approx(row["step_time_s"], abs=1e-12)
    rep = led.report()
    assert rep["steps"] == 3
    assert sum(rep["decomposition"].values()) == pytest.approx(
        rep["step_time_s"]["mean"], rel=1e-9)
    assert abs(sum(rep["fractions"].values()) - 1.0) < 1e-9
    assert rep["verdict"] in ("compute-bound", "comm-bound",
                              "input-bound", "stall-bound")
    assert rep["predicted"]["step_s"] > 0


def test_overpredicting_model_rescales_and_records_drift():
    led = PerfLedger()
    # model predicts 2 s of compute; the measured step is 10 ms
    led.configure(flops_per_step=1e12, chip="cpu", link="loopback")
    row = led.record_step(0.01)
    total = (row["compute_s"] + row["exposed_comm_s"]
             + row["host_input_s"] + row["stall_s"])
    assert total == pytest.approx(0.01, abs=1e-12)  # never sums past dt
    assert row["stall_s"] == 0.0
    rep = led.report()
    assert rep["model_drift_ratio"] > 10  # the overshoot is observable
    assert rep["predicted_vs_measured"]["step_ratio"] > 10


def test_input_wait_is_capped_and_consumed():
    led = PerfLedger()
    led.add_input_wait(5.0)              # absurd wait vs a 10 ms step
    row = led.record_step(0.01)
    assert row["host_input_s"] == pytest.approx(0.01)
    row2 = led.record_step(0.01)         # consumed: next step starts clean
    assert row2["host_input_s"] == 0.0


def test_timed_step_and_global_api():
    import horovod_tpu.perf as perf
    perf.reset()
    with perf.timed_step():
        pass
    rep = perf.report()
    assert rep["steps"] == 1
    assert rep["step_time_s"]["mean"] >= 0.0
    perf.reset()
    assert perf.report()["steps"] == 0


def test_configure_validation():
    led = PerfLedger()
    with pytest.raises(ValueError, match="link"):
        led.configure(link="warp-drive")
    with pytest.raises(ValueError, match="overlap_fraction"):
        led.configure(overlap_fraction=2.0)


def test_perf_knob_validation():
    from horovod_tpu.common.knobs import Knobs
    from horovod_tpu.perf import resolve_link, validate_perf_knobs
    validate_perf_knobs(Knobs())  # defaults pass
    with pytest.raises(ValueError, match="HOROVOD_PERF_LINK"):
        validate_perf_knobs(Knobs({"HOROVOD_PERF_LINK": "wormhole"}))
    with pytest.raises(ValueError, match="HOROVOD_PERF_INTERVAL"):
        validate_perf_knobs(Knobs({"HOROVOD_PERF_INTERVAL": -1.0}))
    assert resolve_link(Knobs({"HOROVOD_PERF_LINK": "dcn"})) == "dcn"
    assert resolve_link(Knobs()) == "loopback"  # auto, no mesh


def test_loader_prefetch_accounts_input_wait():
    """data/loader.prefetch feeds the ledger's host_input component."""
    import time

    import horovod_tpu.perf as perf
    from horovod_tpu.data.loader import prefetch
    perf.reset()

    def slow_batches():
        for i in range(3):
            time.sleep(0.005)
            yield i

    out = list(prefetch(slow_batches(), depth=1, transfer=lambda b: b))
    assert out == [0, 1, 2]
    row = perf.record_step(1.0)
    assert row["host_input_s"] > 0.0
    perf.reset()


# -------------------------------------------------------------- native leg
def test_op_stats_c_api_round_trip():
    import time

    from horovod_tpu.common.basics import (OP_ALLREDUCE, CoordinationCore,
                                           LoopbackHub)
    hub = LoopbackHub(2)
    cores = [CoordinationCore.loopback(hub, r, cycle_ms=0.5)
             for r in range(2)]
    try:
        for i in range(3):
            for c in cores:
                # per-call unique suffixes must COLLAPSE to one key
                c.submit(f"grad.noname.{i}", "f32:8:sum", OP_ALLREDUCE,
                         64)
            for c in cores:
                r = c.wait(10.0)
                assert r is not None and r.type == "OK", r
        for c in cores:
            stats = c.op_stats()
            assert set(stats) == {"grad"}, stats
            s = stats["grad"]
            assert s["count"] == 3
            assert s["bytes"] == 3 * 64
            assert s["sum_us"] >= s["max_us"] > 0
        # the report's native leg reads the same aggregates
        rows = native_op_stats(cores[0])
        assert rows and rows[0]["name"] == "grad"
        assert rows[0]["mean_us"] == pytest.approx(
            cores[0].op_stats()["grad"]["sum_us"] / 3)
    finally:
        for c in cores:
            c.shutdown()
        time.sleep(0.3)
        for c in cores:
            c.close()
        hub.close()


def test_op_stats_distinct_names_and_join_excluded():
    import time

    from horovod_tpu.common.basics import (OP_ALLREDUCE, OP_BROADCAST,
                                           CoordinationCore, LoopbackHub)
    hub = LoopbackHub(1)
    core = CoordinationCore.loopback(hub, 0, cycle_ms=0.5)
    try:
        core.submit("a", "f32:4:sum", OP_ALLREDUCE, 16)
        assert core.wait(10.0).type == "OK"
        core.submit("b", "f32:4:bcast", OP_BROADCAST, 8)
        assert core.wait(10.0).type == "OK"
        stats = core.op_stats()
        assert set(stats) == {"a", "b"}, stats
        assert stats["a"]["bytes"] == 16
        assert stats["b"]["bytes"] == 8
    finally:
        core.shutdown()
        time.sleep(0.2)
        core.close()
        hub.close()


# ------------------------------------------------------------------- gate
def _art(value, metric="llama train tokens/sec/chip (cpu, run detail)",
         unit="tokens/sec/chip"):
    return {"metric": metric, "value": value, "unit": unit}


def test_gate_metric_key_strips_run_detail():
    a = _art(1.0, "llama train tokens/sec/chip (cpu, loss 5.9->5.0)")
    b = _art(2.0, "llama train tokens/sec/chip (v5e, loss 4.2->4.0)")
    assert gate.metric_key(a) == gate.metric_key(b)


def test_gate_pass_fail_matrix():
    doc = gate.empty_baseline()
    gate.update_baseline(doc, [_art(v) for v in (100.0, 102.0, 98.0)])
    # unmodified re-run: within noise -> pass
    res = gate.check_artifacts(doc, [_art(101.0)])
    assert not res["failed"]
    key = next(iter(res["results"]))
    assert res["results"][key]["status"] == "pass"
    # 2x slowdown (tokens/sec halves) -> regression
    res = gate.check_artifacts(doc, [_art(50.0)])
    assert res["failed"]
    assert next(iter(res["results"].values()))["status"] == "regression"
    # 2x speedup -> improved, NOT a failure
    res = gate.check_artifacts(doc, [_art(200.0)])
    assert not res["failed"]
    assert next(iter(res["results"].values()))["status"] == "improved"
    # unknown key -> no-baseline, not a failure
    res = gate.check_artifacts(doc, [_art(5.0, metric="new mode",
                                          unit="GB/s")])
    assert not res["failed"]
    assert next(iter(res["results"].values()))["status"] == "no-baseline"


def test_gate_lower_is_better_units():
    doc = gate.empty_baseline()
    art = {"metric": "step time", "value": 0.1, "unit": "seconds"}
    gate.update_baseline(doc, [art])
    worse = dict(art, value=0.25)
    assert gate.check_artifacts(doc, [worse])["failed"]
    better = dict(art, value=0.05)
    assert not gate.check_artifacts(doc, [better])["failed"]


def test_gate_zero_mad_uses_relative_floor():
    doc = gate.empty_baseline()
    gate.update_baseline(doc, [_art(100.0)])  # singleton: MAD 0
    # 5% off: under the 10% floor -> pass despite zero MAD
    assert not gate.check_artifacts(doc, [_art(95.0)])["failed"]
    assert gate.check_artifacts(doc, [_art(80.0)])["failed"]


def test_gate_noisy_baseline_tolerates_jitter():
    doc = gate.empty_baseline()
    gate.update_baseline(doc, [_art(v) for v in
                               (80.0, 120.0, 100.0, 90.0, 110.0)])
    # well inside the MAD band of a noisy baseline
    assert not gate.check_artifacts(doc, [_art(75.0)])["failed"]


def test_gate_rolling_window_and_file_round_trip(tmp_path):
    doc = gate.empty_baseline()
    for i in range(gate.MAX_BASELINE_VALUES + 7):
        gate.update_baseline(doc, [_art(float(i))])
    entry = next(iter(doc["entries"].values()))
    assert len(entry["values"]) == gate.MAX_BASELINE_VALUES
    path = str(tmp_path / "baseline.json")
    gate.save_baseline(path, doc)
    again = gate.load_baseline(path)
    assert again == doc
    with pytest.raises(ValueError, match="schema"):
        bad = str(tmp_path / "bad.json")
        with open(bad, "w") as f:
            json.dump({"schema": "nope"}, f)
        gate.load_baseline(bad)


def test_gate_ignores_invalid_bench_rows():
    doc = gate.empty_baseline()
    invalid = {"metric": "BENCH_INVALID", "value": 0, "unit": "error"}
    assert gate.update_baseline(doc, [invalid]) == []
    assert not gate.check_artifacts(doc, [invalid])["failed"]


def test_committed_baseline_ledger_loads():
    """The committed trajectory ledger must stay parseable — it is the
    gate's reference point (docs/profiling.md#regression-gate)."""
    import os
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "PERF_BASELINE.json")
    doc = gate.load_baseline(path)
    assert doc["entries"], "committed baseline has no entries"
    for key, entry in doc["entries"].items():
        assert entry["values"], key


# ------------------------------------------------------------ fleet merge
def _rank_report(rank, step_s, comp=None):
    led = PerfLedger()
    if comp:
        led.configure(**comp)
    for _ in range(3):
        led.record_step(step_s)
    rep = led.report()
    rep["rank"] = rank
    return rep


def test_merge_straggler_verdict_outranks_components():
    stored = {
        "rank.0": json.dumps(_rank_report(0, 0.01)).encode(),
        "rank.1": json.dumps(_rank_report(1, 0.01)).encode(),
        "rank.2": json.dumps(_rank_report(2, 0.05)).encode(),
    }
    view = merge_perf_reports(stored)
    assert view["fleet"]["verdict"] == "straggler-bound"
    assert view["fleet"]["straggler"]["rank"] == "2"
    assert set(view["ranks"]) == {"0", "1", "2"}


def test_merge_component_verdict_and_torn_put():
    comp = dict(flops_per_step=1e6, comm_bytes_per_step=8e7,
                chip="cpu", link="loopback")  # comm 8 ms >> compute 2 µs
    stored = {
        "rank.0": json.dumps(_rank_report(0, 0.01, comp)).encode(),
        "rank.1": json.dumps(_rank_report(1, 0.011, comp)).encode(),
        "rank.2": b"{torn json",  # must not 500 the view
    }
    view = merge_perf_reports(stored)
    assert view["fleet"]["verdict"] == "comm-bound"
    assert set(view["ranks"]) == {"0", "1"}


def test_local_verdict_dominant_component():
    assert local_verdict({"compute_s": 0.9, "exposed_comm_s": 0.05,
                          "host_input_s": 0.0, "stall_s": 0.05}) == \
        "compute-bound"
    assert local_verdict({"compute_s": 0.1, "exposed_comm_s": 0.1,
                          "host_input_s": 0.7, "stall_s": 0.1}) == \
        "input-bound"


# ----------------------------------------------------------------- doctor
def test_doctor_perf_render_and_file_source(tmp_path):
    from horovod_tpu.runner.doctor import load_perf_view, render_perf
    stored = {
        "rank.0": json.dumps(_rank_report(0, 0.01)).encode(),
        "rank.1": json.dumps(_rank_report(1, 0.05)).encode(),
    }
    view = merge_perf_reports(stored)
    text = render_perf(view)
    assert "BOTTLENECK: straggler-bound" in text
    assert "rank 1" in text and "rank 0: step 10.00ms" in text
    # file + directory sources resolve to the same rendering
    path = tmp_path / "perf.json"
    path.write_text(json.dumps(view))
    assert render_perf(load_perf_view(str(path))) == text
    assert render_perf(load_perf_view(str(tmp_path))) == text
    # a saved single-rank hvd.perf_report() payload wraps cleanly
    single = tmp_path / "single.json"
    single.write_text(json.dumps(_rank_report(0, 0.02)))
    text1 = render_perf(load_perf_view(str(single)))
    assert "1 rank(s)" in text1


def test_doctor_perf_cli_dispatch(tmp_path, capsys):
    from horovod_tpu.runner.doctor import main as doctor_main
    stored = {"rank.0": json.dumps(_rank_report(0, 0.01)).encode()}
    path = tmp_path / "perf.json"
    path.write_text(json.dumps(merge_perf_reports(stored)))
    assert doctor_main(["--perf", str(path)]) == 0
    out = capsys.readouterr().out
    assert "step-time attribution" in out
    assert doctor_main(["--perf", str(tmp_path / "missing.json")]) == 2


def test_empty_perf_view_renders_hint():
    from horovod_tpu.runner.doctor import render_perf
    text = render_perf(merge_perf_reports({}))
    assert "no perf reports recorded" in text
