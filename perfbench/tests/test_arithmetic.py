"""The benchmark's own arithmetic: trace reduction, roofline, percentiles,
traffic, data files.  No chip, no topology call, no import of jax at
module level."""

import json
import math
import os
import re

import pytest

from perfbench.lib import peaks, serve_math, spec, stats, tracered as tr, traffic as T

BENCH = spec.benchmark()


# ----------------------------------------------------------- trace reduction
def test_union_subtract_gaps():
    busy = tr.union([(0, 1), (0.5, 2), (3, 4), (4, 4)])
    assert busy == [(0, 2), (3, 4)]
    assert tr.total(busy) == 3
    assert tr.gaps(busy, 0, 5) == [(2, 3), (4, 5)]
    assert tr.subtract([(0, 10)], [(1, 2), (3, 4), (9, 12)]) == \
        [(0, 1), (2, 3), (4, 9)]


def test_reduce_device_busy_idle_and_attribution():
    ops = [("while", 0.0, 4.0), ("fusion.1", 0.0, 1.0), ("fusion.2", 1.0, 4.0),
           ("fusion.3", 6.0, 8.0)]
    host = [("pb:harvest", 4.0, 5.8), ("pb:dispatch", 5.8, 6.0)]
    r = tr.reduce_device(ops, 0.0, 10.0, host)
    assert r["busy_s"] == pytest.approx(6.0)
    assert r["window_s"] == 10.0
    assert r["idle_gaps"][0] == ("pb:harvest", pytest.approx(2.0))
    assert r["idle_gaps"][1] == ("unattributed", pytest.approx(2.0))
    # the while's own time is what its body does not cover
    assert dict(r["device_ops"])["while"] == pytest.approx(0.0)
    assert dict(r["device_ops"])["fusion.2"] == pytest.approx(3.0)


def test_every_op_is_kept_by_name_and_the_breakdown_is_the_first_ten():
    ops = [(f"fusion.{i}", float(i), i + 0.1 * (i + 1)) for i in range(14)]
    r = tr.reduce_device(ops, 0.0, 20.0)
    assert len(r["ops_s"]) == 14 and len(r["device_ops"]) == 10
    assert list(r["ops_s"].items())[:10] == r["device_ops"]
    assert r["ops_s"]["fusion.0"] == pytest.approx(0.1)      # the least, kept
    both = tr.combine([r, tr.reduce_device(ops[:2], 0.0, 20.0)])
    assert both["ops_s"] == r["ops_s"]           # the first device's, as the
    assert both["device_ops"] == [list(x) for x in r["device_ops"]]  # ten are


@pytest.mark.parametrize("ops,coll,exposed,count", [
    # a plain all-reduce with nothing beside it is all exposed
    ([("fusion", 0, 1), ("all-reduce.1", 1, 2)], 1.0, 1.0, 1),
    # an asynchronous one: start..done in flight, compute hides the middle
    ([("all-reduce-start.1", 0, 0.1), ("fusion", 0.1, 0.9),
      ("all-reduce-done.1", 0.9, 1.0)], 1.0, 0.2, 1),
    ([("fusion", 0, 1)], 0.0, 0.0, 0),
])
def test_collective_time_and_exposed(ops, coll, exposed, count):
    r = tr.reduce_device(ops, 0.0, 2.0)
    assert r["collective_s"] == pytest.approx(coll)
    assert r["exposed_collective_s"] == pytest.approx(exposed)
    assert r["collective_count"] == count


def test_async_line_collective_is_hidden_by_compute():
    r = tr.reduce_device([("fusion", 0, 1)], 0, 2,
                         async_ops=[("all-reduce-start", 0.5, 1.5),
                                    ("copy-start", 0, 2)])
    assert r["collective_s"] == pytest.approx(1.0)
    assert r["exposed_collective_s"] == pytest.approx(0.5)


def test_short_name_groups_layers():
    a = tr.short_name("%fusion.192 = (bf16[4096]{0:T(1024)}, f32[8,1024]{1,0}) "
                      "fusion(bf16[4096,14336]{1,0} %params__layers___2___w_gate____kernel__.1)")
    b = tr.short_name("%fusion.7 = (bf16[4096]{0:T(1024)}, f32[8,1024]{1,0}) "
                      "fusion(bf16[4096,14336]{1,0} %params__layers___0___w_gate____kernel__.1)")
    assert a == b == "fusion bf16[4096] params_layers_w_gate_kernel"
    assert tr.COLLECTIVE.search(tr.short_name(
        "%all-reduce-start.3 = bf16[12]{0} all-reduce-start(bf16[12] %x)"))


def test_combine_averages_devices():
    d = [tr.reduce_device([("f", 0, 1)], 0, 2),
         tr.reduce_device([("f", 0, 2)], 0, 2)]
    assert tr.combine(d)["busy_s"] == pytest.approx(1.5)


# ------------------------------------------------------------------ roofline
@pytest.mark.parametrize("name", [c["name"] for c in BENCH["configs"]])
def test_param_counts_match_weight_specs(name):
    config = spec.load_json(os.path.join(spec.ROOT, next(
        c["file"] for c in BENCH["configs"] if c["name"] == name)))
    fam = spec.family(config)
    n = sum(math.prod(shape) for _, shape, _ in fam.leaf_specs(config))
    assert n == fam.param_counts(config)["total"]


@pytest.mark.parametrize("valid,ctx", [(1, 1), (16, 16 * 300), (512, 16 * 1400),
                                       (2048, 16 * 2048)])
def test_required_time_is_under_any_tick_the_engine_can_run(valid, ctx):
    """The least time never exceeds the time of the work the tick really
    does (the full [slots, chunk] forward over full contexts) at peak."""
    _, config, _ = spec.cell("serve-decode", BENCH)
    e = config["engine"]
    pk = peaks.device_peaks("TPU v5 lite")
    need, bound = peaks.serve_required_seconds(config, pk, valid, ctx, 1)
    full, _ = peaks.serve_required_seconds(
        config, pk, e["max_slots"] * e["prefill_chunk"],
        e["max_slots"] * e["max_seq_len"], 1)
    assert 0 < need <= full
    assert bound in ("flops", "bytes")


def test_unknown_device_kind_is_an_error():
    with pytest.raises(ValueError):
        peaks.device_peaks("cpu")


def test_train_flops_per_token():
    _, config, traffic = spec.cell("train-dp1", BENCH)
    n = spec.family(config).param_counts(config)
    assert n["total"] == 1_140_887_552
    f = peaks.train_flops_per_token(config, traffic["seq"])
    assert 6.0 * n["matmul"] < f < 6.2 * n["matmul"]


# ------------------------------------------------- percentiles and open loop
def test_percentile_with_missing():
    assert stats.percentile([1, 2, 3, 4, 5, 6, 7, 8, 9, 10], 90) == 9
    assert stats.percentile([1, 2, 3, 4, 5, 6, 7, 8, 9], 90, missing=1) == 9
    assert stats.percentile([1, 2], 90, missing=2) is None
    assert stats.percentile([], 50) is None


def test_token_gaps_share_a_part():
    gaps = stats.token_gaps([1.0, 1.2, 1.5], [1, 2, 1])
    assert [round(g, 6) for _, g in gaps] == [0.1, 0.1, 0.3]


def test_iqr_share_is_the_rule_of_the_bound():
    assert stats.iqr_share([10, 10, 10, 10, 10, 10]) == 0
    assert stats.iqr_share([9, 10, 11, 9, 10, 11]) == pytest.approx(0.2)


def _ctx(records, t0=100.0, seconds=10.0):
    return {"records": records, "t0": t0, "t1": t0 + seconds,
            "t_end": t0 + seconds + 1, "seconds": seconds}


def test_serving_window_arithmetic():
    rec = {"due": 100.5, "sent": 100.501, "part_t": [101.0, 101.2, 111.0],
           "part_n": [1, 2, 1], "prompt_len": 10, "end": 111.0}
    late_rec = {"due": 109.0, "sent": 109.0, "part_t": [], "part_n": [],
                "prompt_len": 5, "end": None}
    ctx = _ctx([rec, late_rec])
    got, missing, late = serve_math.ttfts(ctx)
    assert got == [pytest.approx(0.5)] and missing == 1
    assert late == [pytest.approx(2.0)]          # waited 109 -> 111
    assert serve_math.window_tokens(ctx) == 3          # the last part is late
    assert len(serve_math.window_gaps(ctx)) == 2
    # the two gaps are one part's 0.2 s over its two tokens
    assert serve_math.gap_quantiles_ms(ctx, (50, 100)) == \
        (2, {50: 100.0, 100: 100.0})
    read = spec.metric_reader("front.itl_p99_ms.serve")
    assert read(ctx) == pytest.approx(100.0)
    assert read(_ctx([late_rec])) is None        # no gap: nothing to read
    # 11 positions for 0.2 s, then 13 until the end of the span
    assert serve_math.context_token_seconds([rec], 101.0, 102.0) == \
        pytest.approx(11 * 0.2 + 13 * 0.8)


# ------------------------------------------------------------------- traffic
def test_same_seed_same_requests_and_the_seed_changes_ids_only():
    _, config, mix = spec.cell("serve-decode", BENCH)
    secs = BENCH["run_seconds"]
    a = T.requests(mix, 7, secs, config["vocab_size"])
    b = T.requests(mix, 7, secs, config["vocab_size"])
    c = T.requests(mix, 2**31 + 11, secs, config["vocab_size"])
    assert a == b
    shape = lambda rs: [(r["due"], len(r["tokens"]), r["max_new_tokens"])
                        for r in rs]
    assert shape(a) == shape(c)
    assert [r["tokens"] for r in a] != [r["tokens"] for r in c]
    assert len(a) == round(mix["arrivals"]["rate_per_s"] * secs)
    assert all(0 <= r["due"] < secs for r in a)
    assert sorted(r["due"] for r in a) == [r["due"] for r in a]
    assert all(mix["prompt_len"]["min"] <= len(r["tokens"])
               <= mix["prompt_len"]["max"] for r in a)
    assert max(len(r["tokens"]) + r["max_new_tokens"] for r in a) <= \
        config["engine"]["max_seq_len"]
    other = dict(mix, schedule_seed=mix.get("schedule_seed", 0) + 1)
    d = T.requests(other, 7, secs, config["vocab_size"])
    assert shape(d) != shape(a)
    assert sorted(x[1:] for x in shape(d)) == sorted(x[1:] for x in shape(a))


def test_sessions_and_shared_prefix_are_data():
    """The Open-question cells are expressible as data alone."""
    mix = {"arrivals": {"rate_per_s": 1.0},
           "prompt_len": {"median": 64, "sigma": 0.3, "min": 40, "max": 96},
           "output_len": {"median": 48, "sigma": 0.3, "min": 32, "max": 96},
           "shared_prefix": {"tokens": 32, "groups": 1},
           "sessions": {"turns": [4, 8], "think_s": 1.5}}
    rs = T.requests(mix, 3, 30, 1000)
    assert all(r["tokens"][:32] == rs[0]["tokens"][:32] for r in rs)
    waiting = [r for r in rs if r["after"] is not None]
    assert waiting and all(r["due"] is None and r["think_s"] == 1.5
                           for r in waiting)
    assert rs[0]["after"] is None


# ---------------------------------------------------------------- data files
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def test_benchmark_json_meets_the_contract_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for m in BENCH["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert 0 < m["bound"] <= 0.1 and m["source"] in ("host_clock", "device_trace")
    cells = {w["name"] for w in BENCH["workloads"]}
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves",
                          "workloads"}
        assert m["moves"] in e2e and set(m.get("workloads", cells)) <= cells
        assert re.match(r"^[A-Za-z0-9_/%.\-]{1,16}$", m["unit"])
    for entry in BENCH["workloads"] + BENCH["configs"] + BENCH["end_to_end"] \
            + BENCH["per_layer"]:
        assert NAME.match(entry["name"])
        assert len(entry.get("why", "x")) <= 200
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(
        1, len(BENCH["workloads"]) // 4)
    assert len(json.dumps(BENCH)) < 64 * 1024


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_finds_its_files_and_readers(cell):
    entry, config, traffic = spec.cell(cell, BENCH)
    assert traffic["kind"] in ("train", "serve")
    conf = next(c for c in BENCH["configs"] if c["name"] == entry["config"])
    assert conf["source"] == config["source"]
    assert set(conf["reduced"]) == set(config["reduced"])
    e2e, layer = spec.cell_metrics(cell, BENCH)
    assert len(e2e) >= 2 and len(layer) >= 1
    for m in e2e + layer:
        assert callable(spec.metric_reader(m["name"]))
    limits = traffic["check"].get("limits", traffic["check"])
    assert all(isinstance(v, (int, float)) for k, v in limits.items()
               if k != "sample_requests")


# what `reduced` may never name: a hidden, intermediate, latent, state or
# projection size, a key that ends in _dim or _rank, a head size, an expansion
# factor, a window, the experts per token.  Depth, the count of routed experts
# held and the vocabulary's rows are a chip's share (model-configs, section 4).
WIDTH = re.compile(r"hidden_size|intermediate|_dim$|_rank$|head_size|d_model|"
                   r"d_state|d_conv|state_size|latent|proj|expand|window|"
                   r"experts_per_tok|top_k", re.I)


def test_no_width_is_reduced():
    for conf in BENCH["configs"]:
        assert not [k for k in conf["reduced"] if WIDTH.search(k)]


@pytest.mark.parametrize("key,refused", [
    ("num_hidden_layers", False), ("n_routed_experts", False),
    ("num_local_experts", False), ("vocab_size", False),
    ("num_nextn_predict_layers", False),
    ("hidden_size", True), ("intermediate_size", True),
    ("moe_intermediate_size", True), ("head_dim", True),
    ("qk_rope_head_dim", True), ("v_head_dim", True), ("kv_lora_rank", True),
    ("q_lora_rank", True), ("sliding_window", True), ("ssm_state_size", True),
    ("num_experts_per_tok", True), ("expand", True),
])
def test_the_width_rule_on_hand_made_entries(key, refused):
    assert bool(WIDTH.search(key)) is refused
