"""The seam between the benchmark's harness and a model family
(perfbench/families/__init__.py), in tier 1, for the seven families there
are: the dense GQA decoder, the latent-attention expert decoder, the
window-and-global expert decoder (two kinds of cache), the
short-convolution-and-attention expert decoder (a paged kind and a fixed
state a slot, a tied head), the expert decoder that denoises blocks (its own
served-path check), the decoder-hybrid-decoder (four kinds of cache, a scan's
carry among them), and the switch family that only the benchmark's tests
use.  At toy width on the CPU:
a family's leaf names spell the program's pytree, its program agrees with its
plain reference, its counts are the pytree's sizes, and only the family with
routed experts reads a tick's tokens.  Then the new cell's rehearsal."""

import json
import math
import os
import subprocess
import sys

import numpy as np
import pytest

from perfbench.lib import reference, spec, weights

_TESTS_FAMILIES = os.path.join(spec.BENCH_DIR, "tests", "families")
if _TESTS_FAMILIES not in spec.FAMILY_DIRS:
    spec.FAMILY_DIRS.append(_TESTS_FAMILIES)

FAMILIES = ["dense_gqa", "moe_switch", "latent_moe", "swa_moe", "conv_moe",
            "blockdiff_moe", "sambay", "gdn_hybrid"]
ROUTED_BY_TOKENS = {"latent_moe", "swa_moe", "conv_moe", "blockdiff_moe"}
SEED = 2**31 + 27


def _toy(family):
    if family == "moe_switch":
        return spec.family({"family": family}).TOY
    cell = {"dense_gqa": "serve-decode",
            "latent_moe": "serve-moe-mla-decode",
            "swa_moe": "serve-moe-swa-longdoc",
            "conv_moe": "serve-moe-conv-chat",
            "blockdiff_moe": "serve-moe-blockdiff-gen",
            "sambay": "serve-ssm-yoco-reason",
            "gdn_hybrid": "serve-gdn-mixedlen"}[family]
    return spec.tiny(spec.cell(cell)[1])


def _cache(model, cfg, blocks, size, dtype=None):
    """(cache of ``blocks`` blocks a kind, the table of one row that owns
    them in order): one pool and one table, or one of each a cache kind for
    a module that declares kinds (a ring as long as the whole context; a
    state kind: one slot of ``STATE_COLS`` columns and no table)."""
    import jax.numpy as jnp
    table = jnp.arange(blocks, dtype=jnp.int32)[None]
    if not hasattr(model, "cache_kinds"):
        return model.init_cache(cfg, blocks, size, dtype=dtype), table
    kinds = model.cache_kinds(cfg)
    return (model.init_cache(
        cfg, {k.name: (1, STATE_COLS) if k.state else blocks for k in kinds},
        size, dtype=dtype), {k.name: table for k in kinds if not k.state})


STATE_COLS = 7


def _head_is_the_embedding(fam):
    """A tied head: the embedding is also the last matmul's matrix."""
    return fam.EMBED[0] in fam.HEAD


def test_each_configuration_finds_its_family_file():
    assert spec.family(_toy("dense_gqa")).__name__.endswith("dense_gqa")
    assert spec.family(_toy("latent_moe")).__file__.endswith(
        "perfbench/families/latent_moe.py")
    assert not hasattr(spec.family(_toy("latent_moe")), "loss")  # served only


def test_the_parent_process_loads_the_new_family_without_jax():
    code = ("import sys; from perfbench.lib import peaks, spec\n"
            "_, c, _ = spec.cell('serve-moe-mla-decode'); f = spec.family(c)\n"
            "spec.tiny(c); f.param_counts(c); f.pool_op_types(c)\n"
            "f.window_counts({'marks': {k: {'stats': {}} for k in ('start', 'end')}})\n"
            "peaks.serve_required_seconds(c, peaks.PEAKS['TPU v5 lite'], 9, 9, 1)\n"
            "assert 'jax' not in sys.modules and 'numpy' not in sys.modules\n"
            # the one function that asks the program (its tile's rows), when
            # a traced run's children are gone: it starts no backend
            "types = f.expert_op_types(c)\n"
            "from jax._src import xla_bridge\n"
            "assert not xla_bridge._backends, 'a backend was started'\n"
            "print(types)")
    out = subprocess.run([sys.executable, "-c", code], check=True,
                         cwd=spec.ROOT, timeout=60, capture_output=True,
                         text=True)
    from horovod_tpu.models import latent_moe
    assert out.stdout.strip() == str(
        [f"[{latent_moe.EXPERT_TILE},2048]", f"[{latent_moe.EXPERT_TILE},7680]"])


def test_the_expert_roofline_counts_the_loops_own_time(monkeypatch):
    """The reader's denominator is the tile ops' self-time plus every
    ``while``'s, found by the program's tile size whatever it is."""
    from horovod_tpu.models import latent_moe
    monkeypatch.setattr(latent_moe, "EXPERT_TILE", 32)
    _, config, _ = spec.cell("serve-moe-mla-decode")
    fam = spec.family(config)
    moe = {"ticks": 10, "assignments": 800, "assignments_held": 50,
           "experts_touched": 40, "load_max": 20}
    ctx = {"config": config, "peaks": {"hbm_gbps": 819.0, "bf16_tflops": 197.0},
           "marks": {"start": {"stats": {"moe": dict.fromkeys(moe, 0)}},
                     "end": {"stats": {"moe": moe}}},
           "trace": {"module_count": 5.0, "ops_s": {
               "fusion bf16[32,2048]": 0.004, "fusion f32[32,7680]": 0.002,
               "fusion bf16[64,2048]": 9.0, "while s32[]": 0.002,
               "copy bf16[5,5120,16,576]": 0.5}}}
    need, bound = fam.expert_required_seconds(config, ctx["peaks"], 20, 25)
    assert bound == "bytes"
    got = spec.metric_reader("moe.expert_roofline_share.serve")(ctx)
    assert got == pytest.approx(100.0 * need / 0.008)
    ctx["trace"]["ops_s"] = {"while s32[]": 0.002}     # no tile op: nothing
    assert spec.metric_reader("moe.expert_roofline_share.serve")(ctx) is None


@pytest.mark.parametrize("family", FAMILIES)
def test_leaf_names_spell_the_programs_pytree(family):
    import jax
    config = _toy(family)
    model, cfg = spec.family(config).program(config)
    tree = weights.flat(jax.eval_shape(
        lambda k: model.init(k, cfg), jax.random.PRNGKey(0)))
    specs = {n: tuple(s) for n, s, _ in spec.family(config).leaf_specs(config)}
    assert sorted(tree) == sorted(specs)
    assert {n: x.shape for n, x in tree.items()} == specs


@pytest.mark.parametrize("family", FAMILIES)
def test_the_program_agrees_with_the_reference_at_toy_width(family):
    """One row prefilled in one chunk through the family's program module as
    the engine would, against the family's plain equations."""
    import jax
    import jax.numpy as jnp
    config = _toy(family)
    fam = spec.family(config)
    model, cfg = fam.program(config)
    params = jax.jit(lambda k: weights.make(config, k, jnp.float32))(
        weights.seed_key(SEED))
    T, size = 48, 4
    row = np.random.default_rng(11).integers(0, config["vocab_size"], (1, T))
    cache, table = _cache(model, cfg, T // size, size)
    got = model.apply_cached(
        params, jnp.asarray(row, jnp.int32), cfg, cache, table,
        jnp.zeros((1,), jnp.int32), jnp.full((1,), T, jnp.int32))[0]
    w = reference.Weights(config, SEED)
    x = reference.hidden_states(config, w, row)
    want = jax.jit(reference._highest(lambda p, x: fam.head(
        p, x, config, reference.plain_mm)))(w.part(fam.HEAD), x)
    assert got.shape == want.shape == (1, T, config["vocab_size"])
    assert float(jnp.max(jnp.abs(got - want))) < 1e-3 * float(jnp.std(want))


@pytest.mark.parametrize("family", FAMILIES)
def test_the_counts_are_the_pytrees_sizes_and_the_programs_cache(family):
    import jax.numpy as jnp
    config = _toy(family)
    fam = spec.family(config)
    specs = fam.leaf_specs(config)
    n = fam.param_counts(config)
    assert n["total"] == sum(math.prod(s) for _, s, _ in specs)
    assert n["embed"] == math.prod(
        dict((k, s) for k, s, _ in specs)[fam.EMBED[0]])
    vectors = sum(math.prod(s) for _, s, _ in specs if len(s) == 1)
    tied = n["embed"] if _head_is_the_embedding(fam) else 0
    assert 0 < n["matmul"] <= n["total"] - n["embed"] - vectors + tied
    model, cfg = fam.program(config)
    blocks, size = 6, 4
    import jax
    pool, _ = _cache(model, cfg, blocks, size, dtype=jnp.bfloat16)
    held = sum(x.size * x.dtype.itemsize
               for x in jax.tree_util.tree_leaves(pool))
    if family == "sambay":
        # one full layer and the window layers hold a position each; the
        # state-space layers two fixed states a slot, the carries in float32
        # whatever the pool's type; what a new token READS of a position is
        # the full layer once for itself and once a cross layer, and a
        # window layer's share
        state = fam.state_bytes_per_slot(config, STATE_COLS, 2)
        assert state == {
            "conv": 3 * STATE_COLS * 2 * config["hidden_size"] * 2,
            "carry": 3 * STATE_COLS * 16 * 2 * config["hidden_size"] * 4}
        a_layer = fam.cache_bytes_per_position_per_layer(config, 2)
        assert a_layer * (1 + 2) * blocks * size + sum(state.values()) == held
        assert fam.cache_bytes_per_position(config, 2) == pytest.approx(
            a_layer * (2 + 2 * config["sliding_window"]
                       / fam.MEAN_LIVE_CONTEXT))
    elif family == "gdn_hybrid":
        # the full layers hold a position each, and a new token reads just
        # those; the linear layers two fixed states a slot, of which the
        # matrix state is ONE a layer, float32 whatever the pool's type,
        # beside the position it stands after and a ring of rows
        state = fam.state_bytes_per_slot(config, 2, columns=STATE_COLS,
                                         rows=STATE_COLS)
        assert state == {
            "conv": 6 * STATE_COLS * 4 * (8 + 8 + 16) * 2,
            "delta": 6 * (4 * 16 * 8 * 4 + 4
                          + STATE_COLS * 4 * (8 + 16 + 2) * 4)}
        a_layer = fam.cache_bytes_per_position_per_layer(config, 2)
        assert a_layer * 2 * blocks * size + sum(state.values()) == held
        assert fam.cache_bytes_per_position(config, 2) == a_layer * 2
    elif hasattr(fam, "state_bytes_per_slot"):
        # a paged kind, which alone a new token reads a position of, and a
        # fixed state a slot
        state = fam.state_bytes_per_slot(config, STATE_COLS, 2)
        assert state == 5 * STATE_COLS * config["hidden_size"] * 2
        assert fam.cache_bytes_per_position(config, 2) * blocks * size \
            == held - state
    elif hasattr(fam, "cache_bytes_per_position_per_layer"):
        # layers of several cache kinds: what a layer HOLDS a position is
        # one number, what a new token READS a position of its context is
        # reckoned at a stated context and is less (a window layer reads its
        # window of it)
        a_layer = fam.cache_bytes_per_position_per_layer(config, 2)
        layers = config["num_hidden_layers"]
        assert a_layer * layers * blocks * size == held
        assert 0 < fam.cache_bytes_per_position(config, 2) <= a_layer * layers
    else:
        # what a position HOLDS: the latent pool pads it with zeros to whole
        # 128 lanes (latent_moe.init_cache; PERF.md §6, PR 36), which no
        # token reads and the yardstick does not count
        values = getattr(cfg, "latent_dim", None)
        assert fam.cache_bytes_per_position(config, 2) * blocks * size == sum(
            x[..., :values].size * x.dtype.itemsize
            for x in jax.tree_util.tree_leaves(pool))
        assert all(x.shape[-1] - (values or x.shape[-1]) < 128
                   for x in jax.tree_util.tree_leaves(pool))
    assert fam.attn_flops_per_position(config) > 0


@pytest.mark.parametrize("family", FAMILIES)
def test_a_ticks_weight_bytes_grow_with_its_tokens_for_routed_experts_only(
        family):
    config = _toy(family)
    fam = spec.family(config)
    n = fam.param_counts(config)
    read = [fam.tick_weight_bytes(config, t, 2) for t in (1, 4, 32, 512)]
    assert read[0] <= 2 * n["matmul"] + 1e-6
    if family in ROUTED_BY_TOKENS:
        assert read == sorted(read) and read[0] < read[1] < read[2]
        vectors = sum(math.prod(s) for _, s, _ in fam.leaf_specs(config)
                      if len(s) == 1)
        # at most every held expert once, and then it is all the matrices
        # (a tied head's among them)
        matrices = n["total"] - vectors - (
            0 if _head_is_the_embedding(fam) else n["embed"])
        assert read[3] <= 2 * matrices
        assert read[3] == pytest.approx(2 * matrices, rel=1e-3)
    else:
        assert len(set(read)) == 1


def test_the_published_cut_is_the_issues_arithmetic():
    """4,919.0M matrix parameters (ISSUE 27's 4,918.8M adds rounded terms),
    9.84 GB in bfloat16, 5,760 B a cached position, the absorbed attention's
    FLOPs, and a decode tick's bytes at 32 and 64 tokens."""
    _, config, _ = spec.cell("serve-moe-mla-decode")
    fam = spec.family(config)
    n = fam.param_counts(config)
    vectors = sum(math.prod(s) for _, s, _ in fam.leaf_specs(config)
                  if len(s) == 1)
    assert n["total"] - vectors == 4_918_968_320
    assert round(2 * n["total"] / 1e9, 2) == 9.84
    assert fam.cache_bytes_per_position(config, 2) == 5 * 576 * 2
    assert fam.attn_flops_per_position(config) == 5 * 2 * 128 * (576 + 512)
    assert 7.3e9 < fam.tick_weight_bytes(config, 32, 2) < 7.5e9
    assert 8.7e9 < fam.tick_weight_bytes(config, 64, 2) < 8.8e9
    assert config["engine"]["cache_blocks"] * config["engine"]["block_size"] \
        >= config["engine"]["max_slots"] * config["engine"]["max_seq_len"]


def test_the_swa_cut_is_the_issues_arithmetic():
    """ISSUE 31's reckoning, held to the configuration file: 3,966,937,600
    parameters (7.93 GB in bfloat16), 2,048 B a cached position a layer,
    every published width, 64 experts, 6 a token, the whole vocabulary, the
    depth alone reduced, and 9.81 GB resident in the deployment's pools."""
    entry, config, traffic = spec.cell("serve-moe-swa-longdoc")
    fam = spec.family(config)
    bench = spec.benchmark()
    listed = next(c for c in bench["configs"] if c["name"] == entry["config"])
    assert listed["reduced"] == list(config["reduced"]) == ["num_hidden_layers"]
    catalog = {"head_dim": 128, "hidden_size": 2560, "moe_ffn_hidden_size": 768,
               "moe_num_active_primary_experts": 6,
               "moe_num_primary_experts": 64, "num_attention_heads": 28,
               "num_key_value_heads": 4, "sliding_window_size": 4096,
               "vocab_size": 151936, "max_position_embeddings": 16384,
               "rope_theta": 1500000, "rms_norm_eps": 1e-06}
    assert {k: config[k] for k in catalog} == catalog
    assert config["rope_layout"] == config["sliding_window_layout"] \
        == [0, 1, 1, 1] * 13 and config["num_hidden_layers"] == 8
    assert fam.layer_kinds(config) == ["global", "window+rope", "window+rope",
                                       "window+rope"] * 2
    n, dep, e = fam.param_counts(config), config["deployment"], config["engine"]
    assert n["total"] == dep["parameters"] == 3_966_937_600
    assert n["total"] == sum(math.prod(s) for _, s, _ in fam.leaf_specs(config))
    assert dep["weight_bytes"] == 2 * n["total"]
    assert round(2 * n["total"] / 1e9, 2) == 7.93
    assert fam.cache_bytes_per_position_per_layer(config, 2) \
        == dep["cache_bytes_per_position_per_layer"] == 2048
    # the pools: the global kind covers every slot at full length, the window
    # kind a ring of the window plus one chunk a slot (the program's bound)
    from horovod_tpu.models import paged
    ring = e["block_size"] * paged.ring_blocks(
        config["sliding_window_size"], e["prefill_chunk"], e["block_size"],
        -(-e["max_seq_len"] // e["block_size"]))
    assert ring == fam.ring_positions(config) == 4096 + 512
    assert e["cache_blocks"] * e["block_size"] == 16 * 14848 \
        == e["max_slots"] * e["max_seq_len"]
    assert dep["global_pool_bytes"] == 2 * 16 * 14848 * 2048
    assert dep["window_pool_bytes"] == 6 * e["max_slots"] * ring * 2048
    assert dep["resident_bytes"] == dep["weight_bytes"] \
        + dep["global_pool_bytes"] + dep["window_pool_bytes"] >= 9e9
    assert e["max_seq_len"] == traffic["prompt_len"]["max"] \
        + traffic["output_len"]["max"]
    assert traffic["prompt_len"]["min"] > config["sliding_window_size"]
    assert e["prefix_cache"] is False
    # what a new token reads of a position of context, at the stated context
    read = fam.cache_bytes_per_position(config, 2)
    assert read == pytest.approx(2048 * (2 + 6 * 4096 / fam.MEAN_LIVE_CONTEXT))
    assert fam.attn_flops_per_position(config) == pytest.approx(
        4 * 28 * 128 * read / 2048)
    assert fam.pool_op_types(config, "window") == [
        "[6,4608,16,4,128]", "[4608,16,4,128]", "[288,16,4,128]",
        ",288,16,4,128]", ",4608,4,128]"]
    assert fam.pool_op_types(config, "global") == [
        "[2,14848,16,4,128]", "[14848,16,4,128]", "[928,16,4,128]",
        ",928,16,4,128]", ",14848,4,128]"]
    assert fam.tick_columns(config) == [5, 8, 512]


def test_the_parent_process_loads_the_swa_family_without_jax():
    code = ("import sys; from perfbench.lib import peaks, spec\n"
            "_, c, _ = spec.cell('serve-moe-swa-longdoc'); f = spec.family(c)\n"
            "spec.tiny(c); f.param_counts(c); f.pool_op_types(c, 'window')\n"
            "f.ring_positions(c)\n"
            "m = {'marks': {k: {'stats': {}, 'tick': 0} for k in ('start', 'end')}}\n"
            "assert f.window_counts(m) is None and f.ring_counts(m) is None\n"
            "peaks.serve_required_seconds(c, peaks.PEAKS['TPU v5 lite'], 9, 9, 1)\n"
            "f.window_attn_required_seconds(c, peaks.PEAKS['TPU v5 lite'], 16, 16, 65536)\n"
            "assert 'jax' not in sys.modules and 'numpy' not in sys.modules\n"
            # the two functions that ask the program (its tile's rows, its
            # narrow columns), when a traced run's children are gone: they
            # start no backend
            "assert ',4608]' in f.window_attn_op_types(c)\n"
            "types = f.expert_op_types(c)\n"
            "from jax._src import xla_bridge\n"
            "assert not xla_bridge._backends, 'a backend was started'\n"
            "print(types)")
    out = subprocess.run([sys.executable, "-c", code], check=True,
                         cwd=spec.ROOT, timeout=60, capture_output=True,
                         text=True)
    from horovod_tpu.models import swa_moe
    assert out.stdout.strip() == str(
        [f"[{swa_moe.EXPERT_TILE},768]", f"[{swa_moe.EXPERT_TILE},2560]"])


def test_the_swa_readers_read_a_trace_and_the_rings_counters():
    """The five readers the cell brings, on a made-up trace and marks: pool
    ops by kind, the window attention's share, the resident share, the
    experts touched; each None where there is nothing to read."""
    _, config, _ = spec.cell("serve-moe-swa-longdoc")
    fam = spec.family(config)
    ring = {"slot_ticks": 160, "resident_position_ticks": 4608 * 100,
            "window_position_ticks": 4096 * 160, "full_position_ticks": 9000 * 100}
    moe = {"ticks": 10, "assignments": 960, "assignments_held": 960,
           "experts_touched": 4000, "load_max": 30}
    mark = lambda t, r, m: {"tick": t, "tokens_prefill": 0, "tokens_decode": 16 * t,
                            "stats": {"moe": m, "kv_pool": {"kinds": {"window": r}}}}
    # short names as PR 31's first trace has them
    ops = {"fusion bf16[4608,16,4,128]": 0.003, "fusion f32[16,4,7,5,4608]": 0.002,
           "fusion bf16[16,4,128,7,5]": 0.0006, "fusion f32[4,7,512]": 0.0004,
           "fusion bf16[6,4608,16,4,128]": 0.0005,
           "fusion bf16[14848,16,4,128]": 0.007, "fusion bf16[64,768]": 0.004,
           "expert_tile_ffn f32[64,2560]": 0.006, "while s32[]": 0.001}
    ctx = {"config": config, "peaks": {"hbm_gbps": 819.0, "bf16_tflops": 197.0},
           "marks": {"start": mark(0, dict.fromkeys(ring, 0), dict.fromkeys(moe, 0)),
                     "end": mark(10, ring, moe)},
           "trace": {"module_count": 5.0, "ops_s": ops}}
    read = lambda name: spec.metric_reader(name)(ctx)
    assert read("swa.window_pool_ops_ms.serve") == pytest.approx(
        1e3 * 0.0035 / 5)
    assert read("swa.global_pool_ops_ms.serve") == pytest.approx(1e3 * 0.007 / 5)
    need, bound = fam.window_attn_required_seconds(
        config, ctx["peaks"], 80, 80, 4096 * 80)
    assert bound == "bytes" and need == pytest.approx(
        6 * 4096 * 80 * 2048 / 819e9)
    assert read("swa.window_attn_roofline_share.serve") == pytest.approx(
        100 * need / 0.006)
    assert read("kv.window_resident_share.serve") == pytest.approx(51.2)
    assert read("moe.experts_touched_per_layer.serve") == pytest.approx(50.0)
    assert read("moe.expert_roofline_share.serve") is not None
    bare = dict(ctx, trace=None, marks={k: {"tick": 0, "stats": {}}
                                        for k in ("start", "end")})
    for name in ("swa.window_pool_ops_ms.serve", "swa.global_pool_ops_ms.serve",
                 "swa.window_attn_roofline_share.serve",
                 "kv.window_resident_share.serve",
                 "moe.experts_touched_per_layer.serve"):
        assert spec.metric_reader(name)(bare) is None, name


def test_the_conv_cut_is_the_issues_arithmetic():
    """ISSUE 33's reckoning, held to the configuration file: 4,667,077,376
    parameters (9.33 GB in bfloat16), 6,144 B a cached position over the
    three attention layers, every published width, 32 experts, 4 a token,
    the whole vocabulary, the depth alone reduced, the layer list kept whole,
    and 9.65 GB resident in the deployment's pools."""
    entry, config, traffic = spec.cell("serve-moe-conv-chat")
    fam = spec.family(config)
    bench = spec.benchmark()
    listed = next(c for c in bench["configs"] if c["name"] == entry["config"])
    assert listed["reduced"] == list(config["reduced"]) == ["num_hidden_layers"]
    assert listed["source"] == config["source"]
    catalog = {"conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
               "intermediate_size": 7168, "max_position_embeddings": 128000,
               "model_type": "lfm2_moe", "moe_intermediate_size": 1792,
               "norm_eps": 1e-05, "norm_topk_prob": True,
               "num_attention_heads": 32, "num_dense_layers": 2,
               "num_experts": 32, "num_experts_per_tok": 4,
               "num_key_value_heads": 8, "rope_theta": 1000000,
               "routed_scaling_factor": 1, "use_expert_bias": True,
               "vocab_size": 65536}
    assert {k: config[k] for k in catalog} == catalog
    period = ["full_attention", "conv", "conv", "conv"]
    tail = ["full_attention", "conv", "conv"]
    assert config["layer_types"] == ["conv", "conv"] + period * 4 + tail * 2
    assert config["num_hidden_layers"] == 14
    assert config["reduced"]["num_hidden_layers"]["published"] == 24
    assert fam.layer_kinds(config) == ["conv+dense"] * 2 + [
        "attn+routed", "conv+routed", "conv+routed", "conv+routed"] * 3
    n, dep, e = fam.param_counts(config), config["deployment"], config["engine"]
    conv = 2048 * 6144 + 2048 * 2048 + 2048 * 3
    attn = 2 * 2048 * 2048 + 2 * 2048 * 512 + 2 * 64
    routed = 2048 * 32 + 32 + 32 * 3 * 2048 * 1792
    assert (conv, attn, routed) == (16_783_360, 10_485_888, 352_387_104)
    assert n["total"] == dep["parameters"] == 4_667_077_376 == (
        2 * (conv + 3 * 2048 * 7168 + 4096) + 9 * (conv + routed + 4096)
        + 3 * (attn + routed + 4096) + 65536 * 2048 + 2048)
    assert n["total"] == sum(math.prod(s) for _, s, _ in fam.leaf_specs(config))
    assert dep["weight_bytes"] == 2 * n["total"] == 9_334_154_752
    # all 24 layers would be the published 8.3 B, tied
    whole = fam.param_counts(dict(config, num_hidden_layers=24))["total"]
    assert round(whole / 1e9, 2) == 8.34
    assert fam.cache_bytes_per_position(config, 2) \
        == dep["cache_bytes_per_position"] == 3 * 2048
    assert fam.attn_flops_per_position(config) == 3 * 4 * 32 * 64
    # the pools: the paged kind covers every slot at full length, the state
    # kind is the program's bound of columns a slot
    from horovod_tpu.models import paged
    cols = paged.state_columns(config["conv_L_cache"] - 1, 1 + 4)
    assert cols == fam.state_columns(config) == 7
    assert e["cache_blocks"] * e["block_size"] == 32 * 1536 \
        == e["max_slots"] * e["max_seq_len"]
    assert dep["attn_pool_bytes"] == 32 * 1536 * 6144
    assert dep["conv_state_bytes"] == e["max_slots"] * fam.state_bytes_per_slot(
        config, cols, 2) == 11 * 32 * 7 * 2048 * 2
    assert dep["resident_bytes"] == dep["weight_bytes"] \
        + dep["attn_pool_bytes"] + dep["conv_state_bytes"] >= 9.6e9
    assert e["max_seq_len"] == traffic["prompt_len"]["max"] \
        + traffic["output_len"]["max"]
    assert e["max_batch_tokens"] == e["prefill_chunk"] + 2 * e["max_slots"]
    assert e["prefix_cache"] is False
    # a full decode tick reads every expert: the issue's 9.3 GB
    assert 9.2e9 < fam.tick_weight_bytes(config, 160, 2) < 9.34e9
    assert fam.pool_op_types(config, "conv") == ["[11,32,7,2048]",
                                                 "[32,7,2048]"]
    # the traffic, as ISSUE 33 gives it
    assert traffic["prompt_len"] == {"median": 192, "sigma": 0.6, "min": 32,
                                     "max": 1024}
    assert traffic["output_len"] == {"median": 160, "sigma": 0.5, "min": 32,
                                     "max": 512}
    assert "shared_prefix" not in traffic and "sessions" not in traffic
    knee = traffic["arrivals"]["knee"]["rate_per_s"]
    assert traffic["arrivals"]["rate_per_s"] == pytest.approx(0.8 * knee)


def test_the_parent_process_loads_the_conv_family_without_jax():
    code = ("import sys; from perfbench.lib import peaks, spec\n"
            "_, c, _ = spec.cell('serve-moe-conv-chat'); f = spec.family(c)\n"
            "spec.tiny(c); f.param_counts(c); f.pool_op_types(c, 'conv')\n"
            "m = {'trace': None, 'config': c,\n"
            "     'marks': {k: {'stats': {}, 'tick': 0} for k in ('start', 'end')}}\n"
            "assert f.window_counts(m) is None and f.state_counts(m) is None\n"
            "assert f.pool_ops_ms(m) is None and f.mixer_share(m) is None\n"
            "peaks.serve_required_seconds(c, peaks.PEAKS['TPU v5 lite'], 9, 9, 1)\n"
            "assert 'jax' not in sys.modules and 'numpy' not in sys.modules\n"
            "types = f.expert_op_types(c)\n"
            "from jax._src import xla_bridge\n"
            "assert not xla_bridge._backends, 'a backend was started'\n"
            "print(types)")
    out = subprocess.run([sys.executable, "-c", code], check=True,
                         cwd=spec.ROOT, timeout=60, capture_output=True,
                         text=True)
    from horovod_tpu.models import conv_moe
    assert out.stdout.strip() == str(
        [f"[{conv_moe.EXPERT_TILE},1792]", f"[{conv_moe.EXPERT_TILE},2048]"])


def test_the_conv_readers_read_a_trace_and_the_states_counters():
    """The four readers the cell brings, on a made-up trace and marks: the
    state pool's ops, the mixer's share, the resident share, the experts
    touched; each None where there is nothing to read, on another family's
    configuration too."""
    _, config, _ = spec.cell("serve-moe-conv-chat")
    state = {"slot_ticks": 160, "state_bytes_ticks": 160 * 315392,
             "kv_bytes_ticks": 160 * 350 * 11 * 2048}
    moe = {"ticks": 10, "assignments": 4 * 12 * 10 * 20,
           "assignments_held": 4 * 12 * 10 * 20, "experts_touched": 2400,
           "load_max": 30}
    mark = lambda t, s, m: {"tick": t, "stats": {
        "moe": m, "kv_pool": {"kinds": {"conv": s}}}}
    ops = {"fusion bf16[11,32,7,2048]": 0.0004, "gather bf16[160,2048]": 0.0002,
           "gather bf16[160,2048] params_embed_table": 0.0003,
           "fusion bf16[160,6144] params_layers_conv_in_proj_kernel": 0.003,
           "fusion bf16[160,2048] params_layers_conv_taps": 0.0005,
           "fusion bf16[160,2048] params_layers_conv_out_proj_kernel": 0.001,
           "fusion bf16[160,2048] params_layers_attn_wo_kernel": 0.002,
           "expert_tile_ffn f32[64,2048]": 0.03, "while s32[]": 0.001}
    ctx = {"config": config, "peaks": {"hbm_gbps": 819.0, "bf16_tflops": 197.0},
           "marks": {"start": mark(0, dict.fromkeys(state, 0),
                                   dict.fromkeys(moe, 0)),
                     "end": mark(10, state, moe)},
           "trace": {"module_count": 5.0, "module_s": 0.09, "ops_s": ops}}
    read = lambda name: spec.metric_reader(name)(ctx)
    assert read("conv.state_ops_ms.serve") == pytest.approx(1e3 * 0.0006 / 5)
    assert read("conv.mixer_share_of_tick.serve") == pytest.approx(
        100 * 0.0045 / 0.09)
    assert read("kv.state_resident_share.serve") == pytest.approx(
        100 * 315392 / (350 * 11 * 2048))
    assert read("moe.experts_touched_per_routed_layer.serve") == \
        pytest.approx(20.0)
    assert read("moe.expert_roofline_share.serve") is not None
    names = ("conv.state_ops_ms.serve", "conv.mixer_share_of_tick.serve",
             "kv.state_resident_share.serve",
             "moe.experts_touched_per_routed_layer.serve")
    bare = dict(ctx, trace=None, marks={k: {"tick": 0, "stats": {}}
                                        for k in ("start", "end")})
    other = dict(ctx, config=spec.cell("serve-moe-swa-longdoc")[1])
    for name in names:
        assert spec.metric_reader(name)(bare) is None, name
        assert spec.metric_reader(name)(other) is None, name


# --------------------------------------- the decoder-hybrid-decoder family
NEW_CELL, NEW_CONFIG = "serve-ssm-yoco-reason", "phi-4-mini-flash-reasoning"
NEW_METRICS = ("ssm.scan_share_of_tick.serve", "ssm.state_ops_ms.serve",
               "yoco.shared_kv_ops_ms.serve")


def test_the_benchmark_holds_the_new_configuration_and_its_cell():
    """What PR 43 lacked: ``BENCHMARK.json`` itself has the configuration,
    the cell on one chip, the cell in both serving end-to-end lists and in
    the per-layer lists it reports, and the three new per-layer entries,
    each with a reader file."""
    bench = spec.benchmark()
    conf = next(c for c in bench["configs"] if c["name"] == NEW_CONFIG)
    assert conf["file"] == f"perfbench/configs/{NEW_CONFIG}.json"
    assert conf["reduced"] == [] and conf["source"].startswith(
        "https://huggingface.co/microsoft/Phi-4-mini-flash-reasoning/")
    cell = next(w for w in bench["workloads"] if w["name"] == NEW_CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NEW_CONFIG, "reason-decode", 1) and len(cell["why"]) <= 200
    e2e, layer = spec.cell_metrics(NEW_CELL, bench)
    assert {m["name"] for m in e2e} == {"ttft_p50_ms",
                                        "serve_out_tokens_per_s", "setup_s"}
    for name in ("ttft_p50_ms", "serve_out_tokens_per_s"):
        listed = next(m for m in bench["end_to_end"] if m["name"] == name)
        assert NEW_CELL in listed["workloads"]
    mine = {m["name"]: m for m in layer}
    for name in NEW_METRICS:
        assert NEW_CELL in mine[name]["workloads"], name
        assert mine[name]["moves"] == "serve_out_tokens_per_s"
        assert mine[name]["source"] == "device_trace"
        assert callable(spec.metric_reader(name))
    # (``model_step.required_roofline_share.serve`` is owed: PERF.md §7)
    for name in ("kv.state_resident_share.serve",
                 "kv.window_resident_share.serve", "device.idle_share.serve",
                 "engine.tick_ms.serve", "engine.ahead_share.serve",
                 "engine.head_rows_share.serve",
                 "swa.window_pool_ops_ms.serve"):
        assert name in mine, name
    # every per-layer metric that lists all the serving cells lists this one
    serving = {w["name"] for w in bench["workloads"]
               if w["name"].startswith("serve-")}
    for m in bench["per_layer"]:
        if {"serve-decode", "serve-moe-blockdiff-gen"} <= set(m["workloads"]) \
                and len(m["workloads"]) >= 5:
            assert set(m["workloads"]) == serving, m["name"]


def test_the_sambay_configuration_is_the_issues_arithmetic():
    """ISSUE 44's reckoning, held to the configuration file: 3,852,457,984
    parameters by kind of layer (7.705 GB in bfloat16), nothing cut, every
    catalog key, 5,120 B a cached position an attention layer, the four
    pools' bytes (the carries at the program's 6 columns, not the issue's
    8: paged.state_columns(1, 5)), and the traffic as the issue gives it."""
    entry, config, traffic = spec.cell(NEW_CELL)
    fam = spec.family(config)
    assert config["reduced"] == {} and config["family"] == "sambay"
    catalog = {"embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
               "intermediate_size": 10240, "layer_norm_eps": 1e-05,
               "max_position_embeddings": 262144, "mb_per_layer": 2,
               "model_type": "phi4flash", "num_attention_heads": 40,
               "num_hidden_layers": 32, "num_key_value_heads": 20,
               "resid_pdrop": 0, "sliding_window": 512,
               "tie_word_embeddings": True, "mlp_bias": False,
               "lm_head_bias": False, "vocab_size": 200064}
    assert {k: config[k] for k in catalog} == catalog
    assert config["assumed"]["sizes"] == {"d_state": 16, "d_conv": 4,
                                          "expand": 2, "dt_rank": 160}
    n = fam.dims(config)
    assert (n["di"], n["N"], n["K"], n["R"], n["hd"]) == (5120, 16, 4, 160, 64)
    kinds = [fam.layer_kind(config, i) for i in range(32)]
    assert kinds[:18] == ["mamba", "swa"] * 8 + ["mamba", "full"]
    assert kinds[18:] == ["gmu", "cross"] * 7
    assert fam.layer_kinds(config)[16:20] == ["mamba+m", ("full", 17), "gmu",
                                              ("cross", 19)]
    by_kind = fam.params_by_kind(config)
    mixer = {k: v - 3 * 2560 * 10240 - 4 * 2560 for k, v in by_kind.items()}
    assert mixer == {"mamba": 41_241_600, "swa": 19_661_184,
                     "full": 19_661_184, "cross": 13_107_584,
                     "gmu": 26_214_400}
    dep, e = config["deployment"], config["engine"]
    assert by_kind == dep["parameters_by_kind_of_layer"] == {
        "mamba": 119_895_040, "swa": 98_314_624, "full": 98_314_624,
        "cross": 91_761_024, "gmu": 104_867_840}
    total = fam.param_counts(config)["total"]
    assert total == dep["parameters"] == 3_852_457_984 == (
        9 * 119_895_040 + 9 * 98_314_624 + 7 * 91_761_024 + 7 * 104_867_840
        + 512_163_840 + 5_120)
    assert total == sum(math.prod(s) for _, s, _ in fam.leaf_specs(config))
    assert dep["weight_bytes"] == 2 * total and round(2 * total / 1e9, 3) \
        == 7.705
    assert fam.cache_bytes_per_position_per_layer(config, 2) \
        == dep["cache_bytes_per_position_per_layer"] == 5120
    from horovod_tpu.models import paged
    cols = fam.state_columns(config)
    assert cols == {"conv": paged.state_columns(3, 5),
                    "carry": paged.state_columns(1, 5)} == {"conv": 8,
                                                             "carry": 6}
    ring = e["block_size"] * paged.ring_blocks(
        512, e["prefill_chunk"], e["block_size"],
        -(-e["max_seq_len"] // e["block_size"]))
    assert ring == fam.ring_positions(config) == 768
    assert e["cache_blocks"] * e["block_size"] == 32 * 2560 \
        == e["max_slots"] * e["max_seq_len"]
    assert dep["kv_pool_bytes"] == 32 * 2560 * 5120
    assert dep["window_pool_bytes"] == 8 * 32 * 768 * 5120
    assert dep["conv_state_bytes"] == 9 * 32 * 8 * 10_240 == 32 * \
        fam.state_bytes_per_slot(config, 8, 2)["conv"]
    assert dep["carry_state_bytes"] == 9 * 32 * 6 * 327_680 == 32 * \
        fam.state_bytes_per_slot(config, 6, 2)["carry"]
    assert dep["resident_bytes"] == sum(dep[k] for k in (
        "weight_bytes", "kv_pool_bytes", "window_pool_bytes",
        "conv_state_bytes", "carry_state_bytes")) >= 0.25 * 16e9
    assert (e["max_slots"], e["prefill_chunk"], e["max_batch_tokens"],
            e["block_size"], e["max_seq_len"], e["prefix_cache"]) == (
        32, 256, 384, 16, 2560, False)
    # (the pool is the issue's, prompt 1,024 + answer 1,536; the answers are
    # cut below, the engine is not)
    assert e["max_seq_len"] == traffic["prompt_len"]["max"] + 1536 \
        >= traffic["prompt_len"]["max"] + traffic["output_len"]["max"]
    # a tick reads every matrix once; a new token reads the one full layer
    # eight times and the eight rings' share of a position
    assert fam.tick_weight_bytes(config, 1, 2) == fam.tick_weight_bytes(
        config, 384, 2) == 2 * fam.param_counts(config)["matmul"]
    assert fam.cache_bytes_per_position(config, 2) == pytest.approx(
        5120 * (8 + 8 * 512 / fam.MEAN_LIVE_CONTEXT))
    assert fam.attn_flops_per_position(config) == pytest.approx(
        2 * 40 * (64 + 128) * (8 + 8 * 512 / fam.MEAN_LIVE_CONTEXT))
    assert fam.state_op_types(config) == [
        "[9,32,6,16,5120]", "[32,6,16,5120]", "[9,32,8,5120]", "[32,8,5120]",
        "[5,32,16,5120]", "[384,1,16,5120]"]
    # the rings' pool, and the 34 or 48 entries of a ring that a verify row's
    # or a chunk's windows can reach
    assert fam.pool_op_types(config, "global") == []
    assert fam.pool_op_types(config, "window") == [
        "[8,1536,16,1280]",
        ",34,16,1280]", "[34,16,1280]", "[1088,16,1280]", ",544,1280]",
        ",544,10,128]",
        ",48,16,1280]", "[48,16,1280]", "[1536,16,1280]", ",768,1280]",
        ",768,10,128]"]
    # the traffic, as ISSUE 44 gives it
    assert traffic["prompt_len"] == {"median": 192, "sigma": 0.6, "min": 32,
                                     "max": 1024}
    # (the longest answer is what 26 s of ticks give, ISSUE 44's rule for a
    # tick over 17 ms: 1,536 as given, 1,088 at the measured 23.5 ms)
    assert traffic["output_len"] == {"median": 768, "sigma": 0.4, "min": 256,
                                     "max": 1088}
    assert "shared_prefix" not in traffic and "sessions" not in traffic
    knee = traffic["arrivals"]["knee"]["rate_per_s"]
    assert traffic["arrivals"]["rate_per_s"] == pytest.approx(0.8 * knee)


def test_the_parent_process_loads_the_sambay_family_without_jax():
    code = ("import sys; from perfbench.lib import peaks, spec\n"
            "_, c, _ = spec.cell('serve-ssm-yoco-reason'); f = spec.family(c)\n"
            "spec.tiny(c); f.param_counts(c); f.state_op_types(c)\n"
            "f.leaf_specs(c); f.layer_kinds(c)\n"
            "m = {'trace': None, 'config': c,\n"
            "     'marks': {k: {'stats': {}, 'tick': 0} for k in ('start', 'end')}}\n"
            "assert f.state_counts(m) is None and f.ring_counts(m) is None\n"
            "assert f.scan_share(m) is None and f.state_ops_ms(m) is None\n"
            "assert f.shared_kv_ops_ms(m) is None\n"
            "assert f.pool_ops_ms(m, 'window') is None\n"
            "peaks.serve_required_seconds(c, peaks.PEAKS['TPU v5 lite'], 9, 9, 1)\n"
            "assert 'jax' not in sys.modules and 'numpy' not in sys.modules\n"
            # the one function that asks the program (its tile's positions),
            # when a traced run's children are gone: it starts no backend
            "types = f.shared_kv_op_types(c)\n"
            "from jax._src import xla_bridge\n"
            "assert not xla_bridge._backends, 'a backend was started'\n"
            "print(types)")
    out = subprocess.run([sys.executable, "-c", code], check=True,
                         cwd=spec.ROOT, timeout=60, capture_output=True,
                         text=True)
    from horovod_tpu.models import paged
    assert paged.TILE == 256 and out.stdout.strip() == str(
        [",16,16,1280]", ",256,1280]", "[32,16,1280]", "[16,16,1280]",
         "[1,5120,16,1280]", "[5120,16,1280]"])


def test_the_sambay_readers_read_a_trace_and_the_kinds_counters():
    """The three readers the cell brings and those it joins, on a made-up
    trace and marks: the scans' share (the carries' pool and the buffer the
    snapshots are taken from apart), the state kinds' ops, the shared
    layer's fetches, the rings' ops, the two resident shares, the head's
    rows; each None where there is nothing to read, on another family's
    configuration too."""
    _, config, _ = spec.cell(NEW_CELL)
    conv = {"slot_ticks": 160, "state_bytes_ticks": 160 * 737_280,
            "kv_bytes_ticks": 160 * 700 * 9 * 5120}
    carry = dict(conv, state_bytes_ticks=160 * 17_694_720)
    ring = {"slot_ticks": 160, "resident_position_ticks": 160 * 600,
            "window_position_ticks": 160 * 500,
            "full_position_ticks": 160 * 700}
    loop = {"head_rows": 160 * 3, "packed_rows": 384 * 3}
    mark = lambda t, zero: {"tick": t, "stats": {
        "loop": dict.fromkeys(loop, 0) if zero else loop,
        "kv_pool": {"kinds": {
            name: dict.fromkeys(d, 0) if zero else d for name, d in
            (("conv", conv), ("carry", carry), ("window", ring))}}}}
    ops = {"fusion f32[32,16,5120]": 0.004,
           "broadcast_select_fusion f32[32,16,5120]": 0.002,
           "copy-done f32[5,32,16,5120]": 0.0021,
           "fusion f32[384,1,16,5120]": 0.0007,
           "gather f32[32,16,5120]": 0.0003,
           "scatter f32[9,32,6,16,5120]": 0.0007,
           "scatter bf16[9,32,8,5120]": 0.0002,
           "fusion bf16[32,5,5120]": 0.05,
           "fusion bf16[32,16,1280]": 0.0011,
           "fusion bf16[2,256,1280]": 0.0004,
           "scatter bf16[1,5120,16,1280]": 0.0001,
           "fusion bf16[1088,16,1280]": 0.006,
           "reshape bf16[32,544,10,128]": 0.002,
           "scatter bf16[8,1536,16,1280]": 0.001,
           "gather bf16[1,48,16,1280]": 0.0005, "while s32[]": 0.001}
    ctx = {"config": config, "peaks": {"hbm_gbps": 819.0, "bf16_tflops": 197.0},
           "marks": {"start": mark(0, True), "end": mark(10, False)},
           "trace": {"module_count": 5.0, "module_s": 0.09, "ops_s": ops}}
    read = lambda name: spec.metric_reader(name)(ctx)
    assert read("ssm.scan_share_of_tick.serve") == pytest.approx(
        100 * 0.006 / 0.09)
    assert read("ssm.state_ops_ms.serve") == pytest.approx(1e3 * 0.004 / 5)
    assert read("yoco.shared_kv_ops_ms.serve") == pytest.approx(
        1e3 * 0.0016 / 5)
    assert read("kv.state_resident_share.serve") == pytest.approx(
        100 * (737_280 + 17_694_720) / (700 * 9 * 5120))
    assert read("kv.window_resident_share.serve") == pytest.approx(
        100 * 600 / 700)
    assert read("swa.window_pool_ops_ms.serve") == pytest.approx(
        1e3 * 0.0095 / 5)
    assert read("engine.head_rows_share.serve") == pytest.approx(
        100 * 160 / 384)
    bare = dict(ctx, trace=None, marks={k: {"tick": 0, "stats": {}}
                                        for k in ("start", "end")})
    other = dict(ctx, config=spec.cell("serve-decode")[1])
    for name in NEW_METRICS + ("kv.state_resident_share.serve",
                               "kv.window_resident_share.serve",
                               "swa.window_pool_ops_ms.serve"):
        assert spec.metric_reader(name)(bare) is None, name
        assert spec.metric_reader(name)(other) is None, name
    assert spec.metric_reader("engine.head_rows_share.serve")(bare) is None


# ------------------------------------------- the gated delta-rule family
GDN_CELL, GDN_CONFIG = "serve-gdn-mixedlen", "olmo-hybrid-7b"
GDN_METRICS = ("gdn.mix_share_of_tick.serve", "gdn.state_ops_ms.serve")


def test_the_benchmark_holds_the_gdn_configuration_and_its_cell():
    """``BENCHMARK.json`` itself has the configuration with its file, the
    cell on one chip, the cell in both serving end-to-end lists and in the
    per-layer lists it reports, and the two new per-layer entries at the END
    of the list, each with a reader file."""
    bench = spec.benchmark()
    conf = bench["configs"][-1]
    assert conf["name"] == GDN_CONFIG
    assert conf["file"] == f"perfbench/configs/{GDN_CONFIG}.json"
    assert conf["reduced"] == ["num_hidden_layers"] and conf["source"] == \
        "https://huggingface.co/allenai/Olmo-Hybrid-7B/blob/main/config.json"
    cell = bench["workloads"][-1]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == (
        GDN_CELL, GDN_CONFIG, "mixedlen-decode", 1) and len(cell["why"]) <= 200
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    e2e, layer = spec.cell_metrics(GDN_CELL, bench)
    assert {m["name"] for m in e2e} == {"ttft_p50_ms",
                                        "serve_out_tokens_per_s", "setup_s"}
    mine = {m["name"]: m for m in layer}
    assert tuple(m["name"] for m in bench["per_layer"][-2:]) == GDN_METRICS
    for name, source in zip(GDN_METRICS, ("device_trace", "device_trace")):
        assert mine[name]["workloads"] == [GDN_CELL], name
        assert mine[name]["moves"] == "serve_out_tokens_per_s"
        assert mine[name]["layer"] == "gated delta rule"
        assert mine[name]["source"] == source
        assert callable(spec.metric_reader(name))
    # (``model_step.required_roofline_share.serve`` is not joined: PERF.md §7)
    assert "model_step.required_roofline_share.serve" not in mine
    for name in ("kv.state_resident_share.serve", "device.idle_share.serve",
                 "engine.tick_ms.serve", "engine.ahead_share.serve",
                 "engine.late_launch_share.serve",
                 # the module samples on the rows a tick reads
                 "engine.head_rows_share.serve"):
        assert name in mine, name
    # the rows a tick replayed are counted by the program (TICK_COUNTERS)
    # and read by no metric: uniform tokens never draft (PERF.md section 7)
    assert not any("replayed" in m["name"] for m in bench["per_layer"])
    assert len(json.dumps(bench)) < 64 * 1024


def test_the_gdn_cut_is_whole_periods_and_the_issues_arithmetic():
    """ISSUE 48's reckoning, held to the configuration file: every catalog
    key but the depth, the first 16 ``layer_types`` four whole periods of
    (linear, linear, linear, full), 4,100,788,944 parameters by kind of
    layer (8.20 GB in bfloat16), the uncut model 7,430,870,688, 15,360 B a
    cached position a full layer, the pools' bytes with ONE matrix state a
    slot a layer, the engine's settings and the traffic as the issue gives
    them."""
    entry, config, traffic = spec.cell(GDN_CELL)
    fam = spec.family(config)
    assert set(config["reduced"]) == {"num_hidden_layers"}
    assert config["family"] == "gdn_hybrid"
    with open("/opt/skills/guides/model-configs/architectures.jsonl") \
            if os.path.isfile(
                "/opt/skills/guides/model-configs/architectures.jsonl") \
            else open(os.devnull) as f:
        rows = [json.loads(line) for line in f]
    catalog = next((r["config"] for r in rows
                    if r["name"] == "Olmo-Hybrid-7B"), None) or {
        "model_type": "olmo_hybrid", "vocab_size": 100352,
        "hidden_size": 3840, "intermediate_size": 11008,
        "num_attention_heads": 30, "num_key_value_heads": 30,
        "max_position_embeddings": 65536, "rms_norm_eps": 1e-06,
        "tie_word_embeddings": False, "linear_num_key_heads": 30,
        "linear_num_value_heads": 30, "linear_key_head_dim": 96,
        "linear_value_head_dim": 192, "linear_conv_kernel_dim": 4,
        "linear_allow_neg_eigval": True,
        "rope_parameters": {"rope_theta": None}}
    assert {k: config[k] for k in catalog if k != "num_hidden_layers"} == {
        k: v for k, v in catalog.items() if k != "num_hidden_layers"}
    assert config["num_hidden_layers"] == 16
    period = ["linear_attention"] * 3 + ["full_attention"]
    assert config["layer_types"] == period * 8           # kept whole
    assert config["layer_types"][:16] == period * 4 and fam.full_every(
        config) == 4
    assert fam.layer_kinds(config) == (["linear"] * 3 + ["full"]) * 4
    for key in ("block", "no_rotary", "qk_norm", "head_size", "convolution",
                "norms_and_scale", "beta", "gate", "state", "weights",
                "tokens"):
        assert len(config["assumed"][key]) > 40, key
    n = fam.dims(config)
    assert (n["hd"], n["Hl"], n["dk"], n["dv"], n["K"]) == (128, 30, 96, 192,
                                                            4)
    by_kind = fam.params_by_kind(config)
    mixer = {k: v - 3 * 3840 * 11008 - 2 * 3840 for k, v in by_kind.items()}
    assert mixer == {"linear": 88_750_332, "full": 58_990_080}
    dep, e = config["deployment"], config["engine"]
    assert by_kind == dep["parameters_by_kind_of_layer"] == {
        "linear": 215_570_172, "full": 185_809_920}
    total = fam.param_counts(config)["total"]
    assert total == dep["parameters"] == 4_100_788_944 == (
        12 * 215_570_172 + 4 * 185_809_920 + 770_707_200)
    assert total == sum(math.prod(s) for _, s, _ in fam.leaf_specs(config))
    assert fam.uncut_param_count(config) == dep["parameters_uncut"] == \
        7_430_870_688 == 24 * 215_570_172 + 8 * 185_809_920 + 770_707_200
    assert dep["weight_bytes"] == 2 * total and round(2 * total / 1e9, 2) \
        == 8.2
    assert (dep["stages"], dep["layers_per_stage"], dep["this_stage"],
            dep["chips_per_layer"]) == (2, 16, 0, 1)
    assert fam.cache_bytes_per_position_per_layer(config, 2) \
        == dep["cache_bytes_per_position_per_layer"] == 15_360
    assert fam.cache_bytes_per_position(config, 2) == 61_440
    from horovod_tpu.models import paged
    assert fam.replay_rows(config) == paged.replay_rows(5) == 4
    state = fam.state_bytes_per_slot(config, 2)
    assert state == {"conv": 12 * paged.state_columns(3, 5) * 11_520 * 2,
                     "delta": 12 * (2_211_840 + 4 + 4 * 34_800)}
    assert (e["max_slots"], e["prefill_chunk"], e["max_batch_tokens"],
            e["block_size"], e["max_seq_len"], e["cache_blocks"],
            e["prefix_cache"]) == (16, 512, 576, 16, 12_800, 3_072, False)
    assert e["max_batch_tokens"] == e["prefill_chunk"] + e["max_slots"] * 4
    assert dep["kv_pool_bytes"] == 3_072 * 16 * 61_440
    assert dep["conv_state_bytes"] == 16 * state["conv"]
    assert dep["delta_state_bytes"] + dep["delta_ring_bytes"] \
        == 16 * state["delta"]
    assert dep["delta_state_bytes"] == 12 * 16 * 30 * 192 * 96 * 4
    assert dep["resident_bytes"] == sum(dep[k] for k in (
        "weight_bytes", "kv_pool_bytes", "conv_state_bytes",
        "delta_state_bytes", "delta_ring_bytes"))
    assert 0.25 * 16e9 < 10.5e9 < dep["resident_bytes"] < 13e9
    assert e["max_seq_len"] == traffic["prompt_len"]["max"] \
        + traffic["output_len"]["max"]
    assert fam.tick_weight_bytes(config, 1, 2) == fam.tick_weight_bytes(
        config, 576, 2) == 2 * fam.param_counts(config)["matmul"]
    assert fam.attn_flops_per_position(config) == 2 * 30 * 256 * 4
    # the program the file asks for: four kinds of cache, chunks of 64
    model, cfg = fam.program(config)
    assert (cfg.n_layers, cfg.chunk, cfg.full_every, cfg.max_seq) == (
        16, 64, 4, 12_800)
    assert [(k.name, k.layers) for k in model.cache_kinds(cfg)] == [
        ("kv", 4), ("conv", 12), ("delta", 12)]
    # the traffic, as ISSUE 48 gives it
    assert traffic["prompt_len"] == {"median": 2048, "sigma": 0.9, "min": 256,
                                     "max": 12288}
    assert traffic["output_len"] == {"median": 256, "sigma": 0.5, "min": 64,
                                     "max": 512}
    assert "shared_prefix" not in traffic and "sessions" not in traffic
    knee = traffic["arrivals"]["knee"]["rate_per_s"]
    assert traffic["arrivals"]["rate_per_s"] == pytest.approx(0.8 * knee)
    assert set(traffic["check"]["limits"]) == {
        "served_gap_share", "served_gap_max", "protocol_violations",
        "window_compilations"}


def test_the_parent_process_loads_the_gdn_family_without_jax():
    code = ("import sys; from perfbench.lib import peaks, spec\n"
            "_, c, _ = spec.cell('serve-gdn-mixedlen'); f = spec.family(c)\n"
            "spec.tiny(c); f.param_counts(c); f.state_op_group('x f32[1]', c)\n"
            "f.mix_op_group('x f32[1]', c); f.leaf_specs(c); f.layer_kinds(c)\n"
            "m = {'trace': None, 'config': c,\n"
            "     'marks': {k: {'stats': {}, 'tick': 0} for k in ('start', 'end')}}\n"
            "assert f.state_counts(m) is None\n"
            "assert f.mix_share(m) is None and f.state_ops_ms(m) is None\n"
            "peaks.serve_required_seconds(c, peaks.PEAKS['TPU v5 lite'], 9, 9, 1)\n"
            "assert 'jax' not in sys.modules and 'numpy' not in sys.modules\n")
    subprocess.run([sys.executable, "-c", code], check=True, cwd=spec.ROOT,
                   timeout=60)


def test_the_gdn_readers_read_a_trace_and_the_ticks_counters():
    """The two readers the cell brings and the one it joins, on a made-up
    trace and marks: the recurrence's share (the states' and the ring's
    moves apart), the state kinds' ops, the resident share; each None where
    there is nothing to read, on another family's configuration too, and
    where the trace lacks one of the groups a reader expects — a program
    that runs the recurrence in other ops must not read as a smaller
    share."""
    _, config, _ = spec.cell(GDN_CELL)
    conv = {"slot_ticks": 100, "state_bytes_ticks": 100 * 2_211_840,
            "kv_bytes_ticks": 100 * 3000 * 12 * 15_360}
    delta = dict(conv, state_bytes_ticks=100 * 28_212_528)
    mark = lambda t, zero: {"tick": t, "stats": {"kv_pool": {"kinds": {
        name: dict.fromkeys(d, 0) if zero else d for name, d in
        (("conv", conv), ("delta", delta))}}}}
    mix = {n: s for n, (_, s) in GDN_MIX_OPS.items()}
    state = {n: s for n, (_, s) in GDN_STATE_OPS.items()}
    ops = dict(mix, **state, **dict.fromkeys(GDN_OTHER_OPS, 0.01))
    ctx = {"config": config, "peaks": {"hbm_gbps": 819.0, "bf16_tflops": 197.0},
           "marks": {"start": mark(0, True), "end": mark(10, False)},
           "trace": {"module_count": 5.0, "module_s": 0.2, "ops_s": ops}}
    read = lambda name, ctx=ctx: spec.metric_reader(name)(ctx)
    assert read("gdn.mix_share_of_tick.serve") == pytest.approx(
        100 * sum(mix.values()) / 0.2)
    assert read("gdn.state_ops_ms.serve") == pytest.approx(
        1e3 * sum(state.values()) / 5)
    assert read("kv.state_resident_share.serve") == pytest.approx(
        100 * (2_211_840 + 28_212_528) / (3000 * 12 * 15_360))
    bare = dict(ctx, trace=None, marks={k: {"tick": 0, "stats": {}}
                                        for k in ("start", "end")})
    other = dict(ctx, config=spec.cell("serve-decode")[1])
    for name in GDN_METRICS + ("kv.state_resident_share.serve",):
        assert spec.metric_reader(name)(bare) is None, name
        assert spec.metric_reader(name)(other) is None, name
    # a group with no op in the trace: None, not what is left of the share
    without = lambda group: dict(ctx, trace=dict(ctx["trace"], ops_s={
        n: s for n, s in ops.items()
        if (GDN_MIX_OPS.get(n) or GDN_STATE_OPS.get(n) or ("",))[0]
        != group}))
    for group in ("state", "square", "rows"):
        assert read("gdn.mix_share_of_tick.serve", without(group)) is None
        assert read("gdn.state_ops_ms.serve", without(group)) is not None
    for group in ("states", "ring", "conv"):
        assert read("gdn.state_ops_ms.serve", without(group)) is None
        assert read("gdn.mix_share_of_tick.serve", without(group)) is not None
    for group in ("fed", "at"):     # fused away, or too small to matter
        assert read("gdn.mix_share_of_tick.serve", without(group))
        assert read("gdn.state_ops_ms.serve", without(group))


#: device ops as the cell's two programs name them on the chip (my chip
#: runs, PR 48), {name: (the group the family's readers give it, seconds)}:
#: the recurrence's own; the moves of the states, the ring and the conv
#: inputs; and what is neither — the projections (a weight's name behind the
#: type), the norms on the tick's own rows, the running sums, the attention
GDN_MIX_OPS = {"fusion f32[16,30,9,9]": ("square", 0.004),
               "fusion f32[16,30,1,9,9]": ("square", 0.0001),
               "fusion f32[16,30,18,192]": ("rows", 0.003),
               "convolution_add_fusion f32[16,30,192,96]": ("state", 0.005),
               "slice_add_fusion f32[16,30,9,192]": ("rows", 0.001),
               "fusion.494.remat = f32[16,30,18,192]{3,2,1,0:T(8,128)S(1)} "
               "fusion(f32[12,16,1,30": ("rows", 0.0002),
               "fusion f32[144,30,192]": ("fed", 0.0003),
               "fusion f32[8,30,64,288]": ("rows", 0.006),
               "fusion f32[8,30,2,32,32]": ("square", 0.002),
               "fusion f32[512,30,96]": ("fed", 0.001),
               "fusion f32[30,128,192]": ("rows", 0.003),
               "fusion f32[32,30,64,192]": ("rows", 0.0007),
               "fusion f32[30,192,96]": ("state", 0.002)}
GDN_STATE_OPS = {"fusion f32[12,16,30,192,96]": ("states", 0.003),
                 "fusion f32[12,16,1,30,192,96]": ("states", 0.004),
                 "copy-done f32[12,16,4,8700]": ("ring", 0.0005),
                 "fusion f32[16,4,8700]": ("ring", 0.0004),
                 "concatenate f32[16,5,8700]": ("ring", 0.0001),
                 "reshape f32[16,4,30,192]": ("ring", 0.0001),
                 "fusion s32[12,16]": ("at", 0.0001),
                 "copy s32[12,16,1,1]": ("at", 0.0001),
                 "fusion bf16[12,16,8,11520]": ("conv", 0.0006),
                 "fusion bf16[16,8,11520]": ("conv", 0.0002),
                 "slice-done bf16[3,16,8,11520]": ("conv", 0.0001),
                 "fusion bf16[80,11520]": ("conv", 0.0008),
                 "fusion bf16[576,11520]": ("conv", 0.002)}
GDN_OTHER_OPS = ("fusion bf16[576,11520] params_layers_gdn_qkv_kernel",
                 "fusion bf16[16,5,11520] params_layers_gdn_qkv_kernel",
                 "fusion f32[576,30,192]", "fusion f32[80,30,96]",
                 "reshape f32[16,5,30,96]", "reduce_window_sum f32[16,30,9]",
                 "reshape bf16[2,256,30,128]", "fusion f32[2,30,5]",
                 "fusion f32[2,30,1,5,128]", "fusion bf16[16,5,3840]",
                 "fusion f32[16,5,11520]", "while s32[]")


@pytest.mark.parametrize("name", sorted(GDN_MIX_OPS) + sorted(GDN_STATE_OPS)
                         + sorted(GDN_OTHER_OPS))
def test_a_device_op_of_the_gdn_cell_is_told_by_the_models_sizes(name):
    """``mix_op_group`` / ``state_op_group``: an op is the recurrence's, a
    pool's or neither by the type it makes and H, dk, dv, the slots and the
    tick's rows alone — no chunk's length and no grouping of the program's
    is in the rule, so a program that cuts its chunks another way keeps its
    readers."""
    _, config, _ = spec.cell(GDN_CELL)
    fam = spec.family(config)
    mix, state = (GDN_MIX_OPS.get(name, (None,))[0],
                  GDN_STATE_OPS.get(name, (None,))[0])
    assert fam.mix_op_group(name, config) == mix
    assert fam.state_op_group(name, config) == state


def test_the_gdn_readers_rules_hold_no_constant_of_the_program():
    """The yardstick's rules are the configuration's: with another chunk
    length (128) and grouping (4) the same ops of the same kinds are found;
    and where the full layers' head size is one of dk, dv, dk + dv nothing
    is told apart and nothing is counted."""
    _, config, _ = spec.cell(GDN_CELL)
    fam = spec.family(config)
    for name, group in (("fusion f32[4,30,128,288]", "rows"),
                        ("fusion f32[4,30,4,32,32]", "square"),
                        ("fusion f32[30,256,192]", "rows"),
                        ("fusion f32[16,30,11,11]", "square")):
        assert fam.mix_op_group(name, config) == group
    same = dict(config, hidden_size=30 * 96)
    assert fam.mix_op_group("fusion f32[16,30,9,9]", same) is None


@pytest.mark.parametrize("fraction", [0.0, 0.01, 0.5, 0.99])
def test_the_gdn_cells_traced_window_leaves_the_timelines_reader_its_seconds(
        fraction):
    """``engine.warm_excess_ms.serve`` reads the window's first 8 whole
    seconds against the whole seconds after them and BEFORE the profiler
    session, and is None where none lies between: the cell's trace began at
    7.9 s once and the check refused the PR for the missing metric.  At the
    file's ``trace.start_s`` the reader has a second to read at whatever
    fraction of a wall second the window starts, and the session still ends
    with the 17 s the profiler took to stop inside the window of 45 s."""
    _, _, traffic = spec.cell(GDN_CELL)
    start, seconds = traffic["trace"]["start_s"], traffic["trace"]["seconds"]
    assert start + seconds + 17.0 < 45.0
    t0 = 1000.0 + fraction
    sec = list(range(998, 1050))
    cols = lambda value: [value] * len(sec)
    timeline = {"sec": sec, "narrow": cols(30.0), "wide": cols(3.0),
                "phase_s": {"stage": cols(0.033), "idle": cols(0.2)},
                "phase_n": {"stage": cols(33.0), "idle": cols(10.0)}}
    ctx = {"trace": {"t0": t0 + start}, "marks": {
        "start": {"t": t0}, "end": {"t": t0 + 45.0, "stats": {
            "loop": {"timeline": timeline}}}}}
    read = spec.metric_reader("engine.warm_excess_ms.serve")
    assert read(ctx) == pytest.approx(0.0)
    assert read(dict(ctx, trace={"t0": t0 + 7.9})) is None


# ------------------------------------------- the block-denoising family
def test_the_blockdiff_cut_is_the_issues_arithmetic():
    """ISSUE 40: a layer of 623,120,640 parameters, 4,984,176,384 in seven
    layers with embedding and head, 9.968 GB in bfloat16; 14,336 B a cached
    position; a narrow tick of 128 rows reads 9.34 GB; the engine and the
    traffic as set out."""
    _, config, traffic = spec.cell("serve-moe-blockdiff-gen")
    fam = spec.family(config)
    n = fam.param_counts(config)
    layer = sum(math.prod(s) for k, s, _ in fam.leaf_specs(config)
                if k.startswith("layers.0."))
    assert layer == 623_120_640
    assert n["total"] == 7 * layer + 2 * 151_936 * 2_048 + 2_048 \
        == 4_984_176_384 == config["deployment"]["parameters"]
    assert config["deployment"]["weight_bytes"] == 2 * n["total"]
    assert fam.cache_bytes_per_position(config, 2) == 14_336 \
        == config["deployment"]["cache_bytes_per_position"]
    e = config["engine"]
    pool = 14_336 * e["cache_blocks"] * e["block_size"]
    assert pool == config["deployment"]["pool_bytes"] == 939_524_096
    assert config["deployment"]["resident_bytes"] == 2 * n["total"] + pool
    assert 0.25 * 16e9 < config["deployment"]["resident_bytes"] < 16e9
    assert 9.3e9 < fam.tick_weight_bytes(config, 128, 2) < 9.4e9
    assert fam.experts_touched(config, 128) > 127.9
    assert fam.attn_flops_per_position(config) == 4 * 32 * 128 * 7
    # every width, the experts and the vocabulary as published; depth alone cut
    assert config["reduced"].keys() == {"num_hidden_layers"}
    assert config["reduced"]["num_hidden_layers"]["published"] == 48
    assert (config["hidden_size"], config["moe_intermediate_size"],
            config["num_experts"], config["num_experts_per_tok"],
            config["vocab_size"], config["head_dim"]) == (
                2048, 768, 128, 8, 151_936, 128)
    g = fam.gen(config)
    assert (g["B"], g["steps"], g["tau"], g["M"]) == (4, 4, 0.9, 151_669)
    assert e == {"max_slots": 32, "prefill_chunk": 256,
                 "max_batch_tokens": 384, "block_size": 16,
                 "max_seq_len": 2048, "cache_blocks": 4096,
                 "prefix_cache": False, "spec_decode": False}
    assert e["max_batch_tokens"] == e["prefill_chunk"] + 4 * e["max_slots"]
    assert traffic["prompt_len"] == {"median": 256, "sigma": 0.6, "min": 64,
                                     "max": 1024}
    assert traffic["output_len"] == {"median": 384, "sigma": 0.4, "min": 128,
                                     "max": 1024}
    assert e["max_seq_len"] == traffic["prompt_len"]["max"] \
        + traffic["output_len"]["max"]
    assert "shared_prefix" not in traffic and "sessions" not in traffic
    knee = traffic["arrivals"]["knee"]["rate_per_s"]
    assert traffic["arrivals"]["rate_per_s"] == pytest.approx(0.8 * knee)
    assert set(traffic["check"]["limits"]) == {
        "served_gap_share", "served_gap_max", "early_unmask_share",
        "protocol_violations", "window_compilations"}


def test_the_parent_process_loads_the_blockdiff_family_without_jax():
    code = ("import sys; from perfbench.lib import peaks, spec\n"
            "_, c, _ = spec.cell('serve-moe-blockdiff-gen'); f = spec.family(c)\n"
            "spec.tiny(c); f.param_counts(c); f.gen(c)\n"
            "m = {'trace': None, 'config': c,\n"
            "     'marks': {k: {'stats': {}, 'tick': 0} for k in ('start', 'end')}}\n"
            "assert f.window_counts(m) is None\n"
            "for name in ('diffusion.tokens_per_pass.serve',\n"
            "             'diffusion.commit_pass_share.serve'):\n"
            "    assert spec.metric_reader(name)(m) is None\n"
            "peaks.serve_required_seconds(c, peaks.PEAKS['TPU v5 lite'], 9, 9, 1)\n"
            "assert 'jax' not in sys.modules and 'numpy' not in sys.modules\n"
            "types = f.expert_op_types(c)\n"
            "from jax._src import xla_bridge\n"
            "assert not xla_bridge._backends, 'a backend was started'\n"
            "print(types)")
    out = subprocess.run([sys.executable, "-c", code], check=True,
                         cwd=spec.ROOT, timeout=60, capture_output=True,
                         text=True)
    from horovod_tpu.models import blockdiff_moe
    assert out.stdout.strip() == str(
        [f"[{blockdiff_moe.EXPERT_TILE},768]",
         f"[{blockdiff_moe.EXPERT_TILE},2048]"])


def test_the_diffusion_readers_read_a_hand_made_stats_pair():
    """The two readers the cell brings on made-up marks: positions a
    denoising pass, the commit passes' share of the block rows; None where
    there is nothing to read, on another family's marks too; and the expert
    roofline through the family's hooks."""
    _, config, _ = spec.cell("serve-moe-blockdiff-gen")
    a = {"slot_passes": 100, "commit_passes": 20, "tokens_fixed": 90,
         "fixed_by_threshold": 30, "fixed_as_surest": 60, "blocks_done": 21}
    b = {"slot_passes": 500, "commit_passes": 120, "tokens_fixed": 510,
         "fixed_by_threshold": 150, "fixed_as_surest": 360,
         "blocks_done": 125}
    moe = {"ticks": 10, "assignments": 8 * 7 * 10 * 128,
           "assignments_held": 8 * 7 * 10 * 128, "experts_touched": 8960,
           "load_max": 300}
    mark = lambda d, m: {"tick": m["ticks"], "stats": {"diffusion": d,
                                                       "moe": m}}
    ctx = {"config": config, "peaks": {"hbm_gbps": 819.0, "bf16_tflops": 197.0},
           "marks": {"start": mark(a, dict.fromkeys(moe, 0)),
                     "end": mark(b, moe)},
           "trace": {"module_count": 5.0, "module_s": 0.07, "ops_s": {
               "expert_tile_ffn f32[64,2048]": 0.04, "fusion bf16[64,768]": 0.001,
               "while s32[]": 0.004}}}
    read = lambda name, c=ctx: spec.metric_reader(name)(c)
    assert read("diffusion.tokens_per_pass.serve") == pytest.approx(
        420 / 300)
    assert read("diffusion.commit_pass_share.serve") == pytest.approx(25.0)
    # 1 / (1 + passes a block): 100 commits beside 300 denoising passes
    assert read("diffusion.commit_pass_share.serve") == pytest.approx(
        100 / (1 + 300 / 100))
    fam = spec.family(config)
    need, bound = fam.expert_required_seconds(config, ctx["peaks"],
                                              8960 / 2, 8 * 7 * 128 * 5)
    assert bound == "bytes"
    assert read("moe.expert_roofline_share.serve") == pytest.approx(
        100 * need / 0.045)
    assert fam.window_counts(ctx)["experts_touched"] == 8960
    bare = dict(ctx, marks={k: {"tick": 0, "stats": {}}
                            for k in ("start", "end")})
    still = dict(ctx, marks={"start": mark(a, moe), "end": mark(a, moe)})
    for name in ("diffusion.tokens_per_pass.serve",
                 "diffusion.commit_pass_share.serve"):
        assert read(name, bare) is None and read(name, still) is None


@pytest.fixture(scope="module")
def block_toy():
    """(toy configuration, a function that serves six requests through the
    program's engine — one fault planted in the program or none — and
    returns the sample as ``run.build_sample`` writes it)."""
    import importlib.util
    import jax
    import jax.numpy as jnp
    from horovod_tpu.models import blockdiff_moe
    from horovod_tpu.serve.config import ServeConfig
    from horovod_tpu.serve.engine import ServeEngine
    from perfbench import run
    planted = importlib.util.spec_from_file_location(
        "pb_planted", os.path.join(spec.BENCH_DIR, "tools", "planted",
                                   "sitecustomize.py"))
    plant = importlib.util.module_from_spec(planted)
    planted.loader.exec_module(plant)
    config = spec.tiny(spec.cell("serve-moe-blockdiff-gen")[1])
    params = jax.jit(lambda k: weights.make(config, k, jnp.float32))(
        weights.seed_key(SEED))
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("hvd",))
    rng = np.random.default_rng(3)
    reqs = [{"tokens": rng.integers(0, config["vocab_size"], p).tolist()}
            for p in (13, 22, 7, 16, 30, 9)]
    new = (23, 18, 30, 9, 14, 21)

    def serve(fault=None):
        keep = blockdiff_moe._attend_tile, blockdiff_moe.fix_positions
        if fault:
            plant.plant(blockdiff_moe, fault)
        try:
            model, cfg = spec.family(config).program(config)
            engine = ServeEngine(model, cfg, params,
                                 ServeConfig(**config["engine"]), mesh=mesh)
            live = [engine.submit(r["tokens"], n, req_id=f"r{i}")
                    for i, (r, n) in enumerate(zip(reqs, new))]
            engine.flush()
            engine.close()
        finally:
            blockdiff_moe._attend_tile, blockdiff_moe.fix_positions = keep
        records = [{"tokens": q.out_tokens, "part_n": [len(q.out_tokens)],
                    "done": {"done": True, "tokens": q.out_tokens,
                             "steps": q.steps, "tail": q.tail}}
                   for q in live]
        return run.build_sample(range(len(reqs)), reqs, records, 64)
    return config, serve


#: The toy's limits.  The cell's own are read on the chip at the published
#: widths in bfloat16; the toy runs float32 on both sides, where the sound
#: program reads 0 in all three, the int8 control 0.07 and 1.2 in the gaps,
#: the causal mask 0.6, 2.5 and 0.04, the early unmasking 0, 0 and 0.34.
TOY_LIMITS = {"served_gap_share": 0.02, "served_gap_max": 0.3,
              "early_unmask_share": 0.05}


def _judge(config, sample, tokens_of="served", quant=None):
    from perfbench.lib import checks, serve_child
    stats, numbers = serve_child.served_check(config, SEED, sample,
                                              tokens_of, quant)
    return stats, numbers, checks.judge(numbers, TOY_LIMITS)[1]


def test_the_blockdiff_check_passes_the_sound_program(block_toy):
    """The engine's streams through the family's own ``served_stats``, as
    serve_child calls it: one gap a served token, every served token the
    reference's best in the state its pass saw (float32 on both sides),
    nothing fixed early; judged correct."""
    config, serve = block_toy
    sample = serve()
    assert reference.served_stats_for(config) is \
        spec.family(config).served_stats
    stats, numbers, ok = _judge(config, sample)
    assert len(stats["gap"]) == len(stats["flip"]) == sum(
        n for _, n in sample["spans"])
    assert not any(stats["flip"]) and max(stats["gap"]) < 1e-3
    assert numbers == {"served_gap_share": 0.0,
                       "served_gap_max": max(stats["gap"]),
                       "early_unmask_share": 0.0}
    assert ok
    # the states the records name: every pass of every block, the first
    # block's known positions never masked
    rows, at = spec.family(config).states(config, sample)
    assert rows.shape == (6, 64)
    M = spec.family(config).gen(config)["M"]
    first = next(a for a in at if a[0] == 0)
    assert first[1] == 12 and first[2][0] == sample["seqs"][0][12] != M
    assert len({(r, P) for r, P, *_ in at}) == sum(
        -(-(f + 1 + n) // 4) - (f + 1) // 4 for f, n in sample["spans"])


def test_the_blockdiff_check_fails_the_causal_mask_by_the_gaps(block_toy):
    """Planted fault A: the plain causal mask inside a block.  The served
    tokens lie far under the reference's best: not correct, by the gaps."""
    config, serve = block_toy
    stats, numbers, ok = _judge(config, serve("A"))
    assert not ok
    assert numbers["served_gap_share"] > 10 * TOY_LIMITS["served_gap_share"]
    assert numbers["served_gap_max"] > 5 * TOY_LIMITS["served_gap_max"]


def test_the_blockdiff_check_fails_early_unmasking_by_its_own_number(
        block_toy):
    """Planted fault B: every position fixed in its block's first pass, the
    steps reported honestly.  Each served token is the reference's best in
    the state it was chosen in, so the gaps pass; the family's own number
    does not."""
    config, serve = block_toy
    sample = serve("B")
    assert all(set(d["steps"]) == {0} for d in sample["done"])
    stats, numbers, ok = _judge(config, sample)
    assert numbers["served_gap_max"] < 1e-3 and not any(stats["flip"])
    assert numbers["early_unmask_share"] > 4 * TOY_LIMITS["early_unmask_share"]
    assert not ok


def test_the_blockdiff_control_is_not_correct(block_toy):
    """The reference in int8 in the program's place, at the same states:
    its tokens lie under the float32 reference's best at a good share of
    the positions."""
    config, serve = block_toy
    _, low, ok = _judge(config, serve(), "quant", "int8")
    assert not ok
    assert low["served_gap_share"] > 2 * TOY_LIMITS["served_gap_share"]
    assert low["served_gap_max"] > 2 * TOY_LIMITS["served_gap_max"]


# ----------------------------- what PR 39 had to leave out of tier 1, lifted
@pytest.mark.parametrize("family", FAMILIES)
def test_who_gives_a_configurations_served_statistics(family):
    """``served_stats_for``: ``generated_logit_stats`` itself, by identity,
    for the families that bring none of their own, the family's own for the
    two that do (a block's passes; rows cut to the sample's longest)."""
    config = _toy(family)
    fam = spec.family(config)
    if family in ("blockdiff_moe", "gdn_hybrid"):
        assert reference.served_stats_for(config) is fam.served_stats
    else:
        assert not hasattr(fam, "served_stats")
        assert reference.served_stats_for(config) is \
            reference.generated_logit_stats


def test_the_sample_is_the_golden_one():
    """``run.build_sample``: prompt + streamed tokens zero-padded, the span
    of positions whose logits predict them, and beside them the done record
    and the parts as the stream delivered them, in the sample's order."""
    from perfbench import run
    reqs = [{"tokens": [11, 12, 13]}, {"tokens": [21]},
            {"tokens": [31, 32, 33, 34, 35]}]
    done = [{"done": True, "tokens": [14, 15], "steps": [0, 1],
             "tail": [[16, 1], [17, 0]]}, None,
            {"done": True, "tokens": [36, 37, 38], "timing": {"queue": 0.1}}]
    records = [{"tokens": [14, 15], "part_n": [2], "done": done[0]},
               {"tokens": [], "part_n": [], "done": None},
               {"tokens": [36, 37, 38], "part_n": [2, 1], "done": done[2]}]
    sample = run.build_sample([2, 0], reqs, records, 7)
    assert sample == {"seqs": [[31, 32, 33, 34, 35, 36, 37],
                               [11, 12, 13, 14, 15, 0, 0]],
                      "spans": [[4, 3], [2, 2]],
                      "done": [done[2], done[0]],
                      "part_n": [[2, 1], [2]]}
    assert json.loads(json.dumps(sample)) == sample
    assert run.build_sample([], reqs, records, 7) == {
        "seqs": [], "spans": [], "done": [], "part_n": []}


@pytest.mark.parametrize("cell,metrics", [
    ("serve-moe-blockdiff-gen", ("diffusion.tokens_per_pass.serve",
                                 "diffusion.commit_pass_share.serve",
                                 "engine.tick_ms.serve")),
    ("serve-moe-conv-chat", ("kv.state_resident_share.serve",
                             "moe.experts_touched_per_routed_layer.serve",
                             "engine.tick_ms.serve")),
    ("serve-moe-mla-decode", ("moe.experts_touched.serve",
                              "moe.load_max_over_mean.serve",
                              "engine.tick_ms.serve")),
    ("serve-moe-swa-longdoc", ("kv.window_resident_share.serve",
                               "moe.experts_touched_per_layer.serve",
                               "engine.tick_ms.serve")),
    ("serve-ssm-yoco-reason", ("kv.state_resident_share.serve",
                               "kv.window_resident_share.serve",
                               "engine.tick_ms.serve")),
    ("serve-gdn-mixedlen", ("kv.state_resident_share.serve",
                            "engine.head_rows_share.serve",
                            "engine.tick_ms.serve"))])
def test_the_new_cells_rehearsal_passes(cell, metrics):
    out = subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", cell, "--seed",
         str(2**32 + 15), "--seconds", "6", "--trace", "1", "--dry-run", "1"],
        cwd=spec.ROOT, timeout=600, capture_output=True, text=True)
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    for name in metrics:
        assert name in line["metrics"], sorted(line["metrics"])
