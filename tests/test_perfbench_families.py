"""The seam between the benchmark's harness and a model family
(perfbench/families/__init__.py), in tier 1, for the three families there
are: the dense GQA decoder, the latent-attention expert decoder, and the
switch family that only the benchmark's tests use.  At toy width on the CPU:
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

FAMILIES = ["dense_gqa", "moe_switch", "latent_moe"]
ROUTED_BY_TOKENS = {"latent_moe"}
SEED = 2**31 + 27


def _toy(family):
    if family == "moe_switch":
        return spec.family({"family": family}).TOY
    cell = {"dense_gqa": "serve-decode",
            "latent_moe": "serve-moe-mla-decode"}[family]
    return spec.tiny(spec.cell(cell)[1])


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
    table = np.arange(T // size, dtype=np.int32)[None]
    got = model.apply_cached(
        params, jnp.asarray(row, jnp.int32), cfg,
        model.init_cache(cfg, T // size, size), jnp.asarray(table),
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
    assert 0 < n["matmul"] <= n["total"] - n["embed"] - vectors
    model, cfg = fam.program(config)
    blocks, size = 6, 4
    pool = model.init_cache(cfg, blocks, size, dtype=jnp.bfloat16)
    held = sum(x.size * x.dtype.itemsize for x in pool.values())
    assert fam.cache_bytes_per_position(config, 2) * blocks * size == held
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
        assert read[3] <= 2 * (n["total"] - n["embed"] - vectors)
        assert read[3] == pytest.approx(
            2 * (n["total"] - n["embed"] - vectors), rel=1e-3)
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


def test_the_new_cells_rehearsal_passes():
    out = subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload",
         "serve-moe-mla-decode", "--seed", str(2**32 + 15), "--seconds", "6",
         "--trace", "1", "--dry-run", "1"], cwd=spec.ROOT, timeout=600,
        capture_output=True, text=True)
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    for name in ("moe.experts_touched.serve", "moe.load_max_over_mean.serve",
                 "engine.tick_ms.serve"):
        assert name in line["metrics"], sorted(line["metrics"])
