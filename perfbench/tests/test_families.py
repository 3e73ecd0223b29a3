"""The seam between the harness and a model family, at toy width on the CPU,
for every family the lookup finds (the benchmark's and the one that only the
tests use): the weights' one program against ``leaf`` one by one, the counts
against the leaves and the program's cache, the program module against the
family's reference."""

import math
import subprocess
import sys

import numpy as np
import pytest

from perfbench.lib import reference, spec, weights

FAMILIES = ["dense_gqa", "moe_switch"]
SEED = 2**31 + 29


def _toy(family):
    if family == "dense_gqa":
        return spec.tiny(spec.cell("serve-decode")[1])
    return spec.family({"family": family}).TOY


def _bits(x):
    return np.asarray(x, np.float32)


# ------------------------------------------------------------------ the lookup
def test_a_file_without_the_key_is_the_dense_decoder_and_a_name_finds_its_file():
    for _, config, _ in map(spec.cell, ("train-dp1", "serve-decode")):
        assert "family" not in config
        assert spec.family(config).__name__ == "perfbench_family_dense_gqa"
    moe = spec.family({"family": "moe_switch"})
    assert moe.__file__.endswith("tests/families/moe_switch.py")
    assert spec.family({"family": "moe_switch"}) is moe
    with pytest.raises(SystemExit):
        spec.family({"family": "no_such_family"})


def test_the_parent_process_loads_a_family_without_jax():
    code = ("import sys; from perfbench.lib import peaks, spec\n"
            "_, c, _ = spec.cell('serve-decode'); f = spec.family(c)\n"
            "spec.tiny(c); f.param_counts(c)\n"
            "peaks.serve_required_seconds(c, peaks.PEAKS['TPU v5 lite'], 9, 9, 1)\n"
            "assert 'jax' not in sys.modules and 'numpy' not in sys.modules")
    subprocess.run([sys.executable, "-c", code], check=True, cwd=spec.ROOT,
                   timeout=60)


# ----------------------------------------------------------------- the weights
@pytest.mark.parametrize("family", FAMILIES)
def test_make_gives_every_leaf_the_bits_of_leaf_one_by_one(family):
    import jax
    import jax.numpy as jnp
    config = _toy(family)
    specs = spec.family(config).leaf_specs(config)
    key = weights.seed_key(SEED)
    for dtype in (jnp.float32, jnp.bfloat16):
        made = weights.flat(jax.jit(
            lambda k: weights.make(config, k, dtype))(key))
        assert sorted(made) == sorted(n for n, _, _ in specs)
        for i, (name, shape, std) in enumerate(specs):
            one = weights.leaf_jit(key, i, tuple(shape), std, dtype)
            assert made[name].dtype == dtype and made[name].shape == shape
            assert np.array_equal(_bits(made[name]), _bits(one)), name
    # what the reference regenerates by name is the same leaf again
    w = reference.Weights(dict(config, torch_dtype="bfloat16"), SEED)
    assert np.array_equal(_bits(w(specs[4][0])), _bits(made[specs[4][0]]))


def test_draw_on_a_hand_made_list_with_two_layer_kinds_and_a_module_outside():
    """A leading dense layer, three expert layers and a module outside the
    stack: every leaf has the bits of ``leaf`` at its index, and the tree is
    the one the names spell."""
    import jax
    import jax.numpy as jnp
    d, f, E, h = 16, 24, 3, 8
    specs = [("embed.table", (32, d), 0.02), ("final_norm.scale", (d,), None),
             ("layers.0.attn.wq", (d, d), 0.25), ("layers.0.norm.scale", (d,), None),
             ("layers.0.ffn.w_up", (d, f), 0.25)]
    for i in (1, 2, 3):
        specs += [(f"layers.{i}.attn.wq", (d, d), 0.25),
                  (f"layers.{i}.norm.scale", (d,), None),
                  (f"layers.{i}.moe.router", (d, E), 0.25),
                  (f"layers.{i}.moe.wi", (E, d, h), 0.25)]
    specs += [("mtp.proj.kernel", (2 * d, d), 0.1),
              ("mtp.layer.moe.wi", (E, d, h), 0.25)]
    key = weights.seed_key(7)
    drawn = jax.jit(lambda k: weights.draw(specs, k, jnp.bfloat16))(key)
    assert sorted(drawn) == sorted(n for n, _, _ in specs)
    for i, (name, shape, std) in enumerate(specs):
        assert np.array_equal(_bits(drawn[name]), _bits(
            weights.leaf(key, i, shape, std, jnp.bfloat16))), name
    tree = weights.tree(drawn)
    assert isinstance(tree["layers"], list) and len(tree["layers"]) == 4
    assert set(tree["layers"][0]) == {"attn", "norm", "ffn"}
    assert set(tree["layers"][2]) == {"attn", "norm", "moe"}
    assert tree["mtp"]["layer"]["moe"]["wi"].shape == (E, d, h)
    assert weights.flat(tree).keys() == drawn.keys()
    # leaves of all four layers that share a signature come from one call
    text = jax.make_jaxpr(lambda k: weights.draw(specs, k, jnp.bfloat16))(key)
    assert str(text).count("random_bits") < len(specs) - 4


# --------------------------------------------------------------- the yardstick
@pytest.mark.parametrize("family", FAMILIES)
def test_the_counts_are_the_leaves_and_the_programs_cache(family):
    import jax.numpy as jnp
    config = _toy(family)
    fam = spec.family(config)
    specs = fam.leaf_specs(config)
    n = fam.param_counts(config)
    assert n["total"] == sum(math.prod(s) for _, s, _ in specs)
    assert n["embed"] == math.prod(dict((k, s) for k, s, _ in specs)[fam.EMBED[0]])
    vectors = sum(math.prod(s) for _, s, _ in specs if len(s) == 1)
    assert 0 < n["matmul"] <= n["total"] - n["embed"] - vectors
    assert fam.tick_weight_bytes(config, 1, 2) <= 2 * n["matmul"]
    assert fam.tick_weight_bytes(config, 64, 2) <= 2 * (
        n["total"] - n["embed"] - vectors)
    model, cfg = fam.program(config)
    blocks, size = 6, 4
    pool = model.init_cache(cfg, blocks, size, dtype=jnp.bfloat16)
    held = sum(x.size * x.dtype.itemsize for x in pool.values())
    assert fam.cache_bytes_per_position(config, 2) * blocks * size == held
    assert fam.train_flops_per_token(config, 64) > 6.0 * n["matmul"]
    assert fam.attn_flops_per_position(config) > 0


# --------------------------------------------------- program against reference
@pytest.mark.parametrize("family", FAMILIES)
def test_a_teacher_forced_row_through_the_program_agrees_with_the_reference(
        family):
    """One row, prefilled in one chunk through the family's program module
    as the engine would, against the family's plain equations."""
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
