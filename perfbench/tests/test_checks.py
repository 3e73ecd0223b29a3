"""The output checks at toy width on the CPU: what must pass passes, what
must fail fails.  The limits are the cells' own (traffic/<mix>.json).  The
serving checks run for every family the lookup finds: the benchmark's, and
the one that only these tests use (tests/families/)."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from perfbench.lib import checks, reference, spec, weights

ROOT = spec.ROOT
SEED = 2**31 + 17


FAMILIES = ["dense_gqa", "moe_switch"]
# what a family's configuration adds to the control's sizes below
CONTROL_SIZES = {
    "dense_gqa": dict(intermediate_size=512),
    "moe_switch": dict(family="moe_switch", moe_intermediate_size=256,
                       num_local_experts=4, num_experts_per_tok=2)}


def _toy(family):
    """(a toy configuration of the family, serve-decode's limits)"""
    _, config, traffic = spec.cell("serve-decode")
    toy = (spec.tiny(config) if family == "dense_gqa"
           else spec.family({"family": family}).TOY)
    assert spec.family(toy).__name__.endswith(family)
    return toy, traffic["check"]["limits"]


# ---------------------------------------------------------- serving checks
def _decode(config, seed, prompt, n_new, flip_at=None, swap_blocks=False):
    """Greedy decoding through the family's program module's apply_cached
    as the engine drives it:
    chunked prefill into a paged pool with a shuffled block table, another
    slot active beside it, then one-token decode calls.  ``flip_at`` takes
    the runner-up token at the step where the top two lie closest;
    ``swap_blocks`` points block-table entries at another request's blocks
    after prefill."""
    import jax
    import jax.numpy as jnp
    model, cfg = spec.family(config).program(
        dict(config, torch_dtype="float32"))
    params = jax.jit(lambda key: weights.make(config, key, jnp.float32))(
        weights.seed_key(seed))
    bs, chunk, slots, nblocks = 4, 16, 2, 64
    cache = model.init_cache(cfg, nblocks, bs)
    rng = np.random.default_rng(seed)
    order = rng.permutation(nblocks)
    need = -(-(len(prompt) + n_new) // bs)
    tables = -np.ones((slots, 32), np.int32)
    tables[0, :need] = order[:need]
    tables[1, :need] = order[need:2 * need]
    other = rng.integers(0, config["vocab_size"], len(prompt))
    # (logits, cache) and, from some modules, more behind them
    step = jax.jit(lambda *a, **k: model.apply_cached(*a, cfg=cfg, **k)[:2])
    pos, logits = 0, None
    while pos < len(prompt):
        n = min(chunk, len(prompt) - pos)
        toks = np.zeros((slots, chunk), np.int32)
        toks[0, :n], toks[1, :n] = prompt[pos:pos + n], other[pos:pos + n]
        logits, cache = step(params, jnp.asarray(toks), cache=cache,
                             block_tables=jnp.asarray(tables),
                             lengths=jnp.full((slots,), pos, jnp.int32),
                             n_new=jnp.full((slots,), n, jnp.int32))
        last, pos = n - 1, pos + n
    if swap_blocks:     # the first prompt blocks now name the other slot's
        tables[0, :4] = tables[1, :4]
    out, margins = [], []
    z = np.asarray(logits[0, last], np.float64)
    for i in range(n_new):
        top = np.argsort(z)[::-1]
        margins.append(z[top[0]] - z[top[1]])
        out.append(int(top[1] if flip_at == i else top[0]))
        toks = np.zeros((slots, chunk), np.int32)
        toks[:, 0] = out[-1]
        logits, cache = step(params, jnp.asarray(toks), cache=cache,
                             block_tables=jnp.asarray(tables),
                             lengths=jnp.full((slots,), len(prompt) + i,
                                              jnp.int32),
                             n_new=jnp.ones((slots,), jnp.int32))
        z = np.asarray(logits[0, 0], np.float64)
    return out, margins


def _served_numbers(config, seed, prompt, served, T=96):
    seq = (list(prompt) + list(served) + [0] * T)[:T]
    stats = reference.generated_logit_stats(
        config, seed, [seq], [(len(prompt) - 1, len(served))], "served")
    return checks.serve_numbers(stats)


@pytest.fixture(scope="module", params=FAMILIES)
def serve_case(request):
    config, limits = _toy(request.param)
    prompt = np.random.default_rng(5).integers(
        0, config["vocab_size"], 37).tolist()
    sound, margins = _decode(config, SEED, prompt, 24)
    return config, limits, prompt, sound, margins


def _verdict(numbers, limits):
    return checks.judge(numbers, {k: limits[k] for k in numbers})[1]


def test_served_path_agrees_with_the_reference(serve_case):
    config, limits, prompt, sound, _ = serve_case
    assert _verdict(_served_numbers(config, SEED, prompt, sound), limits)


def test_an_argmax_flip_between_near_ties_passes(serve_case):
    config, limits, prompt, _, margins = serve_case
    flipped, _ = _decode(config, SEED, prompt, 24,
                         flip_at=int(np.argmin(margins)))
    numbers = _served_numbers(config, SEED, prompt, flipped)
    assert numbers["served_gap_max"] > 0
    assert _verdict(numbers, limits)


def test_a_swapped_block_table_entry_fails(serve_case):
    config, limits, prompt, sound, _ = serve_case
    wrong, _ = _decode(config, SEED, prompt, 24, swap_blocks=True)
    assert wrong != sound
    assert not _verdict(_served_numbers(config, SEED, prompt, wrong), limits)


@pytest.mark.parametrize("family", FAMILIES)
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_serving_control_int8_comes_out_not_correct(seed, family):
    """The control at a size a test run can hold (hidden 128, 16 layers,
    vocabulary 8192): the reference in int8 put in the program's place.
    The token it puts first lies more than 0.05 standard deviations below the
    float32 reference's best far more often than the cell's limit allows."""
    _, limits = _toy(family)
    config = dict(hidden_size=128, num_hidden_layers=16,
                  num_attention_heads=4, num_key_value_heads=2,
                  vocab_size=8192, rms_norm_eps=1e-5, rope_theta=1e6,
                  assumed={"rms_norm_eps": 1e-6}, **CONTROL_SIZES[family])
    seqs = np.random.default_rng(seed).integers(0, 8192, (4, 96)).tolist()
    low = checks.serve_numbers(reference.generated_logit_stats(
        config, seed, seqs, [(31, 64)] * 4, "quant", quant="int8"))
    assert low["served_gap_share"] > limits["served_gap_share"]
    assert not _verdict(low, limits)


# --------------------------------------------------------- training checks
def test_worst_leaf_gap_uses_the_median_floor():
    ref = {"a": 1.0, "b": 1.0, "tiny": 1e-6}
    prog = {"a": 1.01, "b": 1.0, "tiny": 3e-6}
    gap, leaf = checks.worst_leaf_gap(prog, ref)
    assert leaf == "a" and gap == pytest.approx(0.01)


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_training_control_int8_comes_out_not_correct(seed):
    """The control at a size a test run can hold (hidden 128, 4 layers,
    batch 4 x 64): the reference in int8 put in the program's place fails
    the cell's limit on the first moment's norm; the reference against
    itself passes every limit."""
    _, _, traffic = spec.cell("train-dp1")
    config = dict(hidden_size=128, intermediate_size=512, num_hidden_layers=4,
                  num_attention_heads=4, num_key_value_heads=2,
                  vocab_size=2048, rms_norm_eps=1e-5, rope_theta=1e6,
                  torch_dtype="float32", assumed={"rms_norm_eps": 1e-6})
    rng = np.random.default_rng(seed)
    batches = [rng.integers(0, 2048, (4, 65), dtype=np.int32)
               for _ in range(3)]
    opt = traffic["optimizer"]
    ref = reference.train_steps(config, seed, batches, opt)
    low = reference.train_steps(config, seed, batches, opt, quant="int8")
    for r in (ref, low):
        r["mnorm"] = r["mnorm"][0]
    numbers, _ = checks.train_numbers(low, ref)
    again, _ = checks.train_numbers(ref, ref)
    assert _verdict(again, traffic["check"])
    assert numbers["first_moment_norm_gap"] > \
        traffic["check"]["first_moment_norm_gap"]
    assert not _verdict(numbers, traffic["check"])


# ------------------------------------- a whole run with the path broken
def _run(cell, *extra):
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "perfbench", "run.py"),
         "--workload", cell, "--seed", str(SEED), "--seconds", "3",
         "--trace", "0", "--dry-run", "1", *extra],
        capture_output=True, text=True, timeout=900, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("cell,broken", [
    ("train-dp1", "frozen-step"),      # a step that returns its state unchanged
    ("serve-decode", "wrong-token"),   # a token altered where it is produced
])
def test_a_broken_timed_path_comes_out_not_correct(cell, broken):
    line = _run(cell, "--break", broken)
    assert line["correct"] is False
    assert line["device"]["platform"] == "cpu"


def test_a_dry_run_reports_no_number():
    line = _run("train-dp1")
    assert line["correct"] is True
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "compared"]
    _, _, traffic = spec.cell("train-dp1")
    # each number compared beside its limit, the line's last key
    assert {k: c["limit"] for k, c in line["compared"].items()} == {
        k: traffic["check"][k] for k in line["compared"]}
    assert len(line["compared"]) == 5
    assert all(m["value"] is None for m in line["metrics"].values())
