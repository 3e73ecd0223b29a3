"""models/blockdiff_moe.py at toy width on the CPU, float32: the cached path
under the block mask against the benchmark family's plain reference
(perfbench/families/blockdiff_moe.py), logits; the mask itself
(models/paged.py ``context_mask`` with a block length); the module's
candidates, confidences and the rule of one denoising pass; and the causal
mask in the block mask's place, which fails the same comparison."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import blockdiff_moe as M
from horovod_tpu.models import paged
from perfbench.lib import reference, spec, weights

SEED = 2**31 + 40
CONFIG = spec.tiny(spec.cell("serve-moe-blockdiff-gen")[1])
FAM = spec.family(CONFIG)
BS = 4          # a pool block: one of the model's blocks
#: float32 on both sides: what is left is the order of sums (the program's
#: online softmax across tiles, its experts' sum in expert order), a few
#: 1e-6 of the logits' spread at this width; 1e-3 of it is far under what a
#: wrong mask or a position off by one moves (over 0.1 of it, below)
TOL = 1e-3


@pytest.fixture(scope="module")
def served():
    model, cfg = FAM.program(CONFIG, max_seq=64)
    params = jax.jit(lambda k: weights.make(CONFIG, k, jnp.float32))(
        weights.seed_key(SEED))
    return model, cfg, params


def _reference_logits(rows, fault=None):
    """The family's plain forward of token rows [R, T]: float32, highest
    precision, the block mask inside its layer."""
    w = reference.Weights(CONFIG, SEED)
    x = FAM.embed(w.part(FAM.EMBED), jnp.asarray(rows), CONFIG)
    for i, kind in enumerate(FAM.layer_kinds(CONFIG)):
        x = jax.jit(reference.highest(lambda p, x: FAM.layer(
            kind, p, x, CONFIG, reference.plain_mm, fault)))(w.layer(i), x)
    return jax.jit(reference.highest(lambda p, x: FAM.head(
        p, x, CONFIG, reference.plain_mm)))(w.part(FAM.HEAD), x)


def _through_the_cache(model, cfg, params, rows, chunks, C=16):
    """``rows`` [S, T] through apply_cached in ``chunks`` (each every
    slot's next n positions): float32 logits [S, T, V]."""
    S, T = rows.shape
    mb = T // BS
    cache = model.init_cache(cfg, S * mb, BS)
    tables = jnp.arange(S * mb, dtype=jnp.int32).reshape(S, mb)
    at, out = 0, []
    for n in chunks:
        tok = np.zeros((S, C), np.int32)
        tok[:, :n] = rows[:, at:at + n]
        lg, cache, _ = model.apply_cached(
            params, jnp.asarray(tok), cfg, cache, tables,
            jnp.full((S,), at, jnp.int32), jnp.full((S,), n, jnp.int32))
        out.append(np.asarray(lg)[:, :n])
        at += n
    return np.concatenate(out, axis=1)


def _rows(T=32, masked=((28, 29, 31), (29,), ())):
    """Token rows whose last block holds M where ``masked`` says (a block
    state: prefix, then a block some positions of which are not yet
    known), M as an ordinary id further up in one of them."""
    rng = np.random.default_rng(5)
    rows = rng.integers(0, CONFIG["vocab_size"] - 1, (len(masked), T))
    for r, at in enumerate(masked):
        rows[r, list(at)] = FAM.gen(CONFIG)["M"]
    rows[0, 6] = FAM.gen(CONFIG)["M"]
    return rows.astype(np.int32)


@pytest.mark.parametrize("chunks", [(32,), (16, 16), (8, 8, 8, 4, 4),
                                    (16, 12, 4)])
def test_the_cached_path_is_the_references_forward(served, chunks):
    """A prefix prefilled in chunks that end on block boundaries — one pass,
    chunks of 16, of 8 — and then a block row over it, against the
    reference's full forward of the same rows: the logits at every
    position, the block state's among them."""
    model, cfg, params = served
    rows = _rows()
    want = np.asarray(_reference_logits(rows))
    got = _through_the_cache(model, cfg, params, rows, chunks,
                             C=max(chunks))
    assert got.shape == want.shape
    assert np.abs(got - want).max() < TOL * want.std()


def test_the_causal_mask_in_the_block_masks_place_fails(served):
    """What this model does not do: under the plain causal mask a position
    sees nothing of its own block behind it, and the logits move by a good
    part of their spread — the reference's with the fault planted, and the
    program's own forward with a block of 1."""
    model, cfg, params = served
    rows = _rows()
    want = np.asarray(_reference_logits(rows))
    wrong = np.asarray(_reference_logits(rows, fault="causal"))
    assert np.abs(wrong - want).max() > 0.1 * want.std()
    ids = np.zeros((rows.shape[0], cfg.max_seq), np.int32)
    ids[:, :rows.shape[1]] = rows
    T = rows.shape[1]
    full = np.asarray(model.apply(params, jnp.asarray(ids), cfg))[:, :T]
    causal = np.asarray(model.apply(params, jnp.asarray(ids), cfg,
                                    block=1))[:, :T]
    assert np.abs(full - want).max() < TOL * want.std()
    assert np.abs(causal - wrong).max() < TOL * want.std()


def test_greedy_cached_is_candidate_and_confidence_of_the_same_logits(served):
    """On the tick's rows: the best id other than M and its probability
    among the ids other than M, float32 — never M, whatever the logits."""
    model, cfg, params = served
    rows = _rows()
    S, T = rows.shape
    cache = model.init_cache(cfg, S * T // BS, BS)
    tables = jnp.arange(S * T // BS, dtype=jnp.int32).reshape(S, -1)
    args = (params, jnp.asarray(rows), cfg, cache, tables,
            jnp.zeros((S,), jnp.int32), jnp.full((S,), T, jnp.int32))
    (cand, conf), _, counters = model.greedy_cached(*args)
    logits = np.asarray(model.apply_cached(*args)[0], np.float64)
    assert conf.dtype == jnp.float32 and cand.dtype == jnp.int32
    logits[..., cfg.mask_token_id] = -np.inf
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    assert np.array_equal(cand, logits.argmax(-1))
    assert np.abs(np.asarray(conf) - p.max(-1)).max() < 1e-5
    assert len(counters) == len(model.TICK_COUNTERS)
    # a head that prefers M above all: M is still no candidate
    z = jnp.zeros((2, cfg.vocab)).at[:, cfg.mask_token_id].set(50.0)
    z = z.at[0, 7].set(3.0)
    cand, conf = model.candidates(z, cfg)
    assert cand.tolist() == [7, 0] and float(conf[0]) > float(conf[1])
    assert float(conf[1]) == pytest.approx(1.0 / (cfg.vocab - 1), rel=1e-5)


@pytest.mark.parametrize("conf,masked,steps,fixed,sure", [
    # nothing reaches the threshold: the surest masked one alone
    ([.2, .5, .4, .1], [1, 1, 1, 1], 4, [0, 1, 0, 0], [0, 0, 0, 0]),
    # two reach it (the surest among them): both, and no third
    ([.95, .5, .91, .1], [1, 1, 1, 1], 4, [1, 0, 1, 0], [1, 0, 1, 0]),
    # a known position's confidence counts for nothing
    ([.99, .5, .4, .1], [0, 1, 1, 1], 4, [0, 1, 0, 0], [0, 0, 0, 0]),
    # equal confidences: the earlier position
    ([.3, .3, .3, .3], [0, 1, 1, 1], 4, [0, 1, 0, 0], [0, 0, 0, 0]),
    # two steps a block: the two surest at the least
    ([.2, .5, .4, .1], [1, 1, 1, 1], 2, [0, 1, 1, 0], [0, 0, 0, 0]),
    ([.2, .95, .4, .92], [1, 1, 1, 1], 2, [0, 1, 0, 1], [0, 1, 0, 1]),
    # the last masked position goes whatever its confidence
    ([.9, .9, .01, .9], [0, 0, 1, 0], 4, [0, 0, 1, 0], [0, 0, 0, 0]),
    # exactly the threshold is enough
    ([.9, .1, .1, .95], [1, 1, 1, 1], 4, [1, 0, 0, 1], [1, 0, 0, 1]),
    # nothing masked (a commit pass): nothing fixed
    ([.99, .99, .99, .99], [0, 0, 0, 0], 4, [0, 0, 0, 0], [0, 0, 0, 0])])
def test_a_denoising_pass_fixes_by_threshold_and_the_surest(conf, masked,
                                                            steps, fixed,
                                                            sure):
    cfg = dataclasses.replace(M.CONFIGS["tiny"], unmask_threshold=0.9,
                              denoising_steps=steps)
    got = M.fix_positions(jnp.asarray([conf], jnp.float32),
                          jnp.asarray([masked], bool), cfg)
    assert [g[0].astype(int).tolist() for g in got] == [fixed, sure]


def test_the_block_mask_by_a_plain_loop_and_one_is_the_old_mask():
    """``context_mask(positions, ctx, B, held)``: key j is visible to the
    query at position i iff ``j // B <= i // B`` and ``j < held``; over a
    tile that begins at a multiple of B the shifted positions keep the
    blocks; B = 1 is the causal mask, the expression the four accepted
    modules lower today."""
    positions = jnp.asarray([[0, 1, 2, 3, 4, 5], [8, 9, 10, 11, 12, 13]])
    ctx = 16
    old = (jnp.arange(ctx)[None, None, :] <= positions[:, :, None])[:, None]
    assert np.array_equal(paged.context_mask(positions, ctx), old)
    assert np.array_equal(paged.context_mask(positions, ctx, 1), old)
    lower = lambda *a: jax.jit(paged.context_mask, static_argnums=(1, 2)
                               ).lower(*a).as_text()
    assert lower(positions, ctx) == lower(positions, ctx, 1)
    held = jnp.asarray([6, 14])
    for B in (2, 4):
        got = np.asarray(paged.context_mask(positions, ctx, B, held))
        for s in range(2):
            for c in range(6):
                i = int(positions[s, c])
                want = [j // B <= i // B and j < int(held[s])
                        for j in range(ctx)]
                assert got[s, 0, c].tolist() == want, (B, s, c)
    # a tile that begins at position 8: what the whole mask says of its keys
    whole = np.asarray(paged.context_mask(positions, ctx, 4, held))
    tile = np.asarray(paged.context_mask(positions - 8, 8, 4, held - 8))
    assert np.array_equal(tile, whole[..., 8:])


def test_a_wide_tick_serves_block_rows_beside_a_chunk(served):
    """One tick at the chunk's width whose slots hold a prompt's chunk, a
    block row and nothing: packed onto the budget's rows, attended in the
    narrow first pass and the chunk-wide second, each slot's logits are the
    reference's for its own row."""
    model, cfg, params = served
    rows = _rows(T=32, masked=((), (29, 30), ()))
    want = np.asarray(_reference_logits(rows))
    S, C = 3, 16
    packed = dataclasses.replace(cfg, max_tick_tokens=24)
    cache = model.init_cache(cfg, S * 8, BS)
    tables = jnp.arange(S * 8, dtype=jnp.int32).reshape(S, 8)
    # slot 0 has prefilled 16, slot 1 28 (its block row comes now)
    for slot, n in ((0, 16), (1, 28)):
        tok = np.zeros((S, 32), np.int32)
        tok[slot, :n] = rows[slot, :n]
        n_new = np.zeros(S, np.int32)
        n_new[slot] = n
        _, cache, _ = model.apply_cached(
            params, jnp.asarray(tok), cfg, cache, tables,
            jnp.zeros((S,), jnp.int32), jnp.asarray(n_new))
    tok = np.zeros((S, C), np.int32)
    tok[0, :16], tok[1, :4] = rows[0, 16:32], rows[1, 28:32]
    got, _, _ = model.apply_cached(
        params, jnp.asarray(tok), packed, cache, tables,
        jnp.asarray([16, 28, 0], jnp.int32), jnp.asarray([16, 4, 0],
                                                         jnp.int32))
    got = np.asarray(got)
    assert np.abs(got[0, :16] - want[0, 16:]).max() < TOL * want.std()
    assert np.abs(got[1, :4] - want[1, 28:]).max() < TOL * want.std()


def test_a_config_that_the_rule_cannot_run_is_refused():
    with pytest.raises(ValueError, match="steps must divide the block"):
        dataclasses.replace(M.CONFIGS["tiny"], denoising_steps=3)
    with pytest.raises(ValueError, match="no id of a vocabulary"):
        dataclasses.replace(M.CONFIGS["tiny"], mask_token_id=256)
