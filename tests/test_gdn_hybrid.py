"""Gated delta-rule layers beside full attention
(horovod_tpu/models/gdn_hybrid.py over models/paged.py's cache kinds, of
which ``delta`` keeps ONE matrix state a slot and a ring of rows to replay;
docs/serving.md#replay-kind): the full path against the benchmark's plain
reference (perfbench/families/gdn_hybrid.py), the chunked form against the
recurrence as a loop, the cached path against the full one over chunk
boundaries, packed rows, rejected drafts and reused slots, on logits; and
the planted faults that the reference must fail."""

import dataclasses
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import gdn_hybrid as M, paged
from horovod_tpu.serve.engine import load_servable, save_servable

from perfbench.lib import reference, spec, weights

SEED = 2**31 + 48
CELL = "serve-gdn-mixedlen"
#: float32 on the CPU, program against reference or against itself: the two
#: differ by the order of float32 sums (a chunk's triangular solve against a
#: loop over rows, the softmax over a tile of context, the taps), 1e-5 of a
#: logit's spread at most positions here — and up to 3e-3 at the rare one
#: where a head's output ``S q`` nearly cancels (rms 1e-3 of values of 1),
#: which the norm over that head's output then blows up: the float32
#: reference lies as far from the same equations in float64 (measured, PR
#: 48).  The gap is a maximum over positions, so it reads those.  5e-3 is
#: thirty-six times below what a bfloat16 state in place of float32 changes
#: (0.18) and a thousand below any planted fault (3 to 5: the same positions
#: turn a wrong term into another head output altogether)
TOL = 5e-3
#: a slot's columns and rows at the engine's default verify row of 5
CONV_COLS = paged.state_columns(3, 5)
RING_ROWS = paged.replay_rows(5)


@pytest.fixture(scope="module")
def toy():
    """The benchmark's toy copy of the configuration (three linear layers,
    a full one, twice; chunks of 8): (config, module, config object,
    weights)."""
    config = spec.tiny(spec.cell(CELL)[1])
    model, cfg = spec.family(config).program(config)
    params = jax.jit(lambda k: weights.make(config, k, jnp.float32))(
        weights.seed_key(SEED))
    return config, model, cfg, params


def _ref_logits(config, ids, params=None, fam=None):
    """The family's plain equations on token rows ``ids`` [B, T], over the
    seeded leaves or over those of the program's ``params``."""
    fam = fam or spec.family(config)
    if params is None:
        w = reference.Weights(config, SEED)
        part, layer = w.part, w.layer
    else:
        flat = weights.flat(params)
        part = lambda names: {n: flat[n] for n in names}
        layer = lambda i: {n[len(f"layers.{i}."):]: x for n, x in flat.items()
                           if n.startswith(f"layers.{i}.")}
    with jax.default_matmul_precision("highest"):
        x = fam.embed(part(fam.EMBED), jnp.asarray(ids), config)
        for i, kind in enumerate(fam.layer_kinds(config)):
            x = fam.layer(kind, layer(i), x, config, reference.plain_mm)
        return fam.head(part(fam.HEAD), x, config, reference.plain_mm)


def _gap(a, b):
    """Largest difference over the spread of ``b``'s values."""
    return float(jnp.max(jnp.abs(a - b))) / float(jnp.std(b))


_full = jax.jit(M.apply, static_argnums=2)


def _pools(cfg, S, block_size=4, max_seq=96, rows=RING_ROWS,
           conv_cols=CONV_COLS):
    """(cache, tables) of S slots that own their blocks in order."""
    mb = -(-max_seq // block_size)
    cache = M.init_cache(cfg, {M.KV: S * mb, M.CONV: (S, conv_cols),
                               M.DELTA: (S, rows)}, block_size)
    return cache, {M.KV: jnp.arange(S * mb, dtype=jnp.int32).reshape(S, mb)}


# ONE jitted tick for the whole file: tests that feed it the same shapes
# share a compilation (the config is hashable: a static argument)
_TICK = jax.jit(lambda params, cfg, tables, c, t, l, n: M.apply_cached(
    params, t, cfg, c, tables, l, n)[:2], static_argnums=1)


def _stepper(params, cfg, tables):
    return lambda c, t, l, n: _TICK(params, cfg, tables, c, t, l, n)


def _run(step, cache, ids, plan, C, vocab, done=None):
    """Run the ticks of ``plan`` — a slot's entry ``n`` (all its rows
    stand) or ``(n, accepted)`` (a verify row of which the first and
    ``accepted`` drafts stand) — over token rows ``ids`` [S, T]: the logits
    of every position that stood, [S, T, vocab], and how far each slot
    came."""
    S, T = ids.shape
    done = np.zeros(S, np.int32) if done is None else done
    got = np.zeros((S, T, vocab), np.float32)
    for tick in plan:
        fed = np.asarray([t[0] if isinstance(t, tuple) else t for t in tick],
                         np.int32)
        kept = np.asarray([1 + t[1] if isinstance(t, tuple) else t
                           for t in tick], np.int32)
        tok = np.zeros((S, C), np.int32)
        for s in range(S):
            # a rejected draft is another token than the row's own
            row = ids[s, done[s]:done[s] + fed[s]].copy()
            row[kept[s]:] = (row[kept[s]:] + 1) % vocab
            tok[s, :fed[s]] = row
        logits, cache = step(cache, jnp.asarray(tok),
                             jnp.asarray(np.where(fed > 0, done, 0)),
                             jnp.asarray(fed))
        for s in range(S):
            got[s, done[s]:done[s] + kept[s]] = np.asarray(
                logits[s, :kept[s]])
        done = done + kept
    return jnp.asarray(got), cache, done


# --------------------------------------------------- 1. apply vs reference
def test_apply_is_the_references_forward_pass(toy):
    config, model, cfg, params = toy
    assert model is M and cfg.n_layers == 8 and cfg.chunk == 8
    assert [cfg.kind(i) for i in range(8)] == [M.LINEAR] * 3 + [M.FULL] \
        + [M.LINEAR] * 3 + [M.FULL]
    kinds = M.cache_kinds(cfg)
    assert [(k.name, k.layers, k.window, k.state, bool(k.replay))
            for k in kinds] == [(M.KV, 2, None, None, False),
                                (M.CONV, 6, None, 3, False),
                                (M.DELTA, 6, None, 1, True)]
    # a row's k, v, g and beta side by side
    assert kinds[2].dtype == jnp.float32 and kinds[2].replay == {
        "row": (4 * (8 + 16 + 2),)}
    # the published stack's first stage: twelve matrix states, four pools
    stage = M.GdnHybridConfig(n_layers=16)
    assert [stage.count(k) for k in (M.LINEAR, M.FULL)] == [12, 4]
    ids = np.random.default_rng(1).integers(0, cfg.vocab, (2, 70))
    assert _gap(_full(params, jnp.asarray(ids), cfg),
                _ref_logits(config, ids)) < TOL


# ----------------------------------- 2. the chunked form against the loop
def _rows(T, H=4, dk=8, dv=16, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)
    return (unit(jax.random.normal(k[0], (T, H, dk))) * dk ** -0.5,
            unit(jax.random.normal(k[1], (T, H, dk))),
            jax.random.normal(k[2], (T, H, dv)),
            -8.0 * jax.random.uniform(k[3], (T, H)) ** 4,
            2.0 * jax.random.uniform(k[4], (T, H)),
            jax.random.normal(k[5], (H, dv, dk)))


def _loop(q, k, v, g, beta, S0):
    """The family's plain loop over positions (perfbench/families/
    gdn_hybrid.py ``delta_loop``, the reference's own) on ONE row of
    positions from the state S0: (o [T, H, dv], the state after the last)."""
    o, S = spec.family({"family": "gdn_hybrid"}).delta_loop(
        *(a[None] for a in (q, k, v, g, beta)), S0=S0[None], last=True)
    return o[0], S[0]


@pytest.mark.parametrize("T", [1, 5, 63, 64, 65, 200])
def test_the_chunked_form_is_the_loop_over_rows(T):
    """Chunks of 64 with the state passed between them give the recurrence's
    outputs and its last state, at widths on both sides of a chunk and at
    one that is no multiple of it; the rows that pad the last chunk (beta 0,
    g 0, k 0) change nothing."""
    q, k, v, g, beta, S0 = _rows(T, seed=T)
    want_o, want_S = _loop(q, k, v, g, beta, S0)
    pad = lambda a: jnp.pad(a, ((0, -T % 64),) + ((0, 0),) * (a.ndim - 1)
                            ).reshape((-1, 64) + a.shape[1:])
    chunks = M.delta_chunks(*map(pad, (q, k, v, g, beta)))
    S, got = S0, []
    for c in range(-(-T // 64)):
        o, S = M.delta_apply(jax.tree_util.tree_map(lambda a: a[c], chunks),
                             S)
        got.append(jnp.moveaxis(o, 0, 1))
    got = jnp.concatenate(got)[:T]
    assert float(jnp.max(jnp.abs(got - want_o))) < 2e-5
    assert float(jnp.max(jnp.abs(S - want_S))) < 2e-5


@pytest.mark.parametrize("T", [1, 9, 16, 64])
def test_the_inverse_is_forward_substitution_whatever_the_keys(T):
    """``unit_lower_inverse``: ``(I + A) X = I`` for A as the chunked form
    makes it — ``beta (k_t . k_i)`` under a decay — with keys as alike as
    they come (one direction and a little noise, beta 2: entries near 2,
    where a sum of powers of A would cancel digits away), by rows inside
    blocks of 16 and by products between them."""
    k = jax.random.split(jax.random.PRNGKey(T), 3)
    keys = 1.0 + 0.05 * jax.random.normal(k[0], (3, T, 8))
    keys = keys / jnp.linalg.norm(keys, axis=-1, keepdims=True)
    G = jnp.cumsum(-0.05 * jax.random.uniform(k[1], (3, T)), axis=-1)
    decay = jnp.exp(jnp.minimum(G[:, :, None] - G[:, None, :], 0.0))
    A = jnp.tril(2.0 * decay * jnp.einsum("btd,bid->bti", keys, keys), -1)
    X = M.unit_lower_inverse(A)
    with jax.default_matmul_precision("highest"):
        back = (jnp.eye(T) + A) @ X
    # (LAPACK's own solve lies as far from it at 64 rows: 3e-5)
    assert float(jnp.max(jnp.abs(back - jnp.eye(T)))) < 1e-4
    assert bool((jnp.triu(X, 1) == 0).all())
    with pytest.raises(ValueError, match="power of two"):
        M.unit_lower_inverse(jnp.zeros((48, 48)))


def test_a_chunk_gives_the_state_after_any_of_its_rows():
    """``delta_apply(.., r)``: the state after row r of the chunk, every
    slot its own r — what a verify row commits (paged.commit_row)."""
    rows = [_rows(9, seed=s) for s in range(3)]
    stack = lambda i: jnp.stack([r[i] for r in rows])
    chunks = M.delta_chunks(*(stack(i) for i in range(5)))
    r = jnp.asarray([0, 4, 8])
    _, got = M.delta_apply(chunks, stack(5), r)
    for s, (q, k, v, g, beta, S0) in enumerate(rows):
        n = int(r[s]) + 1
        _, want = _loop(q[:n], k[:n], v[:n], g[:n], beta[:n], S0)
        assert float(jnp.max(jnp.abs(got[s] - want))) < 2e-5


def test_a_strong_decay_neither_overflows_nor_leaks():
    """g of -30 a row: exp(G_t - G_i) above the diagonal would overflow and
    is never made; below it underflows to an honest zero."""
    q, k, v, g, beta, S0 = _rows(64, seed=3)
    g = jnp.full_like(g, -30.0)
    o, S = M.delta_apply(M.delta_chunks(q, k, v, g, beta), S0)
    want_o, want_S = _loop(q, k, v, g, beta, S0)
    assert bool(jnp.isfinite(o).all()) and bool(jnp.isfinite(S).all())
    assert float(jnp.max(jnp.abs(jnp.moveaxis(o, 0, 1) - want_o))) < 2e-5
    assert float(jnp.max(jnp.abs(S - want_S))) < 2e-5


# --------------------------- 3. the cached path vs apply: chunks, then rows
@pytest.mark.parametrize("chunk", [1, 5, 8, 16, 40])
def test_chunks_then_decode_through_the_kinds_match_apply(toy, chunk):
    """A prompt prefilled in chunks of any size, then decoded a token a
    tick, gives what ``apply`` gives on the whole sequence: every chunk
    boundary carries three conv inputs and one matrix state a layer across;
    a chunk of at most 5 commits after its first row and leaves the rest to
    the ring, which the next tick replays; one of 16 or 40 is cut into the
    recurrence's chunks of 8 and commits after its last row."""
    config, model, cfg, params = toy
    T, P = 52, 40
    ids = np.random.default_rng(2).integers(0, cfg.vocab, (1, T))
    want = _full(params, jnp.asarray(ids), cfg)
    cache, tables = _pools(cfg, 1)
    plan = [[min(chunk, P - at)] for at in range(0, P, chunk)]
    got, cache, done = _run(_stepper(params, cfg, tables), cache, ids, plan,
                            chunk, cfg.vocab)
    got2, _, done = _run(_stepper(params, cfg, tables), cache, ids,
                         [[1]] * (T - P), 1, cfg.vocab, done)
    assert int(done[0]) == T
    assert _gap((got + got2)[0], want[0]) < TOL


@pytest.mark.parametrize("budget", [0, 44])
def test_packed_rows_of_three_slots_never_mix(toy, budget):
    """Three slots at different lengths in one tick — a prompt's chunk, a
    tail that fits the ring, decode rows — each from its own state, with and
    without the rows packed to a budget; a slot that runs no row keeps its
    state."""
    config, model, cfg, params = toy
    cfg = dataclasses.replace(cfg, max_tick_tokens=budget)
    T = 60
    ids = np.random.default_rng(3).integers(0, cfg.vocab, (3, T))
    want = _full(params, jnp.asarray(ids), cfg)
    cache, tables = _pools(cfg, 3)
    plan = [[16, 5, 0], [11, 16, 3], [1, 16, 0], [16, 1, 16], [1, 5, 13],
            [0, 16, 1], [15, 1, 16], [0, 0, 11]]
    got, _, done = _run(_stepper(params, cfg, tables), cache, ids, plan, 16,
                        cfg.vocab)
    assert done.tolist() == [60, 60, 60]
    assert _gap(got, want) < TOL


def test_the_wide_pass_goes_a_group_of_chunks_at_a_time(toy, monkeypatch):
    """With groups of two chunks, three slots' six chunks are three trips
    of the outer loop, the last chunk of one group and the first of the next
    one slot's: the state crosses the groups as it crosses chunks."""
    config, model, cfg, params = toy
    monkeypatch.setattr(M, "GROUP", 2)
    ids = np.random.default_rng(10).integers(0, cfg.vocab, (3, 32))
    want = _full(params, jnp.asarray(ids), cfg)
    cache, tables = _pools(cfg, 3)
    step = jax.jit(lambda c, t, l, n: M.apply_cached(
        params, t, cfg, c, tables, l, n)[:2])
    got, _, done = _run(step, cache, ids, [[16, 13, 16], [16, 3, 9]], 16,
                        cfg.vocab)
    for s in range(3):
        assert _gap(got[s, :done[s]], want[s, :done[s]]) < TOL


@pytest.mark.parametrize("accepted", [0, 1, 2, 4])
def test_a_verify_row_with_drafts_rejected(toy, accepted):
    """A verify row of five of which ``accepted`` drafts stand: the state is
    committed after the row's first column, the next tick replays exactly
    the accepted rows from the ring and none of the rejected ones, whose
    tokens were others; again and again, in narrow ticks and beside a
    prompt's chunk."""
    config, model, cfg, params = toy
    T = 64
    ids = np.random.default_rng(4).integers(0, cfg.vocab, (2, T))
    want = _full(params, jnp.asarray(ids), cfg)
    cache, tables = _pools(cfg, 2)
    step = _stepper(params, cfg, tables)
    got, cache, done = _run(step, cache, ids, [[16, 9]], 16, cfg.vocab)
    narrow = [[(5, accepted), (5, 4 - accepted)]] * 3
    got2, cache, done = _run(step, cache, ids, narrow, 5, cfg.vocab, done)
    got3, cache, done = _run(step, cache, ids, [[(5, accepted), 16]], 16,
                             cfg.vocab, done)
    got4, cache, done = _run(step, cache, ids, [[(3, 1), (2, 0)]], 5,
                             cfg.vocab, done)
    total = got + got2 + got3 + got4
    for s in range(2):
        assert _gap(total[s, :done[s]], want[s, :done[s]]) < TOL
    # the state stands after the last row's first column; ONE a slot a layer
    delta = cache[M.DELTA]
    assert delta["S"].shape[:3] == (6, 2, 1)
    assert delta[paged.AT][:, :, 0, 0].tolist() == [
        [int(done[0]) - 1, int(done[1])]] * 6


def test_a_slots_new_tenant_starts_from_nothing(toy):
    """A slot's next tenant reads neither the state, nor the ring, nor the
    conv inputs of the stream that left it: what it serves is what a fresh
    cache serves."""
    config, model, cfg, params = toy
    rng = np.random.default_rng(5)
    first = rng.integers(0, cfg.vocab, (1, 30))
    second = rng.integers(0, cfg.vocab, (1, 30))
    cache, tables = _pools(cfg, 1)
    step = _stepper(params, cfg, tables)
    _, cache, _ = _run(step, cache, first, [[16], [9], [(5, 2)]], 16,
                       cfg.vocab)
    plan = [[3], [16], [(5, 1)], [(5, 3)]]
    got, _, done = _run(step, cache, second, plan, 16, cfg.vocab)
    fresh, _, _ = _run(step, _pools(cfg, 1)[0], second, plan, 16, cfg.vocab)
    assert np.array_equal(np.asarray(got), np.asarray(fresh))
    assert _gap(got[0, :done[0]],
                _full(params, jnp.asarray(second), cfg)[0, :done[0]]) < TOL


def test_the_head_runs_on_the_rows_the_tick_reads(toy):
    """``greedy_cached(.., read)`` is the argmax of ``apply_cached``'s
    logits at those columns, and counts the rows run and replayed."""
    config, model, cfg, params = toy
    ids = np.random.default_rng(6).integers(0, cfg.vocab, (2, 16))
    cache, tables = _pools(cfg, 2)
    args = (jnp.asarray(ids), cfg, cache, tables, jnp.zeros(2, jnp.int32),
            jnp.asarray([16, 7]))
    logits, _, counted = jax.jit(M.apply_cached, static_argnums=2)(
        params, *args)
    read = jnp.asarray([[15, 15], [5, 6]])
    tokens, _, counted2 = jax.jit(M.greedy_cached, static_argnums=2)(
        params, *args, read)
    assert tokens.tolist() == [
        [int(jnp.argmax(logits[s, c])) for c in read[s]] for s in range(2)]
    # a first ``ticks``, then over the six linear layers: 23 rows, none
    # replayed in a slot's first tick
    assert counted.tolist() == counted2.tolist() == [1, 6 * 23, 0]
    assert M.TICK_COUNTERS == ("ticks", "gdn_rows", "gdn_replayed_rows")


# ------------------------------------- 3b. what the tick's text is free of
def _dims(t):
    return tuple(int(d) for d in t.split("x")[:-1])


def _tile_reshapes(text, tile, cfg):
    """The reshapes in a lowered text that rewrite something of a gathered
    tile's size, a position's heads side by side ``[.., tile, .., dim]``,
    into heads ``[.., n_heads, head_dim]``."""
    found = []
    for m in re.finditer(r"stablehlo\.reshape [^\n]*: \(tensor<([\dx]+\w+)>\)"
                         r" -> tensor<([\dx]+\w+)>", text):
        src, dst = _dims(m.group(1)), _dims(m.group(2))
        if (src[-1:] == (cfg.dim,) and tile in src[:-1]
                and dst[-2:] == (cfg.n_heads, cfg.head_dim)):
            found.append((src, dst))
    return found


def _pool_scatters(text, pool):
    """(operand axes its indices address) of every scatter in a lowered text
    into an array shaped like ``pool``."""
    return [tuple(int(d) for d in m.group(1).split(",") if d.strip())
            for m in re.finditer(
                r'"stablehlo\.scatter".*?scatter_dims_to_operand_dims = '
                r"\[([\d, ]*)\].*?\}\) : \(tensor<([\dx]+\w+)>", text, re.S)
            if _dims(m.group(2)) == tuple(pool)]


def test_the_narrow_tick_relays_no_tile_and_scatters_across_no_heads(toy):
    """What the CPU can count of the pool by head (PERF.md section 6, PR
    50): the lowered narrow tick rewrites no gathered tile from ``[..,
    dim]`` into ``[.., n_heads, head_dim]`` — the tile is scored as the
    gather left it —, and every scatter into the paged pool addresses whole
    blocks, its window never spanning the head axis round a scattered
    position (the write that made the compiler relay the pool whole, PR
    48).  Both counts find the forms they exclude when they are planted."""
    config, model, cfg, params = toy
    S, C, bs = 4, 5, 4
    cache, tables = _pools(cfg, S, block_size=bs)
    shape = cache[M.KV]["k"].shape
    assert shape[2:] == (cfg.n_heads, bs, cfg.head_dim)
    tile = paged.tile_blocks(bs, tables[M.KV].shape[1]) * bs
    text = _TICK.lower(
        params, cfg, tables, cache, jnp.zeros((S, C), jnp.int32),
        jnp.asarray([9, 0, 30, 17]), jnp.asarray([5, 1, 0, 2])).as_text()
    assert _tile_reshapes(text, tile, cfg) == []
    into = _pool_scatters(text, shape)
    # a key and a value leaf a full layer, each by (layer, block) alone
    assert into == [(0, 1)] * (2 * cfg.count(M.FULL))

    # the forms that went, planted: a pool side by side whose gathered tile
    # is split into heads, and a write by row into the pool by head
    side = jnp.zeros(shape[:2] + (bs, cfg.dim))
    entries = jnp.zeros((2, tile // bs), jnp.int32)
    relaid = jax.jit(lambda p: paged.gather(p, 1, entries).reshape(
        2, tile, cfg.n_heads, cfg.head_dim)).lower(side).as_text()
    assert len(_tile_reshapes(relaid, tile, cfg)) == 1
    rows = jnp.zeros((3, cfg.n_heads, cfg.head_dim))
    at = jnp.zeros(3, jnp.int32)
    by_row = jax.jit(lambda p: p.at[1, at, :, at].set(rows)).lower(
        cache[M.KV]["k"]).as_text()
    assert _pool_scatters(by_row, shape) == [(0, 1, 3)]


# ------------------------------------------------------ 4. planted faults
def _fault(name):
    """The family with one term of the gated delta rule altered."""
    import types
    fam = spec.family({"family": "gdn_hybrid"})
    bad = types.ModuleType("faulty")
    bad.__dict__.update(fam.__dict__)
    loop = fam.delta_loop
    if name == "beta-without-2":
        bad.delta_loop = lambda q, k, v, g, beta: loop(q, k, v, g, beta / 2)
    elif name == "no-decay":
        bad.delta_loop = lambda q, k, v, g, beta: loop(q, k, v, 0 * g, beta)
    elif name == "q-not-normalised":
        bad.delta_loop = lambda q, k, v, g, beta: loop(
            q * (1.0 + jnp.arange(q.shape[-1]) / q.shape[-1]), k, v, g, beta)
    elif name == "k-not-normalised":
        bad.delta_loop = lambda q, k, v, g, beta: loop(q, 1.3 * k, v, g, beta)
    bad.linear_mixer = types.FunctionType(
        fam.linear_mixer.__code__, bad.__dict__, "linear_mixer")
    bad.layer = types.FunctionType(fam.layer.__code__, bad.__dict__, "layer")
    return bad


@pytest.mark.parametrize("name", ["beta-without-2", "no-decay",
                                  "q-not-normalised", "k-not-normalised"])
def test_a_dropped_term_fails_the_tolerance(toy, name):
    config, model, cfg, params = toy
    ids = np.random.default_rng(7).integers(0, cfg.vocab, (1, 48))
    got = _full(params, jnp.asarray(ids), cfg)
    assert _gap(got, _ref_logits(config, ids)) < TOL
    assert _gap(got, _ref_logits(config, ids, fam=_fault(name))) > 100 * TOL


def test_a_bfloat16_state_fails_the_tolerance(toy):
    """The committed state rounded to bfloat16 between ticks: thirty-six
    times the tolerance."""
    config, model, cfg, params = toy
    T = 48
    ids = np.random.default_rng(8).integers(0, cfg.vocab, (1, T))
    want = _full(params, jnp.asarray(ids), cfg)
    cache, tables = _pools(cfg, 1)
    step = _stepper(params, cfg, tables)

    def rounding(cache, *a):
        logits, cache = step(cache, *a)
        state = cache[M.DELTA]["S"]
        return logits, dict(cache, **{M.DELTA: dict(
            cache[M.DELTA], S=state.astype(jnp.bfloat16).astype(state.dtype))})
    plan = [[16], [16]] + [[1]] * 16
    sound, _, _ = _run(step, cache, ids, plan, 16, cfg.vocab)
    rounded, _, _ = _run(rounding, cache, ids, plan, 16, cfg.vocab)
    assert _gap(sound[0], want[0]) < TOL
    assert _gap(rounded[0], want[0]) > 10 * TOL


def test_a_rejected_row_replayed_fails_the_tolerance(toy):
    """The next tick replaying ALL the ring's rows, the rejected ones too
    (``at`` read as if every draft had stood): not what ``apply`` gives."""
    config, model, cfg, params = toy
    ids = np.random.default_rng(9).integers(0, cfg.vocab, (1, 40))
    want = _full(params, jnp.asarray(ids), cfg)
    cache, tables = _pools(cfg, 1)
    step = _stepper(params, cfg, tables)
    got, cache, done = _run(step, cache, ids, [[16], [(5, 1)]], 16,
                            cfg.vocab)
    sound, _, _ = _run(step, cache, ids, [[1]], 5, cfg.vocab, done.copy())
    # the fault: the committed position moved back, so that three rows more
    # than were accepted lie between it and the slot's length
    at = cache[M.DELTA][paged.AT]
    moved = dict(cache, **{M.DELTA: dict(cache[M.DELTA], **{
        paged.AT: at - 2})})
    faulty, _, _ = _run(step, moved, ids, [[1]], 5, cfg.vocab, done.copy())
    p = int(done[0])
    assert _gap(sound[0, p], want[0, p]) < TOL
    assert _gap(faulty[0, p], want[0, p]) > 100 * TOL


# ------------------------------------------------------------ 5. manifest
def test_the_manifest_round_trips(tmp_path, toy):
    config, model, cfg, params = toy
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("hvd",))
    small = dataclasses.replace(cfg, n_layers=4, vocab=64)
    save_servable(str(tmp_path), "gdn_hybrid", small,
                  M.init(jax.random.PRNGKey(0), small))
    with open(os.path.join(tmp_path, "serve.json")) as f:
        assert json.load(f)["model"] == "gdn_hybrid"
    got, got_cfg, got_params = load_servable(str(tmp_path), mesh)
    assert got is M and got_cfg == small
    assert M.param_count(small) == sum(
        x.size for x in jax.tree_util.tree_leaves(got_params))
