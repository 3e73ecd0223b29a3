"""The decoder-hybrid-decoder (horovod_tpu/models/sambay.py over
models/paged.py's four cache kinds; docs/serving.md#cache-kinds): the full
path against the benchmark's plain reference (perfbench/families/sambay.py),
the cached path against the full one over chunk boundaries, a ring that
wraps, packed rows, rejected drafts and reused slots, on logits; what the
cross layers and the gated memory units read; and the planted faults of the
differential attention that the reference must fail."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import paged, sambay as M
from horovod_tpu.serve.engine import load_servable, save_servable

from perfbench.lib import reference, spec, weights

SEED = 2**31 + 44
CELL = "serve-ssm-yoco-reason"
#: float32 on the CPU, program against reference or against itself: the two
#: differ by the order of float32 sums (the scan's readout over d_state, the
#: softmax over a tile of context, the taps), about 1e-5 of a logit's spread
#: here; 1e-4 of it leaves an order of room, is a hundred times below what
#: bfloat16 in place of float32 changes (3e-2 of the spread at this width)
#: and over forty times below what any planted fault changes
TOL = 1e-4
#: columns of a slot's two states at the engine's default verify row of 5
CONV_COLS = paged.state_columns(3, 5)
CARRY_COLS = paged.state_columns(1, 5)


@pytest.fixture(scope="module")
def toy():
    """The benchmark's toy copy of the configuration (mamba swa mamba swa
    mamba full gmu cross; window 8): (config, module, config object,
    weights)."""
    config = spec.tiny(spec.cell(CELL)[1])
    model, cfg = spec.family(config).program(config)
    params = jax.jit(lambda k: weights.make(config, k, jnp.float32))(
        weights.seed_key(SEED))
    return config, model, cfg, params


def _ref_logits(config, ids, params=None):
    """The family's plain equations on token rows ``ids`` [B, T], over the
    seeded leaves or over those of the program's ``params``."""
    fam = spec.family(config)
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


def _pools(cfg, S, C, block_size=4, max_seq=96, conv_cols=CONV_COLS,
           carry_cols=CARRY_COLS):
    """(cache, tables) of S slots that own their blocks and rings in order,
    for ticks of at most C columns."""
    mb = -(-max_seq // block_size)
    ring = paged.ring_blocks(cfg.window, C, block_size, mb)
    cache = M.init_cache(
        cfg, {M.KV: S * mb, M.WINDOW: S * ring, M.CONV: (S, conv_cols),
              M.CARRY: (S, carry_cols)}, block_size)
    table = lambda n: jnp.arange(S * n, dtype=jnp.int32).reshape(S, n)
    return cache, {M.KV: table(mb), M.WINDOW: table(ring)}


def _stepper(params, cfg, tables):
    return jax.jit(lambda c, t, l, n: M.apply_cached(
        params, t, cfg, c, tables, l, n))


def _run(step, cache, ids, plan, C, vocab):
    """Run the ticks of ``plan`` ([n_new a slot] a tick) over token rows
    ``ids`` [S, T]: the logits of every position fed, [S, T, vocab]."""
    S, T = ids.shape
    done = np.zeros(S, np.int32)
    got = np.zeros((S, T, vocab), np.float32)
    for n in plan:
        n = np.asarray(n, np.int32)
        tok = np.zeros((S, C), np.int32)
        for s in range(S):
            tok[s, :n[s]] = ids[s, done[s]:done[s] + n[s]]
        logits, cache = step(cache, jnp.asarray(tok), jnp.asarray(done),
                             jnp.asarray(n))
        for s in range(S):
            got[s, done[s]:done[s] + n[s]] = np.asarray(logits[s, :n[s]])
        done += n
    assert (done == T).all()
    return jnp.asarray(got), cache


# --------------------------------------------------- 1. apply vs reference
def test_apply_is_the_references_forward_pass(toy):
    config, model, cfg, params = toy
    assert model is M and cfg.n_layers == 8 and cfg.window == 8
    assert [cfg.kind(i) for i in range(8)] == [
        M.MAMBA, M.SWA, M.MAMBA, M.SWA, M.MAMBA, M.FULL, M.GMU, M.CROSS]
    assert [(k.name, k.layers, k.window, k.state)
            for k in M.cache_kinds(cfg)] == [
        (M.KV, 1, None, None), (M.WINDOW, 2, 8, None),
        (M.CONV, 3, None, 3), (M.CARRY, 3, None, 1)]
    # the published stack: nine scans, eight rings, one layer read by eight
    whole = M.SambaYConfig(n_layers=32)
    assert [whole.count(k) for k in (M.MAMBA, M.SWA, M.FULL, M.CROSS, M.GMU)
            ] == [9, 8, 1, 7, 7] and whole.kind(16) == M.MAMBA \
        and whole.kind(17) == M.FULL
    ids = np.random.default_rng(1).integers(0, cfg.vocab, (2, 70))
    assert _gap(_full(params, jnp.asarray(ids), cfg),
                _ref_logits(config, ids)) < TOL


# ----------------------------- 2. the cached path vs apply: chunks, a ring
@pytest.mark.parametrize("chunk", [1, 3, 5, 8, 16, 40])
def test_chunks_then_decode_through_the_four_kinds_match_apply(toy, chunk):
    """A prompt prefilled in chunks of any size, then decoded a token a
    tick, gives what ``apply`` gives on the whole sequence: every chunk
    boundary carries three conv inputs and one scan carry a layer across;
    a chunk of 1 reads both from the state at every token; a chunk of 16 or
    40 writes only its last columns' snapshots; 52 positions pass the window
    of 8 and wrap every ring but the widest chunk's.  The rings are sized
    for a chunk of 16 at the least, as an engine's are for its widest tick:
    the narrower ticks gather only the entries their windows reach (4 or 5
    of 6)."""
    _, _, cfg, params = toy
    T, prompt = 52, 40
    ids = np.random.default_rng(2).integers(0, cfg.vocab, (1, T))
    want = _full(params, jnp.asarray(ids), cfg)
    C = max(chunk, 2)
    cache, tables = _pools(cfg, 1, max(C, 16))
    entries = tables[M.WINDOW].shape[1]
    assert chunk == 40 or entries * 4 < T
    assert ((cfg.window + C - 2) // 4 + 2 < entries) == (chunk < 16)
    plan = [[min(chunk, prompt - at)] for at in range(0, prompt, chunk)]
    got, _ = _run(_stepper(params, cfg, tables), cache, ids,
                  plan + [[1]] * (T - prompt), C, cfg.vocab)
    assert _gap(got, want) < TOL


# ------------------------------ 3. a tick that packs several slots' rows
@pytest.mark.parametrize("budget", [0, 12])
def test_packed_rows_scan_each_slot_from_its_own_carry(toy, budget):
    """Three slots at different offsets in one tick — one prefilling, one
    decoding, one admitted late —, packed to ``budget`` rows (0: the slab
    itself): the row before a slot's first is another slot's, and neither
    the convolution nor the scan reads it."""
    _, _, cfg, params = toy
    cfg = dataclasses.replace(cfg, max_tick_tokens=budget)
    S, T, C = 3, 30, 8
    ids = np.random.default_rng(3).integers(0, cfg.vocab, (S, T))
    want = _full(params, jnp.asarray(ids), cfg)
    cache, tables = _pools(cfg, S, C)
    done, plan, mixed = np.zeros(S, np.int32), [], 0
    while (done < T).any():
        n = np.zeros(S, np.int32)
        n[0] = min(7, T - done[0]) if done[0] < 14 else min(1, T - done[0])
        n[1] = min(2, T - done[1]) if plan else 0
        n[2] = min(3, T - done[2]) if len(plan) >= 2 else 0
        mixed += int((n > 0).sum() == 3 and len(set(done.tolist())) == 3)
        plan.append(n)
        done += n
    assert mixed >= 3 and max(int(n.sum()) for n in plan) <= 12
    got, _ = _run(_stepper(params, cfg, tables), cache, ids, plan, C,
                  cfg.vocab)
    assert _gap(got, want) < TOL


# -------------------------------------------- 4. rejected drafts, by hand
@pytest.mark.parametrize("accepted", [0, 1, 2, 4])
def test_a_rejected_drafts_carry_is_never_read(toy, accepted):
    """A verify row of 1 + 4 columns whose drafts past the first
    ``accepted`` were wrong leaves the carry after each of its 5 rows in the
    slot's columns; the next tick starts after the accepted ones and must
    scan from the carry after the LAST ACCEPTED row — with no second forward
    and nothing reset — and read the last three accepted conv inputs.  A
    carry ring of 4 columns puts the last draft's carry where the first
    row's lies, which is the one read when nothing was accepted."""
    _, _, cfg, params = toy
    T, L, k = 30, 17, 4
    ids = np.random.default_rng(4).integers(0, cfg.vocab, (1, T))
    want = _full(params, jnp.asarray(ids), cfg)

    def served(carry_cols):
        cache, tables = _pools(cfg, 1, 8, carry_cols=carry_cols)
        step = _stepper(params, cfg, tables)
        _, cache = _run(step, cache, ids[:, :L], [[8], [8], [1]], 8,
                        cfg.vocab)
        # the verify row: the true next token, ``accepted`` true drafts, then
        # wrong ones (another token than the sequence's)
        row = ids[0, L:L + 1 + k].copy()
        row[1 + accepted:] = (row[1 + accepted:] + 1) % cfg.vocab
        pad = lambda t: jnp.asarray(np.pad(t, (0, 8 - len(t)))[None],
                                    jnp.int32)
        _, cache = step(cache, pad(row), jnp.asarray([L], jnp.int32),
                        jnp.asarray([1 + k], jnp.int32))
        at = L + 1 + accepted           # what the engine's ctx_len becomes
        logits, _ = step(cache, pad(ids[0, at:at + 2]),
                         jnp.asarray([at], jnp.int32),
                         jnp.asarray([2], jnp.int32))
        return _gap(logits[0, :2], want[0, at:at + 2])
    assert served(CARRY_COLS) < TOL
    assert CARRY_COLS == 1 + (1 + k)
    if accepted == 0:
        assert served(4) > 40 * TOL


# ------------------------------------------------------ 5. a slot reused
def test_a_new_tenant_starts_from_nothing(toy, monkeypatch):
    """A shorter request admitted into the slot a longer one left — its
    carries, conv inputs, ring and blocks all still there — gives ``apply``'s
    logits: the carry is zero at length 0 and no position below 0 is read.
    Read without that mask it is another function."""
    _, _, cfg, params = toy
    rng = np.random.default_rng(5)
    first = rng.integers(0, cfg.vocab, (1, 40))
    second = rng.integers(0, cfg.vocab, (1, 9))
    cache, tables = _pools(cfg, 1, 8)
    step = _stepper(params, cfg, tables)
    _, cache = _run(step, cache, first, [[8]] * 5, 8, cfg.vocab)
    assert all(float(jnp.abs(x).max()) > 0
               for x in jax.tree_util.tree_leaves(cache))
    run = lambda: _run(_stepper(params, cfg, tables), cache, second,
                       [[8], [1]], 8, cfg.vocab)[0]
    want = _full(params, jnp.asarray(second), cfg)
    assert _gap(run(), want) < TOL
    monkeypatch.setattr(
        paged, "carry_read", lambda pool, layer, lengths: pool[
            layer, jnp.arange(lengths.shape[0]),
            (lengths - 1) % pool.shape[2]])
    assert _gap(run(), want) > 40 * TOL


# ----------------------- 6. what the cross decoder reads, and from where
def test_cross_layers_read_the_full_layers_pool_and_units_its_memory(
        toy, monkeypatch):
    """The one paged layer is written by the full layer alone and read by
    the cross layer too: with that pool emptied between two ticks (the
    rings and states left alone) a decoded token's logits move by far more
    than the tolerance.  A gated memory unit multiplies by the LAST scan's output
    before its gate: the reference with the memory taken after the gate is
    another function."""
    config, _, cfg, params = toy
    assert cfg.kind(cfg.n_layers - 1) == M.CROSS
    ids = np.random.default_rng(6).integers(0, cfg.vocab, (1, 41))
    want = _full(params, jnp.asarray(ids), cfg)
    cache, tables = _pools(cfg, 1, 8)
    assert cache[M.KV]["k"].shape[0] == 1
    step = _stepper(params, cfg, tables)
    _, cache = _run(step, cache, ids[:, :40], [[8]] * 5, 8, cfg.vocab)
    last = lambda c: step(c, jnp.asarray(ids[:, 40:41].repeat(8, 1)),
                          jnp.asarray([40], jnp.int32),
                          jnp.asarray([1], jnp.int32))[0][0, :1]
    assert _gap(last(cache), want[0, 40:41]) < TOL
    emptied = dict(cache, **{M.KV: jax.tree_util.tree_map(
        jnp.zeros_like, cache[M.KV])})
    assert _gap(last(emptied), want[0, 40:41]) > 40 * TOL
    got = _full(params, jnp.asarray(ids), cfg)
    assert _gap(got, _ref_logits(config, ids)) < TOL
    fam = spec.family(config)
    plain = fam.mamba

    def gated_memory(p, a, config, mm):
        out, y = plain(p, a, config, mm)
        z = jnp.split(mm(a, p["mamba.in_proj.kernel"]), 2, axis=-1)[1]
        return out, y * jax.nn.silu(z)
    monkeypatch.setattr(fam, "mamba", gated_memory)
    assert _gap(got, _ref_logits(config, ids)) > 40 * TOL


# -------------------------------------------------- 7. the planted faults
@pytest.mark.parametrize("fault", ["lambda_init_only", "one_softmax",
                                   "no_subln"])
def test_a_planted_fault_fails_the_reference(toy, fault, monkeypatch):
    """``lam`` left at its init, the second softmax dropped, a head's output
    not normed, each planted in the reference's own piece of the equations
    (``lam``, ``subln``): each is another function by more than forty
    tolerances, so the program cannot have it and pass test 1.  The lambda
    leaves and the head norm's gain are drawn wider for this test: at the
    seeded std of 0.1 over a head of 8 the learnt part of ``lam`` is a few
    hundredths."""
    config, _, cfg, params = toy
    keys = iter(jax.random.split(jax.random.PRNGKey(7), 64))
    draw = lambda n, std, mean=0.0: mean + std * jax.random.normal(
        next(keys), (n,))
    params = dict(params, layers=[
        p if "attn" not in p else dict(p, attn=dict(
            p["attn"], subln={"scale": draw(2 * cfg.head_dim, 0.5, 1.0)},
            **{"lambda_" + n: draw(cfg.head_dim, 0.4)
               for n in ("q1", "k1", "q2", "k2")}))
        for p in params["layers"]])
    ids = np.random.default_rng(7).integers(0, cfg.vocab, (1, 48))
    got = _full(params, jnp.asarray(ids), cfg)
    assert _gap(got, _ref_logits(config, ids, params)) < TOL
    fam = spec.family(config)
    name, wrong = {
        "lambda_init_only": ("lam", lambda p, i: fam.lambda_init(i)),
        "one_softmax": ("lam", lambda p, i: 0.0),
        "no_subln": ("subln", lambda o, p, config: o)}[fault]
    monkeypatch.setattr(fam, name, wrong)
    assert _gap(got, _ref_logits(config, ids, params)) > 40 * TOL


def test_bfloat16_in_place_of_float32_fails_the_tolerance(toy):
    """The tolerance is tight enough that a lower precision than the one the
    toy states would fail it."""
    config, _, cfg, params = toy
    ids = np.random.default_rng(8).integers(0, cfg.vocab, (1, 48))
    low = jax.tree_util.tree_map(lambda x: x.astype(jnp.bfloat16), params)
    got = _full(low, jnp.asarray(ids),
                dataclasses.replace(cfg, dtype=jnp.bfloat16))
    assert _gap(got.astype(jnp.float32), _ref_logits(config, ids)) > 40 * TOL


# ---------------------------------------------------------- 8. the module
def test_the_module_samples_where_the_tick_reads(toy):
    """``greedy_cached(.., read)`` is the argmax of ``apply_cached`` at the
    columns read, and its program holds no ``[rows, vocab]`` wider than
    those columns."""
    # (a vocabulary that is no other array's width: the toy's 256 is the
    # input projection's too)
    cfg = dataclasses.replace(toy[2], vocab=250, max_tick_tokens=12)
    params = M.init(jax.random.PRNGKey(9), cfg)
    cache, tables = _pools(cfg, 2, 8)
    tok = jnp.asarray(np.random.default_rng(9).integers(0, cfg.vocab, (2, 8)))
    args = (params, tok, cfg, cache, tables, jnp.zeros(2, jnp.int32),
            jnp.asarray([8, 3], jnp.int32))
    read = jnp.asarray([[6, 7], [1, 2]], jnp.int32)
    logits, _ = M.apply_cached(*args)
    ids, _ = M.greedy_cached(*args, read)
    assert ids.shape == (2, 2) and ids.dtype == jnp.int32
    assert jnp.array_equal(ids, jnp.argmax(jnp.take_along_axis(
        logits, read[:, :, None], 1), -1))
    text = jax.jit(M.greedy_cached, static_argnums=2).lower(
        *args, read).as_text().replace("tensor<", "x")
    assert f"2x2x{cfg.vocab}x" in text and f"12x{cfg.vocab}x" not in text


def test_the_serve_manifest_knows_the_module(tmp_path):
    cfg = M.CONFIGS["tiny"]
    params = M.init(jax.random.PRNGKey(0), cfg)
    assert M.param_count(cfg) == sum(
        x.size for x in jax.tree_util.tree_leaves(params))
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("hvd",))
    save_servable(str(tmp_path), "sambay", cfg, params)
    model, got, _ = load_servable(str(tmp_path), mesh)
    assert model is M and got == cfg and hash(got) == hash(cfg)
