"""The short-convolution-and-attention expert decoder
(horovod_tpu/models/conv_moe.py over models/paged.py's paged kind and its
FIXED STATE a slot, parallel/expert.py ``held_experts`` under the biased
sigmoid router; docs/serving.md#cache-kinds): the full path against the
benchmark's plain reference (perfbench/families/conv_moe.py), the cached
path against the full one over chunk boundaries at every offset, packed
rows, rejected drafts and reused slots (the state kind's four properties),
the bias that picks and does not weigh, the experts' shares against the
whole layer, three planted faults, and the serving engine over the state
kind."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import conv_moe as M, paged
from horovod_tpu.parallel import expert as X
from horovod_tpu.serve.config import ServeConfig
from horovod_tpu.serve.engine import (Request, Scheduler, ServeEngine,
                                      decode_width, load_servable,
                                      save_servable)

from perfbench.lib import reference, spec, weights

SEED = 2**31 + 33
CELL = "serve-moe-conv-chat"
#: float32 on the CPU, program against reference or against itself: the two
#: differ by the order of float32 sums (the experts' tiles, the taps, the
#: softmax over a tile of context), under 1e-5 of a logit's spread here;
#: 1e-4 of it leaves an order of room and is over forty times below what any
#: of the planted faults changes (the tests at the end)
TOL = 1e-4
#: columns of a slot's state at the engine's default verify row of 5
COLS = paged.state_columns(2, 5)


def _scfg(**kw):
    base = dict(max_slots=3, block_size=4, cache_blocks=96, max_seq_len=96,
                max_batch_tokens=20, prefill_chunk=8, prefix_cache=False)
    base.update(kw)
    return ServeConfig(**base)


def _mesh():
    return jax.sharding.Mesh(np.array(jax.devices()[:1]), ("hvd",))


@pytest.fixture(scope="module")
def toy():
    """The benchmark's toy copy of the configuration (conv conv attn conv
    conv conv attn, two dense layers): (config, module, config object,
    weights)."""
    config = spec.tiny(spec.cell(CELL)[1])
    model, cfg = spec.family(config).program(config)
    params = jax.jit(lambda k: weights.make(config, k, jnp.float32))(
        weights.seed_key(SEED))
    return config, model, cfg, params


def _ref_logits(config, ids, params=None, **layer_kw):
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
            x = fam.layer(kind, layer(i), x, config, reference.plain_mm,
                          **layer_kw)
        return fam.head(part(fam.HEAD), x, config, reference.plain_mm)


def _gap(a, b):
    """Largest difference over the spread of ``b``'s values."""
    return float(jnp.max(jnp.abs(a - b))) / float(jnp.std(b))


_full = jax.jit(M.apply, static_argnums=2)


def _pools(cfg, S, block_size=4, max_seq=96, cols=COLS):
    """(cache, tables) of S slots that own their blocks in order."""
    mb = -(-max_seq // block_size)
    cache = M.init_cache(cfg, {M.ATTN: S * mb, M.CONV: (S, cols)}, block_size)
    return cache, {M.ATTN: jnp.arange(S * mb, dtype=jnp.int32).reshape(S, -1)}


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
    assert model is M and cfg.n_layers == 7 and cfg.n_dense_layers == 2
    assert [cfg.conv(i) for i in range(7)] == [True, True, False, True, True,
                                               True, False]
    assert [(k.name, k.layers, k.window, k.state)
            for k in M.cache_kinds(cfg)] == [(M.ATTN, 2, None, None),
                                             (M.CONV, 5, None, 2)]
    ids = np.random.default_rng(1).integers(0, cfg.vocab, (2, 70))
    assert _gap(_full(params, jnp.asarray(ids), cfg),
                _ref_logits(config, ids)) < TOL


# ------------------------- 2. the cached path vs apply: chunks (property 1)
@pytest.mark.parametrize("chunk", [1, 3, 4, 5, 8, 40])
def test_chunks_then_decode_through_both_kinds_match_apply(toy, chunk):
    """Property 1: a prompt prefilled in chunks of any size, then decoded a
    token a tick, gives what ``apply`` gives on the whole sequence.  Chunks
    of 4, 5 and 3 put a boundary at every offset mod 3 (the taps) and at
    columns before, at and past a ring of 7; a chunk of 1 reads both earlier
    columns from the state at every token; a chunk of 40 > 7 columns writes
    its last columns only."""
    _, _, cfg, params = toy
    T, prompt = 52, 40
    ids = np.random.default_rng(2).integers(0, cfg.vocab, (1, T))
    want = _full(params, jnp.asarray(ids), cfg)
    cache, tables = _pools(cfg, 1)
    step = jax.jit(lambda c, t, l, n: M.apply_cached(
        params, t, cfg, c, tables, l, n)[:2])
    plan = [[min(chunk, prompt - at)] for at in range(0, prompt, chunk)]
    got, _ = _run(step, cache, ids, plan + [[1]] * (T - prompt),
                  max(chunk, 2), cfg.vocab)
    assert _gap(got, want) < TOL


# -------------------- 3. a tick that packs several slots' rows (property 2)
@pytest.mark.parametrize("budget", [0, 12])
def test_packed_rows_never_read_a_neighbour_slots_row(toy, budget):
    """Property 2: three slots at different offsets in one tick — one
    prefilling, one decoding, one admitted late —, packed to ``budget`` rows
    (0: the slab itself): the row before a slot's first is another slot's,
    and no row reads it."""
    _, _, cfg, params = toy
    cfg = dataclasses.replace(cfg, max_tick_tokens=budget)
    S, T, C = 3, 30, 8
    ids = np.random.default_rng(3).integers(0, cfg.vocab, (S, T))
    want = _full(params, jnp.asarray(ids), cfg)
    cache, tables = _pools(cfg, S)
    step = jax.jit(lambda c, t, l, n: M.apply_cached(
        params, t, cfg, c, tables, l, n)[:2])
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
    got, _ = _run(step, cache, ids, plan, C, cfg.vocab)
    assert _gap(got, want) < TOL


# --------------------------------- 4. rejected drafts (property 3), by hand
@pytest.mark.parametrize("accepted", [0, 1, 2, 4])
def test_a_rejected_drafts_columns_are_never_read(toy, accepted):
    """Property 3: a verify row of 1 + 4 columns whose drafts past the first
    ``accepted`` were wrong leaves ``u`` of 5 positions in the ring; the next
    tick starts after the accepted ones and must read the last two ACCEPTED
    positions' ``u`` — with no second forward and nothing reset.  A ring one
    column too short (``state + tick_cols - 2``) loses the older of them
    when nothing was accepted."""
    _, _, cfg, params = toy
    T, L, k = 30, 17, 4
    rng = np.random.default_rng(4)
    ids = rng.integers(0, cfg.vocab, (1, T))
    want = _full(params, jnp.asarray(ids), cfg)

    def served(cols):
        cache, tables = _pools(cfg, 1, cols=cols)
        step = jax.jit(lambda c, t, l, n: M.apply_cached(
            params, t, cfg, c, tables, l, n)[:2])
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
    assert served(COLS) < TOL
    assert COLS == 2 + (1 + k)
    if accepted == 0:
        # position L - 1 shares its column with the last draft's L + 4
        assert served(COLS - 2) > 40 * TOL


# ------------------------------------------- 5. the bias picks, not weighs
def test_the_bias_picks_and_does_not_weigh(toy):
    config, _, cfg, params = toy
    ids = np.random.default_rng(5).integers(0, cfg.vocab, (1, 48))
    got = _full(params, jnp.asarray(ids), cfg)
    assert _gap(got, _ref_logits(config, ids)) < TOL
    # the seeded bias changes which experts are chosen for some tokens ...
    p = params["layers"][2]["moe"]
    h = jax.random.normal(jax.random.PRNGKey(5), (64, cfg.dim))
    biased, gates = X.route_sigmoid_topk(h, p["router"]["kernel"], cfg.top_k,
                                         1.0, bias=p["bias"], eps=M.GATE_EPS)
    plain, _ = X.route_sigmoid_topk(h, p["router"]["kernel"], cfg.top_k, 1.0,
                                    eps=M.GATE_EPS)
    moved = (np.sort(np.asarray(biased), -1)
             != np.sort(np.asarray(plain), -1)).any(-1)
    assert 0.1 < moved.mean() < 1.0
    # ... the gates are the unbiased scores over their sum ...
    s = jax.nn.sigmoid(h @ p["router"]["kernel"])
    chosen = jnp.take_along_axis(s, biased, -1)
    assert np.allclose(gates, chosen / (chosen.sum(-1, keepdims=True) + 1e-6),
                       atol=1e-6)
    # ... so a router without the bias, or with the bias in the gates too,
    # is another function by far more than the tolerance
    assert _gap(got, _ref_logits(config, ids, fault="no_bias")) > 40 * TOL
    assert _gap(got, _ref_logits(config, ids,
                                 fault="bias_in_gates")) > 40 * TOL


# ------------------------------- 6. the shares add up to the whole layer
def test_four_shares_of_eight_experts_add_up_to_the_layer_of_32():
    """32 SiLU-gated experts, 4 a token by the biased sigmoid: the parts that
    four chips holding 8 experts each compute add up to the reference's
    whole layer."""
    d, hidden, total, k, T = 32, 24, 32, 4, 40
    config = dict(spec.cell(CELL)[1], hidden_size=d,
                  moe_intermediate_size=hidden, num_experts=total,
                  num_experts_per_tok=k)
    fam = spec.family(config)
    p = X.init_held_experts(jax.random.PRNGKey(6), d, hidden, total, total)
    bias = 0.1 * jax.random.normal(jax.random.PRNGKey(8), (total,))
    h = jax.random.normal(jax.random.PRNGKey(7), (T, d))
    ref_p = {"moe.router.kernel": p["router"]["kernel"], "moe.bias": bias,
             **{f"moe.experts.{n}": w for n, w in p["experts"].items()}}
    with jax.default_matmul_precision("highest"):
        want = fam.experts(ref_p, h, fam.route(ref_p, h, config, jnp.matmul),
                           config, jnp.matmul)
        routing = X.route_sigmoid_topk(h, p["router"]["kernel"], k, 1.0,
                                       bias=bias, eps=M.GATE_EPS)
        got, held = 0.0, 0
        for first in range(0, total, 8):
            share = {"experts": {n: w[first:first + 8]
                                 for n, w in p["experts"].items()}}
            y, counts = X.held_experts(share, h, jnp.ones(T, bool),
                                       first=first, routing=routing,
                                       act=jax.nn.silu, tile=8)
            got, held = got + y, held + int(counts[1])
    assert held == T * k            # every assignment is some share's
    assert _gap(got, want) < 1e-5   # float32 sums in another order


# ------------------------------------------------- 7. three planted faults
@pytest.mark.parametrize("fault", ["taps_moved", "norm_after_rope",
                                   "b_c_swapped"])
def test_a_planted_fault_fails_the_reference(toy, fault):
    """The taps moved by one position, the head norm applied after the
    rotary encoding, B and C swapped: each is another function by more than
    forty tolerances, so the program cannot have it and pass test 1.  The
    head norms' gains are drawn for this test: with the seeded gains of 1 a
    norm over a head commutes with a rotation of it, and the second fault
    would be no fault."""
    config, _, cfg, params = toy
    keys = iter(jax.random.split(jax.random.PRNGKey(7), 8))
    params = dict(params, layers=[
        p if "attn" not in p else dict(p, attn=dict(
            p["attn"], **{n: {"scale": 1.0 + 0.5 * jax.random.normal(
                next(keys), (cfg.head_dim,))} for n in ("q_norm", "k_norm")}))
        for p in params["layers"]])
    ids = np.random.default_rng(7).integers(0, cfg.vocab, (1, 48))
    got = _full(params, jnp.asarray(ids), cfg)
    assert _gap(got, _ref_logits(config, ids, params)) < TOL
    assert _gap(got, _ref_logits(config, ids, params,
                                 fault=fault)) > 40 * TOL


# --------------------------------------------------- 8. the engine's state
def test_the_scheduler_sizes_counts_and_refuses_by_kind():
    kinds = (paged.CacheKind("attn", 2), paged.CacheKind("conv", 5, state=2))
    s = Scheduler(_scfg(cache_blocks=40), kinds=kinds)
    state = s.states["conv"]
    assert state.columns == 2 + decode_width(s.cfg) == COLS and not s.rings
    assert Scheduler(_scfg(spec_decode=False), kinds=kinds
                     ).states["conv"].columns == 3
    # the state kind has no table and no allocator: the tick is handed the
    # paged kind's table alone, and admission counts the paged kind's blocks
    req = s.submit(Request([1] * 50, 14))
    s.plan()
    assert set(s.device_tables()) == {"attn"} and len(req.blocks) == 16
    s.finish(req, "completed")
    assert s.allocator.free_count == 40
    for bad in (dict(prefix_cache=True), dict(prefix_cache=True,
                                              spill_blocks=4)):
        with pytest.raises(ValueError, match=r"state cache kinds \(conv\)"):
            Scheduler(_scfg(**bad), kinds=kinds)
    for role in ("prefill", "decode"):
        with pytest.raises(ValueError, match="hand-off"):
            Scheduler(_scfg(), role=role, kinds=kinds)
    both = kinds + (paged.CacheKind("window", 1, 16),)
    with pytest.raises(ValueError, match=r"window cache kinds \(window\) and "
                                         r"state cache kinds \(conv\)"):
        Scheduler(_scfg(prefix_cache=True), kinds=both)


#: a vocabulary so small that a context's last two tokens have nearly
#: always been seen before: ``draft_lookup`` drafts at most ticks, and a
#: toy's continuations seldom agree, so most drafts are rejected
DRAFTING_VOCAB = 8


@pytest.fixture(scope="module")
def drafting_toy(toy):
    config = dict(toy[0], vocab_size=DRAFTING_VOCAB)
    model, cfg = spec.family(config).program(config)
    params = jax.jit(lambda k: weights.make(config, k, jnp.float32))(
        weights.seed_key(SEED))
    return config, model, cfg, params


def _repeating_prompts(cfg):
    """Prompts that repeat an n-gram."""
    rng = np.random.RandomState(7)
    motif = rng.randint(0, cfg.vocab, 6).tolist()
    return [rng.randint(0, cfg.vocab, 50).tolist(), motif * 6,
            rng.randint(0, cfg.vocab, 23).tolist() + motif * 5,
            rng.randint(0, cfg.vocab, 9).tolist()]


def _served(engine, prompts, new=12):
    reqs = [engine.submit(p, new, req_id=f"r{i}")
            for i, p in enumerate(prompts)]
    while engine.has_work():
        engine.step()
    assert all(r.state == "done" and len(r.out_tokens) == new for r in reqs)
    return reqs


def _reference_tokens(config, prompt, out):
    seq = prompt + out          # padded: one shape, one compilation
    want = reference.logits_at(config, SEED, seq + [0] * (96 - len(seq)),
                               range(len(prompt) - 1, len(seq) - 1))
    return np.asarray(jnp.argmax(want, -1)).tolist()


def test_the_engine_serves_the_references_greedy_tokens_over_rejected_drafts(
        drafting_toy, monkeypatch):
    """Property 3 end to end: ServeEngine over both kinds, speculation on
    with a drafter that is mostly wrong, four requests through three slots
    (the fourth takes a slot another stream left): every served token is
    the plain reference's first choice.  A state NOT rolled back — a ring of
    the two carried columns alone, which after a verify row holds its last
    two drafts' ``u`` whatever was accepted — fails it."""
    config, model, cfg, params = drafting_toy
    engine = ServeEngine(model, cfg, params, _scfg(), mesh=_mesh())
    assert engine.cache[M.CONV]["u"].shape == (5, 3, COLS, cfg.dim)
    assert engine.cache[M.ATTN]["k"].shape == (
        2, 96, 4, cfg.n_kv_heads * cfg.head_dim)
    prompts = _repeating_prompts(cfg)
    reqs = _served(engine, prompts)
    st = engine.stats()
    drafted, accepted = (st["spec"][k + "_tokens"]
                         for k in ("drafted", "accepted"))
    assert drafted >= 30 and 0 < accepted < drafted / 2
    assert st["moe"]["ticks"] == st["tick"] and st["moe"]["assignments"] > 0
    pool = st["kv_pool"]["kinds"]
    assert pool[M.ATTN]["used_blocks"] == 0
    conv = pool[M.CONV]
    assert conv["pool_bytes"] == 5 * 3 * COLS * cfg.dim * 4
    assert conv["state"] == 2 and conv["state_columns"] == COLS
    assert conv["slots"] == 3 and conv["slots_used"] == 0
    assert conv["slot_ticks"] > st["tick"]
    assert conv["state_bytes_ticks"] == conv["slot_ticks"] * 5 * COLS * cfg.dim * 4
    # a key-value cache of the five layers at those slots' lengths: more
    # once a context passes a few positions (K and V of 2 heads of 16)
    assert conv["kv_bytes_ticks"] % (5 * 2 * 2 * 16 * 4) == 0
    assert conv["kv_bytes_ticks"] > conv["state_bytes_ticks"]
    assert st["kv_pool"]["pool_bytes"] == conv["pool_bytes"] + pool[
        M.ATTN]["pool_bytes"]
    with pytest.raises(ValueError, match="state cache kinds"):
        engine.export_handoff(reqs[0], 0)
    engine.close()
    for p, r in zip(prompts, reqs):
        assert r.out_tokens == _reference_tokens(config, p, r.out_tokens)

    # the planted fault: no column beside the two a tick reads back
    monkeypatch.setattr(paged, "state_columns", lambda state, cols: state)
    broken = ServeEngine(model, cfg, params, _scfg(), mesh=_mesh())
    assert broken.cache[M.CONV]["u"].shape[2] == 2
    bad = _served(broken, prompts)
    broken.close()
    assert any(r.out_tokens != _reference_tokens(config, p, r.out_tokens)
               for p, r in zip(prompts, bad))


def test_a_reused_slot_serves_what_a_fresh_engine_serves(toy):
    """Property 4: one slot, three streams one after another: each is
    admitted into the state its predecessor left and serves what an engine
    that never held another stream serves."""
    _, model, cfg, params = toy
    rng = np.random.RandomState(9)
    prompts = [rng.randint(0, cfg.vocab, n).tolist() for n in (21, 1, 13)]
    one = ServeEngine(model, cfg, params, _scfg(max_slots=1), mesh=_mesh())
    reused = [r.out_tokens for r in _served(one, prompts, new=6)]
    assert float(jnp.abs(one.cache[M.CONV]["u"]).max()) > 0   # never reset
    one.close()
    for p, got in zip(prompts, reused):
        fresh = ServeEngine(model, cfg, params, _scfg(max_slots=1),
                            mesh=_mesh())
        assert _served(fresh, [p], new=6)[0].out_tokens == got
        fresh.close()


def test_a_tenant_that_read_its_predecessors_state_would_differ(
        toy, monkeypatch):
    """... and the mask below position 0 is what does it: a first chunk over
    a state another stream left gives ``apply``'s logits; read without the
    mask it is another function."""
    _, _, cfg, params = toy
    ids = np.random.default_rng(10).integers(0, cfg.vocab, (1, 8))
    want = _full(params, jnp.asarray(ids), cfg)
    cache, tables = _pools(cfg, 1)
    dirty = dict(cache, **{M.CONV: {"u": jnp.ones_like(cache[M.CONV]["u"])}})
    run = lambda: M.apply_cached(
        params, jnp.asarray(ids), cfg, dirty, tables,
        jnp.zeros(1, jnp.int32), jnp.full(1, 8, jnp.int32))[0]
    assert _gap(run(), want) < TOL

    def unmasked(pool, layer, lengths, state):
        at = lengths[:, None] - state + jnp.arange(state)[None, :]
        return pool[layer, jnp.arange(pool.shape[1])[:, None],
                    at % pool.shape[2]]
    monkeypatch.setattr(paged, "state_head", unmasked)
    assert _gap(run(), want) > 40 * TOL


def test_prefix_cache_spill_and_hand_off_are_refused_at_start_up(toy):
    _, model, cfg, params = toy
    for bad in (dict(prefix_cache=True),
                dict(prefix_cache=True, spill_blocks=4)):
        with pytest.raises(ValueError, match="prefix cache"):
            ServeEngine(model, cfg, params, _scfg(**bad), mesh=_mesh())
    with pytest.raises(ValueError, match="hand-off"):
        ServeEngine(model, cfg, params, _scfg(), mesh=_mesh(), role="decode")


def test_the_engine_samples_on_the_rows_and_packs_them(toy):
    """The module's greedy_cached is the argmax of its apply_cached, and the
    engine's tick holds no [slots, chunk, vocab] slab."""
    _, model, cfg, params = toy
    cfg = dataclasses.replace(cfg, max_tick_tokens=12)
    cache, tables = _pools(cfg, 2)
    tok = jnp.asarray(np.random.default_rng(8).integers(0, cfg.vocab, (2, 8)))
    args = (params, tok, cfg, cache, tables, jnp.zeros(2, jnp.int32),
            jnp.asarray([8, 3], jnp.int32))
    logits, _, counters = M.apply_cached(*args)
    ids, _, counters2 = M.greedy_cached(*args)
    assert ids.shape == (2, 8) and ids.dtype == jnp.int32
    assert jnp.array_equal(ids[0], jnp.argmax(logits[0], -1))
    assert jnp.array_equal(ids[1, :3], jnp.argmax(logits[1, :3], -1))
    assert jnp.array_equal(counters, counters2)
    # valid rows only, the five routed layers only
    assert int(counters[1]) == 11 * cfg.top_k * 5
    text = jax.jit(M.greedy_cached, static_argnums=2).lower(*args).as_text()
    assert f"x{cfg.vocab}x" in text.replace("tensor<", "x")
    assert f"2x8x{cfg.vocab}" not in text


def test_the_serve_manifest_knows_the_module(tmp_path):
    cfg = M.CONFIGS["tiny"]
    params = M.init(jax.random.PRNGKey(0), cfg)
    assert M.param_count(cfg) == sum(
        x.size for x in jax.tree_util.tree_leaves(params))
    save_servable(str(tmp_path), "conv_moe", cfg, params)
    model, got, _ = load_servable(str(tmp_path), _mesh())
    assert model is M and got == cfg and hash(got) == hash(cfg)
