"""The window-and-global expert decoder (horovod_tpu/models/swa_moe.py over
models/paged.py's two cache kinds, parallel/expert.py ``held_experts`` with
the routing handed in; docs/serving.md#cache-kinds): the full path against
the benchmark's plain reference (perfbench/families/swa_moe.py), the cached
path against the full one past three windows, the window's edge, the
position-free global layers, the router's early reading, the experts' shares
against the whole layer, and the serving engine over a ring a slot."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import paged, swa_moe as M
from horovod_tpu.parallel import expert as X
from horovod_tpu.serve.config import ServeConfig
from horovod_tpu.serve.engine import (Request, Scheduler, ServeEngine,
                                      load_servable, save_servable)

from perfbench.lib import reference, spec, weights

SEED = 2**31 + 31
CELL = "serve-moe-swa-longdoc"
#: float32 on the CPU, program against reference or against itself: the two
#: differ by the order of float32 sums (the cached path sums a softmax over a
#: gathered ring, the reference over a slice), a few 1e-6 of a logit's
#: spread; 1e-4 of it leaves two orders of room and is forty times below
#: what a window moved by one key changes (test_the_windows_edge)
TOL = 1e-4


def _scfg(**kw):
    base = dict(max_slots=3, block_size=4, cache_blocks=96, max_seq_len=96,
                max_batch_tokens=20, prefill_chunk=8, prefix_cache=False)
    base.update(kw)
    return ServeConfig(**base)


def _mesh():
    return jax.sharding.Mesh(np.array(jax.devices()[:1]), ("hvd",))


@pytest.fixture(scope="module")
def toy():
    """The benchmark's toy copy of the configuration (window 16, layouts
    global / window+rope x3): (config, module, config object, weights)."""
    config = spec.tiny(spec.cell(CELL)[1])
    model, cfg = spec.family(config).program(config)
    params = jax.jit(lambda k: weights.make(config, k, jnp.float32))(
        weights.seed_key(SEED))
    return config, model, cfg, params


def _ref_logits(config, ids, **layer_kw):
    """The family's plain equations on token rows ``ids`` [B, T]."""
    fam = spec.family(config)
    w = reference.Weights(config, SEED)
    with jax.default_matmul_precision("highest"):
        x = fam.embed(w.part(fam.EMBED), jnp.asarray(ids), config)
        for i, kind in enumerate(fam.layer_kinds(config)):
            x = fam.layer(kind, w.layer(i), x, config, reference.plain_mm,
                          **layer_kw)
        return fam.head(w.part(fam.HEAD), x, config, reference.plain_mm)


def _gap(a, b):
    """Largest difference over the spread of ``b``'s values."""
    return float(jnp.max(jnp.abs(a - b))) / float(jnp.std(b))


_full = jax.jit(M.apply, static_argnums=2)


# --------------------------------------------------- 1. apply vs reference
def test_apply_is_the_references_forward_pass(toy):
    config, model, cfg, params = toy
    assert model is M and cfg.window == 16
    assert [cfg.windowed(i) for i in range(4)] == [False, True, True, True]
    assert [cfg.rotary(i) for i in range(4)] == [False, True, True, True]
    ids = np.random.default_rng(1).integers(0, cfg.vocab, (2, 70))
    assert _gap(_full(params, jnp.asarray(ids), cfg),
                _ref_logits(config, ids)) < TOL


# ------------------------------------------- 2. the cached path vs apply
@pytest.mark.parametrize("chunk,block_size", [(8, 4), (16, 4), (5, 8),
                                              (16, 16)])
def test_chunks_then_decode_through_both_caches_match_apply(toy, chunk,
                                                           block_size):
    """Two slots over contexts that run past three windows (70 > 3 x 16):
    slot 0 prefills in chunks and then decodes a token a tick WHILE slot 1,
    admitted later, still prefills — a tick that mixes both — through the
    global pool and the window kind's ring, whose length is the window plus
    the chunk, rounded up to blocks."""
    _, _, cfg, params = toy
    T, S, start1 = 70, 2, 3
    ids = np.random.default_rng(2).integers(0, cfg.vocab, (S, T))
    want = _full(params, jnp.asarray(ids), cfg)
    max_blocks = -(-96 // block_size)
    ring = paged.ring_blocks(cfg.window, chunk, block_size, max_blocks)
    assert ring * block_size < T          # the ring wraps
    blocks = {M.GLOBAL: S * max_blocks, M.WINDOW: S * ring}
    cache = M.init_cache(cfg, blocks, block_size)
    tables = {k: jnp.arange(n, dtype=jnp.int32).reshape(S, -1)
              for k, n in blocks.items()}
    step = jax.jit(lambda c, t, l, n: M.apply_cached(
        params, t, cfg, c, tables, l, n)[:2])
    done = np.zeros(S, np.int32)
    got = np.zeros(want.shape, np.float32)
    mixed = tick = 0
    while (done < T).any():
        n = np.zeros(S, np.int32)
        # slot 0 decodes once 40 tokens are in; slot 1 starts 3 ticks late
        n[0] = min(chunk, T - done[0]) if done[0] < 40 else min(1, T - done[0])
        n[1] = min(chunk, T - done[1]) if tick >= start1 else 0
        mixed += int(n[0] == 1 and n[1] > 1)
        tok = np.zeros((S, chunk), np.int32)
        for s in range(S):
            tok[s, :n[s]] = ids[s, done[s]:done[s] + n[s]]
        logits, cache = step(cache, jnp.asarray(tok), jnp.asarray(done),
                             jnp.asarray(n))
        for s in range(S):
            got[s, done[s]:done[s] + n[s]] = np.asarray(logits[s, :n[s]])
        done += n
        tick += 1
    assert mixed >= 1
    assert _gap(jnp.asarray(got), want) < TOL


# ------------------------------------------------------ 3. the window's edge
@pytest.mark.parametrize("window,same", [(15, False), (16, True),
                                         (17, False)])
def test_the_windows_edge(toy, window, same):
    """The reference with the window one key narrower or wider is another
    function by far more than the tolerance, so an off-by-one in the
    program's mask or ring cannot pass test 1."""
    config, _, cfg, params = toy
    ids = np.random.default_rng(3).integers(0, cfg.vocab, (1, 70))
    gap = _gap(_full(params, jnp.asarray(ids), cfg),
               _ref_logits(dict(config, sliding_window_size=window), ids))
    assert (gap < TOL) if same else (gap > 40 * TOL), gap


def test_a_program_window_moved_by_one_fails_the_reference(toy):
    config, _, cfg, params = toy
    ids = np.random.default_rng(3).integers(0, cfg.vocab, (1, 70))
    want = _ref_logits(config, ids)
    for w in (15, 17):
        moved = dataclasses.replace(cfg, window=w)
        assert _gap(_full(params, jnp.asarray(ids), moved), want) > 40 * TOL


# --------------------------------------- 4. position-free global layers
def test_global_layers_see_no_position_and_window_layers_do(toy):
    """A stack of global layers alone gives the same logits whatever
    positions its tokens are said to lie at (here 5, 8, 11, ...: shifted AND
    strided — a shift alone would not tell, rotary scores depend on
    distances only); one window layer with its rotary does not."""
    _, _, cfg, params = toy
    ids = jnp.asarray(np.random.default_rng(4).integers(0, cfg.vocab, (1, 40)))
    said = (5 + 3 * jnp.arange(40, dtype=jnp.int32))[None]
    flat = dataclasses.replace(cfg, window_layout=(0,), rope_layout=(0,))
    assert jnp.array_equal(M.apply(params, ids, flat),
                           M.apply(params, ids, flat, rope_positions=said))
    assert _gap(M.apply(params, ids, cfg, rope_positions=said),
                M.apply(params, ids, cfg)) > 40 * TOL
    # ... and through the cache: a global-only stack keeps one kind
    assert [k.name for k in M.cache_kinds(flat)] == [M.GLOBAL]
    assert [(k.name, k.layers, k.window) for k in M.cache_kinds(cfg)] == [
        (M.GLOBAL, 1, None), (M.WINDOW, 3, 16)]


# ------------------------------ 5. the router reads the attention's input
def test_the_router_reads_the_attentions_input(toy):
    config, _, cfg, params = toy
    fam = spec.family(config)
    ids = np.random.default_rng(5).integers(0, cfg.vocab, (1, 48))
    # the two readings choose other experts for some token of layer 0 ...
    p = params["layers"][0]
    x = params["embed"]["table"][jnp.asarray(ids)]
    h = M.L.norm(p["input_norm"], x, cfg)
    q, k, v = M.L.qkv(p["attn"], h, cfg, None, None, None,
                      rotary=cfg.rotary(0))
    a = M.L.dense(p["attn"]["wo"], M.L.causal_attention(q, k, v).reshape(
        1, 48, -1))
    assert float(jnp.abs(a).max()) > 0          # W_o is not zero
    h2 = M.L.norm(p["post_attn_norm"], x + a, cfg)
    early, late = (np.sort(np.asarray(M._route(p, t, cfg)[0]), -1)
                   for t in (h, h2))
    assert (early != late).any()
    # ... and the program is the early one
    got = _full(params, jnp.asarray(ids), cfg)
    assert _gap(got, _ref_logits(config, ids)) < TOL
    assert _gap(got, _ref_logits(config, ids,
                                 route_from="attention_output")) > 40 * TOL


# ------------------------------- 6. the shares add up to the whole layer
def test_four_shares_of_sixteen_experts_add_up_to_the_layer_of_64():
    """64 ReLU-gated experts, 6 a token, routed ONCE from another tensor than
    the experts multiply: the parts that four chips holding 16 experts each
    compute add up to the reference's whole layer."""
    d, hidden, total, k, T = 32, 24, 64, 6, 40
    config = dict(spec.cell(CELL)[1], hidden_size=d, moe_ffn_hidden_size=hidden,
                  moe_num_primary_experts=total,
                  moe_num_active_primary_experts=k)
    fam = spec.family(config)
    p = X.init_held_experts(jax.random.PRNGKey(6), d, hidden, total, total)
    h, h2 = jax.random.normal(jax.random.PRNGKey(7), (2, T, d))
    ref_p = {"moe.router.kernel": p["router"]["kernel"],
             **{f"moe.experts.{n}": w for n, w in p["experts"].items()}}
    with jax.default_matmul_precision("highest"):
        want = fam.experts(ref_p, h2, fam.route(ref_p, h, config, jnp.matmul),
                           config, jnp.matmul)
        routing = X.route_softmax_topk(h, p["router"]["kernel"], k)
        got, held = 0.0, 0
        for first in range(0, total, 16):
            share = {"experts": {n: w[first:first + 16]
                                 for n, w in p["experts"].items()}}
            y, counts = X.held_experts(share, h2, jnp.ones(T, bool),
                                       first=first, routing=routing,
                                       act=jax.nn.relu, tile=8)
            got, held = got + y, held + int(counts[1])
    assert held == T * k            # every assignment is some share's
    assert np.allclose(np.asarray(routing[1]).sum(-1), 1.0, atol=1e-6)
    assert _gap(got, want) < 1e-5   # float32 sums in another order


# --------------------------------------------------- 7. the engine's rings
def test_a_ring_keeps_what_a_tick_and_a_rejected_draft_can_still_see():
    """paged.ring_blocks' bound, position by position: with ring length R a
    write at position p lands on p - R; whether it is this tick's last
    column or a rejected draft's stale one, that position is outside the
    window of every query that may still be accepted."""
    for window, cols, bs in ((16, 8, 4), (16, 16, 4), (4096, 512, 16),
                             (10, 5, 8)):
        R = bs * paged.ring_blocks(window, cols, bs, 10**6)
        assert window + cols <= R < window + cols + bs
        for n in range(1, cols + 1):      # a tick writes L .. L+n-1 first
            L = 1000
            oldest_seen = L - window + 1  # by its first (accepted) query
            assert (L + n - 1) - R < oldest_seen
    assert paged.ring_blocks(4096, 512, 16, 100) == 100    # a short context


def test_the_scheduler_counts_gives_back_and_refuses_by_kind():
    kinds = (paged.CacheKind("global", 1), paged.CacheKind("window", 3, 16))
    s = Scheduler(_scfg(cache_blocks=40), kinds=kinds)
    ring = s.rings["window"]
    assert ring.entries == 6 and ring.allocator.num_blocks == 3 * 6
    long, short = Request([1] * 50, 14), Request([2] * 5, 3)
    for r in (long, short):
        s.submit(r)
    s.plan()
    # the window kind reserves min(what the request needs, its ring) ...
    assert len(long.blocks) == 16 and len(long.ring_blocks["window"]) == 6
    assert len(short.blocks) == 2 and len(short.ring_blocks["window"]) == 2
    assert (s.device_tables()["window"][long.slot] >= 0).sum() == 6
    # ... a third request that the global kind cannot hold takes neither
    third = s.submit(Request([3] * 90, 6))
    s.plan()
    assert third.state == "waiting" and ring.allocator.free_count == 18 - 8
    for r in (long, short):
        s.finish(r, "completed")
    assert s.allocator.free_count == 40 and ring.allocator.free_count == 18
    assert (s.device_tables()["window"] == -1).all()
    for bad in (dict(prefix_cache=True), dict(prefix_cache=True,
                                              spill_blocks=4)):
        with pytest.raises(ValueError, match="window cache kinds"):
            Scheduler(_scfg(**bad), kinds=kinds)
    for role in ("prefill", "decode"):
        with pytest.raises(ValueError, match="hand-off"):
            Scheduler(_scfg(), role=role, kinds=kinds)
    assert Scheduler(_scfg(prefix_cache=True)).rings == {}   # one kind: as ever


def test_the_engine_serves_the_references_greedy_tokens_past_the_window(toy):
    """ServeEngine over both kinds, speculation on with a drafter that is
    sometimes wrong (prompts that repeat themselves draft; a toy's
    continuations seldom agree): every served token is the plain reference's
    first choice; a slot's resident window positions never pass the ring;
    every block of both kinds comes back."""
    config, model, cfg, params = toy
    engine = ServeEngine(model, cfg, params, _scfg(), mesh=_mesh())
    ring = engine.scheduler.rings[M.WINDOW]
    bound = ring.entries * 4
    assert bound == 16 + 8 and engine.cache[M.WINDOW]["k"].shape == (
        3, 3 * ring.entries, 4, cfg.n_kv_heads, cfg.head_dim)
    assert engine.cache[M.GLOBAL]["k"].shape[:2] == (1, 96)
    rng = np.random.RandomState(7)
    motif = rng.randint(0, cfg.vocab, 6).tolist()
    prompts = [rng.randint(0, cfg.vocab, 50).tolist(), motif * 6,
               rng.randint(0, cfg.vocab, 23).tolist() + motif * 5,
               rng.randint(0, cfg.vocab, 9).tolist()]
    reqs = [engine.submit(p, 12, req_id=f"r{i}") for i, p in enumerate(prompts)]
    most = 0
    while engine.has_work():
        engine.step()
        pool = engine.kv_pool()["kinds"]
        live = [r for r in engine.scheduler.slots if r is not None]
        assert pool[M.WINDOW]["positions_resident"] <= bound * len(live)
        assert pool[M.WINDOW]["used_blocks"] == sum(
            len(r.ring_blocks[M.WINDOW]) for r in live) <= ring.entries * 3
        most = max([most] + [min(r.ctx_len, bound) for r in live])
    assert most == bound                    # contexts did pass the ring
    st = engine.stats()
    assert st["spec"]["drafted_tokens"] > st["spec"]["accepted_tokens"] >= 0
    assert st["moe"]["ticks"] == st["tick"] and st["moe"]["assignments"] > 0
    pool = st["kv_pool"]["kinds"]
    for kind in (M.GLOBAL, M.WINDOW):
        assert pool[kind]["used_blocks"] == 0
        assert pool[kind]["free_blocks"] == pool[kind]["num_blocks"]
    win = pool[M.WINDOW]
    assert win["resident_position_ticks"] < win["full_position_ticks"]
    assert win["window_position_ticks"] <= win["resident_position_ticks"]
    assert win["ring_positions"] == bound and win["slot_ticks"] > st["tick"]
    with pytest.raises(ValueError, match="window cache kinds"):
        engine.export_handoff(reqs[0], 0)
    engine.close()
    for p, r in zip(prompts, reqs):
        assert r.state == "done" and len(r.out_tokens) == 12
        seq = p + r.out_tokens      # padded: one shape, one compilation
        want = reference.logits_at(config, SEED, seq + [0] * (96 - len(seq)),
                                   range(len(p) - 1, len(seq) - 1))
        assert r.out_tokens == np.asarray(jnp.argmax(want, -1)).tolist()


def test_prefix_cache_spill_and_hand_off_are_refused_at_start_up(toy):
    _, model, cfg, params = toy
    for bad, words in ((dict(prefix_cache=True), "prefix cache"),
                       (dict(prefix_cache=True, spill_blocks=4),
                        "prefix cache"),):
        with pytest.raises(ValueError, match=words):
            ServeEngine(model, cfg, params, _scfg(**bad), mesh=_mesh())
    with pytest.raises(ValueError, match="hand-off"):
        ServeEngine(model, cfg, params, _scfg(), mesh=_mesh(), role="decode")


def test_the_engine_samples_on_the_rows_and_packs_them(toy):
    """The module's greedy_cached is the argmax of its apply_cached, and the
    engine's tick holds no [slots, chunk, vocab] slab: what it asks for is
    an id a position."""
    _, model, cfg, params = toy
    cfg = dataclasses.replace(cfg, max_tick_tokens=12)
    blocks = {M.GLOBAL: 2 * 24, M.WINDOW: 2 * 6}
    tables = {k: jnp.arange(n, dtype=jnp.int32).reshape(2, -1)
              for k, n in blocks.items()}
    tok = jnp.asarray(np.random.default_rng(8).integers(0, cfg.vocab, (2, 8)))
    args = (params, tok, cfg, M.init_cache(cfg, blocks, 4), tables,
            jnp.zeros(2, jnp.int32), jnp.asarray([8, 3], jnp.int32))
    logits, _, counters = M.apply_cached(*args)
    ids, _, counters2 = M.greedy_cached(*args)
    assert ids.shape == (2, 8) and ids.dtype == jnp.int32
    assert jnp.array_equal(ids[0], jnp.argmax(logits[0], -1))
    assert jnp.array_equal(ids[1, :3], jnp.argmax(logits[1, :3], -1))
    assert jnp.array_equal(counters, counters2)
    assert int(counters[1]) == 11 * cfg.top_k * cfg.n_layers   # valid rows only
    text = jax.jit(M.greedy_cached, static_argnums=2).lower(*args).as_text()
    assert f"x{cfg.vocab}x" in text.replace("tensor<", "x")
    assert f"2x8x{cfg.vocab}" not in text


def test_the_serve_manifest_knows_the_module(tmp_path):
    cfg = M.CONFIGS["tiny"]
    params = M.init(jax.random.PRNGKey(0), cfg)
    save_servable(str(tmp_path), "swa_moe", cfg, params)
    model, got, _ = load_servable(str(tmp_path), _mesh())
    assert model is M and got == cfg and hash(got) == hash(cfg)
