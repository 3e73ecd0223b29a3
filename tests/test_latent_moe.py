"""The latent-attention expert decoder (horovod_tpu/models/latent_moe.py,
parallel/expert.py ``held_experts``; docs/serving.md#latent-pool): the cached
path against the full one, the absorbed attention against the expanded, the
expert layer's batch invariance and its shares against the uncut layer of the
benchmark's plain reference (perfbench/families/latent_moe.py), and the
serving engine's block traffic on a pool with no head axis."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import latent_moe as M, paged
from horovod_tpu.parallel import expert as X
from horovod_tpu.serve.config import ServeConfig
from horovod_tpu.serve.engine import ServeEngine, load_servable, save_servable

from perfbench.lib import reference, spec, weights

SEED = 2**31 + 27


def _scfg(**kw):
    base = dict(max_slots=2, block_size=4, cache_blocks=32, max_seq_len=32,
                max_batch_tokens=12, prefill_chunk=8)
    base.update(kw)
    return ServeConfig(**base)


def _mesh():
    return jax.sharding.Mesh(np.array(jax.devices()[:1]), ("hvd",))


@pytest.fixture(scope="module")
def tiny():
    cfg = M.CONFIGS["tiny"]
    return cfg, M.init(jax.random.PRNGKey(0), cfg)


@pytest.fixture(scope="module")
def toy():
    """The benchmark's toy copy of the configuration: (config, module, its
    config object, seeded float32 weights)."""
    config = spec.tiny(spec.cell("serve-moe-mla-decode")[1])
    model, cfg = spec.family(config).program(config)
    params = jax.jit(lambda k: weights.make(config, k, jnp.float32))(
        weights.seed_key(SEED))
    return config, model, cfg, params


def _cached_logits(cfg, params, ids, chunk, block_size=4):
    """Prefill ``ids`` [B, T] in chunks of ``chunk`` (the last one short),
    then one token a step for the last third, through apply_cached."""
    B, T = ids.shape
    n_blocks = -(-T // block_size)
    table = np.arange(B * n_blocks, dtype=np.int32).reshape(B, n_blocks)
    cache = M.init_cache(cfg, B * n_blocks, block_size)
    step = jax.jit(lambda c, t, l, n: M.apply_cached(
        params, t, cfg, c, jnp.asarray(table), l, n))
    split, outs, pos, counts = T - T // 3, [], 0, []
    while pos < T:
        n = min(chunk, split - pos) if pos < split else 1
        width = chunk if pos < split else 3    # a decode row in a wider slab
        tok = np.zeros((B, width), np.int32)
        tok[:, :n] = ids[:, pos:pos + n]
        lg, cache, c = step(cache, jnp.asarray(tok),
                            jnp.full((B,), pos, jnp.int32),
                            jnp.full((B,), n, jnp.int32))
        outs.append(lg[:, :n])
        counts.append(np.asarray(c))
        pos += n
    return jnp.concatenate(outs, 1), np.sum(counts, 0)


# ------------------------------------------------- cached against the full
@pytest.mark.parametrize("chunk", [16, 5])
def test_prefill_in_chunks_then_decode_reproduces_the_full_forward(tiny,
                                                                   chunk):
    cfg, params = tiny
    ids = np.random.default_rng(1).integers(0, cfg.vocab, (2, 40)).astype(
        np.int32)
    full = M.apply(params, jnp.asarray(ids), cfg)
    got, counts = _cached_logits(cfg, params, ids, chunk)
    assert got.shape == full.shape
    assert float(jnp.max(jnp.abs(got - full))) < 2e-5 * float(jnp.std(full))
    # every valid token is routed top_k ways in each expert layer, none
    # dropped: the tiny model holds all its experts
    named = dict(zip(M.TICK_COUNTERS, counts))
    routed_layers = cfg.n_layers - cfg.n_dense
    assert named["assignments"] == ids.size * cfg.top_k * routed_layers
    assert named["assignments_held"] == named["assignments"]
    assert 0 < named["experts_touched"] <= named["ticks"] * routed_layers \
        * cfg.experts_held


@pytest.mark.parametrize("small", [{"SCORE_BYTES": 1}, {"NARROW_COLS": 2},
                                   {"SCORE_BYTES": 1, "NARROW_COLS": 1}])
def test_blocks_of_slots_and_narrow_columns_do_not_change_the_result(
        tiny, small, monkeypatch):
    """The cached attention a block of slots after another (a small
    ``SCORE_BYTES``), and decode rows of a chunk-wide tick attended in
    their first columns only, give what one block over all columns gives."""
    cfg, params = tiny
    ids = np.random.default_rng(2).integers(0, cfg.vocab, (4, 24)).astype(
        np.int32)
    want, _ = _cached_logits(cfg, params, ids, 12)
    for name, value in small.items():
        monkeypatch.setattr(M, name, value)
    got, _ = _cached_logits(cfg, params, ids, 12)     # traced anew
    assert float(jnp.max(jnp.abs(got - want))) < 2e-5


def test_packing_the_valid_tokens_changes_no_logit_and_no_cached_value(tiny):
    """A prefill-wide tick of four slots — two chunks, a decode row, a dead
    slot — with the valid tokens packed to the front of 26 rows
    (``max_tick_tokens``) against the same tick on all 48 positions."""
    cfg, params = tiny
    rng = np.random.default_rng(14)
    S, C, bs = 4, 12, 4
    table = np.arange(S * 8, dtype=np.int32).reshape(S, 8)
    cache = {"latent": jnp.asarray(rng.normal(size=(cfg.n_layers, S * 8, bs,
                                                    cfg.latent_dim)),
                                   jnp.float32)}
    tokens = rng.integers(0, cfg.vocab, (S, C)).astype(np.int32)
    lengths = jnp.asarray([0, 9, 12, 5], jnp.int32)
    n_new = jnp.asarray([12, 1, 12, 0], jnp.int32)

    def tick(cfg):
        return jax.jit(lambda c: M.apply_cached(
            params, jnp.asarray(tokens), cfg, c, jnp.asarray(table), lengths,
            n_new))(cache)
    want, want_cache, want_n = tick(cfg)
    got, got_cache, got_n = tick(dataclasses.replace(cfg, max_tick_tokens=26))
    valid = np.arange(C)[None] < np.asarray(n_new)[:, None]
    assert float(jnp.max(jnp.abs(got - want)[valid])) < 2e-5
    # positions that were not packed read zero: 26 rows hold 25 tokens
    assert np.asarray(got)[~valid].any(-1).sum() == 1
    assert float(jnp.max(jnp.abs(got_cache["latent"]
                                 - want_cache["latent"]))) < 2e-5
    assert np.array_equal(np.asarray(got_n), np.asarray(want_n))
    assert int(got_n[1]) == 25 * cfg.top_k * (cfg.n_layers - cfg.n_dense)


def test_absorbed_attention_is_the_expanded_attention(tiny):
    """One layer, random queries and a random cached latent: scores against
    the latent with ``W^K`` absorbed into the query and values from the
    latent through ``W^V``, against per-head K and V expanded from it."""
    cfg, params = tiny
    a = params["layers"][1]["attn"]
    S, C, ctx = 2, 3, 16
    rng = np.random.default_rng(3)
    latent = jnp.asarray(rng.normal(size=(S, ctx, cfg.latent_dim)),
                         jnp.float32)
    q_nope = jnp.asarray(rng.normal(size=(S, C, cfg.n_heads,
                                          cfg.qk_nope_dim)), jnp.float32)
    q_rope = jnp.asarray(rng.normal(size=(S, C, cfg.n_heads,
                                          cfg.qk_rope_dim)), jnp.float32)
    lengths = np.array([9, 13], np.int32)
    positions = jnp.asarray(lengths[:, None] + np.arange(C)[None])
    wk, wv = M._wkv_b(a, cfg)
    q = jnp.concatenate([jnp.einsum("schn,lhn->schl", q_nope, wk), q_rope],
                        -1)
    # as apply_cached runs it: the latent lies in a pool of blocks of 4, a
    # slot's table is its row of block numbers, and the shared loop hands
    # the model's ``attend`` a tile of it at a time (paged.attend_by_blocks)
    cache = {"latent": latent.reshape(1, S * ctx // 4, 4, cfg.latent_dim)}
    tables = jnp.arange(S * ctx // 4, dtype=jnp.int32).reshape(S, ctx // 4)
    o = paged.attend_by_blocks(
        M.latent_attend(cfg), (q, positions, tables),
        jnp.full((S,), C, jnp.int32), S, C,
        bound=paged.Bound(jnp.asarray(lengths), cache, 0,
                          paged.Slab(None, None)))      # [S, H, C, kv_rank]
    o = jnp.swapaxes(o, 1, 2)
    absorbed = jnp.einsum("schl,lhv->schv", o, wv)
    c_kv, k_rope = latent[..., :cfg.kv_rank], latent[..., cfg.kv_rank:]
    k = jnp.concatenate(
        [jnp.einsum("skl,lhn->skhn", c_kv, wk),
         jnp.broadcast_to(k_rope[:, :, None], (S, ctx, cfg.n_heads,
                                               cfg.qk_rope_dim))], -1)
    v = jnp.einsum("skl,lhv->skhv", c_kv, wv)
    s = jnp.einsum("schd,skhd->shck", jnp.concatenate([q_nope, q_rope], -1),
                   k) / np.sqrt(cfg.qk_dim)
    mask = jnp.arange(ctx)[None, None, None, :] <= positions[:, None, :, None]
    expanded = jnp.einsum("shck,skhv->schv",
                          jax.nn.softmax(jnp.where(mask, s, -jnp.inf), -1), v)
    assert float(jnp.max(jnp.abs(absorbed - expanded))) < 1e-5


# ----------------------------------------------------------- the expert layer
def _moe_params(cfg, key=5):
    return X.init_held_experts(jax.random.PRNGKey(key), cfg.dim,
                               cfg.moe_hidden, cfg.n_experts, cfg.n_experts,
                               jnp.float32)


def _share(p, first, held):
    """The pytree a chip holding experts first..first+held-1 would have."""
    return {"router": p["router"],
            "experts": {k: w[first:first + held]
                        for k, w in p["experts"].items()}}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_a_token_alone_equals_itself_among_fifteen_others_bit_for_bit(dtype):
    cfg = M.CONFIGS["tiny"]
    p = jax.tree_util.tree_map(lambda w: w.astype(dtype),
                               _share(_moe_params(cfg), 2, 4))
    x = jnp.asarray(np.random.default_rng(6).normal(size=(16, cfg.dim)),
                    dtype)
    run = jax.jit(lambda x, valid: X.held_experts(
        p, x, valid, first=2, k=cfg.top_k, scale=cfg.route_scale, tile=4))
    among, counts = run(x, jnp.ones(16, bool))
    assert int(counts[1]) > 0       # some assignment is held here
    for t in (0, 7, 15):
        alone, c1 = run(x, jnp.arange(16) == t)
        assert np.array_equal(np.asarray(alone[t]), np.asarray(among[t])), t
        assert int(c1[0]) == cfg.top_k
        # rows that are not valid are routed nowhere
        assert not np.asarray(alone)[np.arange(16) != t].any()
        # and a slab of another height gives the same bits
        lone, _ = jax.jit(lambda x: X.held_experts(
            p, x, jnp.ones(1, bool), first=2, k=cfg.top_k,
            scale=cfg.route_scale, tile=4))(x[t:t + 1])
        assert np.array_equal(np.asarray(lone[0]), np.asarray(among[t])), t


def test_no_assignment_is_dropped_when_every_token_picks_the_same_experts():
    """All tokens alike: every one of them lands on the same top-k experts,
    far past any tile, and each gets its whole sum."""
    cfg = M.CONFIGS["tiny"]
    p = _moe_params(cfg)
    row = np.random.default_rng(8).normal(size=(1, cfg.dim))
    x = jnp.asarray(np.repeat(row, 40, 0), jnp.float32)
    y, counts = X.held_experts(p, x, jnp.ones(40, bool), first=0,
                               k=cfg.top_k, scale=cfg.route_scale, tile=4)
    named = dict(zip(X.HELD_COUNTERS, np.asarray(counts)))
    assert named == {"assignments": 40 * cfg.top_k,
                     "assignments_held": 40 * cfg.top_k,
                     "experts_touched": cfg.top_k, "load_max": 40}
    assert float(jnp.max(jnp.abs(y - y[0]))) == 0.0 and float(
        jnp.max(jnp.abs(y[0]))) > 0


def test_the_router_scores_in_float32_whatever_the_models_type():
    cfg = M.CONFIGS["tiny"]
    w = _moe_params(cfg)["router"]["kernel"]
    x = jnp.asarray(np.random.default_rng(9).normal(size=(64, cfg.dim)),
                    jnp.float32)
    idx32, g32 = X.route_sigmoid_topk(x, w, cfg.top_k, cfg.route_scale)
    idx16, g16 = X.route_sigmoid_topk(x.astype(jnp.bfloat16).astype(
        jnp.float32), w, cfg.top_k, cfg.route_scale)
    assert g32.dtype == g16.dtype == jnp.float32
    text = str(jax.make_jaxpr(lambda x, w: X.route_sigmoid_topk(
        x, w, cfg.top_k, cfg.route_scale))(x.astype(jnp.bfloat16),
                                           w.astype(jnp.bfloat16)))
    assert "preferred_element_type=float32" in text and "HIGHEST" in text
    np.testing.assert_allclose(np.asarray(g32.sum(-1)), cfg.route_scale,
                               rtol=1e-6)


def test_the_shares_add_up_to_the_uncut_layer_of_the_reference(toy):
    """8 experts in 4 shares of 2: the routed parts that the four shares
    compute, with the shared expert counted once, are the reference's uncut
    expert layer."""
    config, _, cfg, _ = toy
    fam = spec.family(config)
    total = cfg.n_experts
    whole = dict(config, n_routed_experts=total, deployment=dict(
        config["deployment"], first_expert_held=0))
    assert fam.dims(whole)["held"] == fam.dims(whole)["total"] == 8
    full_cfg = dataclasses.replace(cfg, experts_held=total, first_expert=0)
    p = M.init_layer(jax.random.PRNGKey(12), full_cfg, routed=True)["moe"]
    h = jnp.asarray(np.random.default_rng(13).normal(size=(24, cfg.dim)),
                    jnp.float32)
    ref_p = {"moe.router.kernel": p["router"]["kernel"],
             **{f"moe.experts.{k}": w for k, w in p["experts"].items()},
             **{f"moe.shared.{k}.kernel": w["kernel"]
                for k, w in p["shared"].items()}}
    with jax.default_matmul_precision("highest"):
        want = fam.gated(h, ref_p["moe.shared.w_gate.kernel"],
                         ref_p["moe.shared.w_up.kernel"],
                         ref_p["moe.shared.w_down.kernel"], jnp.matmul) \
            + fam.routed(ref_p, h, whole, jnp.matmul)
        got, held = M._gated(p["shared"], h), 0
        for first in range(0, total, 2):
            y, counts = X.held_experts(
                _share(p, first, 2), h, jnp.ones(24, bool), first=first,
                k=cfg.top_k, scale=cfg.route_scale, tile=4)
            got, held = got + y, held + int(counts[1])
    assert held == 24 * cfg.top_k       # every assignment is some share's
    assert float(jnp.max(jnp.abs(got - want))) < 1e-5 * float(jnp.std(want))


# -------------------------------------------------- the engine on this pool
def test_the_pool_holds_the_latent_and_no_head_axis(tiny):
    cfg, params = tiny
    # the tick's budget is the engine's, whatever the config's author wrote:
    # 12 tokens a tick are packed into 12 rows of the 2 x 8 slab
    engine = ServeEngine(M, dataclasses.replace(cfg, max_tick_tokens=8),
                         params, _scfg(), mesh=_mesh())
    assert engine.model_cfg.max_tick_tokens == 12
    assert cfg.max_tick_tokens == 0
    pool = engine.cache["latent"]
    # a position's 40 values and zeros up to the device's 128 lanes: the
    # shape puts the pool's layout on the chip (PERF.md §6, PR 36)
    assert cfg.latent_dim == cfg.kv_rank + cfg.qk_rope_dim == 40
    assert pool.shape == (cfg.n_layers, 32, 4, 128)
    assert set(engine.cache) == {"latent"}
    assert engine.kv_pool()["pool_bytes"] == 32 * 4 * cfg.n_layers * 128 * 4
    assert engine._cache_shd.spec == jax.sharding.PartitionSpec(
        None, "hvd", None, None)
    engine.close()


def test_copy_blocks_and_block_transfer_on_the_four_axis_pool(tiny):
    cfg, params = tiny
    engine = ServeEngine(M, cfg, params, _scfg(), mesh=_mesh())
    fill = np.random.default_rng(4).normal(size=engine.cache["latent"].shape)
    engine.cache = {"latent": jnp.asarray(fill, jnp.float32)}
    out = M.copy_blocks(engine.cache, jnp.array([3, 0], jnp.int32),
                        jnp.array([5, 32], jnp.int32))["latent"]
    assert np.array_equal(np.asarray(out[:, 5]), np.asarray(out[:, 3]))
    assert np.array_equal(np.asarray(out[:, :5]),
                          fill[:, :5].astype(np.float32))   # dst 32 dropped
    payload = engine._read_block(7)
    assert list(payload) == ["latent"]
    assert payload["latent"].shape == (cfg.n_layers, 4, cfg.pool_dim)
    engine._write_block(9, payload)
    assert np.array_equal(np.asarray(engine.cache["latent"][:, 9]),
                          np.asarray(engine.cache["latent"][:, 7]))
    engine.close()


def _is_greedy_by_full_forward(cfg, params, prompt, out, pad_to=32):
    """Whether ``out`` is the full forward's greedy continuation of
    ``prompt``: teacher-forced over prompt + out (padded at the end, which a
    causal model does not see), every served token is the first choice at
    its position; by induction that is greedy decoding."""
    seq = prompt + out
    ids = np.zeros((1, pad_to), np.int32)
    ids[0, :len(seq)] = seq
    lg = _full(params, jnp.asarray(ids), cfg)
    first = np.asarray(jnp.argmax(lg[0], -1))
    return first[len(prompt) - 1:len(seq) - 1].tolist() == out


_full = jax.jit(M.apply, static_argnums=2)


@pytest.mark.parametrize("path", ["cow", "spill", "handoff"])
def test_prefix_hits_cow_and_spill_keep_the_engines_tokens(tiny, path):
    """Shared prefixes (whole blocks and a divergence inside one); pool
    pressure that spills the prefix to the host and reloads it; a prefill
    engine's hand-off exported, sent as JSON and imported by a decode
    engine: the tokens stay the full forward's greedy ones.  The pool's
    last axis is the latent and zeros up to 128 lanes (``init_cache``):
    ``paged.read_block`` / ``write_block`` move a block as it lies, zeros
    and all, nothing ever lands in the zero columns, and ``kv_pool`` says
    what the pool holds, logically and as laid out, and where it lies."""
    import json
    cfg, params = tiny
    rng = np.random.RandomState(5)
    engines = []

    def build(**kw):
        role = kw.pop("role", "mixed")
        engines.append(ServeEngine(M, cfg, params, _scfg(**kw), mesh=_mesh(),
                                   role=role))
        return engines[-1]

    def greedy(prompt, out, n):
        return len(out) == n and _is_greedy_by_full_forward(
            cfg, params, prompt, out)

    if path == "cow":
        system = rng.randint(0, cfg.vocab, 9).tolist()
        prompts = [system + [11, 12, 11, 12], system + [11, 12, 11, 99],
                   system + rng.randint(0, cfg.vocab, 3).tolist()]
        engine = build(prefill_chunk=6, spec_k=4)
        reqs = [engine.submit(p, 6, req_id=f"r{i}")
                for i, p in enumerate(prompts)]
        engine.flush()
        assert all(greedy(p, r.out_tokens, 6) for p, r in zip(prompts, reqs))
        st = engine.stats()
        assert st["prefix_cache"]["hits"] >= 1
        assert st["prefix_cache"]["cow_copies"] >= 1
        assert st["moe"]["ticks"] == st["tick"] and st["moe"]["assignments"] > 0
    elif path == "spill":
        pa, pb = (rng.randint(0, cfg.vocab, 12).tolist() for _ in range(2))
        engine = build(max_slots=1, cache_blocks=6, spill_blocks=8,
                       spec_decode=False)
        for i, p in enumerate((pa, pb, pa)):
            req = engine.submit(p, 4, req_id=f"s{i}")
            engine.flush()
            assert greedy(p, req.out_tokens, 4), i
        spill = engine.kv_pool()["spill"]
        assert spill["spilled_total"] >= 1 and spill["reloaded_total"] >= 1
    else:
        prompts = [rng.randint(0, cfg.vocab, n).tolist() for n in (9, 13)]
        pre = build(spec_decode=False, role="prefill")
        engine = build(spec_decode=False, role="decode")
        for i, p in enumerate(prompts):
            pre.submit(p, 6, req_id=f"r{i}")
        handoffs = []
        while pre.has_work():
            handoffs.extend(pre.step().get("handoff", []))
        assert handoffs and all(
            b["latent"]["shape"] == [cfg.n_layers, 4, cfg.pool_dim]
            for h in handoffs for b in h["blocks"])
        reqs = [engine.import_prefill(json.loads(json.dumps(h)))
                for h in handoffs]
        engine.flush()
        assert all(greedy(p, r.out_tokens, 6) for p, r in zip(prompts, reqs))
    for e in engines:
        pool, kv = np.asarray(e.cache["latent"]), e.kv_pool()
        assert pool.shape[-1] == cfg.pool_dim == 128
        assert np.any(pool[..., :cfg.latent_dim])
        assert not np.any(pool[..., cfg.latent_dim:])
        assert kv["pool_bytes"] == kv["resident_bytes"] == pool.nbytes
        assert kv["layout"] == {"latent": [0, 1, 2, 3]}
        e.close()


def test_the_engine_serves_the_references_greedy_tokens(toy):
    """ServeEngine on the new module, a share of experts held (4 of 8 from
    number 2), float32: every served token is the benchmark's plain
    reference's first choice at its position."""
    config, model, cfg, params = toy
    assert (cfg.experts_held, cfg.n_experts, cfg.first_expert) == (4, 8, 2)
    engine = ServeEngine(model, cfg, params, _scfg(max_seq_len=40),
                         mesh=_mesh())
    rng = np.random.RandomState(11)
    prompts = [rng.randint(0, cfg.vocab, n).tolist() for n in (9, 4, 17, 11)]
    reqs = [engine.submit(p, 6, req_id=f"r{i}") for i, p in enumerate(prompts)]
    engine.flush()
    moe = engine.stats()["moe"]
    assert 0 < moe["assignments_held"] < moe["assignments"]
    engine.close()
    for p, r in zip(prompts, reqs):
        assert r.state == "done" and len(r.out_tokens) == 6
        seq = p + r.out_tokens      # padded: one shape, one compilation
        want = reference.logits_at(config, SEED, seq + [0] * (40 - len(seq)),
                                   range(len(p) - 1, len(seq) - 1))
        assert r.out_tokens == np.asarray(jnp.argmax(want, -1)).tolist()


def test_the_serve_manifest_knows_the_module(tiny, tmp_path):
    cfg, params = tiny
    save_servable(str(tmp_path), "latent_moe", cfg, params)
    model, got, _ = load_servable(str(tmp_path), _mesh())
    assert model is M and got == cfg
