"""ServeEngine over gated delta-rule layers' cache kinds
(horovod_tpu/models/gdn_hybrid.py; docs/serving.md#replay-kind): the streams
it serves are the plain reference's greedy tokens with a drafter that is
mostly wrong, speculation on and off serve the same tokens, a slot's next
tenant serves what a fresh engine serves, a request waits for blocks, the
scheduler sizes and counts each kind — ONE matrix state a slot —, and
prefix reuse, spill and hand-off are refused."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import gdn_hybrid as M, paged
from horovod_tpu.serve.config import ServeConfig
from horovod_tpu.serve.engine import Scheduler, ServeEngine, decode_width

from perfbench.lib import reference, spec, weights

SEED = 2**31 + 48
CELL = "serve-gdn-mixedlen"
#: a vocabulary so small that a context's last two tokens have nearly
#: always been seen before: ``draft_lookup`` drafts at most ticks, and a
#: toy's continuations seldom agree, so most drafts are rejected
DRAFTING_VOCAB = 8


def _scfg(**kw):
    base = dict(max_slots=3, block_size=4, cache_blocks=96, max_seq_len=96,
                max_batch_tokens=28, prefill_chunk=16, prefix_cache=False)
    base.update(kw)
    return ServeConfig(**base)


def _mesh():
    return jax.sharding.Mesh(np.array(jax.devices()[:1]), ("hvd",))


@pytest.fixture(scope="module")
def toy():
    config = dict(spec.tiny(spec.cell(CELL)[1]), vocab_size=DRAFTING_VOCAB)
    model, cfg = spec.family(config).program(config)
    params = jax.jit(lambda k: weights.make(config, k, jnp.float32))(
        weights.seed_key(SEED))
    return config, model, cfg, params


def _prompts(cfg):
    """Prompts that repeat an n-gram, of lengths on both sides of a chunk
    (16) and of the recurrence's chunk (8)."""
    rng = np.random.RandomState(7)
    motif = rng.randint(0, cfg.vocab, 6).tolist()
    return [rng.randint(0, cfg.vocab, 50).tolist(), motif * 6,
            rng.randint(0, cfg.vocab, 23).tolist() + motif * 5,
            rng.randint(0, cfg.vocab, 5).tolist()]


def _served(engine, prompts, new=14):
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


def test_the_scheduler_sizes_a_replay_kind_by_what_it_declares():
    cfg = M.CONFIGS["tiny"]
    s = Scheduler(_scfg(), kinds=M.cache_kinds(cfg))
    width = decode_width(s.cfg)
    # the conv inputs by paged.state_columns; the matrix states ONE a slot,
    # beside the verify row's rows that a later tick may replay
    assert {n: st.columns for n, st in s.states.items()} == {
        M.CONV: 3 + width, M.DELTA: width - 1}
    assert not s.rings and set(s.device_tables()) == {M.KV}
    assert s.pool_blocks() == {M.KV: 96, M.CONV: (3, 3 + width),
                               M.DELTA: (3, width - 1)}
    plain = Scheduler(_scfg(spec_decode=False), kinds=M.cache_kinds(cfg))
    assert plain.pool_blocks()[M.DELTA] == (3, 1)
    with pytest.raises(ValueError,
                       match=r"state cache kinds \(conv, delta\)"):
        Scheduler(_scfg(prefix_cache=True), kinds=M.cache_kinds(cfg))


def test_the_engine_serves_the_references_greedy_tokens_over_rejected_drafts(
        toy):
    """Four requests through three slots (the fourth takes a slot another
    stream left), speculation on with a drafter that is mostly wrong: every
    served token is the plain reference's first choice, so no rejected row
    left a trace in a matrix state, the ring or a conv column; and the same
    engine with speculation off serves the same tokens."""
    config, model, cfg, params = toy
    engine = ServeEngine(model, cfg, params, _scfg(), mesh=_mesh())
    width = decode_width(engine.cfg)
    H, dk, dv = cfg.lin_heads, cfg.lin_key_dim, cfg.lin_value_dim
    delta = engine.cache[M.DELTA]
    assert delta["S"].shape == (6, 3, 1, H, dv, dk)
    assert delta["S"].dtype == jnp.float32
    assert delta[paged.AT].shape == (6, 3, 1, 1)
    assert delta[paged.AT].dtype == jnp.int32
    assert delta["row"].shape == (6, 3, width - 1, H * (dk + dv + 2))
    assert set(delta) == {"S", paged.AT, "row"}
    assert engine.cache[M.CONV]["u"].shape == (6, 3, 3 + width, cfg.conv_dim)
    # keys and values by head inside a block of 4 positions
    assert engine.cache[M.KV]["k"].shape == (2, 96, cfg.n_heads, 4,
                                             cfg.head_dim)
    assert engine.stats()["kv_pool"]["layout"]["kv/k"] == [0, 1, 2, 3, 4]
    prompts = _prompts(cfg)
    reqs = _served(engine, prompts)
    st = engine.stats()
    drafted, accepted = (st["spec"][k + "_tokens"]
                         for k in ("drafted", "accepted"))
    assert drafted >= 30 and 0 < accepted < drafted / 2
    pool = st["kv_pool"]["kinds"]
    assert set(pool) == {M.KV, M.CONV, M.DELTA}
    assert pool[M.KV]["used_blocks"] == 0
    kind = pool[M.DELTA]
    # a kind's bytes are its pool's: ONE [H, dv, dk] float32 a slot a layer,
    # where it stands, and the ring's rows
    slot_bytes = 6 * (H * dv * dk * 4 + 4
                      + (width - 1) * H * (dk + dv + 2) * 4)
    assert kind["pool_bytes"] == 3 * slot_bytes
    assert kind["slot_bytes"] == slot_bytes
    assert (kind["state"], kind["state_columns"]) == (1, width - 1)
    assert kind["state_bytes_ticks"] == kind["slot_ticks"] * slot_bytes
    # the tick counted its rows: every accepted draft was replayed in each
    # of the six linear layers, and so was every prompt's short tail
    counted = st["moe"]
    assert counted["ticks"] == st["tick"]
    assert counted["gdn_rows"] > counted["gdn_replayed_rows"] >= 6 * accepted
    with pytest.raises(ValueError, match="cache kinds"):
        engine.export_handoff(reqs[0], 0)
    engine.close()
    for p, r in zip(prompts, reqs):
        assert r.out_tokens == _reference_tokens(config, p, r.out_tokens)

    plain = ServeEngine(model, cfg, params, _scfg(spec_decode=False),
                        mesh=_mesh())
    assert plain.cache[M.DELTA]["row"].shape[2] == 1
    assert [r.out_tokens for r in _served(plain, prompts)] == \
        [r.out_tokens for r in reqs]
    plain.close()


def test_a_reused_slot_serves_what_a_fresh_engine_serves(toy):
    """One slot, three streams one after another, the second and third
    shorter than the first: each is admitted into the state, the ring and
    the columns its predecessor left and serves what an engine that never
    held another stream serves."""
    _, model, cfg, params = toy
    rng = np.random.RandomState(9)
    prompts = [rng.randint(0, cfg.vocab, n).tolist() for n in (21, 1, 13)]
    one = ServeEngine(model, cfg, params, _scfg(max_slots=1), mesh=_mesh())
    reused = [r.out_tokens for r in _served(one, prompts, new=6)]
    assert float(jnp.abs(one.cache[M.DELTA]["S"]).max()) > 0   # never reset
    one.close()
    for p, got in zip(prompts, reused):
        fresh = ServeEngine(model, cfg, params, _scfg(max_slots=1),
                            mesh=_mesh())
        assert _served(fresh, [p], new=6)[0].out_tokens == got
        fresh.close()


def test_a_request_that_waits_for_blocks_serves_the_same(toy):
    """A pool too small for three streams at once: the third waits for
    blocks with a slot free, as a long request of the cell's mix does, and
    serves what it serves from a pool that has room."""
    _, model, cfg, params = toy
    rng = np.random.RandomState(11)
    prompts = [rng.randint(0, cfg.vocab, n).tolist() for n in (40, 38, 30)]
    tight = ServeEngine(model, cfg, params, _scfg(cache_blocks=28),
                        mesh=_mesh())
    reqs = [tight.submit(p, 10, req_id=f"w{i}")
            for i, p in enumerate(prompts)]
    waited = False
    while tight.has_work():
        tight.step()
        waited |= (reqs[2].state == "waiting"
                   and sum(r is not None for r in tight.scheduler.slots) < 3
                   and tight.scheduler.allocator.free_count < 10)
    assert waited and all(r.state == "done" for r in reqs)
    tight.close()
    roomy = ServeEngine(model, cfg, params, _scfg(), mesh=_mesh())
    assert [r.out_tokens for r in _served(roomy, prompts, new=10)] == \
        [r.out_tokens for r in reqs]
    roomy.close()


def test_prefix_cache_spill_and_hand_off_are_refused_at_start_up(toy):
    _, model, cfg, params = toy
    for bad in (dict(prefix_cache=True),
                dict(prefix_cache=True, spill_blocks=4)):
        with pytest.raises(ValueError, match="prefix cache"):
            ServeEngine(model, cfg, params, _scfg(**bad), mesh=_mesh())
    with pytest.raises(ValueError, match="hand-off"):
        ServeEngine(model, cfg, params, _scfg(), mesh=_mesh(), role="decode")
