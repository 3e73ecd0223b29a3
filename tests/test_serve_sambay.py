"""ServeEngine over the decoder-hybrid-decoder's four cache kinds
(horovod_tpu/models/sambay.py; docs/serving.md#cache-kinds): the streams it
serves are the plain reference's greedy tokens with a drafter that is mostly
wrong, speculation on and off serve the same tokens, a slot's next tenant
serves what a fresh engine serves, the scheduler sizes and counts each kind,
and prefix reuse, spill and hand-off are refused."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import paged, sambay as M
from horovod_tpu.serve.config import ServeConfig
from horovod_tpu.serve.engine import Scheduler, ServeEngine, decode_width

from perfbench.lib import reference, spec, weights

SEED = 2**31 + 45     # of four seeds tried, the one whose drafts are sometimes right
CELL = "serve-ssm-yoco-reason"
#: a vocabulary so small that a context's last two tokens have nearly
#: always been seen before: ``draft_lookup`` drafts at most ticks, and a
#: toy's continuations seldom agree, so most drafts are rejected
DRAFTING_VOCAB = 8


def _scfg(**kw):
    # (a chunk of 16: the rings hold 6 blocks, of which a verify row of 5
    # gathers the 4 its windows reach and a chunk all)
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
    (16) and of the window (8)."""
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


def test_the_scheduler_sizes_and_counts_four_kinds():
    cfg = M.CONFIGS["tiny"]
    s = Scheduler(_scfg(), kinds=M.cache_kinds(cfg))
    width = decode_width(s.cfg)
    assert {n: st.columns for n, st in s.states.items()} == {
        M.CONV: 3 + width, M.CARRY: 1 + width}
    assert set(s.rings) == {M.WINDOW} and s.rings[M.WINDOW].entries == \
        paged.ring_blocks(cfg.window, 16, 4, 24) == 6
    # the state kinds have no table and no allocator
    assert set(s.device_tables()) == {M.KV, M.WINDOW}
    assert s.pool_blocks() == {M.KV: 96, M.WINDOW: 3 * 6,
                               M.CONV: (3, 3 + width),
                               M.CARRY: (3, 1 + width)}
    with pytest.raises(ValueError, match=r"window cache kinds \(window\) and "
                                         r"state cache kinds \(conv, carry\)"):
        Scheduler(_scfg(prefix_cache=True), kinds=M.cache_kinds(cfg))


def test_the_engine_serves_the_references_greedy_tokens_over_rejected_drafts(
        toy):
    """Four requests through three slots (the fourth takes a slot another
    stream left, and is shorter than the stream before it), speculation on
    with a drafter that is mostly wrong: every served token is the plain
    reference's first choice, so no rejected row left a trace in a carry, a
    conv column or a ring; and the same engine with speculation off serves
    the same tokens."""
    config, model, cfg, params = toy
    engine = ServeEngine(model, cfg, params, _scfg(), mesh=_mesh())
    width = decode_width(engine.cfg)
    assert engine.cache[M.CARRY]["h"].shape == (
        3, 3, 1 + width, cfg.d_state, cfg.d_inner)
    assert engine.cache[M.CARRY]["h"].dtype == jnp.float32
    assert engine.cache[M.CONV]["u"].shape == (3, 3, 3 + width, cfg.d_inner)
    assert engine.cache[M.KV]["k"].shape == (1, 96, 4, cfg.dim // 2)
    prompts = _prompts(cfg)
    reqs = _served(engine, prompts)
    st = engine.stats()
    drafted, accepted = (st["spec"][k + "_tokens"]
                         for k in ("drafted", "accepted"))
    assert drafted >= 30 and 0 < accepted < drafted / 2
    pool = st["kv_pool"]["kinds"]
    assert set(pool) == {M.KV, M.WINDOW, M.CONV, M.CARRY}
    assert pool[M.KV]["used_blocks"] == pool[M.WINDOW]["used_blocks"] == 0
    carry, conv = pool[M.CARRY], pool[M.CONV]
    # a kind's bytes are its pool's: a carry's column is [d_state, d_inner]
    # float32, not ``columns x d x itemsize``
    assert carry["pool_bytes"] == 3 * 3 * (1 + width) * cfg.d_state \
        * cfg.d_inner * 4
    assert conv["pool_bytes"] == 3 * 3 * (3 + width) * cfg.d_inner * 4
    assert (carry["state"], carry["state_columns"]) == (1, 1 + width)
    assert (conv["state"], conv["state_columns"]) == (3, 3 + width)
    for kind in (carry, conv):
        assert kind["slot_ticks"] > st["tick"]
        assert kind["state_bytes_ticks"] == kind["slot_ticks"] \
            * kind["pool_bytes"] // 3
        # a key-value cache of the three layers at those slots' lengths
        assert kind["kv_bytes_ticks"] % (3 * 2 * cfg.dim // 2 * 4) == 0
    assert st["kv_pool"]["pool_bytes"] == sum(
        k["pool_bytes"] for k in pool.values())
    with pytest.raises(ValueError, match="cache kinds"):
        engine.export_handoff(reqs[0], 0)
    engine.close()
    for p, r in zip(prompts, reqs):
        assert r.out_tokens == _reference_tokens(config, p, r.out_tokens)

    plain = ServeEngine(model, cfg, params, _scfg(spec_decode=False),
                        mesh=_mesh())
    assert plain.cache[M.CARRY]["h"].shape[2] == 2
    assert [r.out_tokens for r in _served(plain, prompts)] == \
        [r.out_tokens for r in reqs]
    plain.close()


def test_a_reused_slot_serves_what_a_fresh_engine_serves(toy):
    """One slot, three streams one after another, the second and third
    shorter than the first: each is admitted into the carries, columns and
    ring its predecessor left and serves what an engine that never held
    another stream serves."""
    _, model, cfg, params = toy
    rng = np.random.RandomState(9)
    prompts = [rng.randint(0, cfg.vocab, n).tolist() for n in (21, 1, 13)]
    one = ServeEngine(model, cfg, params, _scfg(max_slots=1), mesh=_mesh())
    reused = [r.out_tokens for r in _served(one, prompts, new=6)]
    assert float(jnp.abs(one.cache[M.CARRY]["h"]).max()) > 0   # never reset
    one.close()
    for p, got in zip(prompts, reused):
        fresh = ServeEngine(model, cfg, params, _scfg(max_slots=1),
                            mesh=_mesh())
        assert _served(fresh, [p], new=6)[0].out_tokens == got
        fresh.close()


def test_prefix_cache_spill_and_hand_off_are_refused_at_start_up(toy):
    _, model, cfg, params = toy
    for bad in (dict(prefix_cache=True),
                dict(prefix_cache=True, spill_blocks=4)):
        with pytest.raises(ValueError, match="prefix cache"):
            ServeEngine(model, cfg, params, _scfg(**bad), mesh=_mesh())
    with pytest.raises(ValueError, match="hand-off"):
        ServeEngine(model, cfg, params, _scfg(), mesh=_mesh(), role="decode")
