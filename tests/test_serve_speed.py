"""Serving raw-speed legs (serve/engine.py; docs/serving.md#raw-speed):
refcounted radix prefix cache (match/insert/evict/CoW), the
new-blocks-only admission math, n-gram draft lookup, and the
determinism proof — engine output byte-identical to reference greedy
under every prefix x chunked x spec combination.  Module basename is
unique across tests/ and tests/integration/ (pytest basename-collision
gotcha)."""

import jax
import jax.numpy as jnp
import dataclasses

import numpy as np
import pytest

from horovod_tpu.serve.config import ServeConfig
from horovod_tpu.serve.engine import (BlockAllocator, PrefixCache,
                                      Request, Scheduler, ServeEngine)
from test_serve import _reference_greedy


def _cfg(**kw):
    base = dict(max_slots=2, block_size=4, cache_blocks=16, max_seq_len=32,
                max_batch_tokens=16, prefill_chunk=8)
    base.update(kw)
    return ServeConfig(**base)


def _one_device_mesh():
    return jax.sharding.Mesh(np.array(jax.devices()[:1]), ("hvd",))


# ------------------------------------------------- refcounted allocator
def test_allocator_refcounts_shared_blocks():
    """A shared block returns to the free list only when its LAST owner
    frees it; LIFO order is preserved for the final release."""
    a = BlockAllocator(4)
    blocks = a.alloc(2)
    assert blocks == [0, 1] and a.free_count == 2
    a.incref(blocks)            # second owner (the cache / a matcher)
    a.free(blocks)
    assert a.free_count == 2    # still referenced: nothing freed
    assert a.ref(0) == 1 and a.ref(1) == 1
    a.free(blocks)
    assert a.free_count == 4 and a.ref(0) == 0
    assert a.alloc(2) == [0, 1]  # LIFO reuse intact after refcounting


# ------------------------------------------------------ radix prefix tree
def test_prefix_cache_full_block_match_and_dedup():
    a = BlockAllocator(8)
    pc = PrefixCache(4, a)
    prompt = list(range(10))            # 2 full blocks + 2-token tail
    row = a.alloc(3)
    pc.insert(prompt, row)
    assert pc.size == 3 and all(a.ref(b) == 2 for b in row)
    # identical prompt: matches both full blocks; the tail is capped at
    # prompt_len - 1 = 9, so only 1 of the 2 tail tokens is shareable —
    # via CoW on the partial block.
    full, cow, hit = pc.match(prompt)
    assert full == row[:2] and cow == (row[2], 1) and hit == 9
    # dedup: re-inserting the same prompt with different blocks keeps
    # the existing nodes (the duplicate's blocks stay request-owned)
    row2 = a.alloc(3)
    pc.insert(prompt, row2)
    assert pc.size == 3 and all(a.ref(b) == 1 for b in row2)


def test_prefix_cache_cow_on_divergence_within_block():
    """Divergence INSIDE a cached block is shared copy-on-write: the
    matcher gets (src_block, n_valid) for the common positions."""
    a = BlockAllocator(8)
    pc = PrefixCache(4, a)
    prompt_a = [1, 2, 3, 4, 5, 6, 7]    # 1 full block + tail [5, 6, 7]
    row = a.alloc(2)
    pc.insert(prompt_a, row)
    # b shares the full block and the first 2 tail tokens, then diverges
    full, cow, hit = pc.match([1, 2, 3, 4, 5, 6, 99, 100, 101])
    assert full == [row[0]] and cow == (row[1], 2) and hit == 6
    # no common prefix at all -> clean miss
    assert pc.match([9, 9, 9, 9, 9]) == ([], None, 0)


def test_prefix_cache_lru_eviction_skips_referenced_leaves():
    a = BlockAllocator(4)
    pc = PrefixCache(4, a)
    r1, r2 = a.alloc(1), a.alloc(1)
    pc.insert([1, 2, 3, 4], r1)         # older leaf
    pc.insert([5, 6, 7, 8], r2)         # newer leaf
    a.free(r1)
    a.free(r2)                          # both now cache-only (ref 1)
    a.incref(r2)                        # ...but r2 gains a sequence ref
    assert pc.evict(2) == 1             # only the unreferenced LRU leaf
    assert pc.size == 1 and a.ref(r1[0]) == 0 and a.ref(r2[0]) == 2


# ----------------------------------------------- admission math (fix)
def test_admission_counts_only_new_blocks():
    """THE scheduler admission fix: with shared blocks resident, the
    worst-case reservation counts only NEW blocks — the conservative
    total-need math would refuse this admissible request."""
    s = Scheduler(_cfg(max_slots=2, cache_blocks=4, block_size=4,
                       max_seq_len=16))
    first = s.submit(Request([1] * 8, 4, req_id="first"))  # needs 3
    s.plan()
    first.pos = first.ctx_len = 8
    s.register_prefix(first)            # prompt blocks become shareable
    s.finish(first, "completed")
    assert s.allocator.free_count == 2  # 2 of 3 blocks stay cached
    second = s.submit(Request([1] * 8, 4, req_id="second"))
    plan = s.plan()
    # need=3 > free=2 would block; sharing maps 1 full block + a CoW
    # tail (7 of 8 prompt tokens resident), so only 2 NEW blocks are
    # reserved and the request admits with 1 token left to compute.
    assert plan and plan[0][1] is second
    assert second.pos == 7 and len(second.blocks) == 3
    assert second.blocks[0] == first_block_of(s)
    # the divergent tail block is cloned into the first NEW block
    copies = s.take_copies()
    assert len(copies) == 1 and copies[0][1] == second.blocks[1]


def first_block_of(s):
    """The tree's root full-block node (single chain in these tests)."""
    (child,) = s.prefix.root.children.values()
    return child.block


def test_admission_evicts_lru_cache_blocks_when_pool_dry():
    """An admission that cannot get its new blocks evicts unreferenced
    cached leaves (LRU) instead of head-of-line blocking forever."""
    s = Scheduler(_cfg(max_slots=2, cache_blocks=4, block_size=4,
                       max_seq_len=16))
    a = s.submit(Request([1] * 8, 4, req_id="a"))
    s.plan()
    a.pos = a.ctx_len = 8
    s.register_prefix(a)
    s.finish(a, "completed")
    assert s.allocator.free_count == 2
    # a disjoint prompt shares nothing: needs 3 fresh blocks > 2 free ->
    # the LRU cached leaf is evicted to make room
    b = s.submit(Request([9] * 8, 4, req_id="b"))
    plan = s.plan()
    assert plan and plan[0][1] is b and len(b.blocks) == 3
    assert s.prefix.evictions >= 1


# --------------------------------------------------------- draft lookup
def test_ngram_draft_lookup_prompt_and_self():
    """Prompt-lookup drafting: the most recent PRIOR occurrence of the
    final bigram proposes its continuation; a repeating tail drafts the
    repetition; no occurrence drafts nothing."""
    r = Request([5, 1, 2, 9, 7, 1, 2], 8)
    assert r.draft_lookup(3) == [9, 7, 1]   # bigram (1,2) seen at pos 1
    r.out_tokens = [9]                      # context ...1, 2, 9
    assert r.draft_lookup(2) == [7, 1]      # bigram (2,9) seen at pos 2
    rep = Request([4, 4, 4], 8)
    assert rep.draft_lookup(2) == [4]       # self-repetition, no self-match
    assert Request([1, 2, 3], 8).draft_lookup(2) == []
    assert Request([1, 2], 8).draft_lookup(2) == []


def test_plan_budget_accounts_draft_tokens():
    """A decode slot is charged the columns its verify row may fill, 1 +
    spec_k, whatever the device will draft for it (the plan does not wait
    to learn it) — less where the tick budget or the row's width is short.
    The remaining generation caps the draft on the device
    (test_device_drafter_caps); the plan only leaves out a stream that the
    unfenced tick is sure to end."""
    s = Scheduler(_cfg(max_slots=2, max_batch_tokens=6, prefill_chunk=5,
                       spec_k=4))
    d = s.submit(Request([7, 8, 7, 8, 7], 8, req_id="d"))
    e = s.submit(Request([1, 2, 3], 8, req_id="e"))
    s.plan()
    for r in (d, e):
        r.pos = r.ctx_len = r.prompt_len
        r.state = "decode"
        r.out_tokens = [8]
    # spec_k=4 / row width-1=4 / budget-1=5: d's row is 1 + 4, and e's the
    # one column the budget of 6 has left
    assert s.plan() == [(0, d, 5), (1, e, 1)]
    s.cfg = dataclasses.replace(s.cfg, spec_k=2)
    assert s.plan() == [(0, d, 3), (1, e, 3)]
    # one token of generation left and a launched row that will emit it:
    # the stream ends in the unfenced tick, nothing is planned for it
    d.out_tokens = [0] * 7
    d.unfenced = 1
    assert s.plan() == [(1, e, 3)]
    d.unfenced = 0      # fenced: the last token is still to run
    assert s.plan() == [(0, d, 3), (1, e, 3)]


# ---------------------------------------------- determinism proof (THE
# acceptance contract: every leg combination emits exactly the plain
# greedy reference tokens)
@pytest.fixture(scope="module")
def llama_tiny():
    from horovod_tpu.models import llama
    cfg = llama.CONFIGS["tiny"]
    return llama, cfg, llama.init(jax.random.PRNGKey(0), cfg)


def _speed_prompts(vocab):
    """Shared-prefix + n-gram-friendly traffic: a common 9-token system
    prefix, repetitive tails (prompt-lookup hits), one divergent-tail
    pair (CoW inside a partial block)."""
    rng = np.random.RandomState(5)
    system = rng.randint(0, vocab, 9).tolist()
    return [
        system + [11, 12, 11, 12],
        system + [11, 12, 11, 99],      # diverges inside the tail block
        system + rng.randint(0, vocab, 3).tolist(),
    ]


def _run_engine(model, cfg, params, scfg, prompts, n_new):
    engine = ServeEngine(model, cfg, params, scfg,
                         mesh=_one_device_mesh())
    reqs = [engine.submit(p, n_new, req_id=f"r{i}")
            for i, p in enumerate(prompts)]
    engine.flush()
    assert all(r.state == "done" for r in reqs)
    return engine, [r.out_tokens for r in reqs]


def test_engine_all_legs_on_matches_reference_greedy(llama_tiny):
    """Fast-tier gate: prefix cache + chunked prefill + spec all ON,
    outputs byte-identical to the reference, and every leg verifiably
    FIRED (hits, chunks, accepted drafts)."""
    model, cfg, params = llama_tiny
    # A zeroed lm_head: every logit is 0, the argmax of a tie is token 0 on
    # any platform, so from the third output on the drafter sees the run
    # (0, 0) and proposes it, and greedy must accept.  Whether a random
    # checkpoint's own trajectory repeats a bigram within 10 tokens depends
    # on the platform's rounding (on some CPUs it never drafts); the eight
    # cases of the determinism matrix below hold the equality on
    # trained-shaped weights.
    params = dict(params, lm_head=jax.tree_util.tree_map(
        jnp.zeros_like, params["lm_head"]))
    prompts = _speed_prompts(cfg.vocab)
    scfg = _cfg(max_slots=2, cache_blocks=32, max_batch_tokens=12,
                prefill_chunk=6, spec_k=4)
    engine, outs = _run_engine(model, cfg, params, scfg, prompts, 10)
    assert outs[0] == [0] * 10
    for i, (p, out) in enumerate(zip(prompts, outs)):
        assert out == _reference_greedy(model, cfg, params, p, 10), i
    stats = engine.stats()
    assert stats["prefix_cache"]["hits"] >= 1
    assert stats["prefix_cache"]["cow_copies"] >= 1
    assert stats["prefill_chunks"] >= len(prompts) + 1  # chunking split
    assert stats["spec"]["drafted_tokens"] >= 1
    assert engine._spec_accepted >= 1  # n-gram tails actually accepted
    assert stats["spec"]["accept_rate"] is not None
    # The tokens above are 0 whatever the pool holds; the pool is not.  One
    # request alone, so both allocators hand out the same blocks: the cells
    # the [slots, 1 + spec_k] verify rows wrote (several valid columns a row
    # through paged.write_index) are the ones single-token decode writes, and
    # hold the same K/V — which past layer 0 depend on the gathered context.
    pools = []
    for spec in (True, False):
        one = _cfg(max_slots=2, cache_blocks=32, max_batch_tokens=12,
                   prefill_chunk=6, spec_k=4, spec_decode=spec)
        e, _ = _run_engine(model, cfg, params, one, prompts[:1], 10)
        assert (e._spec_accepted >= 1) == spec
        pools.append(jax.device_get(e.cache))
    for name, with_spec in pools[0].items():
        written = np.abs(pools[1][name]).sum(axis=(0, 3, 4)) > 0
        assert written.sum() == len(prompts[0]) + 10 - 1   # last token unfed
        assert np.array_equal(np.abs(with_spec).sum(axis=(0, 3, 4)) > 0,
                              written), name
        np.testing.assert_allclose(with_spec, pools[1][name],
                                   rtol=1e-5, atol=1e-6, err_msg=name)


@pytest.mark.parametrize("prefix", [False, True])
@pytest.mark.parametrize("chunked", [False, True])
@pytest.mark.parametrize("spec", [False, True])
def test_determinism_matrix_all_leg_combinations(llama_tiny, prefix,
                                                 chunked, spec):
    """The full matrix (prefix on/off x chunked on/off x spec on/off):
    byte-identical to plain greedy in every cell, cold AND warm (the
    warm wave replays the same prompts against a populated prefix
    cache) — the property PR 10's journal redrive and the lockstep plan
    stream depend on."""
    model, cfg, params = llama_tiny
    prompts = _speed_prompts(cfg.vocab)[:2]
    scfg = _cfg(max_slots=2, cache_blocks=32, max_batch_tokens=16,
                prefill_chunk=5 if chunked else 16,
                prefix_cache=prefix, spec_decode=spec, spec_k=4)
    engine = ServeEngine(model, cfg, params, scfg,
                         mesh=_one_device_mesh())
    waves = []
    for wave in ("cold", "warm"):
        reqs = [engine.submit(p, 5, req_id=f"{wave}{i}")
                for i, p in enumerate(prompts)]
        engine.flush()
        assert all(r.state == "done" for r in reqs)
        waves.append([r.out_tokens for r in reqs])
    if prefix:
        assert engine.stats()["prefix_cache"]["hits"] >= 1  # warm wave hit
    for i, p in enumerate(prompts):
        ref = _reference_greedy(model, cfg, params, p, 5)
        for wave, outs in zip(("cold", "warm"), waves):
            assert outs[i] == ref, \
                f"prefix={prefix} chunked={chunked} spec={spec} " \
                f"{wave} req {i}"


def test_prefix_hits_shrink_prefill_work(llama_tiny):
    """The perf mechanism itself: a repeated prompt prefills in fewer
    chunks (ticks) than its first occurrence — the TTFT lever."""
    model, cfg, params = llama_tiny
    prompt = np.random.RandomState(8).randint(0, cfg.vocab, 20).tolist()
    scfg = _cfg(max_slots=1, cache_blocks=16, max_batch_tokens=8,
                prefill_chunk=4, spec_k=3, max_seq_len=32)
    engine = ServeEngine(model, cfg, params, scfg,
                         mesh=_one_device_mesh())
    r1 = engine.submit(prompt, 2, req_id="cold")
    engine.flush()
    cold_chunks = engine._prefill_chunks
    assert cold_chunks == 5                     # 20 tokens / chunk 4
    r2 = engine.submit(prompt, 2, req_id="warm")
    engine.flush()
    assert engine._prefill_chunks == cold_chunks + 1  # 1 token recomputed
    assert r2.out_tokens == r1.out_tokens       # and identical output
    st = engine.stats()["prefix_cache"]
    assert st["hit_tokens"] == 19 and st["blocks_shared"] == 4
