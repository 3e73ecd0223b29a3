"""Serving plane (horovod_tpu/serve; docs/serving.md): the scheduler's
admission/eviction discipline, paged-cache block reuse, the prefill+decode
≡ full-forward equivalence on both model families, router backpressure,
and the HOROVOD_SERVE_* knob validation contract."""

import dataclasses
import json
import re
import threading
import time
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.serve.config import (ServeConfig, from_knobs,
                                      validate_serve_knobs)
from horovod_tpu.serve.engine import (BlockAllocator, Request, Scheduler,
                                      ServeEngine, decode_width, samples_read,
                                      tick_width)
from horovod_tpu.utils.profiler import compile_counts


def _cfg(**kw):
    base = dict(max_slots=2, block_size=4, cache_blocks=16, max_seq_len=32,
                max_batch_tokens=16, prefill_chunk=8)
    base.update(kw)
    return ServeConfig(**base)


def _one_device_mesh():
    return jax.sharding.Mesh(np.array(jax.devices()[:1]), ("hvd",))


# ------------------------------------------------------------- scheduler
def test_scheduler_admits_fcfs_within_token_budget():
    """One tick's plan: decode slots first (1 token each), then prefill
    continuations, then FCFS admissions into leftover budget only."""
    s = Scheduler(_cfg(max_slots=3, max_batch_tokens=10, prefill_chunk=8))
    a = s.submit(Request([1] * 12, 4, req_id="a"))
    b = s.submit(Request([2] * 6, 4, req_id="b"))
    plan = s.plan()
    # a eats one whole chunk (8), b gets the remaining 2-token budget
    assert [(r.req_id, n) for _, r, n in plan] == [("a", 8), ("b", 2)]
    assert a.state == "prefill" and b.state == "prefill"
    assert s.queue_depth == 0 and s.active == 2


def test_scheduler_decode_preempts_prefill_budget():
    """Decode slots are latency-critical: they are planned before any
    prefill work regardless of slot order, and a chunked prefill admits
    new work only into leftover budget."""
    s = Scheduler(_cfg(max_slots=2, max_batch_tokens=5, prefill_chunk=4))
    p = s.submit(Request([1] * 12, 4, req_id="p"))
    s.plan()  # admit p: first prefill chunk (4 of 12)
    p.pos = p.ctx_len = 4
    d = s.submit(Request([2, 3], 4, req_id="d"))
    plan = s.plan()  # p continues (4); d admitted into the last token
    assert [(r.req_id, n) for _, r, n in plan] == [("p", 4), ("d", 1)]
    p.pos = p.ctx_len = 8
    d.pos = d.ctx_len = 2
    d.state = "decode"
    d.out_tokens = [7]
    plan = s.plan()
    # d (decode, slot 1) outranks p (prefill, slot 0), and is charged the
    # columns its verify row may fill (the row of 4: 1 + 3 drafts)
    assert (plan[0][1].req_id, plan[0][2]) == ("d", 4)
    assert (plan[1][1].req_id, plan[1][2]) == ("p", 1)


def test_scheduler_admit_on_slot_free_and_evict():
    """A finished request frees its slot and blocks the same tick, so
    the next waiting request replaces it mid-flight (continuous
    batching, not epoch batching)."""
    cfg = _cfg(max_slots=1, cache_blocks=4, max_seq_len=16)
    s = Scheduler(cfg)
    a = s.submit(Request([1] * 4, 4, req_id="a"))
    b = s.submit(Request([2] * 4, 4, req_id="b"))
    s.plan()
    assert a.slot == 0 and b.state == "waiting"  # no free slot for b
    assert s.plan() and b.state == "waiting"
    s.finish(a, "completed")
    assert a.finish_reason == "completed" and a.slot is None
    plan = s.plan()  # b admitted into a's slot the next plan
    assert plan[0][1] is b and b.slot == 0
    assert s.completed == 1


def test_scheduler_fcfs_head_of_line_blocks_deterministically():
    """Admission is strict FCFS: a head request that cannot get its
    worst-case blocks blocks everything behind it — no skip-ahead, so
    every rank's admission stream is identical."""
    cfg = _cfg(max_slots=2, cache_blocks=4, block_size=4, max_seq_len=32)
    s = Scheduler(cfg)
    big = s.submit(Request([1] * 20, 12, req_id="big"))  # needs 8 blocks
    small = s.submit(Request([2] * 4, 4, req_id="small"))  # would fit
    assert s.plan() == []
    assert big.state == "waiting" and small.state == "waiting"


def test_scheduler_plan_stream_deterministic():
    """Same submission sequence -> byte-identical plan stream (the
    property that lets the fleet run lockstep from a plan log)."""
    def run():
        s = Scheduler(_cfg(max_slots=2, max_batch_tokens=8,
                           prefill_chunk=4))
        stream = []
        for i in range(3):
            s.submit(Request([i + 1] * (3 + i), 3, req_id=f"r{i}"))
        for _ in range(12):
            plan = s.plan()
            stream.append([(r.req_id, slot, n) for slot, r, n in plan])
            for slot, r, n in plan:
                if r.state == "prefill":
                    r.pos += n
                    r.ctx_len += n
                    if r.pos >= r.prompt_len:
                        r.state = "decode"
                else:
                    r.ctx_len += 1
                    r.out_tokens.append(0)
                if r.state == "decode" and \
                        len(r.out_tokens) >= r.max_new_tokens:
                    s.finish(r, "completed")
        return stream
    assert run() == run()


def test_scheduler_rejects_overlong_request():
    s = Scheduler(_cfg(max_seq_len=16))
    with pytest.raises(ValueError, match="HOROVOD_SERVE_MAX_SEQ_LEN"):
        s.submit(Request([1] * 10, 8))


def test_request_validation():
    with pytest.raises(ValueError, match="empty prompt"):
        Request([], 4)
    with pytest.raises(ValueError, match="max_new_tokens"):
        Request([1], 0)


def test_block_allocator_lifo_reuse_and_all_or_nothing():
    """LIFO reuse: the blocks a finished request frees are the first
    ones the next request gets; an alloc that cannot be fully satisfied
    takes nothing."""
    a = BlockAllocator(4)
    first = a.alloc(2)
    assert first == [0, 1] and a.free_count == 2
    assert a.alloc(3) is None and a.free_count == 2  # nothing taken
    a.free(first)
    assert a.alloc(2) == [0, 1]  # freed blocks come back first


# ---------------------------------------------------- paged-cache engine
@pytest.fixture(scope="module")
def llama_tiny():
    from horovod_tpu.models import llama
    cfg = llama.CONFIGS["tiny"]
    return llama, cfg, llama.init(jax.random.PRNGKey(0), cfg)


@pytest.fixture(scope="module")
def moe_tiny():
    from horovod_tpu.models import moe_llama
    cfg = moe_llama.CONFIGS["tiny"]
    return moe_llama, cfg, moe_llama.init(jax.random.PRNGKey(1), cfg)


def _full_logits(model, cfg, params, ids):
    """Full-sequence forward logits; moe uses the batch-invariant
    drop-free routing (the serving contract)."""
    kw = {}
    if hasattr(model, "dropfree_moe_fn"):
        kw["moe_fn"] = model.dropfree_moe_fn(cfg)
    out = model.apply(params, jnp.asarray(ids), cfg, **kw)
    return np.asarray(out[0] if isinstance(out, tuple) else out)


def _cached_logits(model, cfg, params, ids, prefill, block_size=4):
    """Prefill the first ``prefill`` tokens in one chunk, then decode
    the rest one token per call — the engine's tick contract, driven by
    hand so the test owns the block table."""
    T = len(ids)
    nb = -(-T // block_size) + 1
    cache = model.init_cache(cfg, nb, block_size)
    bt = -np.ones((1, nb), np.int32)
    bt[0, : nb - 1] = np.arange(nb - 1)
    bt = jnp.asarray(bt)
    C = prefill
    rows = []
    toks = np.zeros((1, C), np.int32)
    toks[0, :prefill] = ids[:prefill]
    out = model.apply_cached(params, jnp.asarray(toks), cfg, cache, bt,
                             jnp.array([0]), jnp.array([prefill]))
    logits, cache = out[0], out[1]
    rows.append(np.asarray(logits[0, :prefill]))
    for t in range(prefill, T):
        toks = np.zeros((1, C), np.int32)
        toks[0, 0] = ids[t]
        out = model.apply_cached(params, jnp.asarray(toks), cfg, cache,
                                 bt, jnp.array([t]), jnp.array([1]))
        logits, cache = out[0], out[1]
        rows.append(np.asarray(logits[0, :1]))
    return np.concatenate(rows, axis=0)


@pytest.mark.parametrize("family", ["llama", "moe"])
def test_prefill_decode_bit_near_full_forward(family, llama_tiny,
                                              moe_tiny):
    """THE decode-path correctness contract (ISSUE 7 acceptance):
    prefill + N decode steps over the paged cache reproduce the
    full-sequence apply() logits bit-near on the shared prefix."""
    model, cfg, params = llama_tiny if family == "llama" else moe_tiny
    T = 12
    ids = np.random.RandomState(7).randint(0, cfg.vocab, T)
    full = _full_logits(model, cfg, params, ids[None])[0]
    cached = _cached_logits(model, cfg, params, ids, prefill=8)
    err = np.abs(cached - full).max()
    assert err < 1e-5, f"{family}: max |cached - full| = {err}"


def test_paged_layout_is_length_invariant(llama_tiny):
    """Two sequences of different lengths share one pool with disjoint
    block tables; each reproduces its own full forward — blocks are
    genuinely isolated, not strided per slot."""
    model, cfg, params = llama_tiny
    rng = np.random.RandomState(3)
    ids_a = rng.randint(0, cfg.vocab, 11)
    ids_b = rng.randint(0, cfg.vocab, 5)
    bs = 4
    cache = model.init_cache(cfg, 8, bs)
    bt = -np.ones((2, 4), np.int32)
    bt[0, :3] = [0, 1, 2]   # a: up to 12 positions
    bt[1, :2] = [5, 6]      # b: disjoint, out of order vs a
    bt = jnp.asarray(bt)
    C = 11
    toks = np.zeros((2, C), np.int32)
    toks[0, :11] = ids_a
    toks[1, :5] = ids_b
    out = model.apply_cached(params, jnp.asarray(toks), cfg, cache, bt,
                             jnp.array([0, 0]), jnp.array([11, 5]))
    full_a = _full_logits(model, cfg, params, ids_a[None])[0]
    full_b = _full_logits(model, cfg, params, ids_b[None])[0]
    assert np.abs(np.asarray(out[0][0, :11]) - full_a).max() < 1e-5
    assert np.abs(np.asarray(out[0][1, :5]) - full_b).max() < 1e-5


@pytest.mark.parametrize("family", ["llama", "moe"])
def test_tick_leaves_the_rest_of_the_pool_alone(family, llama_tiny,
                                                moe_tiny):
    """One mixed tick over a pool full of a pattern: a prefill row with
    two padding positions, a decode row, a dead slot with a stale table
    row and an empty slot.  Every (layer, block, offset) no valid
    position owns keeps its bytes; position P of slot s lands at
    ``(layer, block_tables[s, P // bs], P % bs)`` — holding what the same
    tick writes when every block sits elsewhere in a pool that is zero
    but for the decode row's context (a position's k/v know nothing of
    block numbers or of what the mask hides)."""
    model, cfg, params = llama_tiny if family == "llama" else moe_tiny
    bs, nb, C = 4, 16, 8
    rng = np.random.RandomState(29)
    shape = (cfg.n_layers, nb, bs, cfg.n_kv_heads, cfg.head_dim)
    pattern = {n: rng.randn(*shape).astype(np.float32) for n in "kv"}
    bt = np.array([[3, 7, -1, -1],      # prefill: positions 0..5 of 8
                   [10, 2, 5, -1],      # decode: position 9
                   [8, 9, -1, -1],      # dead, its row left behind
                   [-1, -1, -1, -1]], np.int32)
    lengths = np.array([0, 9, 5, 0], np.int32)
    n_new = np.array([6, 1, 0, 0], np.int32)
    toks = rng.randint(0, cfg.vocab, (4, C)).astype(np.int32)
    step = jax.jit(lambda cache, bt: model.apply_cached(
        params, jnp.asarray(toks), cfg, cache, bt, jnp.asarray(lengths),
        jnp.asarray(n_new))[1])
    got = {n: np.asarray(x) for n, x in step(
        {n: jnp.asarray(x) for n, x in pattern.items()},
        jnp.asarray(bt)).items()}
    # the same tick with block b at nb - 1 - b, in a pool that holds
    # nothing but the decode row's nine positions of context
    moved = {n: np.zeros(shape, np.float32) for n in "kv"}
    for n in "kv":
        moved[n][:, nb - 1 - bt[1, :3]] = pattern[n][:, bt[1, :3]]
    want = {n: np.asarray(x) for n, x in step(
        {n: jnp.asarray(x) for n, x in moved.items()},
        jnp.asarray(np.where(bt < 0, bt, nb - 1 - bt))).items()}
    written = np.zeros((nb, bs), bool)
    for s in range(4):
        for P in range(lengths[s], lengths[s] + n_new[s]):
            written[bt[s, P // bs], P % bs] = True
    assert written.sum() == 7
    for n in "kv":
        assert got[n][:, ~written].tobytes() == \
            pattern[n][:, ~written].tobytes(), n
        np.testing.assert_allclose(
            got[n][:, written], want[n][:, ::-1][:, written], atol=1e-6)
        assert not np.allclose(got[n][:, written], pattern[n][:, written])


def _reference_greedy(model, cfg, params, prompt, n_new):
    """Greedy continuation via the FULL forward, one token at a time —
    the oracle the continuous-batching engine must match exactly."""
    ids = list(prompt)
    out = []
    for _ in range(n_new):
        logits = _full_logits(model, cfg, params,
                              np.asarray(ids, np.int32)[None])
        tok = int(np.argmax(logits[0, -1].astype(np.float32)))
        out.append(tok)
        ids.append(tok)
    return out


@pytest.mark.parametrize("family", ["llama", "moe"])
def test_engine_matches_reference_greedy_decode(family, llama_tiny,
                                                moe_tiny):
    """Continuous batching must be invisible: mixed-length requests
    admitted/evicted mid-flight produce exactly the tokens each would
    get decoding alone through the full forward."""
    model, cfg, params = llama_tiny if family == "llama" else moe_tiny
    scfg = _cfg(max_slots=2, block_size=4, cache_blocks=32,
                max_seq_len=32, max_batch_tokens=12, prefill_chunk=8)
    engine = ServeEngine(model, cfg, params, scfg,
                         mesh=_one_device_mesh())
    rng = np.random.RandomState(11)
    prompts = [rng.randint(0, cfg.vocab, n).tolist()
               for n in (9, 4, 6, 11)]
    reqs = [engine.submit(p, 5, req_id=f"r{i}")
            for i, p in enumerate(prompts)]
    engine.flush()
    assert all(r.state == "done" for r in reqs)
    for i, (p, r) in enumerate(zip(prompts, reqs)):
        expect = _reference_greedy(model, cfg, params, p, 5)
        assert r.out_tokens == expect, f"req {i}"


# ------------------------------------------------------ the two tick widths
@pytest.mark.parametrize("spec,spec_k,rows,want", [
    (True, 4, [1, 1, 5], 5),       # decode and a full verify row: narrow
    (True, 4, [1, 6], 8),          # one row past the decode width: wide
    (True, 4, [1, 3], 5),          # a 3-token prefill tail rides narrow
    (True, 2, [3, 1], 3),          # the decode width follows spec_k
    (True, 2, [4], 8),
    (False, 4, [1, 1], 1),         # no speculation: one column
    (False, 4, [1, 2], 8),
    (False, 4, [8, 1], 8),         # a whole chunk beside a decode row
])
def test_tick_width_is_read_off_the_plan(spec, spec_k, rows, want):
    cfg = _cfg(spec_decode=spec, spec_k=spec_k, prefill_chunk=8)
    assert decode_width(cfg) == (1 + spec_k if spec else 1)
    work = [(i, None, n) for i, n in enumerate(rows)]
    assert tick_width(cfg, work) == want


def _step_and_note_widths(engine, widths):
    """One engine.step(), with the width of the tick it dispatched held
    against tick_width of that tick's work list."""
    tick = engine.tick
    engine.step()
    if engine.tick > tick:  # what this step launched is the newest in flight
        _, width, rows, report = engine._inflight[-1][:4]
        assert width == tick_width(engine.cfg, rows)
        # the greedy tokens (of the columns the tick reads, where the module
        # samples there), the verify rows as fed, a row's columns, length
        W = decode_width(engine.cfg)
        assert report.shape == (
            engine.cfg.max_slots,
            (W if samples_read(engine.model) else width) + W + 2)
        widths.append(width)


@pytest.mark.parametrize("spec", [False, True], ids=["plain", "spec"])
@pytest.mark.parametrize("family", ["llama", "moe"])
def test_engine_at_two_widths_matches_reference_greedy(
        family, spec, llama_tiny, moe_tiny):
    """Ticks with a prefill chunk run wide, ticks without run at the decode
    width, in one request stream: every request's tokens are the full
    forward's greedy ones.  Under speculation the device drafts from each
    stream's own repeats (16 tokens of a `tiny` model's greedy stream hold
    some): drafts that are accepted and drafts that are rejected both pass
    a narrow row."""
    model, cfg, params = llama_tiny if family == "llama" else moe_tiny
    scfg = _cfg(max_slots=2, cache_blocks=32, max_seq_len=32,
                max_batch_tokens=12, prefill_chunk=8, spec_decode=spec,
                spec_k=2)
    rng = np.random.RandomState(17)
    prompts = [rng.randint(0, cfg.vocab, n).tolist() for n in (9, 4, 11)]
    refs = [_reference_greedy(model, cfg, params, p, 16) for p in prompts]
    engine = ServeEngine(model, cfg, params, scfg, mesh=_one_device_mesh())
    widths = []
    reqs = [engine.submit(prompts[0], 16, req_id="r0")]
    for _ in range(4):      # chunk of 8, the 1-token tail, decode alone
        _step_and_note_widths(engine, widths)
    reqs += [engine.submit(p, 16, req_id=f"r{i + 1}")
             for i, p in enumerate(prompts[1:])]
    while engine.has_work():
        _step_and_note_widths(engine, widths)
    for r, ref in zip(reqs, refs):
        assert r.state == "done" and r.out_tokens == ref, r.req_id
    narrow = decode_width(scfg)
    assert widths[:2] == [8, narrow]    # r0's tail of 1 rode a narrow tick
    assert set(widths) == {8, narrow}
    loop = engine.stats()["loop"]
    assert loop["ticks"] == len(widths) == engine.tick
    assert loop["narrow_ticks"] == widths.count(narrow)
    assert 0.0 <= loop["narrow_wait_s"] <= loop["phase_s"]["harvest_wait"]
    for r in reqs:
        assert 1 <= r.loop["narrow_ticks"] < r.loop["ticks"]
    if spec:
        assert engine._spec_drafted > engine._spec_accepted >= 1
    engine.close()


@pytest.mark.parametrize("first", ["wide", "narrow"])
def test_both_widths_compile_at_the_first_dispatch(first, llama_tiny):
    """Whatever the first tick holds, its dispatch builds both
    executables: the first tick of the other width lowers nothing."""
    model, cfg, params = llama_tiny
    scfg = _cfg(max_slots=2, cache_blocks=32, max_seq_len=32,
                max_batch_tokens=12, prefill_chunk=8, spec_k=2)
    engine = ServeEngine(model, cfg, params, scfg, mesh=_one_device_mesh())
    rng = np.random.RandomState(19)
    long_, short = (rng.randint(0, cfg.vocab, n).tolist() for n in (9, 3))
    assert engine._steps == {}
    engine.submit(long_ if first == "wide" else short, 3, req_id="a")
    engine.step()
    assert set(engine._steps) == {3, 8}
    lowered = compile_counts()["compiles"]
    engine.flush()
    engine.submit(short if first == "wide" else long_, 3, req_id="b")
    engine.flush()
    assert compile_counts()["compiles"] == lowered
    loop = engine.stats()["loop"]
    assert loop["ticks"] - loop["narrow_ticks"] == 1    # long_'s one chunk
    assert loop["narrow_ticks"] >= 5
    engine.close()


@pytest.mark.parametrize("width", ["wide", "narrow"])
@pytest.mark.parametrize("family", ["llama", "moe"])
def test_tick_keeps_no_second_pool(family, width, llama_tiny, moe_tiny,
                                   monkeypatch):
    """The engine's compiled step writes and reads the stacked, donated
    pool in place (counts of the CPU's compiler; the chip has the times):
    its optimized HLO restacks nothing and holds no op shaped like one
    layer's pool, and without ``copy_blocks`` its temporaries stay under
    ONE layer's K pool — a slice a layer and a restack take more than
    two whole stacks.  ``copy_blocks`` is left out of the second
    count only: XLA:CPU transposes both stacks to scatter along their
    block axis, two stacks of temporaries by itself, which the chip's
    compiler does not."""
    model, cfg, params = llama_tiny if family == "llama" else moe_tiny
    scfg = _cfg(cache_blocks=2048, max_batch_tokens=12, spec_k=2)
    C = scfg.prefill_chunk if width == "wide" else decode_width(scfg)

    def compiled():
        engine = ServeEngine(model, cfg, params, scfg,
                             mesh=_one_device_mesh())
        engine.submit(list(range(9)), 2, req_id="a")
        engine.step()       # the first dispatch compiles both widths
        engine.close()
        return engine._steps[C], engine.cache["k"]

    step, pool = compiled()
    dims = lambda shape: "[" + ",".join(map(str, shape)) + "]"
    ops = re.findall(r" = \w+(\[[\d,]*\])\S* ([\w-]+)\(", step.as_text())
    assert (dims(pool.shape), "scatter") in ops     # the regex sees the pool
    assert (dims(pool.shape), "concatenate") not in ops
    layer = {dims(pool.shape[1:]), dims((1,) + pool.shape[1:])}
    assert not [op for op in ops if op[0] in layer]
    monkeypatch.setattr(model, "copy_blocks", lambda cache, src, dst: cache)
    step, _ = compiled()
    assert step.memory_analysis().temp_size_in_bytes < \
        pool.nbytes // pool.shape[0]


# ------------------------------------------------- packed rows, slot blocks
# One chunk-wide tick by hand, C = 12 columns over 32 positions a slot:
# (n_new, lengths) a slot.  "mixed": a prefill chunk, decode rows, a short
# tail and dead slots; "worst": every slot a tail longer than the narrow
# columns, as many valid tokens as the budget has rows.
TICKS = {"mixed": ([12, 1, 0, 3, 0, 1], [4, 9, 7, 12, 0, 5], 20),
         "worst": ([9, 9, 9, 9, 9, 9], [0, 3, 8, 11, 16, 20], 54)}
TICK_C, TICK_CTX = 12, 32


def _tick_by_hand(model, cfg, params, plan):
    """(logits, cache, valid) of one apply_cached call on ``plan``'s slots,
    over a pool of noise, every slot owning its own eight blocks of four."""
    n_new, lengths, _ = TICKS[plan]
    S, rng = len(n_new), np.random.default_rng(11)
    table = jnp.asarray(np.arange(S * 8, dtype=np.int32).reshape(S, 8))
    cache = {k: jnp.asarray(rng.normal(size=v.shape), v.dtype)
             for k, v in model.init_cache(cfg, S * 8, 4).items()}
    tokens = jnp.asarray(rng.integers(0, cfg.vocab, (S, TICK_C)), jnp.int32)
    out = jax.jit(lambda c: model.apply_cached(
        params, tokens, cfg, c, table, jnp.asarray(lengths, jnp.int32),
        jnp.asarray(n_new, jnp.int32)))(cache)
    valid = np.arange(TICK_C)[None] < np.asarray(n_new)[:, None]
    return np.asarray(out[0]), out[1], valid


def _family(family, llama_tiny, moe_tiny):
    return llama_tiny if family == "llama" else moe_tiny


@pytest.mark.parametrize("plan", sorted(TICKS))
@pytest.mark.parametrize("family", ["llama", "moe"])
def test_packing_a_ticks_tokens_changes_no_logit_and_no_cached_value(
        family, plan, llama_tiny, moe_tiny):
    """``max_tick_tokens`` rows against the whole slab on one tick: the
    logits at every valid position and every value of the pool are the
    same, and a position that was not packed reads zero."""
    model, cfg, params = _family(family, llama_tiny, moe_tiny)
    budget = TICKS[plan][2]
    want, want_cache, valid = _tick_by_hand(model, cfg, params, plan)
    got, got_cache, _ = _tick_by_hand(
        model, dataclasses.replace(cfg, max_tick_tokens=budget), params,
        plan)
    assert float(np.max(np.abs(got - want)[valid])) < 2e-5
    for k in want_cache:
        assert float(jnp.max(jnp.abs(got_cache[k] - want_cache[k]))) < 2e-5
    assert np.asarray(got)[~valid].any(-1).sum() == budget - valid.sum()


@pytest.mark.parametrize("family", ["llama", "moe"])
def test_a_budget_no_smaller_than_the_slab_packs_nothing(
        family, llama_tiny, moe_tiny):
    """``R >= S * C`` is the identity: the program of a config whose budget
    covers the slab is the program of one that never set the field."""
    model, cfg, params = _family(family, llama_tiny, moe_tiny)
    S = len(TICKS["mixed"][0])
    args = (jnp.zeros((S, TICK_C), jnp.int32),
            model.init_cache(cfg, S * 8, 4), jnp.zeros((S, 8), jnp.int32),
            jnp.zeros(S, jnp.int32), jnp.ones(S, jnp.int32))

    def lowered(cfg):
        return jax.jit(lambda t, c, bt, l, n: model.apply_cached(
            params, t, cfg, c, bt, l, n)).lower(*args).as_text()
    assert lowered(dataclasses.replace(cfg, max_tick_tokens=S * TICK_C)) \
        == lowered(cfg)
    assert lowered(dataclasses.replace(cfg, max_tick_tokens=S * TICK_C - 1)) \
        != lowered(cfg)


@pytest.mark.parametrize("slots", [1, 2, 6])
@pytest.mark.parametrize("family", ["llama", "moe"])
def test_blocks_of_slots_attend_alike_whatever_their_size(
        family, slots, llama_tiny, moe_tiny, monkeypatch):
    """The attention a block of 1, 2 or all 6 slots after another, blocks of
    decode rows in their first columns only, against every slot at chunk
    width in one block (no narrow columns): the same logits at every valid
    position, the same pool."""
    from horovod_tpu.models import llama
    model, cfg, params = _family(family, llama_tiny, moe_tiny)
    cfg = dataclasses.replace(cfg, max_tick_tokens=TICKS["mixed"][2])
    monkeypatch.setattr(llama, "NARROW_COLS", TICK_C)
    want, want_cache, valid = _tick_by_hand(model, cfg, params, "mixed")
    monkeypatch.setattr(llama, "NARROW_COLS", 4)
    monkeypatch.setattr(llama, "SCORE_BYTES",
                        slots * cfg.n_heads * TICK_C * TICK_CTX * 4)
    assert model.attn_blocks(cfg, 6, TICK_C, TICK_CTX) == (slots, 4)
    got, got_cache, _ = _tick_by_hand(model, cfg, params, "mixed")
    assert float(np.max(np.abs(got - want)[valid])) < 2e-5
    for k in want_cache:
        assert float(jnp.max(jnp.abs(got_cache[k] - want_cache[k]))) < 2e-5


def test_the_loop_over_blocks_of_slots_keeps_no_second_pool(llama_tiny,
                                                           monkeypatch):
    """``test_tick_keeps_no_second_pool``'s counts on a chunk-wide step whose
    attention runs loops over the blocks of two slots that have something to
    read, and inside each a loop over tiles of its context (the tiny step's
    own chunk fits the narrow columns and runs only the first): every step
    gathers its tile from the pool the loops hold, and the pool is still
    scattered in place, never restacked, never an operand of a copy."""
    from horovod_tpu.models import llama
    model, cfg, params = llama_tiny
    scfg = _cfg(max_slots=4, cache_blocks=2048, max_batch_tokens=12,
                spec_k=2)
    monkeypatch.setattr(llama, "NARROW_COLS", 2)
    monkeypatch.setattr(llama, "SCORE_BYTES", 2 * cfg.n_heads
                        * scfg.prefill_chunk * scfg.max_seq_len * 4)
    monkeypatch.setattr(model, "copy_blocks", lambda cache, src, dst: cache)
    engine = ServeEngine(model, cfg, params, scfg, mesh=_one_device_mesh())
    engine.submit(list(range(9)), 2, req_id="a")
    engine.step()
    engine.close()
    step, pool = engine._steps[scfg.prefill_chunk], engine.cache["k"]
    text = step.as_text()
    assert text.count(" while(") >= 4 * cfg.n_layers
    dims = "[" + ",".join(map(str, pool.shape)) + "]"
    ops = re.findall(r" = \w+(\[[\d,]*\])\S* ([\w-]+)\(", text)
    assert (dims, "scatter") in ops
    assert not [op for op in ops if op[0] == dims
                and op[1] in ("copy", "concatenate")]
    assert step.memory_analysis().temp_size_in_bytes < \
        pool.nbytes // pool.shape[0]


def test_wide_shares_read_what_the_plan_implies(llama_tiny, monkeypatch):
    """``stats()["loop"]``'s two shares on a plan known by hand: a prompt
    of 20 is a chunk of 12 then a tail of 8 — two wide ticks of 20 rows each
    (the budget; the slab has 48 positions) that hold 12 and 8 tokens, and
    of their two blocks of two slots one, the chunk's, attends at chunk
    width (the tail fits the narrow columns); the decode ticks are narrow
    and count nowhere."""
    from horovod_tpu.models import llama
    model, cfg, params = llama_tiny
    monkeypatch.setattr(llama, "SCORE_BYTES", 2 * cfg.n_heads * 12 * 32 * 4)
    engine = ServeEngine(
        model, cfg, params,
        _cfg(max_slots=4, cache_blocks=32, max_batch_tokens=20,
             prefill_chunk=12, spec_decode=False, prefix_cache=False),
        mesh=_one_device_mesh())
    assert engine.stats()["loop"]["wide_rows_share"] is None
    engine.submit(list(range(1, 21)), 3, req_id="a")
    engine.flush()
    loop = engine.stats()["loop"]
    engine.close()
    assert loop["ticks"] - loop["narrow_ticks"] == 2
    assert loop["wide_rows_share"] == (12 + 8) / (2 * 20)
    assert loop["wide_blocks_share"] == 1 / (2 * 2)


def test_read_shares_count_what_the_ticks_attention_read(llama_tiny,
                                                         monkeypatch):
    """``stats()["loop"]``'s ``context_read_share`` and ``dead_blocks_share``
    on the plan above, four slots whose tables cover 32 positions each, read
    in tiles of 8 by blocks of two slots: the chunk of 12 reads 2 tiles in
    the first pass and 2 again at chunk width, the tail of 8 (held 20) 3
    tiles, the two decode ticks (held 21, 22) 3 each; the other block of two
    slots holds no stream in any of the four ticks and reads nothing."""
    from horovod_tpu.models import llama, paged
    model, cfg, params = llama_tiny
    monkeypatch.setattr(llama, "SCORE_BYTES", 2 * cfg.n_heads * 12 * 32 * 4)
    monkeypatch.setattr(paged, "TILE", 8)
    monkeypatch.setattr(paged, "NARROW_SLOTS", 2)
    engine = ServeEngine(
        model, cfg, params,
        _cfg(max_slots=4, cache_blocks=32, max_batch_tokens=20,
             prefill_chunk=12, spec_decode=False, prefix_cache=False),
        mesh=_one_device_mesh())
    assert engine.stats()["loop"]["context_read_share"] is None
    engine.submit(list(range(1, 21)), 3, req_id="a")
    engine.flush()
    loop = engine.stats()["loop"]
    engine.close()
    assert loop["ticks"] == 4
    read = 2 * 8 * ((2 + 2) + 3 + 3 + 3)
    assert loop["context_read_share"] == round(read / (4 * 4 * 32), 4)
    assert loop["dead_blocks_share"] == 4 / 8


def test_a_module_without_a_bound_counts_whole_tables(monkeypatch):
    """swa_moe hands ``attend_by_blocks`` no bound (no ``BOUNDED_READ``):
    every dispatched tick reads every slot's table whole, a chunk's block
    twice, and no block is skipped."""
    from horovod_tpu.models import swa_moe
    cfg = swa_moe.CONFIGS["tiny"]
    params = swa_moe.init(jax.random.PRNGKey(0), cfg)
    engine = ServeEngine(
        swa_moe, cfg, params,
        _cfg(cache_blocks=32, max_batch_tokens=12, prefix_cache=False,
             spec_decode=False), mesh=_one_device_mesh())
    assert not hasattr(swa_moe, "BOUNDED_READ")
    engine.submit(list(range(1, 7)), 3, req_id="a")
    engine.flush()
    loop = engine.stats()["loop"]
    engine.close()
    assert loop["context_read_share"] >= 1.0
    assert loop["dead_blocks_share"] == 0.0


def test_two_engines_fed_alike_agree_on_digest_and_widths(llama_tiny):
    """The width is a function of the plan, and the plan is in the
    digest: two engines given the same requests at the same ticks end on
    one sched_digest, one tick count and one narrow-tick count."""
    model, cfg, params = llama_tiny
    scfg = _cfg(max_slots=2, cache_blocks=32, max_seq_len=32,
                max_batch_tokens=12, prefill_chunk=8)
    rng = np.random.RandomState(23)
    prompts = [rng.randint(0, cfg.vocab, n).tolist() for n in (10, 5, 7)]
    ends = []
    for _ in range(2):
        engine = ServeEngine(model, cfg, params, scfg,
                             mesh=_one_device_mesh())
        engine.submit(prompts[0], 5, req_id="r0")
        engine.step()
        engine.step()
        for i, p in enumerate(prompts[1:]):
            engine.submit(p, 4, req_id=f"r{i + 1}")
        engine.flush()
        loop = engine.stats()["loop"]
        ends.append((engine.sched_digest, engine.tick, loop["ticks"],
                     loop["narrow_ticks"]))
        engine.close()
    assert ends[0] == ends[1] and ends[0][0]
    assert 0 < ends[0][3] < ends[0][2]


def test_engine_block_reuse_and_eos_eviction(llama_tiny):
    """Eviction frees blocks back to the pool (same free count after a
    full drain) and an EOS hit finishes a request early with
    finish_reason='eos'; the freed blocks are reused by a later
    admission (LIFO observable through the allocator)."""
    model, cfg, params = llama_tiny
    # prefix_cache off: this test asserts the RAW pool mechanics (free
    # count restored, LIFO reuse); with the cache on, prompt blocks stay
    # resident by design (tests/test_serve_speed.py covers that).
    scfg = _cfg(max_slots=1, block_size=4, cache_blocks=8,
                max_seq_len=32, max_batch_tokens=8, prefill_chunk=8,
                prefix_cache=False)
    engine = ServeEngine(model, cfg, params, scfg,
                         mesh=_one_device_mesh())
    free0 = engine.scheduler.allocator.free_count
    prompt = np.random.RandomState(2).randint(0, cfg.vocab, 6).tolist()
    first = engine.submit(prompt, 4, req_id="probe")
    engine.flush()
    blocks_first = None
    # run the same prompt with eos = its first generated token
    eos = first.out_tokens[0]
    engine2 = ServeEngine(model, cfg, params, scfg,
                          mesh=_one_device_mesh())
    r = engine2.submit(prompt, 4, req_id="eos-req", eos_id=eos)
    engine2.step()
    blocks_first = list(engine2.scheduler.slots[0].blocks)
    engine2.flush()
    assert r.finish_reason == "eos" and r.out_tokens == [eos]
    assert engine2.scheduler.allocator.free_count == free0
    # next admission reuses the just-freed blocks (LIFO free list: the
    # earliest-freed block is appended last, so it pops first)
    r2 = engine2.submit(prompt, 1, req_id="next")
    engine2.step()
    assert r2.blocks == blocks_first[: len(r2.blocks)]
    engine2.flush()


def test_engine_serve_metrics_move(llama_tiny, hvd):
    """hvd_serve_* SLO families move when the engine serves: ttft/tpot
    histogram counts, request outcome counters, token phase counters."""
    model, cfg, params = llama_tiny
    from horovod_tpu.utils import metrics as M
    ttft0 = sum(s["count"] for s in M.SERVE_TTFT.to_family()["samples"])
    req0 = sum(s["value"]
               for s in M.SERVE_REQUESTS.to_family()["samples"])
    engine = ServeEngine(model, cfg, params, _cfg(),
                         mesh=_one_device_mesh())
    engine.submit([1, 2, 3], 3, req_id="m")
    engine.flush()
    fams = hvd.metrics_snapshot()["families"]
    ttft = sum(s["count"]
               for s in fams["hvd_serve_ttft_seconds"]["samples"])
    assert ttft == ttft0 + 1
    outcomes = {s["labels"].get("outcome"): s["value"]
                for s in fams["hvd_serve_requests_total"]["samples"]}
    assert sum(outcomes.values()) == req0 + 1
    phases = {s["labels"].get("phase"): s["value"]
              for s in fams["hvd_serve_tokens_total"]["samples"]}
    # 3 prompt tokens prefilled; the first output token rides the
    # prefill tick, so 3 generated tokens = 2 decode-phase tokens
    assert phases.get("prefill", 0) >= 3 and phases.get("decode", 0) >= 2


def test_cache_shardings_ride_existing_axes():
    """The paged pool shards along the training mesh's own axes: kv
    heads over a model/tp axis when it divides, blocks over a data
    axis; a 1-D mesh puts blocks on it and replicates heads."""
    from horovod_tpu.models import llama

    def cache_shardings(mesh, num_blocks, n_kv_heads):
        # the module contract ServeEngine calls (docs/serving.md)
        return llama.cache_shardings(
            mesh, llama.LlamaConfig(n_kv_heads=n_kv_heads), num_blocks)
    devs = np.array(jax.devices()[:8])
    mesh2 = jax.sharding.Mesh(devs.reshape(4, 2), ("data", "model"))
    spec = cache_shardings(mesh2, num_blocks=64, n_kv_heads=4).spec
    assert spec == jax.sharding.PartitionSpec(
        None, "data", None, "model", None)
    # heads NOT divisible by the model axis -> replicated, blocks still
    # land on the first dividing axis
    spec = cache_shardings(mesh2, num_blocks=64, n_kv_heads=3).spec
    assert spec[3] is None and spec[1] == "data"
    mesh1 = jax.sharding.Mesh(devs, ("hvd",))
    spec = cache_shardings(mesh1, num_blocks=64, n_kv_heads=4).spec
    assert spec == jax.sharding.PartitionSpec(
        None, "hvd", None, None, None)


# ------------------------------------------------------ timeline spans
def test_timeline_record_span_anchored_at_start(tmp_path):
    from horovod_tpu.utils.timeline import Timeline, load_trace_events
    path = str(tmp_path / "tl.json")
    tl = Timeline(path)
    t0 = tl.now_us()
    tl.record_span("serve", "PREFILL", 2000.0, args={"req": "r1"})
    tl.close()
    evs = [e for e in load_trace_events(path) if e.get("name") == "PREFILL"]
    assert len(evs) == 1 and evs[0]["ph"] == "X"
    assert evs[0]["dur"] == 2000.0
    assert evs[0]["args"]["req"] == "r1"
    # anchored at start: ts ~ (emit time - dur), so >= t0 - dur - slack
    assert evs[0]["ts"] + 2000.0 >= t0 - tl._epoch_us - 1e4


# --------------------------------------------------------------- router
def test_router_backpressure_claims():
    from horovod_tpu.serve.router import RouterState
    st = RouterState(max_pending=2)
    assert st.try_claim() == 0 and st.try_claim() == 1
    assert st.try_claim() is None  # full
    st.finish_stream()
    assert st.try_claim() == 2  # slot freed
    c = st.counters()
    assert c["rejected"] == 1 and c["pending"] == 2 and c["submitted"] == 3


def test_parse_generate_body_validation():
    from horovod_tpu.serve.router import parse_generate_body
    ok = parse_generate_body(
        json.dumps({"tokens": [1, 2], "max_new_tokens": 3,
                    "eos_id": 0}).encode())
    assert ok == {"tokens": [1, 2], "max_new_tokens": 3, "eos_id": 0}
    assert parse_generate_body(
        json.dumps({"tokens": [5]}).encode())["max_new_tokens"] == 16
    for bad, msg in ((b"{nope", "not valid JSON"),
                     (b"{}", "'tokens'"),
                     (json.dumps({"tokens": []}).encode(), "'tokens'"),
                     (json.dumps({"tokens": ["x"]}).encode(), "'tokens'"),
                     (json.dumps({"tokens": [1],
                                  "max_new_tokens": 0}).encode(),
                      "max_new_tokens"),
                     (json.dumps({"tokens": [1],
                                  "eos_id": "e"}).encode(), "eos_id")):
        with pytest.raises(ValueError, match=msg):
            parse_generate_body(bad)


@pytest.fixture()
def rendezvous():
    """(RendezvousServer, inner httpd, port): the handler-visible state
    (kv, kv_lock, serve_router) lives on the inner ThreadingHTTPServer."""
    from horovod_tpu.runner.http_server import RendezvousServer
    server = RendezvousServer(host="127.0.0.1")
    port = server.start()
    yield server, server._httpd, port
    server.stop()


def _post(port, body, timeout=10):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/generate", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"}, method="POST")
    return urllib.request.urlopen(req, timeout=timeout)


def test_generate_route_streams_engine_results(rendezvous):
    """Full front-door path with a scripted engine behind the KV: POST
    /generate streams ndjson parts then the done record; /serve/stats
    merges router counters with the engine's published stats."""
    from horovod_tpu.serve import router as R
    server, httpd, port = rendezvous

    def fake_engine():
        # wait for the router's enqueue, then publish two parts + done
        deadline = time.time() + 10
        raw = None
        while time.time() < deadline:
            raw = server.get(R.REQ_SCOPE, R.req_key(0))
            if raw is not None:
                break
            time.sleep(0.01)
        req = json.loads(raw)
        assert req["tokens"] == [1, 2, 3] and req["max_new_tokens"] == 4
        server.put(R.OUT_SCOPE, f"{req['id']}.part.000000",
                   json.dumps({"tokens": [10, 11]}).encode())
        time.sleep(0.05)
        server.put(R.OUT_SCOPE, f"{req['id']}.part.000001",
                   json.dumps({"tokens": [12]}).encode())
        server.put(R.OUT_SCOPE, f"{req['id']}.done",
                   json.dumps({"done": True, "tokens": [10, 11, 12],
                               "finish_reason": "completed",
                               "ttft_s": 0.01, "tpot_s": 0.002}).encode())
        server.put(R.STATS_SCOPE, R.STATS_KEY,
                   json.dumps({"tick": 3, "completed": 1}).encode())

    t = threading.Thread(target=fake_engine)
    t.start()
    try:
        with _post(port, {"tokens": [1, 2, 3], "max_new_tokens": 4}) as r:
            assert r.status == 200
            assert r.headers["X-Serve-Request-Id"] == "req.000000"
            lines = [json.loads(ln) for ln in r.read().splitlines()]
    finally:
        t.join()
    assert [ln.get("tokens") for ln in lines[:2]] == [[10, 11], [12]]
    assert lines[-1]["done"] is True
    assert lines[-1]["tokens"] == [10, 11, 12]
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/serve/stats", timeout=5) as r:
        stats = json.loads(r.read())
    assert stats["router"]["completed"] == 1
    assert stats["engine"]["tick"] == 3


def test_generate_route_rejects_bad_body_and_backpressures(rendezvous):
    from horovod_tpu.serve.router import RouterState
    server, httpd, port = rendezvous
    with pytest.raises(urllib.error.HTTPError) as exc:
        _post(port, {"tokens": []})
    assert exc.value.code == 400
    assert "tokens" in json.loads(exc.value.read())["error"]
    # backpressure: a zero-capacity router answers 429 immediately
    httpd.serve_router = RouterState(max_pending=0)
    with pytest.raises(urllib.error.HTTPError) as exc:
        _post(port, {"tokens": [1]})
    assert exc.value.code == 429
    body = json.loads(exc.value.read())
    assert body["rejected"] == 1 and "queue full" in body["error"]


# ---------------------------------------------------------------- knobs
def test_serve_config_validation_matrix():
    with pytest.raises(ValueError, match="HOROVOD_SERVE_PORT"):
        _cfg(port=70000).validate()
    with pytest.raises(ValueError, match="HOROVOD_SERVE_MAX_BATCH_TOKENS"):
        _cfg(max_batch_tokens=0).validate()
    with pytest.raises(ValueError, match="HOROVOD_SERVE_MAX_SEQ_LEN"):
        _cfg(max_seq_len=-1).validate()
    with pytest.raises(ValueError, match="HOROVOD_SERVE_CACHE_BLOCKS"):
        _cfg(cache_blocks=0).validate()
    with pytest.raises(ValueError, match="PREFILL_CHUNK"):
        _cfg(prefill_chunk=32, max_batch_tokens=16).validate()
    with pytest.raises(ValueError, match="SPEC_K"):
        _cfg(spec_k=0).validate()
    with pytest.raises(ValueError, match="SPEC_K"):
        _cfg(spec_k=8, prefill_chunk=8).validate()
    _cfg(spec_k=8, prefill_chunk=8, spec_decode=False).validate()
    with pytest.raises(ValueError, match="max_seq"):
        _cfg(max_seq_len=64).validate(model_max_seq=32)
    _cfg().validate(model_max_seq=32)  # valid config passes


def test_serve_knobs_validated_at_init():
    """The init-time contract (runtime.py): a bad HOROVOD_SERVE_* knob
    fails hvd.init(), not a serving tick hours later."""
    good = {"HOROVOD_SERVE_PORT": 0,
            "HOROVOD_SERVE_MAX_BATCH_TOKENS": 2048,
            "HOROVOD_SERVE_MAX_SEQ_LEN": 2048,
            "HOROVOD_SERVE_CACHE_BLOCKS": 4096}
    validate_serve_knobs(good)
    cfg = from_knobs(dict(good, HOROVOD_SERVE_MAX_SEQ_LEN=128),
                     max_slots=4)
    assert cfg.max_seq_len == 128 and cfg.max_slots == 4
    with pytest.raises(ValueError, match="HOROVOD_SERVE_CACHE_BLOCKS"):
        validate_serve_knobs(dict(good, HOROVOD_SERVE_CACHE_BLOCKS=-1))
    with pytest.raises(ValueError, match="HOROVOD_SERVE_PORT"):
        validate_serve_knobs(dict(good, HOROVOD_SERVE_PORT=-2))
