"""The engine serving a model that fills a block of positions by denoising
(horovod_tpu/serve/engine.py ``block_tick_program``, ``_emit_block``;
docs/serving.md#block-denoising) at toy width on the CPU: its streams against
the module's plain loop without a cache (models/blockdiff_moe.py
``denoise``), token by token and pass by pass; alone and beside 31 others;
with ``step()`` launching ahead and with every tick fenced before the next;
what the engine and the scheduler refuse for such a model."""

import dataclasses
import functools

import jax
import numpy as np
import pytest

from horovod_tpu.models import blockdiff_moe as M
from horovod_tpu.serve.config import ServeConfig
from horovod_tpu.serve.engine import (BLOCK_ROW, DECODE, ROW, Scheduler,
                                      ServeEngine, block_length,
                                      decode_width)
from horovod_tpu.serve.worker import FleetFrontend

from test_serve_chain import _mesh

CFG = dataclasses.replace(M.CONFIGS["tiny"], unmask_threshold=0.3)
MASK = CFG.mask_token_id
KIND = ROW.index("kind")


@functools.lru_cache(maxsize=None)
def _params(head_std=0.55):
    """At this head scale a toy pass fixes one to four positions (asserted
    where the streams are compared): the threshold and the surest position
    both decide."""
    return M.init(jax.random.PRNGKey(0), CFG, head_std=head_std)


def _engine(slots=3, **kw):
    base = dict(max_slots=slots, block_size=4, cache_blocks=16 * slots,
                max_seq_len=64, max_batch_tokens=8 + 4 * slots,
                prefill_chunk=8, prefix_cache=False, spec_decode=False)
    base.update(kw)
    return ServeEngine(M, CFG, _params(), ServeConfig(**base), mesh=_mesh())


def _fenced_step(engine):
    """A tick fenced before the next is launched: no launch ahead."""
    engine._dispatch()
    engine._launched = False
    return engine._harvest()


def _fenced_flush(engine):
    reports = []
    while engine.has_work():
        reports.append(_fenced_step(engine))
    return reports


@functools.lru_cache(maxsize=None)
def _plain(prompt, max_new, eos=None):
    """The plain loop's (tokens, steps), cut after ``eos`` as a stream
    is."""
    toks, steps = M.denoise(_params(), list(prompt), max_new, CFG)
    if eos in toks:
        n = toks.index(eos) + 1
        toks, steps = toks[:n], steps[:n]
    return toks, steps


def _prompts():
    rng = np.random.RandomState(1)
    # every p mod 4; one shorter than a block; one that holds M
    out = [rng.randint(0, MASK, n).tolist() for n in (13, 8, 3, 6, 17, 11)]
    out[1][3] = MASK
    return out


NEW = (10, 7, 12, 9, 4, 1)      # no multiple of 4 among the first four


@pytest.mark.parametrize("ahead", [True, False])
def test_streams_are_the_plain_loops_token_by_token_and_pass_by_pass(ahead):
    """Six prompts through three slots, admissions while ticks are in
    flight, chunked prompts, slots reused: every stream's tokens AND the
    pass each was fixed in are the plain loop's; M is never served; passes
    fixed one, two, three and four positions."""
    engine = _engine()
    prompts = _prompts()
    reqs = [engine.submit(p, n, req_id=f"r{i}")
            for i, (p, n) in enumerate(zip(prompts[:3], NEW))]
    for _ in range(5):
        engine.step() if ahead else _fenced_step(engine)
    reqs += [engine.submit(p, n, req_id=f"r{i + 3}")
             for i, (p, n) in enumerate(zip(prompts[3:], NEW[3:]))]
    reports = engine.flush() if ahead else _fenced_flush(engine)
    fixed_a_pass = set()
    for req in reqs:
        toks, steps = _plain(tuple(req.tokens), req.max_new_tokens)
        assert req.out_tokens == toks, req.req_id
        assert req.steps == steps, req.req_id
        assert MASK not in req.out_tokens
        assert req.finish_reason == "completed" and req.state == "done"
        # the last block is generated whole: what lies behind the cut
        p, end = req.prompt_len, req.prompt_len + req.max_new_tokens
        assert len(req.tail) == -end % 4
        whole = list(zip(req.out_tokens, req.steps)) + [tuple(t) for t in
                                                        req.tail]
        for at in range(0, len(whole), 4):
            block = whole[max(at - p % 4, 0):at - p % 4 + 4]
            counts = np.bincount([s for _, s in block])
            fixed_a_pass |= set(counts[counts > 0].tolist())
    assert fixed_a_pass == {1, 2, 3, 4}
    d = engine.stats()["diffusion"]
    assert d["tokens_fixed"] == d["fixed_by_threshold"] + d["fixed_as_surest"]
    assert d["tokens_fixed"] == sum(len(r.out_tokens) + len(r.tail)
                                    for r in reqs)
    # every block but a stream's last is committed by a pass of its own
    assert d["commit_passes"] == d["blocks_done"] - len(reqs)
    assert d["slot_passes"] > d["commit_passes"] + d["blocks_done"]
    loop = engine.stats()["loop"]
    if ahead:
        # a row launched ahead for an ended stream ran nothing
        assert loop["ahead_idle_rows"] >= 1 and loop["ahead_n"] > 0
    else:
        assert loop["ahead_idle_rows"] == 0 and loop["ahead_n"] == 0
    assert all(not r["handoff"] for r in reports)
    engine.close()


def test_a_blocks_tokens_come_at_the_fence_of_the_pass_that_fills_it():
    """One stream, tick by tick: nothing is served while a position of the
    block is masked; the fence of the pass that fills it serves the block's
    tokens in order of position (the first block: behind the prompt's
    remainder, and that fence is the first token's time); the commit pass
    that follows serves nothing and moves the context by a block; the
    stream ends at the fill of its last block, cut to ``max_new_tokens``."""
    engine = _engine(slots=1)
    prompt = _prompts()[0]          # 13 tokens: 12 prefilled, 1 known
    req = engine.submit(prompt, 10, req_id="a")
    toks, steps = _plain(tuple(prompt), 10)
    served, ctx = [], []
    while engine.has_work():
        rep = engine.step()
        if rep["tick"] is not None:
            served.append(rep["emitted"].get("a", []))
            ctx.append(req.ctx_len)
    # ticks: two chunks (8 + 4), then per block its passes and a commit
    assert served[:2] == [[], []] and ctx[:2] == [8, 12]
    blocks = [toks[:3], toks[3:7], toks[7:10]]
    passes = [max(steps[:3]) + 1, max(steps[3:7]) + 1]
    want, at = [], 12
    for b, n in zip(blocks[:2], passes):
        want += [([], at)] * (n - 1) + [(b, at), ([], at + 4)]
        at += 4
    got = list(zip(served[2:], ctx[2:]))
    assert got[:len(want)] == want
    # the last block: served once, at its fill; the row launched ahead of
    # that fence ran nothing and is the last report
    rest = [s for s, _ in got[len(want):]]
    assert [s for s in rest if s] == [blocks[2]] and rest[-2:] == [blocks[2],
                                                                   []]
    assert req.ttft() is not None and req.tpot() is not None
    assert req.tpot() == pytest.approx(
        (req.done_t - req.first_token_t) / (len(toks) - 1))
    engine.close()


def test_a_stream_is_the_same_alone_and_beside_31_others():
    """32 slots, 32 streams of every remainder and many lengths at once
    against each one's plain loop: batch-invariant, tokens and passes."""
    rng = np.random.RandomState(7)
    engine = _engine(slots=32, max_batch_tokens=8 + 4 * 32)
    reqs = [engine.submit(rng.randint(0, MASK, 3 + (5 * i) % 19).tolist(),
                          2 + (7 * i) % 11, req_id=f"r{i}")
            for i in range(32)]
    engine.flush()
    for req in reqs[::4]:       # eight of them against the loop
        toks, steps = _plain(tuple(req.tokens), req.max_new_tokens)
        assert (req.out_tokens, req.steps) == (toks, steps), req.req_id
    alone = _engine(slots=1)
    for req in reqs[1:8:2]:     # four more against an engine of their own
        twin = alone.submit(req.tokens, req.max_new_tokens)
        alone.flush()
        assert (req.out_tokens, req.steps, req.tail) == \
            (twin.out_tokens, twin.steps, twin.tail), req.req_id
    assert all(len(r.out_tokens) == r.max_new_tokens for r in reqs)
    engine.close()
    alone.close()


def test_eos_ends_a_stream_inside_a_block():
    """An ``eos_id`` that the stream generates: served up to it and no
    further, on the device (the next row runs nothing) as on the host."""
    prompt = tuple(_prompts()[4])
    toks, _ = _plain(prompt, 12)
    eos = toks[5]
    want = _plain(prompt, 12, eos)
    engine = _engine(slots=1)
    req = engine.submit(list(prompt), 12, req_id="a", eos_id=eos)
    engine.flush()
    assert (req.out_tokens, req.steps) == want
    assert req.finish_reason == "eos" and req.out_tokens[-1] == eos
    assert len(req.out_tokens) == toks.index(eos) + 1 <= 6
    engine.close()


def test_the_chains_state_is_the_devices_not_token_equals_m():
    """A prompt whose remainder holds M as an ordinary id: a known position
    is never masked, whatever it holds — the block row feeds it as it is
    (``rows``' prompt length decides), and the stream is the plain loop's."""
    prompt = _prompts()[0][:10] + [MASK]        # 11: 8 prefilled, 3 known
    engine = _engine(slots=1)
    engine._compile_steps()
    fed = []
    steps = dict(engine._steps)

    def recording(C):
        def run(params, cache, hist, length, done, masked, passes, tables,
                rows, tokens):
            out = steps[C](params, cache, hist, length, done, masked, passes,
                           tables, rows, tokens)
            report = np.asarray(out[-2])
            if np.asarray(rows)[KIND, 0] == DECODE:
                fed.append((np.asarray(out[4])[0].tolist(),
                            report[0, :4].tolist()))
            return out
        return run
    engine._steps = {C: recording(C) for C in steps}
    req = engine.submit(prompt, 5, req_id="a")
    engine.flush()
    assert (req.out_tokens, req.steps) == _plain(tuple(prompt), 5)
    # the first block row: positions 8, 9, 10 are the prompt's, 11 is masked
    masked_after, block = fed[0]
    assert block[:3] == prompt[8:] and block[2] == MASK
    assert masked_after == [0, 0, 0, 0]     # its one masked position fixed
    assert block[3] == req.out_tokens[0] != MASK
    engine.close()


def test_what_a_block_model_cannot_run_is_refused():
    """``spec_decode``; a chunk, a pool block, a budget or a longest
    sequence that is no multiple of the block; prefix reuse (and with it
    copy-on-write), spill, the prefill hand-off — each with its reason."""
    assert block_length(CFG) == 4 and block_length(object()) == 0
    ok = dict(max_slots=2, block_size=4, cache_blocks=32, max_seq_len=64,
              max_batch_tokens=16, prefill_chunk=8, prefix_cache=False,
              spec_decode=False)
    assert decode_width(ServeConfig(**ok), 4) == 4
    assert len(BLOCK_ROW) == len(ROW) + 1

    def build(role="mixed", **kw):
        return ServeEngine(M, CFG, _params(), ServeConfig(**dict(ok, **kw)),
                           mesh=_mesh(), role=role)
    with pytest.raises(ValueError, match="n-gram draft has no place"):
        build(spec_decode=True)
    for knob in ("prefill_chunk", "block_size", "max_batch_tokens",
                 "max_seq_len"):
        with pytest.raises(ValueError, match="no multiple of the served "
                                             "model's block_length=4"):
            build(**{knob: ok[knob] + 2})
    with pytest.raises(ValueError, match="radix prefix cache"):
        build(prefix_cache=True)
    with pytest.raises(ValueError, match="host spill tier"):
        Scheduler(ServeConfig(**dict(ok, prefix_cache=False,
                                     spill_blocks=4)), block=4)
    for role in ("prefill", "decode"):
        with pytest.raises(ValueError, match="prefill hand-off"):
            build(role=role)
    # the same settings serve a model that decodes a token after another
    Scheduler(ServeConfig(**dict(ok, prefix_cache=True, spec_decode=True)))


def test_the_plan_charges_block_rows_and_cuts_chunks_on_block_boundaries():
    """The scheduler alone: a stream in ``decode`` is charged B columns a
    tick until a fence ends it, a prompt's whole blocks are prefilled in
    chunks that are multiples of B, and a prompt shorter than a block
    starts with a block row."""
    cfg = ServeConfig(max_slots=3, block_size=4, cache_blocks=48,
                      max_seq_len=64, max_batch_tokens=20, prefill_chunk=8,
                      prefix_cache=False, spec_decode=False)
    sched = Scheduler(cfg, block=4)
    from horovod_tpu.serve.engine import Request
    a, b, c = (Request(list(range(1, n + 1)), 6, req_id=r)
               for n, r in ((19, "a"), (3, "b"), (13, "c")))
    for req in (a, b, c):
        sched.submit(req)
    work = sched.plan()
    # a: a chunk of 8 of its 16; b: no whole block, a block row; c: what is
    # left of the budget, on a block boundary
    assert [(r.req_id, n) for _, r, n in work] == [("a", 8), ("b", 4),
                                                   ("c", 8)]
    assert (a.state, b.state, c.state) == ("prefill", "decode", "prefill")
    assert sched.prefill_end(a) == 16 and sched.prefill_end(b) == 0
    a.pos, c.pos = 8, 8
    work = sched.plan()
    # decode rows first, then the prompts' rests: 8 of a's, c's last block
    assert [(r.req_id, n) for _, r, n in work] == [("b", 4), ("a", 8),
                                                   ("c", 4)]
    b.out_tokens = [1] * 6      # the plan does not end a stream: the fence does
    assert ("b", 4) in [(r.req_id, n) for _, r, n in sched.plan()]


def test_the_done_record_holds_steps_and_tail():
    """serve/worker.py ``_publish_report``: one pass number a served token,
    and what the last block holds behind the cut."""
    engine = _engine(slots=1)
    front = FleetFrontend.__new__(FleetFrontend)
    front._suppress, front._results, front._parts = {}, {}, {}
    front._first_pub = {}
    sent = {}
    front._publish_part = lambda rid, part, toks: None
    front._publish_done = lambda rid, done: sent.update({rid: done})
    req = engine.submit(_prompts()[3], 9, req_id="a")
    while engine.has_work():
        front._publish_report(engine.step())
    done = sent["a"]
    assert done["tokens"] == req.out_tokens and len(done["tokens"]) == 9
    assert done["steps"] == req.steps and len(done["steps"]) == 9
    assert done["tail"] == req.tail and len(req.tail) == 1
    assert (done["tokens"], done["steps"]) == _plain(tuple(req.tokens), 9)
    engine.close()
