"""The decode chain on the device (horovod_tpu/serve/engine.py
``tick_program``, ``draft_rows``; docs/serving.md#the-loops-order): the
device's n-gram drafter against ``Request.draft_lookup`` token for token, and
the engine that launches tick N+1 before it fences tick N, on a scripted
model whose streams are known by hand (tests/test_serve_chain_families.py
has the five model families)."""

import dataclasses
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from horovod_tpu.models import paged
from horovod_tpu.serve.config import ServeConfig
from horovod_tpu.serve.engine import (DECODE, LAST, ROW, Request, ServeEngine,
                                      decode_width, draft_rows, samples_read)

KIND = ROW.index("kind")


def _mesh():
    return jax.sharding.Mesh(np.array(jax.devices()[:1]), ("hvd",))


# ---------------------------------------------------------------- drafter
def _streams():
    rng = np.random.RandomState(3)
    out = {"random": rng.randint(0, 5, 40).tolist(),      # 5 tokens: repeats
           "sparse": rng.randint(0, 200, 40).tolist(),    # hardly any
           "period3": [4, 9, 2] * 14,
           "period1": [7] * 40,
           "two": [1, 2], "one": [3]}
    out["late"] = out["sparse"][:20] + out["sparse"][5:25]
    return out


STREAMS = _streams()


@pytest.mark.parametrize("name", sorted(STREAMS))
def test_device_drafter_is_draft_lookup_token_for_token(name):
    """Every prefix of the stream, as a context: the device drafts what
    ``Request.draft_lookup`` drafts, under every cap from none to past
    ``spec_k`` — all prefixes at once, a slot each."""
    stream = STREAMS[name]
    H, K = len(stream) + 4, 4
    lens = list(range(1, len(stream) + 1))
    hist = np.full((len(lens), H), 250, np.int32)  # stale beyond a context
    for s, n in enumerate(lens):
        hist[s, :n] = stream[:n]
    run = jax.jit(draft_rows, static_argnums=3)
    for cap in range(-1, K + 2):
        draft, n = run(jnp.asarray(hist), jnp.asarray(lens, jnp.int32),
                       jnp.full(len(lens), cap, jnp.int32), K)
        draft, n = np.asarray(draft), np.asarray(n)
        for s, length in enumerate(lens):
            want = Request(stream[:length], 8).draft_lookup(min(cap, K))
            assert draft[s, :n[s]].tolist() == want, (name, length, cap)


@pytest.mark.parametrize("case,budget,new,prompts", [
    # one stream, room for everything: spec_k caps the draft
    ("spec_k", 16, 12, [[1, 2, 3, 1, 2, 3, 1]]),
    # two streams, a budget of 6: the first row takes 1 + 3, the second is
    # planned the 2 columns left; of 5: the bonus token alone
    # (10 -> 11 -> ... -> 15 -> 15: the first stream decodes all along)
    ("row", 6, 12, [[10], [7, 8, 7, 8, 7]]),
    ("budget", 5, 12, [[10], [7, 8, 7, 8, 7]]),
    # the remaining generation: no draft is verified past max_new
    ("generation", 16, 5, [[7, 8, 7, 8, 7]]),
    ("last", 16, 2, [[7, 8, 7, 8, 7]]),
    # a context of two tokens has no prior bigram
    ("short", 16, 6, [[7]]),
])
def test_device_drafter_caps(scripted, case, budget, new, prompts):
    """Every verify row of a run on the scripted model, replayed on the
    host: the drafts the tick fed are ``draft_lookup``'s under the row's
    caps — ``spec_k``, the columns the plan gave the row (the tick budget;
    the verify row's width), the remaining generation, a stream shorter
    than three — token for token."""
    engine = _markov_engine(model=scripted, max_batch_tokens=budget,
                            prefill_chunk=5)
    ticks = _record_ticks(engine)
    reqs = [engine.submit(p, new, req_id=str(i))
            for i, p in enumerate(prompts)]
    emitted = {r["tick"]: r["emitted"] for r in engine.flush()}
    oracles = [Request(p, new) for p in prompts]
    drafted, planned = [], []
    for i, tick in enumerate(ticks):
        for slot, oracle in enumerate(oracles):
            if tick["kind"][slot] == DECODE and tick["n"][slot]:
                n_plan = tick["rows"][ROW.index("n")][slot]
                cap = min(3, n_plan - 1, new - len(oracle.out_tokens) - 1)
                want = oracle.draft_lookup(cap) if cap >= 1 else []
                n = tick["n"][slot]
                assert tick["fed"][slot, 1:n].tolist() == want, (i, slot)
                drafted.append(len(want))
                planned.append((slot, int(n_plan)))
            oracle.out_tokens += emitted[i].get(str(slot), [])
    for req, prompt in zip(reqs, prompts):
        assert req.out_tokens == _markov_stream(prompt, new)
    if case == "spec_k":
        assert max(drafted) == 3 and min(n for _, n in planned) == 4
    if case == "row":
        assert (1, 2) in planned and (0, 4) in planned
    if case == "budget":
        assert (1, 1) in planned and (1, 2) not in planned
    if case == "generation":
        assert drafted[-1] <= 1 and max(drafted) >= 2
    if case in ("last", "short"):
        assert not any(drafted[:1])
    engine.close()


# ---------------------------------------------- a model known by hand
@dataclasses.dataclass(frozen=True)
class _MarkovConfig:
    max_seq: int = 64
    max_tick_tokens: int = 0
    vocab: int = 16


class Markov:
    """A served model whose greedy stream is a table: the token after ``t``
    is ``NEXT[t]``, whatever came before.  Its cache holds, at every written
    position, the token written + 1: what a tick wrote is there to read."""
    # 7 -> 8 -> 7 ...; 1 -> 2 -> 3 -> 1 ...; 5 -> 6 -> 9 -> 9 ...
    NEXT = np.array([0, 2, 3, 1, 4, 6, 9, 8, 7, 9, 10, 11, 12, 13, 14, 15],
                    np.int32)
    TICK_COUNTERS = ()
    copy_blocks = staticmethod(paged.copy_blocks)

    @staticmethod
    def init_cache(cfg, num_blocks, block_size):
        return {"tok": jnp.zeros((1, num_blocks, block_size), jnp.int32)}

    @staticmethod
    def cache_shardings(mesh, cfg, num_blocks):
        return NamedSharding(mesh, P())

    @staticmethod
    def attn_blocks(cfg, S, C, ctx):
        return S, C

    @staticmethod
    def greedy_cached(params, tokens, cfg, cache, block_tables, lengths,
                      n_new):
        C = tokens.shape[1]
        pos, valid = paged.slot_positions(lengths, n_new, C)
        blk, off = paged.write_index(block_tables, pos, valid,
                                     cache["tok"].shape[1],
                                     cache["tok"].shape[2])
        cache = paged.write(cache, 0, blk, off, {"tok": tokens + 1})
        return params["next"][tokens], cache


class MarkovRead(Markov):
    """``Markov`` sampling where the tick reads (``greedy_cached(.., read)``,
    as models/llama.py has it): the token after each slot's columns ``read``
    [S, W] and after no other."""

    @staticmethod
    def greedy_cached(params, tokens, cfg, cache, block_tables, lengths,
                      n_new, read):
        every, cache = Markov.greedy_cached(
            params, tokens, cfg, cache, block_tables, lengths, n_new)
        return jnp.take_along_axis(every, read, axis=1), cache


@pytest.fixture(params=[Markov, MarkovRead], ids=["columns", "read"])
def scripted(request):
    """The scripted model in either form of ``greedy_cached``: a token a
    column of the tick, or a token a column the tick reads."""
    return request.param


def stripped(model):
    """``model`` (a module) without its ``greedy_cached``: the tick takes
    ``apply_cached``'s ``[slots, chunk, vocab]`` logits and their argmax
    itself (``tick/sample``), and reports a token a column."""
    return types.SimpleNamespace(**{k: v for k, v in vars(model).items()
                                    if k != "greedy_cached"})


def _markov_stream(prompt, n):
    out, tok = [], prompt[-1]
    for _ in range(n):
        tok = int(Markov.NEXT[tok])
        out.append(tok)
    return out


def _markov_engine(role="mixed", model=Markov, **kw):
    base = dict(max_slots=2, block_size=4, cache_blocks=32, max_seq_len=48,
                max_batch_tokens=16, prefill_chunk=8, spec_k=3,
                prefix_cache=False)
    base.update(kw)
    return ServeEngine(model, _MarkovConfig(), {"next": Markov.NEXT},
                       ServeConfig(**base), mesh=_mesh(), role=role)


def _record_ticks(engine):
    """Every launched tick, fetched as it is launched: the rows the host
    staged, and what the program returned — the pool, the chain, the report
    (greedy tokens, of every column or of the W the tick reads | the verify
    rows as fed | columns | lengths)."""
    engine._compile_steps()
    ticks = []
    W = decode_width(engine.cfg)
    read = samples_read(engine.model)

    def recording(C, step):
        def run(params, cache, hist, length, done, tables, rows, tokens):
            out = step(params, cache, hist, length, done, tables, rows,
                       tokens)
            cache, hist, length, done, report = jax.device_get(out[:5])
            rows = np.asarray(rows)
            T = W if read else C
            ticks.append({"kind": rows[KIND], "rows": rows, "cache": cache,
                          "hist": hist, "length": length, "done": done,
                          "greedy": report[:, :T], "fed": report[:, T:T + W],
                          "n": report[:, -2], "at": report[:, -1]})
            return out
        return run
    engine._steps = {C: recording(C, step)
                     for C, step in engine._steps.items()}
    return ticks


def _same_tree(a, b):
    return all(np.array_equal(x, y) for x, y in zip(
        jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)))


def test_eos_in_the_middle_of_a_verify_row_ends_the_stream_there(scripted):
    """1 -> 2 -> 3 -> 1 ...: from the second period on the drafter is right,
    the verify rows are wholly accepted, and an ``eos_id`` of 3 falls in the
    middle of one.  The stream ends at the 3, the drafts verified behind it
    are discarded, and the row launched ahead for the ended stream runs
    nothing: pool, history, length and end of stream as the tick before left
    them."""
    engine = _markov_engine(model=scripted, max_slots=1)
    ticks = _record_ticks(engine)
    req = engine.submit([1, 2, 3, 1], 20, req_id="a", eos_id=3)
    reports = engine.flush()
    assert req.out_tokens == [2, 3] and req.finish_reason == "eos"
    # the verify row: 2 fed with the drafts 3, 1, 2 — all three "accepted"
    # by the greedy rule, the stream over at the first of them
    row = next(t for t in ticks if t["kind"][0] == DECODE)
    assert row["fed"][0].tolist() == [2, 3, 1, 2] and row["n"][0] == 4
    assert row["done"][0] == 1
    assert engine.stats()["spec"] == {
        "enabled": True, "drafted_tokens": 3, "accepted_tokens": 3,
        "accept_rate": 1.0}
    idle = ticks[-1]
    assert idle["kind"][0] == DECODE and idle["n"][0] == 0
    before = ticks[-2]
    for key in ("cache", "hist", "length", "done"):
        assert _same_tree(idle[key], before[key]), key
    loop = engine.stats()["loop"]
    assert loop["ahead_idle_rows"] == 1 and loop["ahead_n"] == len(ticks) - 1
    assert sum(r["processed"] for r in reports) == 4 + 4
    assert engine.scheduler.allocator.free_count == 32
    engine.close()


def test_max_new_at_a_verify_rows_end_and_the_slot_reused_the_tick_after(
        scripted):
    """One slot, two requests.  ``a`` (7, 8, 7, 8 ...) reaches its
    ``max_new_tokens`` with the last token of an accepted verify row, which
    the host cannot know before the fence: the row launched ahead runs
    nothing, and ``b``, waiting for the slot, is admitted into it the tick
    after — its chunk, not the chain's stale end of stream, decides."""
    engine = _markov_engine(model=scripted, max_slots=1)
    ticks = _record_ticks(engine)
    a = engine.submit([7, 8, 7, 8, 7], 6, req_id="a")
    b = engine.submit([5, 6, 5], 4, req_id="b")
    engine.flush()
    assert a.out_tokens == _markov_stream([7], 6) == [8, 7, 8, 7, 8, 7]
    assert b.out_tokens == _markov_stream([5], 4) == [6, 9, 9, 9]
    assert a.finish_reason == b.finish_reason == "completed"
    kinds = [int(t["kind"][0]) for t in ticks]
    ran = [int(t["n"][0]) for t in ticks]
    # a: its prompt, 1 + 2 accepted (the context's end is all that followed
    # the bigram), 1 + 1 (two left: one draft) at the end of which it is
    # done; the row launched ahead; then b in the same slot
    assert kinds[:4] == [LAST, DECODE, DECODE, DECODE]
    assert ran[:4] == [5, 3, 2, 0]
    assert kinds[4] == LAST and ran[4] == 3
    assert ticks[4]["done"][0] == 0 and ticks[3]["done"][0] == 1
    assert _same_tree(ticks[3]["cache"], ticks[2]["cache"])
    # b's blocks are a's, freed at a's fence (LIFO) and written by b alone
    assert ticks[4]["cache"]["tok"][0, 0, :3].tolist() == [6, 7, 6]
    assert engine.stats()["loop"]["ahead_idle_rows"] == 1
    engine.close()


def test_a_predictable_end_is_planned_no_row():
    """Without speculation every row emits one token: the tick that holds
    the last one is known when it is launched, and nothing is launched for
    the stream behind it."""
    engine = _markov_engine(max_slots=1, spec_decode=False)
    ticks = _record_ticks(engine)
    req = engine.submit([1, 2], 5, req_id="a")
    engine.flush()
    assert req.out_tokens == [3, 1, 2, 3, 1]
    assert len(ticks) == 5 and all(t["n"][0] for t in ticks)
    loop = engine.stats()["loop"]
    assert loop["ahead_idle_rows"] == 0 and loop["ahead_n"] == 4
    assert loop["turnaround_n"] + loop["after_idle_n"] == 5
    assert loop["turnaround_s"] == 0.0      # every launch but the first: ahead
    engine.close()


def test_the_first_token_is_reported_by_the_step_that_launched_the_next_tick():
    """The loop's order: a step() launches, then fences the tick before.
    The prompt's tick is fenced, and its first token reported, by the step()
    that launched the first decode row."""
    engine = _markov_engine()
    engine.submit([1, 2, 3], 6, req_id="a")
    first = engine.step()
    assert first["tick"] is None and len(engine._inflight) == 1
    second = engine.step()
    assert second["tick"] == 0 and second["emitted"] == {"a": [1]}
    assert engine.tick == 2 and len(engine._inflight) == 1
    third = engine.step()
    assert third["tick"] == 1 and third["emitted"]["a"][0] == 2
    engine.flush()
    assert not engine.has_work() and not engine._inflight
    engine.close()


def test_a_fence_with_nothing_launched_behind_it_times_the_next_launch():
    """A launch that finds nothing in flight after a fence is the host's
    path between two programs, as before: one slot, a second request that
    can only be admitted once the first is fenced done."""
    engine = _markov_engine(max_slots=1, spec_decode=False)
    engine.submit([1, 2], 1, req_id="a")
    engine.submit([5, 6], 1, req_id="b")
    engine.flush()
    loop = engine.stats()["loop"]
    # a's tick after idle; b's behind a's fence, nothing in flight
    assert (loop["after_idle_n"], loop["turnaround_n"], loop["ahead_n"]) == \
        (1, 1, 0)
    assert loop["turnaround_s"] > 0
    parts = loop["turnaround_parts_s"]
    assert abs(sum(parts.values()) - loop["turnaround_s"]) < 1e-6
    engine.close()


def test_two_engines_fold_the_devices_drafts_into_one_digest():
    """The drafts are the device's and the digest folds them at the fence
    that reports them: two engines fed alike agree, an engine fed a stream
    that drafts differently does not."""
    digests = []
    for prompt in ([1, 2, 3, 1], [1, 2, 3, 1], [1, 2, 3, 2]):
        engine = _markov_engine()
        engine.submit(prompt, 8, req_id="a")
        engine.flush()
        digests.append(engine.sched_digest)
        engine.close()
    assert digests[0] == digests[1] != digests[2]
