"""The engine that launches tick N+1 before it fences tick N
(horovod_tpu/serve/engine.py; docs/serving.md#the-loops-order) on seven
model families at toy sizes: the plain greedy reference's tokens, the
speculation counts of the fenced order, ends of stream, slots reused, a
copy-on-write admitted while a tick is in flight, the hand-off.
tests/test_serve_chain.py has the drafter and a scripted model."""

import importlib
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.serve.config import ServeConfig
from horovod_tpu.serve.engine import DECODE, ROW, ServeEngine, samples_read

from test_serve_chain import _mesh, _record_ticks, _same_tree, stripped

FAMILIES = ["llama", "moe_llama", "latent_moe", "swa_moe", "conv_moe",
            "sambay", "gdn_hybrid"]
SHARING = FAMILIES[:3]      # whole contexts only: prefix cache and hand-off
READS = SHARING + ["sambay", "gdn_hybrid"]  # ``greedy_cached`` takes ``read``


# ----------------------------------------------------- the seven families
def _load(name):
    model = importlib.import_module("horovod_tpu.models." + name)
    cfg = model.CONFIGS["tiny"]
    return name, model, cfg, model.init(jax.random.PRNGKey(3), cfg)


def _scfg(name, **kw):
    base = dict(max_slots=3, block_size=4, cache_blocks=48, max_seq_len=48,
                max_batch_tokens=24, prefill_chunk=8, spec_k=3,
                prefix_cache=name in SHARING)
    base.update(kw)
    return ServeConfig(**base)


def _assert_reference(model, cfg, params, reqs):
    """Every request's tokens are the FULL forward's greedy ones: one
    forward over prompt + output of all requests at once (padded at the
    end, which a causal model's earlier positions do not see), each output
    token the argmax at the position before it."""
    kw = ({"moe_fn": model.dropfree_moe_fn(cfg)}
          if hasattr(model, "dropfree_moe_fn") else {})
    ids = np.zeros((len(reqs), 40), np.int32)
    for i, req in enumerate(reqs):
        seq = req.tokens + req.out_tokens
        ids[i, :len(seq)] = seq
    logits = model.apply(params, jnp.asarray(ids), cfg, **kw)
    logits = logits[0] if isinstance(logits, tuple) else logits
    greedy = np.argmax(np.asarray(logits, np.float32), axis=-1)
    for i, req in enumerate(reqs):
        first = req.prompt_len - 1
        want = greedy[i, first:first + len(req.out_tokens)].tolist()
        assert req.out_tokens == want, req.req_id


def _prompts(vocab):
    rng = np.random.RandomState(5)
    base = rng.randint(1, vocab, 6).tolist()
    # two prompts hold a run of zeros followed by something else: under
    # ``_flat`` weights (every stream zeros) their first drafts are wrong
    return [(base * 3)[:13], rng.randint(1, vocab, 5).tolist(),
            [7, 0, 0, 5, 6, 7], (base * 2)[:9] + [3],
            rng.randint(1, vocab, 17).tolist(), [0, 0, 9] + base[:4]]


def _script(engine, vocab, eos=None):
    """Three requests, five steps, three more: admissions while ticks are in
    flight, chunked prompts, slots reused.  Returns the requests."""
    prompts = _prompts(vocab)
    reqs = [engine.submit(p, 12, req_id=f"r{i}", eos_id=eos)
            for i, p in enumerate(prompts[:3])]
    for _ in range(5):
        engine.step()
    reqs += [engine.submit(p, 9, req_id=f"r{i + 3}", eos_id=eos)
             for i, p in enumerate(prompts[3:])]
    engine.flush()
    return reqs


def _flat(params):
    """Weights under which every logit is 0: the argmax of a tie is token 0
    on any platform, so the streams, the drafts and what is accepted of them
    are known whatever the platform's rounding."""
    key = "lm_head" if "lm_head" in params else "embed"
    return dict(params, **{key: jax.tree_util.tree_map(jnp.zeros_like,
                                                       params[key])})


@pytest.fixture(scope="module", params=FAMILIES)
def family(request):
    """A family at `tiny` and ``_script`` served once on it: (name, model,
    config, weights, the requests, ``stats()``)."""
    name, model, cfg, params = _load(request.param)
    engine = ServeEngine(model, cfg, params, _scfg(name), mesh=_mesh())
    reqs = _script(engine, cfg.vocab)
    stats = engine.stats()
    engine.close()
    return name, model, cfg, params, reqs, stats


def test_launch_ahead_serves_the_plain_greedy_references_tokens(family):
    name, model, cfg, params, reqs, stats = family
    assert all(r.state == "done" and len(r.out_tokens) == r.max_new_tokens
               for r in reqs)
    _assert_reference(model, cfg, params, reqs)
    loop = stats["loop"]
    assert loop["ahead_n"] >= loop["ticks"] - 3     # but after an idle engine
    assert loop["turnaround_n"] + loop["after_idle_n"] == loop["ticks"]


def test_the_slab_branch_serves_the_same_streams_counts_and_ends(family):
    """The module as it samples in the tick — llama, moe_llama, latent_moe,
    sambay and gdn_hybrid on the rows of the columns the tick reads
    (``greedy_cached(.., read)``), swa_moe and conv_moe on every packed row — against the same
    module without ``greedy_cached``, through the tick's slab branch (the
    argmax of ``apply_cached``'s ``[slots, chunk, vocab]`` logits): with
    speculation on and an ``eos_id`` out of the streams, the same tokens,
    drafts made and accepted, ends of stream and ticks at each width.  The
    head of a wide tick ran on 3 slots x (1 + spec_k) rows of the 24 packed
    where the module takes ``read``, on all 24 elsewhere."""
    name, model, cfg, params, plain, _ = family
    eos = plain[0].out_tokens[3]
    runs = {}
    for form, served in (("module", model), ("slab", stripped(model))):
        engine = ServeEngine(served, cfg, params, _scfg(name), mesh=_mesh())
        reqs = _script(engine, cfg.vocab, eos=eos)
        stats = engine.stats()
        engine.close()
        runs[form] = ([(r.out_tokens, r.finish_reason) for r in reqs],
                      stats["spec"], stats["loop"])
    (ours, spec, loop), (theirs, slab_spec, slab_loop) = \
        runs["module"], runs["slab"]
    assert ours == theirs and ours[0][1] == "eos"
    assert spec == slab_spec
    wide = loop["by_width"]["wide"]["ticks"]
    assert wide == slab_loop["by_width"]["wide"]["ticks"] > 0
    assert loop["by_width"]["narrow"]["ticks"] == \
        slab_loop["by_width"]["narrow"]["ticks"] > 0
    assert loop["packed_rows"] == slab_loop["packed_rows"] == 24 * wide
    assert slab_loop["head_rows"] == 24 * wide
    assert samples_read(model) == (name in READS)
    assert loop["head_rows"] == (12 if samples_read(model) else 24) * wide
    # ... and where every draft is known (``_flat``: zeros), the slab branch
    # drafts and accepts what the module's form does
    # (test_speculation_drafts_and_accepts_what_the_fenced_order_did)
    engine = ServeEngine(stripped(model), cfg, _flat(params), _scfg(name),
                         mesh=_mesh())
    _script(engine, cfg.vocab)
    spec = engine.stats()["spec"]
    engine.close()
    assert (spec["drafted_tokens"], spec["accepted_tokens"]) == \
        FENCED_ORDER_SPEC


# drafted / accepted tokens of ``_script`` under ``_flat`` weights on the
# parent commit (a8ed458: fence, then plan with ``Request.draft_lookup`` on
# the host, then launch), recorded there with this file's ``_script``
FENCED_ORDER_SPEC = (27, 22)


def test_speculation_drafts_and_accepts_what_the_fenced_order_did(family):
    name, model, cfg, params = family[:4]
    engine = ServeEngine(model, cfg, _flat(params), _scfg(name), mesh=_mesh())
    reqs = _script(engine, cfg.vocab)
    assert all(r.out_tokens == [0] * r.max_new_tokens for r in reqs)
    spec = engine.stats()["spec"]
    assert (spec["drafted_tokens"], spec["accepted_tokens"]) == \
        FENCED_ORDER_SPEC
    engine.close()


def test_eos_ends_a_stream_where_the_reference_first_says_it(family):
    """An ``eos_id`` taken from the streams themselves (the fourth token of
    ``r0``): every stream ends at its first occurrence, wherever in a verify
    row that falls, and is the plain stream up to there; a row launched
    ahead of such an end runs nothing, and every block comes back."""
    name, model, cfg, params, plain, _ = family
    full = [r.out_tokens for r in plain]
    eos = full[0][3]
    engine = ServeEngine(model, cfg, params, _scfg(name), mesh=_mesh())
    reqs = _script(engine, cfg.vocab, eos=eos)
    for req, whole in zip(reqs, full):
        want = whole[:whole.index(eos) + 1] if eos in whole else whole
        assert req.out_tokens == want, req.req_id
        assert req.finish_reason == ("eos" if eos in whole else "completed")
    assert reqs[0].finish_reason == "eos"
    held = engine.scheduler.prefix.size if engine.scheduler.prefix else 0
    assert engine.scheduler.allocator.free_count == 48 - held
    assert engine.scheduler.active == 0 and not engine._inflight
    engine.close()


def test_an_idle_row_changes_no_pool_and_no_state(family):
    """Under ``_flat`` weights every stream is zeros and its drafts are
    accepted, so a stream ends inside a tick the host has not fenced and the
    row launched ahead for it runs nothing: with one slot, that tick leaves
    every leaf of every cache kind — pool, ring and state — and the chain as
    the tick before left them; the request behind it takes the slot the
    tick after."""
    name, model, cfg, params = family[:4]
    engine = ServeEngine(model, cfg, _flat(params),
                         _scfg(name, max_slots=1, prefix_cache=False),
                         mesh=_mesh())
    ticks = _record_ticks(engine)
    a = engine.submit([5, 6, 7, 5, 6, 7, 5], 9, req_id="a")
    b = engine.submit([9, 8, 9], 2, req_id="b")
    engine.flush()
    assert a.out_tokens == [0] * 9 and b.out_tokens == [0] * 2
    assert engine.stats()["loop"]["ahead_idle_rows"] == 1
    idle = [i for i, t in enumerate(ticks) if t["kind"][0] == DECODE
            and not t["n"][0]]
    assert len(idle) == 1
    at = idle[0]
    for key in ("cache", "hist", "length", "done"):
        assert _same_tree(ticks[at][key], ticks[at - 1][key]), key
    # the history is the prompt and what was emitted, then b's
    assert ticks[at]["hist"][0, :16].tolist() == a.tokens + [0] * 9
    assert ticks[at + 1]["n"][0] == 3 and ticks[at + 1]["done"][0] == 0
    assert ticks[-1]["hist"][0, :5].tolist() == b.tokens + [0] * 2
    engine.close()


@pytest.fixture(scope="module", params=SHARING)
def sharing(request):
    return _load(request.param)


def test_a_prefix_hit_with_copy_on_write_admitted_beside_a_tick_in_flight(
        sharing):
    """``b`` shares a block and a half of ``a``'s prompt and arrives while
    ``a`` decodes: it is admitted (block shared, tail block cloned on the
    device) in a step() that finds ``a``'s tick unfenced, and both streams
    are the reference's."""
    name, model, cfg, params = sharing
    engine = ServeEngine(model, cfg, params, _scfg(name), mesh=_mesh())
    rng = np.random.RandomState(9)
    pa = rng.randint(1, cfg.vocab, 11).tolist()
    pb = pa[:6] + rng.randint(1, cfg.vocab, 5).tolist()
    a = engine.submit(pa, 10, req_id="a")
    while len(a.out_tokens) < 2:
        engine.step()
    b = engine.submit(pb, 6, req_id="b")
    assert engine._inflight                 # a's tick, unfenced
    engine.step()
    assert b.state != "waiting" and len(engine._inflight) == 1
    engine.flush()
    stats = engine.stats()["prefix_cache"]
    assert stats["hits"] == 1 and stats["cow_copies"] == 1
    assert stats["hit_tokens"] == 6
    _assert_reference(model, cfg, params, [a, b])
    engine.close()


def test_a_prefill_roles_hand_off_and_a_decode_roles_import(sharing):
    """A ``prefill`` engine exports at the fence of a prompt's last chunk
    and plans no decode row behind it; a ``decode`` engine takes the first
    token from the hand-off (its first row runs at the host's length, the
    carry flag off) and the stream is the reference's."""
    name, model, cfg, params = sharing
    pre = ServeEngine(model, cfg, params, _scfg(name), mesh=_mesh(),
                      role="prefill")
    dec = ServeEngine(model, cfg, params, _scfg(name), mesh=_mesh(),
                      role="decode")
    prompts = _prompts(cfg.vocab)[:3]
    for i, p in enumerate(prompts):
        pre.submit(p, 8, req_id=f"r{i}")
    handoffs = []
    while pre.has_work():
        handoffs.extend(pre.step().get("handoff", []))
    assert len(handoffs) == 3 and pre.stats()["tokens_decode"] == 0
    assert pre.stats()["loop"]["ahead_idle_rows"] == 0
    fed = _record_ticks(dec)
    reqs = [dec.import_prefill(json.loads(json.dumps(h))) for h in handoffs]
    emitted = {}
    while dec.has_work():
        for rid, toks in dec.step()["emitted"].items():
            emitted.setdefault(rid, []).extend(toks)
    carried = ROW.index("carried")
    assert fed[0]["rows"][carried].tolist() == [0, 0, 0]
    assert all(t["rows"][carried][t["kind"] == DECODE].all() for t in fed[1:])
    assert all(req.out_tokens == emitted[req.req_id] and
               len(req.out_tokens) == 8 for req in reqs)
    _assert_reference(model, cfg, params, reqs)
    pre.close()
    dec.close()


def test_a_hand_offs_first_token_is_the_slab_branchs(sharing):
    """A ``prefill`` role reads one token a prompt, its last chunk's last
    column — column 0 of what a module that takes ``read`` reports: the
    first tokens handed off are those of the tick's slab branch."""
    name, model, cfg, params = sharing
    first = {}
    for served in (model, stripped(model)):
        engine = ServeEngine(served, cfg, params, _scfg(name), mesh=_mesh(),
                             role="prefill")
        for i, p in enumerate(_prompts(cfg.vocab)):
            engine.submit(p, 8, req_id=f"r{i}")
        handoffs = []
        while engine.has_work():
            handoffs.extend(engine.step().get("handoff", []))
        first[served is model] = {h["req_id"]: h["first_token"]
                                  for h in handoffs}
        engine.close()
    assert first[True] == first[False] and len(first[True]) == 6
