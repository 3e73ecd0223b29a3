"""The program's own clocks (ISSUE 24; docs/profiling.md, docs/serving.md
#request-lifecycle): the phase accumulator's arithmetic, the tick phases
and request hops a real ServeEngine reports through FleetFrontend.run, the
compile counter, and the named scopes of the device half — present in the
lowered programs and without effect on what they compute."""

import contextlib
import json
import threading
import time
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from horovod_tpu.models import llama
from horovod_tpu.parallel.data_parallel import make_scanned_train_step
from horovod_tpu.serve.config import ServeConfig
from horovod_tpu.serve.engine import ServeEngine
from horovod_tpu.serve.router import RouterState
from horovod_tpu.serve.worker import FleetFrontend
from horovod_tpu.utils.profiler import PhaseClock, compile_counts
from tests.test_serve_ft import ScriptedEngine

CFG = llama.CONFIGS["tiny"]
PHASES = {"poll", "submit", "harvest_wait", "harvest_emit", "plan", "stage",
          "launch", "publish", "idle"}
# A request's phases against its queue + prefill + decode: the phases are
# exhaustive, so what differs is the two phases cut at its ends (the rest
# of its `submit`, the `harvest_emit` it finished in) and a preempted loop
# thread between two spans.
SUM_TOL_S, SUM_TOL_SHARE = 0.01, 0.1


# ------------------------------------------------------------ accumulator
def test_phase_clock_snapshot_and_delta():
    clock = PhaseClock()
    with clock.span("a"):
        time.sleep(0.002)
    snap = clock.snapshot()
    assert snap["phase_n"] == {"a": 1} and snap["phase_s"]["a"] >= 0.002
    assert {"compiles", "cache_hits"} <= set(snap)
    for _ in range(3):
        with clock.span("a"):
            pass
    with clock.span("b"):
        time.sleep(0.001)
    d = clock.delta(snap)
    assert d["phase_n"] == {"a": 3, "b": 1}
    assert d["phase_s"]["b"] >= 0.001 and 0 <= d["phase_s"]["a"] < 0.001
    assert d["compiles"] == 0 and d["cache_hits"] == 0
    # a snapshot is a copy: later spans leave it as it was
    assert snap["phase_n"] == {"a": 1}
    assert clock.delta(clock.snapshot())["phase_n"] == {"a": 0, "b": 0}


def test_phase_clock_counts_a_span_that_raises():
    clock = PhaseClock()
    with pytest.raises(KeyError):
        with clock.span("a"):
            raise KeyError("x")
    assert clock.phase_n == {"a": 1}


def test_compile_counts_sees_a_new_program_once():
    before = compile_counts()["compiles"]
    f = jax.jit(lambda x: x * 3 + 1)
    f(jnp.ones(7))
    mid = compile_counts()["compiles"]
    f(jnp.ones(7))
    assert mid >= before + 1
    assert compile_counts()["compiles"] == mid


# ------------------------------------------------------- the serving loop
@pytest.fixture(scope="module")
def served():
    """Three requests through the real router, FleetFrontend.run and a
    ServeEngine at `tiny`: the first alone (it pays the compile), then two
    together.  Ticks are counted from outside, by wrapping the engine's
    harvest."""
    from horovod_tpu.runner.http_server import RendezvousServer
    server = RendezvousServer(host="127.0.0.1")
    port = server.start()
    server._httpd.serve_router = RouterState(journal=True)
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("hvd",))
    scfg = ServeConfig(max_slots=2, block_size=4, cache_blocks=32,
                       max_seq_len=64, max_batch_tokens=16, prefill_chunk=8)
    engine = ServeEngine(llama, CFG, llama.init(jax.random.PRNGKey(0), CFG),
                         scfg, mesh=mesh)
    fe = FleetFrontend(engine, "127.0.0.1", port, 0, 1, direct=True)

    seen = {"harvests": 0, "at_submit": {}, "first": {}, "last": {},
            "widths": []}
    submit, harvest = engine.submit, engine._harvest

    def counted_submit(*a, **k):
        seen["at_submit"][k["req_id"]] = seen["harvests"]
        return submit(*a, **k)

    def counted_harvest():
        width = (engine._inflight[0][2].shape[1] if engine._inflight
                 else None)
        rep = harvest()
        if rep["tick"] is not None:
            seen["harvests"] += 1
            seen["widths"].append(width)
            for rid in rep["emitted"]:
                seen["first"].setdefault(rid, seen["harvests"])
            for req in rep["finished"]:
                seen["last"][req.req_id] = seen["harvests"]
        return rep
    engine.submit, engine._harvest = counted_submit, counted_harvest

    loop = threading.Thread(target=fe.run, kwargs={"ttl_s": 60.0})
    loop.start()
    answers = {}

    def generate(name, tokens, new):
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/generate",
            data=json.dumps({"tokens": tokens,
                             "max_new_tokens": new}).encode(),
            headers={"Content-Type": "application/json"}, method="POST")
        with urllib.request.urlopen(req, timeout=60) as r:
            answers[name] = [json.loads(ln) for ln in r.read().splitlines()]

    try:
        generate("cold", list(range(1, 12)), 4)   # 11 tokens: two chunks
        pair = [threading.Thread(target=generate, args=a) for a in
                (("warm", list(range(20, 40)), 6),   # three chunks
                 ("short", [5, 6, 7], 3))]
        for t in pair:
            t.start()
        for t in pair:
            t.join(timeout=60)
        # the loop publishes its stats on a period: wait (over some idle
        # iterations) for the snapshot that has seen all three finish
        stats, deadline = {}, time.time() + 30
        while stats.get("engine", {}).get("completed", 0) < 3 and \
                time.time() < deadline:
            time.sleep(0.1)
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/serve/stats", timeout=10) as r:
                stats = json.loads(r.read())
        urllib.request.urlopen(urllib.request.Request(
            f"http://127.0.0.1:{port}/admin/drain", data=b"{}",
            headers={"Content-Type": "application/json"}, method="POST"),
            timeout=30).read()
    finally:
        loop.join(timeout=30)
        engine.close()
        server.stop()
    assert not loop.is_alive()
    done = {name: lines[-1] for name, lines in answers.items()}
    return {"done": done, "seen": seen, "engine": engine, "stats": stats}


def test_every_phase_has_a_count(served):
    loop = served["engine"].stats()["loop"]
    assert set(loop["phase_n"]) == PHASES
    assert all(n >= 1 for n in loop["phase_n"].values())
    assert all(s >= 0 for s in loop["phase_s"].values())
    assert loop["ticks"] == served["seen"]["harvests"] == \
        loop["phase_n"]["harvest_wait"]
    # a tick is planned, staged, launched and harvested once each
    assert loop["phase_n"]["stage"] == loop["phase_n"]["launch"] == \
        loop["phase_n"]["harvest_emit"] == loop["ticks"]


def test_serve_stats_route_shows_the_loop_table(served):
    loop = served["stats"]["engine"]["loop"]
    assert {"phase_s", "phase_n", "ticks", "compiles", "cache_hits"} <= \
        set(loop)
    assert loop["ticks"] >= 1 and loop["compiles"] >= 1


@pytest.mark.parametrize("name", ["cold", "warm", "short"])
def test_request_phases_sum_to_its_life(served, name):
    done = served["done"][name]
    t = done["timing"]
    life = t["queue"] + t["prefill"] + t["decode"]
    total = sum(done["loop"]["phase_s"].values())
    assert abs(total - life) <= SUM_TOL_S + SUM_TOL_SHARE * life, \
        (total, life, done["loop"])
    assert done["loop"]["phase_s"].get("idle", 0.0) == 0.0


@pytest.mark.parametrize("name", ["cold", "warm", "short"])
def test_request_ticks_equal_the_ticks_counted_outside(served, name):
    done, seen = served["done"][name], served["seen"]
    rid = done["trace"]["rid"]
    assert done["loop"]["ticks"] == seen["last"][rid] - seen["at_submit"][rid]
    assert done["loop"]["prefill_ticks"] == \
        seen["first"][rid] - seen["at_submit"][rid]
    assert 1 <= done["loop"]["prefill_ticks"] <= done["loop"]["ticks"]


def test_prefill_ticks_count_the_chunks(served):
    # nothing in flight at submit: one tick a chunk of 8 prompt tokens
    assert served["done"]["cold"]["loop"]["prefill_ticks"] == 2
    # `warm` is 20 tokens = three chunks, perhaps behind a tick in flight
    assert served["done"]["warm"]["loop"]["prefill_ticks"] in (3, 4)


@pytest.mark.parametrize("name", ["cold", "warm", "short"])
def test_pickup_and_publish_present_and_non_negative(served, name):
    t = served["done"][name]["timing"]
    assert 0.0 <= t["pickup"] < 5.0
    assert 0.0 <= t["publish"] < 5.0
    # the three the router's attribution reads are as they were
    assert {"queue", "prefill", "decode"} <= set(t)


def test_compiles_on_the_first_tick_and_none_after_warm_up(served):
    assert served["done"]["cold"]["loop"]["compiles"] >= 1
    assert served["done"]["warm"]["loop"]["compiles"] == 0
    assert served["done"]["short"]["loop"]["compiles"] == 0


def test_first_dispatch_lowers_both_widths(served):
    # `cold` paid for the chunk's program and the decode-width one; its
    # second tick (the 3-token tail) was the first narrow one
    assert served["done"]["cold"]["loop"]["compiles"] >= 2
    assert served["seen"]["widths"][:2] == [8, 5]
    assert set(served["engine"]._steps) == {5, 8}


def test_serve_stats_narrow_and_wide_ticks_add_up(served):
    widths = served["seen"]["widths"]
    for loop in (served["stats"]["engine"]["loop"],
                 served["engine"].stats()["loop"]):
        assert loop["narrow_ticks"] == widths.count(5)
        assert loop["narrow_ticks"] + widths.count(8) == loop["ticks"]
        assert 0.0 < loop["narrow_wait_s"] < loop["phase_s"]["harvest_wait"]
    assert "narrow_ticks" not in loop["phase_n"]    # still one wait phase


@pytest.mark.parametrize("name", ["cold", "warm", "short"])
def test_request_narrow_ticks_equal_those_counted_outside(served, name):
    done, seen = served["done"][name], served["seen"]
    rid = done["trace"]["rid"]
    life = seen["widths"][seen["at_submit"][rid]:seen["last"][rid]]
    loop = done["loop"]
    assert loop["narrow_ticks"] == life.count(5)
    assert loop["narrow_ticks"] + life.count(8) == loop["ticks"]
    # every request here decodes at least one token alone or beside
    # another decode row, and none is prefilled in narrow ticks alone
    # but `short`, whose 3-token prompt fits the decode width
    assert loop["narrow_ticks"] >= 1
    assert 0.0 <= loop["narrow_wait_s"] <= loop["phase_s"]["harvest_wait"]


def test_scripted_engine_without_a_clock_is_served_as_before():
    """An engine with no `clock`, whose submit returns None and whose
    finished requests are bare stubs: the loop runs on a clock of its
    own and the done record simply lacks the new fields."""
    from horovod_tpu.runner.http_server import RendezvousServer
    server = RendezvousServer(host="127.0.0.1")
    port = server.start()
    server._httpd.serve_router = RouterState(journal=True)
    fe = FleetFrontend(ScriptedEngine(), "127.0.0.1", port, 0, 1, direct=True)
    loop = threading.Thread(target=fe.run, kwargs={"ttl_s": 3.0})
    loop.start()
    try:
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/generate",
            data=json.dumps({"tokens": [3, 5, 8],
                             "max_new_tokens": 4}).encode(),
            headers={"Content-Type": "application/json"}, method="POST")
        with urllib.request.urlopen(req, timeout=30) as r:
            done = json.loads(r.read().splitlines()[-1])
    finally:
        loop.join(timeout=30)
        server.stop()
    assert not loop.is_alive()
    assert done["done"] is True and len(done["tokens"]) == 4
    assert "loop" not in done and "pickup" not in done["timing"]
    assert "publish" not in done["timing"]


# ------------------------------------------------------- the device half
def _cached_args(cfg, slots=2, chunk=8, blocks=16, block_size=4):
    cache = llama.init_cache(cfg, blocks, block_size)
    tables = jnp.full((slots, 8), -1, jnp.int32).at[:, :4].set(
        jnp.arange(slots * 4, dtype=jnp.int32).reshape(slots, 4))
    tokens = jnp.arange(slots * chunk, dtype=jnp.int32).reshape(
        slots, chunk) % cfg.vocab
    return (tokens, cache, tables, jnp.zeros(slots, jnp.int32),
            jnp.full(slots, chunk, jnp.int32))


def _lowered_texts():
    params = llama.init(jax.random.PRNGKey(1), CFG)
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("hvd",))
    opt = optax.adamw(1e-3)
    run = make_scanned_train_step(
        lambda p, ids: llama.loss_fn(p, ids, CFG, remat=True, ce_chunks=2),
        opt, mesh, fusion_threshold_bytes=64 * 1024)
    batches = jnp.zeros((1, 4, 17), jnp.int32)
    train = run.lower(params, opt.init(params), batches).as_text(
        debug_info=True)
    tokens, cache, tables, lengths, n_new = _cached_args(CFG)
    cached = jax.jit(
        lambda p, t, c, b, l, n: llama.apply_cached(p, t, CFG, c, b, l, n)
    ).lower(params, tokens, cache, tables, lengths, n_new).as_text(
        debug_info=True)
    scfg = ServeConfig(max_slots=2, block_size=4, cache_blocks=16,
                       max_seq_len=32, max_batch_tokens=16, prefill_chunk=8)
    engine = ServeEngine(llama, CFG, params, scfg, mesh=jax.sharding.Mesh(
        np.array(jax.devices()[:1]), ("hvd",)))
    try:
        z = jnp.zeros(2, jnp.int32)
        tick = engine._step_fn.lower(
            engine.params, engine.cache, jnp.zeros((2, 8), jnp.int32), z, z,
            jnp.zeros((2, 8), jnp.int32), z, z).as_text(debug_info=True)
    finally:
        engine.close()
    return {"train": train, "cached": cached, "tick": tick}


@pytest.fixture(scope="module")
def lowered():
    return _lowered_texts()


@pytest.mark.parametrize("program,scopes", [
    ("train", ["embed", "attn", "ffn", "head", "optimizer",
               "grad_sync/bucket0", "grad_sync/bucket1"]),
    # the gather is a tile's, inside the loop over blocks of slots and the
    # loop over a block's tiles of the read that the layers share
    # (models/paged.py _attend_tiled, a jit of its own under attn)
    ("cached", ["embed", "attn", "jit(_attend_tiled)",
                "while/body/while/body/kv_gather", "attn/kv_write", "ffn",
                "head", "kv_write"]),
    ("tick", ["tick/copy_blocks", "tick/model/attn", "jit(_attend_tiled)",
              "while/body/while/body/kv_gather",
              "tick/model/ffn", "tick/model/head", "tick/sample"]),
])
def test_lowered_program_names_each_scope(lowered, program, scopes):
    text = lowered[program]
    for scope in scopes:
        assert f"{scope}/" in text or f"{scope})" in text or \
            f'{scope}"' in text, (program, scope)


def test_logits_bit_identical_with_and_without_scopes(monkeypatch):
    params = llama.init(jax.random.PRNGKey(2), CFG)
    ids = jax.random.randint(jax.random.PRNGKey(3), (2, 16), 0, CFG.vocab)
    args = _cached_args(CFG)

    def both():
        full = jax.jit(lambda p, i: llama.apply(p, i, CFG))(params, ids)
        logits, cache = jax.jit(
            lambda p, t, c, b, l, n: llama.apply_cached(p, t, CFG, c, b, l, n)
        )(params, *args)
        return np.asarray(full), np.asarray(logits), np.asarray(cache["k"])

    scoped = both()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = both()
    for a, b in zip(scoped, bare):
        assert np.array_equal(a, b)
