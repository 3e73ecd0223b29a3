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
from tests.test_serve_chain import stripped
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


def _fake_seconds(monkeypatch, start=1_000_000):
    """The profiler module's wall clock, moved by hand."""
    now = {"t": float(start)}
    monkeypatch.setattr("horovod_tpu.utils.profiler.time.time",
                        lambda: now["t"])
    return now


def test_phase_clock_sums_ride_in_snapshot_and_delta():
    clock = PhaseClock()
    clock.add("n", 2)
    snap = clock.snapshot()
    clock.add("n", 3)
    clock.add("s", 0.25)
    assert clock.delta(snap)["sums"] == {"n": 3, "s": 0.25}
    assert snap["sums"] == {"n": 2}
    # a sum is no phase: the table the phase readers add up has none
    assert clock.phase_s == {} and clock.phase_n == {}


def test_phase_clock_timeline_buckets_by_the_wall_second(monkeypatch):
    now = _fake_seconds(monkeypatch)
    clock = PhaseClock()
    with clock.span("early"):       # before the first second(): in no bucket
        pass
    clock.add("n", 7)
    assert clock.timeline()["sec"] == []
    clock.second()
    clock.add("n", 1)
    now["t"] += 0.5
    clock.second()                  # the same second: the same bucket
    clock.add("n", 1)
    now["t"] += 2.0                 # a second with no tick has no bucket
    clock.second()
    clock.add("n", 5)
    with clock.span("late"):
        pass
    tl = clock.timeline()
    assert tl["sec"] == [1_000_000, 1_000_002]
    assert tl["n"] == [2.0, 5.0]    # the open second shows what it has so far
    assert tl["phase_n"]["late"] == [0.0, 1.0]
    assert tl["phase_n"]["early"] == [0.0, 0.0]
    assert set(tl["phase_s"]) == {"early", "late"}


def test_phase_clock_ring_wraps_at_128_without_growing(monkeypatch):
    now = _fake_seconds(monkeypatch)
    clock = PhaseClock()
    shape = clock._ring.shape
    for i in range(300):
        now["t"] += 1.0
        clock.second()
        clock.add("i", i)
        with clock.span("a"):
            pass
    tl = clock.timeline()
    assert clock._ring.shape == shape and shape[0] == PhaseClock.SECONDS == 128
    assert len(tl["sec"]) == 128 and tl["sec"] == sorted(tl["sec"])
    assert tl["sec"][-1] == int(now["t"])
    # the last 128 seconds' adds and entries, and nothing older
    assert tl["i"] == [float(i) for i in range(172, 300)]
    assert tl["phase_n"]["a"] == [1.0] * 128
    assert clock.sums["i"] == sum(range(300))


def test_phase_clock_keeps_a_name_past_the_rings_columns_in_its_total(
        monkeypatch):
    _fake_seconds(monkeypatch)
    clock = PhaseClock()
    clock.second()
    for i in range(PhaseClock.PHASES + 4):
        with clock.span(f"p{i}"):
            pass
    for i in range(PhaseClock.SUMS + 4):
        clock.add(f"s{i}", 1)
    tl = clock.timeline()
    assert list(tl["phase_s"]) == list(tl["phase_n"]) == \
        [f"p{i}" for i in range(PhaseClock.PHASES)]
    assert all(tl["phase_n"][name] == [1.0] for name in tl["phase_n"])
    assert [k for k in tl if k.startswith("s") and k != "sec"] == \
        [f"s{i}" for i in range(PhaseClock.SUMS)]
    assert all(tl[f"s{i}"] == [1.0] for i in range(PhaseClock.SUMS))
    assert len(clock.phase_n) == PhaseClock.PHASES + 4
    assert len(clock.sums) == PhaseClock.SUMS + 4


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
        width = engine._inflight[0][1] if engine._inflight else None
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
        loop.join(timeout=30)
        # what the loop published as it ended (`_publish_stats(force=True)`)
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/serve/stats", timeout=10) as r:
            at_exit = json.loads(r.read())
    finally:
        loop.join(timeout=30)
        engine.close()
        server.stop()
    assert not loop.is_alive()
    done = {name: lines[-1] for name, lines in answers.items()}
    return {"done": done, "seen": seen, "engine": engine, "stats": stats,
            "at_exit": at_exit}


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
    # the loop never slept for want of work in a request's life: what it
    # idled there it held on purpose, at the commit point (worker._hold)
    assert done["loop"]["phase_s"].get("idle", 0.0) == \
        pytest.approx(done["loop"]["hold_s"], abs=1e-5)


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


# ------------------------------------------- the gap between two programs
GAP_KEYS = {"fence_ready_s", "fence_copy_s", "turnaround_s", "turnaround_n",
            "turnaround_parts_s", "after_idle_n", "iteration_s", "by_width",
            "timeline"}
PARTS = {"fence_copy", "harvest_emit", "plan", "stage", "launch", "unspanned"}


def test_phase_table_keeps_the_parents_nine_keys(served):
    loop = served["engine"].stats()["loop"]
    assert GAP_KEYS <= set(loop)
    # none of the new figures is a phase: `engine.loop_host_ms.serve` adds
    # up every key of phase_s, and a request's phases sum to its life
    assert set(loop["phase_s"]) == set(loop["phase_n"]) == PHASES
    for done in served["done"].values():
        assert set(done["loop"]["phase_s"]) <= PHASES


def test_fence_parts_add_up_to_the_wait(served):
    loop = served["engine"].stats()["loop"]
    assert loop["fence_ready_s"] > 0 and loop["fence_copy_s"] > 0
    assert abs(loop["fence_ready_s"] + loop["fence_copy_s"]
               - loop["phase_s"]["harvest_wait"]) < 1e-4


def test_turnaround_parts_add_up_to_it(served):
    loop = served["engine"].stats()["loop"]
    parts = loop["turnaround_parts_s"]
    assert set(parts) == PARTS
    assert abs(sum(parts.values()) - loop["turnaround_s"]) < 1e-6
    assert all(v >= 0 for v in parts.values())
    # a span's own part is at most its phase: it counts back-to-back ticks
    for name in PARTS - {"fence_copy", "unspanned"}:
        assert parts[name] <= loop["phase_s"][name] + 1e-9
    assert parts["fence_copy"] <= loop["fence_copy_s"] + 1e-9


def test_every_launch_is_back_to_back_or_after_idle(served):
    loop = served["engine"].stats()["loop"]
    assert loop["turnaround_n"] + loop["after_idle_n"] == \
        loop["phase_n"]["launch"] == loop["ticks"]
    # `cold` found the engine with nothing in flight, and its ticks after
    # the first were launched while the one before was unfenced
    assert loop["after_idle_n"] >= 1 and loop["turnaround_n"] >= 1
    assert 1 <= loop["ahead_n"] <= loop["turnaround_n"]
    assert loop["ahead_idle_rows"] >= 0


def test_turnaround_lies_inside_the_busy_period(served):
    loop = served["engine"].stats()["loop"]
    # a launch ahead of its fence has a turnaround of 0: the host's path
    # lay behind a program, not between two
    assert 0 <= loop["turnaround_s"] <= loop["iteration_s"]
    assert loop["iteration_s"] > 0
    # the busy period has the programs in it: the waits of the ticks fenced
    assert loop["iteration_s"] <= sum(loop["phase_s"].values())


def test_narrow_figures_are_read_off_by_width(served):
    loop = served["engine"].stats()["loop"]
    narrow, wide = loop["by_width"]["narrow"], loop["by_width"]["wide"]
    assert loop["narrow_ticks"] == narrow["ticks"]
    assert loop["narrow_wait_s"] == narrow["wait_s"]
    assert narrow["ticks"] + wide["ticks"] == loop["ticks"]
    assert wide["ticks"] == served["seen"]["widths"].count(8)
    assert abs(narrow["wait_s"] + wide["wait_s"]
               - loop["phase_s"]["harvest_wait"]) < 1e-6
    assert not hasattr(served["engine"], "_narrow_ticks")


def test_timeline_columns_add_up_to_the_running_figures(served):
    loop = served["engine"].stats()["loop"]
    tl = loop["timeline"]
    assert 1 <= len(tl["sec"]) <= PhaseClock.SECONDS
    assert tl["sec"] == sorted(set(tl["sec"]))
    assert all(len(col) == len(tl["sec"]) for col in
               [*tl["phase_s"].values(), *tl["phase_n"].values(),
                tl["narrow"], tl["wide"], tl["used"], tl["fence_copy_s"],
                tl["turnaround_s"], tl["turnaround_n"], tl["after_idle_n"]])
    assert set(tl["phase_s"]) == PHASES
    # the engine opens the first bucket in its first step(): every figure
    # it keeps lies in the timeline whole
    for name in ("fence_copy_s", "turnaround_s", "turnaround_n",
                 "after_idle_n"):
        assert abs(sum(tl[name]) - loop[name]) < 1e-4, name
    assert sum(tl["narrow"]) == loop["narrow_ticks"]
    assert sum(tl["narrow"]) + sum(tl["wide"]) == loop["ticks"]
    stats = served["engine"].stats()
    assert sum(tl["used"]) >= stats["tokens_prefill"] > 0
    for name in ("harvest_wait", "harvest_emit", "plan", "stage", "launch"):
        assert abs(sum(tl["phase_s"][name]) - loop["phase_s"][name]) < 1e-4
        assert sum(tl["phase_n"][name]) == loop["phase_n"][name]
    # the loop's own phases but for its first iteration, before that step()
    for name in ("poll", "submit", "publish", "idle"):
        assert sum(tl["phase_s"][name]) <= loop["phase_s"][name] + 1e-4
        assert loop["phase_n"][name] - 1 <= sum(tl["phase_n"][name]) <= \
            loop["phase_n"][name]


@pytest.mark.parametrize("name", ["cold", "warm", "short"])
def test_request_turnaround_is_part_of_its_life(served, name):
    loop = served["done"][name]["loop"]
    assert 0.0 <= loop["turnaround_s"] <= sum(loop["phase_s"].values())
    assert 0.0 < loop["fence_copy_s"] <= loop["phase_s"]["harvest_wait"]
    # every request here decodes behind its own prefill: its ticks after
    # the first were launched ahead of the fence before them, which costs
    # the host's path nothing
    assert served["engine"].stats()["loop"]["ahead_n"] >= 3


def test_only_the_forced_stats_payload_carries_the_timeline(served):
    # the once-a-second payload is encoded inside `hvd:publish`
    periodic = served["stats"]["engine"]["loop"]
    assert "timeline" not in periodic
    assert GAP_KEYS - {"timeline"} <= set(periodic)
    at_exit = served["at_exit"]["engine"]["loop"]
    assert at_exit["timeline"]["sec"]
    assert at_exit["ticks"] == served["seen"]["harvests"] == \
        sum(at_exit["timeline"]["narrow"]) + sum(at_exit["timeline"]["wide"])


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
    # the module as it is (it samples where the tick reads), and without its
    # ``greedy_cached``: the tick takes the logits' argmax itself
    ticks = {}
    for name, model in (("tick", llama), ("tick_logits", stripped(llama))):
        engine = ServeEngine(model, CFG, params, scfg, mesh=jax.sharding.Mesh(
            np.array(jax.devices()[:1]), ("hvd",)))
        try:
            ticks[name] = engine._step_fn.lower(
                engine.params, engine.cache, *engine._chain,
                *engine._tick_shapes(8)).as_text(debug_info=True)
        finally:
            engine.close()
    return {"train": train, "cached": cached, **ticks}


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
    ("tick", ["tick/copy_blocks", "tick/chain", "tick/model/attn",
              "jit(_attend_tiled)", "while/body/while/body/kv_gather",
              "tick/model/ffn", "tick/model/head"]),
    ("tick_logits", ["tick/chain", "tick/model/head", "tick/sample"]),
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
