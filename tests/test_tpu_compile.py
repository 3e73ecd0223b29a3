"""Kernels of the serving path compiled at their real widths for a described
TPU v5e, with no chip (the third rehearsal of the on-chip-measurement guide):
what Mosaic refuses — a block that does not fit VMEM, a slice off the tiling
— fails here and costs no chip time.  Nothing runs; a compile that passes is
no measurement.  All such tests live in this ONE file: the worker that runs
it loads the TPU's library, and the topology is described inside a fixture,
never while a module is imported."""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from horovod_tpu.parallel import expert as X


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


# (experts held, model width, expert width): smallthinker-21b-a3b whole,
# openpangu-ultra-moe-718b's share, lfm2-8b-a1b whole
@pytest.mark.parametrize("held,d,f", [(64, 2560, 768), (16, 7680, 2048),
                                      (32, 2048, 1792)])
def test_the_expert_tile_kernel_compiles_at_the_cells_widths(one_chip, held,
                                                            d, f):
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=one_chip)
    bf = jnp.bfloat16
    fn = jax.jit(lambda wg, wu, wd, x, e: X.tile_ffn(wg, wu, wd, x, e,
                                                     jax.nn.relu))
    # the kernel interprets itself on a CPU backend; here it must be Mosaic's
    orig = jax.default_backend
    jax.default_backend = lambda: "tpu"
    try:
        compiled = fn.lower(sds((held, d, f), bf), sds((held, d, f), bf),
                            sds((held, f, d), bf), sds((64, d), bf),
                            sds((), jnp.int32)).compile()
    finally:
        jax.default_backend = orig
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # the expert's matrices are read where they lie: no copy of one out of
    # the stack before the kernel
    assert f"bf16[{d},{f}]" not in text and f"bf16[1,{d},{f}]" not in text


def _tick_program(cell, C, one_chip):
    """The serving cell's tick at ``[slots, C]`` as ServeEngine builds it
    (copy-on-write, ``apply_cached`` on a budget of ``max_batch_tokens``
    rows, the greedy token — the module's own ``greedy_cached`` where it
    samples on its rows), compiled for the described chip: (text, pool
    dims)."""
    import dataclasses

    from perfbench.lib import spec, weights
    _, config, _ = spec.cell(cell)
    e = config["engine"]
    model, cfg = spec.family(config).program(config)
    cfg = dataclasses.replace(cfg, max_tick_tokens=e["max_batch_tokens"])
    sds = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=one_chip)
    tree = lambda t: jax.tree_util.tree_map(lambda x: sds(x.shape, x.dtype), t)
    params = tree(jax.eval_shape(lambda: weights.make(
        config, weights.seed_key(0), weights.dtype_of(config))))
    S, i32 = e["max_slots"], jnp.int32
    # blocks a kind, as ServeEngine sizes them: the paged pool's, and for a
    # state kind (slots, columns a slot)
    blocks = e["cache_blocks"]
    table = sds((S, e["max_seq_len"] // e["block_size"]), i32)
    if hasattr(model, "cache_kinds"):
        from horovod_tpu.models import paged
        kinds = model.cache_kinds(cfg)
        blocks = {k.name: (S, paged.state_columns(k.state, 5)) if k.state
                  else e["cache_blocks"] for k in kinds}
        table = {k.name: table for k in kinds if not k.state}
    cache = tree(jax.eval_shape(lambda: model.init_cache(
        cfg, blocks, e["block_size"])))

    def step(params, cache, bt, lengths, n_new, tokens, src, dst):
        cache = model.copy_blocks(cache, src, dst)
        if hasattr(model, "greedy_cached"):
            out = model.greedy_cached(params, tokens, cfg, cache, bt, lengths,
                                      n_new)
            return out[1], out[0]
        out = model.apply_cached(params, tokens, cfg, cache, bt, lengths,
                                 n_new)
        return out[1], jnp.argmax(out[0].astype(jnp.float32), -1)
    orig = jax.default_backend
    jax.default_backend = lambda: "tpu"     # the expert tile is Mosaic's
    try:
        compiled = jax.jit(step, donate_argnums=(1,)).lower(
            params, cache, table, sds((S,), i32), sds((S,), i32),
            sds((S, C), i32), sds((S,), i32), sds((S,), i32)).compile()
    finally:
        jax.default_backend = orig
    pool = jax.tree_util.tree_leaves(cache)[0].shape
    return compiled.as_text(), "[" + ",".join(map(str, pool)) + "]"


@pytest.mark.parametrize("C", [256, 5])
def test_the_conv_tick_keeps_its_pools_where_they_lie(one_chip, C):
    """``serve-moe-conv-chat``'s two programs: the paged pool (a position's
    heads side by side, ``[3, 3072, 16, 512]``) and the conv layers' state
    (``[11, 32, 7, 2048]``) are scattered into in place and never copied or
    relaid whole — with a last axis of head_dim 64 the chip laid the pool
    out blocks-minor and relaid it on the way into and out of every tick
    (PERF.md §6, PR 33) —, the attention reads a tile of context inside the
    shared loop (nothing is shaped like a slot's whole context), the expert
    tile is Mosaic's, and no ``[.., vocab]`` slab wider than the tick's rows
    exists."""
    import re
    text, pool = _tick_program("serve-moe-conv-chat", C, one_chip)
    ops = re.findall(r" = \w+(\[[\d,]*\])\S* ([\w-]+)\(", text)
    state = "[11,32,7,2048]"
    assert pool == "[3,3072,16,512]"
    assert (pool, "scatter") in ops and (state, "scatter") in ops
    for whole in (pool, state):
        assert not [op for op in ops if op[0] == whole
                    and op[1] in ("copy", "concatenate")]
    assert not [op for op in ops if op[0].endswith(",1536,512]")]
    assert "tpu_custom_call" in text and "expert_tile_ffn" in text
    # the wide tick's 320 packed rows; the narrow one's slab is its rows
    rows = {256: ("[1,320,65536]", "[320,65536]"),
            5: ("[32,5,65536]", "[160,65536]")}[C]
    logits = {op[0] for op in ops if op[0].endswith(",65536]")}
    assert logits and logits <= set(rows)


@pytest.mark.parametrize("C", [64, 5])
def test_the_latent_tick_holds_no_whole_context_and_no_third_pool_copy(
        one_chip, C):
    """``serve-moe-mla-decode``'s two programs: the attention reads a tile
    of context at a time inside the shared loop, so nothing is shaped like a
    slot's whole context (``[slots, 2560, 576]``, whole or split by blocks
    of slots, which the once-gathered form made and copied a layer); and
    the loops hold the pool without copying it — the two relayouts of a
    pool whose last axis is 576 (ROADMAP S10 (b)) are the only copies."""
    import re
    text, pool = _tick_program("serve-moe-mla-decode", C, one_chip)
    ops = re.findall(r" = \w+(\[[\d,]*\])\S* ([\w-]+)\(", text)
    assert (pool, "scatter") in ops
    assert not [op for op in ops if op[0].endswith(",2560,576]")]
    assert len([op for op in ops if op == (pool, "copy")]) <= 2
    assert not [op for op in ops if op == (pool, "concatenate")]


def _primitives(jaxpr):
    """Every primitive of a jaxpr with those of its sub-jaxprs: (name,
    output shapes)."""
    for eqn in jaxpr.eqns:
        yield eqn.primitive.name, [v.aval.shape for v in eqn.outvars]
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _primitives(sub)


def test_without_a_bound_every_table_is_gathered_whole_in_counted_loops(
        monkeypatch):
    """What models/swa_moe.py hands ``attend_by_blocks`` — no bound — keeps
    the form its two compiled programs were measured in: each slot's whole
    table gathered, a loop over blocks of slots whose trip count is the
    program's (a ``scan``), and no ``while`` whose count the device reads
    off the plan; the same call with a bound has one.  Runs without the
    TPU's library."""
    from horovod_tpu.models import layers, llama, paged
    S, C, mb, bs, H, D = 4, 12, 8, 4, 2, 8
    pool = {k: jnp.zeros((1, S * mb, bs, H, D)) for k in "kv"}
    tables = jnp.arange(S * mb, dtype=jnp.int32).reshape(S, mb)
    lengths, n_new = jnp.array([0, 9, 3, 5]), jnp.array([12, 1, 0, 1])
    q = jnp.zeros((S, C, H, D))
    pos, _ = paged.slot_positions(lengths, n_new, C)

    def whole(q, pos, tab):
        ctx = paged.gather(pool, 0, tab)
        return layers.causal_attention(
            q, ctx["k"], ctx["v"], causal=False,
            mask=paged.context_mask(pos, mb * bs))
    run = lambda attend, bound: list(_primitives(jax.make_jaxpr(
        lambda q: paged.attend_by_blocks(
            attend, (q, pos, tables), n_new, 2, 4, bound=bound))(q).jaxpr))
    unbounded = run(whole, None)
    names = {name for name, _ in unbounded}
    assert "while" not in names and {"scan", "cond", "gather"} <= names
    gathered = [shapes[0] for name, shapes in unbounded if name == "gather"]
    assert (S, mb, bs, H, D) in gathered and (2, mb, bs, H, D) in gathered
    monkeypatch.setattr(paged, "TILE", 2 * bs)
    bounded = run(llama._attend_tile,
                  paged.Bound(lengths, pool, 0, paged.Slab(None, None)))
    assert "while" in {name for name, _ in bounded}
    assert all(shape[1] == 2 for name, shapes in bounded
               for shape in shapes if name == "gather" and len(shape) == 5)
