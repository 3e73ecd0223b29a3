"""Kernels of the serving path compiled at their real widths for a described
TPU v5e, with no chip (the third rehearsal of the on-chip-measurement guide):
what Mosaic refuses — a block that does not fit VMEM, a slice off the tiling
— fails here and costs no chip time.  Nothing runs; a compile that passes is
no measurement.  All such tests live in this ONE file: the worker that runs
it loads the TPU's library, and the topology is described inside a fixture,
never while a module is imported."""

import functools
import math
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
# openpangu-ultra-moe-718b's share, lfm2-8b-a1b whole, sdar-30b-a3b-chat whole
@pytest.mark.parametrize("held,d,f", [(64, 2560, 768), (16, 7680, 2048),
                                      (32, 2048, 1792), (128, 2048, 768)])
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


@functools.lru_cache(maxsize=None)      # a cell compiles once a test run
def _tick_programs(cell, one_chip):
    """The serving cell's tick at both of its widths, ServeEngine's own
    program (``tick_program``: copy-on-write, the decode chain in,
    ``apply_cached`` on a budget of ``max_batch_tokens`` rows, the greedy
    token — the module's own ``greedy_cached`` where it samples on its rows
    —, the chain out; each kind's pool sized by the scheduler, in the
    device's default layout for its shape), compiled for the described
    chip as the engine jits it: ({width: text}, (narrow, wide), {leaf: pool
    dims}, {leaf: the pool's axes from major to minor as the program takes
    it})."""
    from horovod_tpu.models import paged
    from perfbench.tools import tick_text
    lowered, cache = tick_text.lowered(cell, one_chip)
    widths = tuple(lowered)
    steps = {C: low.compile() for C, low in lowered.items()}
    fmt = steps[widths[0]].input_formats[0][1]
    by_leaf = lambda f, t: {paged.leaf_key(path): f(x) for path, x in
                            jax.tree_util.tree_flatten_with_path(t)[0]}
    return ({C: c.as_text() for C, c in steps.items()}, widths,
            by_leaf(lambda x: "[" + ",".join(map(str, x.shape)) + "]", cache),
            by_leaf(lambda f: f.layout.major_to_minor, fmt))


def _tick_program(cell, C, one_chip):
    """(text, the first leaf's pool dims) of ``_tick_programs``'s program at
    ``[slots, C]``."""
    texts, _, pools, _ = _tick_programs(cell, one_chip)
    return texts[C], next(iter(pools.values()))


_OPS = r" = \w+(\[[\d,]*\])\S* ([\w-]+)\("    # (dims, op) of every HLO line


def _top_level(text):
    """(dims, op) of the HLO instructions that are device ops of their own:
    those outside fused computations (a ``copy`` INSIDE a fusion is how its
    consumer reads an operand, no pass over memory)."""
    import re
    out, fused = [], False
    for line in text.splitlines():
        if line and not line.startswith(" "):
            fused = "fused_computation" in line.split("(")[0]
        elif not fused:
            out += re.findall(_OPS, line)
    return out


# how a pool is written in place: rows scattered into it (a paged or a ring
# kind, a carry's snapshots, a committed state), or ONE layer's columns laid
# over it a slot at a time (a state kind's inputs, a replay kind's ring:
# paged.write_slots)
_BY_SLOT = "dynamic-update-slice"


# each leaf's axes from major to minor where the device's default is not
# row-major: the conv state keeps its 32 slots inside its 7 columns (7 would
# pad to 8 sublanes), and its tick works on it there
_NOT_ROW_MAJOR = {"serve-moe-conv-chat": {"conv/u": (0, 2, 1, 3)}}


@pytest.mark.parametrize("width", ["narrow", "wide"])
@pytest.mark.parametrize("cell", ["serve-decode", "serve-moe-mla-decode",
                                  "serve-moe-swa-longdoc",
                                  "serve-moe-conv-chat",
                                  "serve-moe-blockdiff-gen",
                                  "serve-ssm-yoco-reason"])
def test_no_tick_relays_a_pool_on_its_way_in_or_out(one_chip, cell, width):
    """Every serving cell's two programs take each pool as the device's
    default layout for its SHAPE has it — a module's ``init_cache`` decides
    the layout by the shape it gives, nothing else can (docs/serving.md
    #where-the-pool-lies) — and work on it there: every pool is scattered
    into in place (a state kind's: laid over a layer at a time), and no op
    shaped like a whole pool is a ``copy`` or a ``concatenate``.  A pool that the chip would relay on the way into and
    out of every tick, as it did the latent pool at ``[5, 5120, 16, 576]``
    (blocks minor by default: PERF.md §6, PR 36), fails here, on a CPU."""
    import re
    texts, widths, pools, layouts = _tick_programs(cell, one_chip)
    ops = re.findall(_OPS, texts[widths[width == "wide"]])
    for leaf, pool in pools.items():
        # (a pool of ONE layer is scattered into as that layer: the leading
        # 1 goes in a bitcast)
        forms = (pool, "[" + pool[3:]) if pool.startswith("[1,") else (pool,)
        assert [f for f in forms if (f, "scatter") in ops
                or (f, _BY_SLOT) in ops], leaf
        assert not [op for op in ops if op[0] in forms
                    and op[1] in ("copy", "concatenate")], leaf
    row_major = {leaf: tuple(range(pool.count(",") + 1))
                 for leaf, pool in pools.items()}
    assert layouts == {**row_major, **_NOT_ROW_MAJOR.get(cell, {})}


@pytest.mark.parametrize("width", ["narrow", "wide"])
@pytest.mark.parametrize("cell", ["serve-decode", "serve-moe-mla-decode",
                                  "serve-moe-swa-longdoc",
                                  "serve-moe-conv-chat",
                                  "serve-moe-blockdiff-gen",
                                  "serve-ssm-yoco-reason",
                                  "serve-gdn-mixedlen"])
def test_the_token_history_is_handed_on_in_place(one_chip, cell, width):
    """The decode chain's state is the tick program's own: the token history
    ``[slots, max_seq_len]`` int32 comes from the tick before donated, is
    scattered into in place and goes to the tick after — no op shaped like it
    is a ``copy``, and the program aliases it (and the pools) to its
    outputs."""
    import re

    from perfbench.lib import spec
    texts, widths, pools, _ = _tick_programs(cell, one_chip)
    text = texts[widths[width == "wide"]]
    engine = spec.cell(cell)[1]["engine"]
    hist = f"[{engine['max_slots']},{engine['max_seq_len']}]"
    ops = [op for op in re.findall(_OPS, text) if op[0] == hist]
    assert (hist, "scatter") in ops
    assert not [op for op in ops if op[1] in ("copy", "concatenate")]
    # every pool leaf, the history, the lengths and the ends of streams (and
    # a block's masked positions and passes where the model denoises blocks)
    aliased = re.search(r"input_output_alias=\{(.*?)\}, entry", text).group(1)
    assert aliased.count("may-alias") + aliased.count("must-alias") == \
        len(pools) + (5 if cell == "serve-moe-blockdiff-gen" else 3)


@pytest.mark.parametrize("cell", ["serve-decode", "serve-moe-mla-decode",
                                  "serve-ssm-yoco-reason",
                                  "serve-gdn-mixedlen"])
def test_the_wide_tick_runs_its_head_on_the_rows_it_reads(one_chip, cell):
    """The chunk-wide program of a module that samples where the tick reads
    (``greedy_cached(.., read)``: models/llama.py, models/latent_moe.py,
    models/sambay.py, whose head is its embedding, models/gdn_hybrid.py):
    beside the vocabulary no array but the head's own matrix holds more rows
    than the ``slots x (1 + spec_k)`` whose token the tick reads — no
    ``[16,128,92544]`` slab and no ``[512,92544]`` logits of every packed
    row in ``serve-decode`` —, and the logits of those rows are there."""
    import math
    import re

    from perfbench.lib import spec
    texts, (W, wide), _, _ = _tick_programs(cell, one_chip)
    config = spec.cell(cell)[1]
    S = config["engine"]["max_slots"]
    mcfg = spec.family(config).program(config)[1]
    V, head = mcfg.vocab, (mcfg.dim, mcfg.vocab)
    shaped = {tuple(map(int, d.split(",")))
              for d in re.findall(r"\[([\d,]+)\]", texts[wide])}
    rows = {math.prod(d[:-1]) for d in shaped
            if len(d) > 1 and d[-1] == V and d != head}
    assert rows and max(rows) == S * W, (cell, sorted(rows))


@pytest.mark.parametrize("C", [256, 5])
def test_the_conv_tick_keeps_its_pools_where_they_lie(one_chip, C):
    """``serve-moe-conv-chat``'s two programs: the paged pool (a position's
    heads side by side, ``[3, 3072, 16, 512]``) and the conv layers' state
    (``[11, 32, 7, 2048]``) are written in place — rows scattered into the
    one, a layer's columns laid over the other — and never copied or relaid
    whole — with a last axis of head_dim 64 the chip laid the pool
    out blocks-minor and relaid it on the way into and out of every tick
    (PERF.md §6, PR 33) —, the attention reads a tile of context inside the
    shared loop (nothing is shaped like a slot's whole context), the expert
    tile is Mosaic's, and no ``[.., vocab]`` slab wider than the tick's rows
    exists."""
    import re
    text, pool = _tick_program("serve-moe-conv-chat", C, one_chip)
    ops = re.findall(_OPS, text)
    state = "[11,32,7,2048]"
    assert pool == "[3,3072,16,512]"
    assert (pool, "scatter") in ops and (state, _BY_SLOT) in ops
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


@pytest.mark.parametrize("C", [256, 5])
def test_the_scan_tick_keeps_its_four_pools_where_they_lie(one_chip, C):
    """``serve-ssm-yoco-reason``'s two programs: the ONE full layer's pool
    (``[1, 5120, 16, 1280]``), the eight rings, the nine layers' conv inputs
    and their scan carries (``[9, 32, 6, 16, 5120]`` float32, d_inner minor)
    are scattered into in place and never copied or relaid whole; the full
    layer and the seven cross layers read a tile of that one pool inside the
    shared loop (nothing is shaped like a slot's whole context of 2,560);
    of a ring a decode row gathers the 34 blocks its windows reach and a
    chunk all 48 (768 positions a slot whatever it holds: S12's debt, shared
    with serve-moe-swa-longdoc); and the scan's carry never exists with d_state
    minor."""
    import re
    texts, _, pools, _ = _tick_programs("serve-ssm-yoco-reason", one_chip)
    ops = re.findall(_OPS, texts[C])
    pool = pools["kv/k"]
    assert pool == "[1,5120,16,1280]" and sorted(pools) == [
        "carry/h", "conv/u", "kv/k", "kv/v", "window/k", "window/v"]
    # (the one-layer pool is scattered into as its layer, a bitcast away)
    for whole in ("[5120,16,1280]", "[8,1536,16,1280]", "[9,32,8,5120]",
                  "[9,32,6,16,5120]"):
        # (the conv inputs a slot at a time, the carries a row at a time)
        how = _BY_SLOT if whole == "[9,32,8,5120]" else "scatter"
        assert (whole, how) in ops, whole
        assert not [op for op in ops if op[0] in (whole, pool)
                    and op[1] in ("copy", "concatenate")], whole
    assert not [op for op in ops if op[0].endswith(",2560,1280]")]
    ring = {256: (",768,1280]", "[48,16,1280]"),     # the chunk's one slot
            5: (",544,1280]", ",34,16,1280]")}[C]
    assert [op for op in ops if op[0].endswith(ring)]
    assert not [op for op in ops if op[0].endswith(",5120,16]")]


@pytest.mark.parametrize("C", [512, 5])
def test_the_delta_tick_keeps_one_state_a_slot_in_place(one_chip, C):
    """``serve-gdn-mixedlen``'s two programs: the four full layers' pool
    (``[4, 3072, 30, 16, 128]``, keys and values by head inside a block,
    written whole blocks at a time: models/paged.py ``write_blocks``), the
    twelve linear layers' conv inputs, their
    ONE committed state a slot (``[12, 16, 1, 30, 192, 96]`` float32, which
    the commit scatters into as ``[12, 16, 30, 192, 96]``, a bitcast away)
    and the ring of rows to replay (``[12, 16, 4, 8700]``: k, v, g and beta
    side by side) are written in place — blocks scattered into the first,
    rows into the third, a layer's columns laid over the two others a slot
    at a time — and never relaid, concatenated or copied whole (a write by
    row into the pool by head relaid it whole twice a tick, PR 48; a read
    of a layer's
    columns that is not the one the write reads made the compiler copy the
    35 MB of conv inputs five times a tick, PR 49); the program takes each
    row-major.  NO state a row exists: nothing with the state's ``[30, 192,
    96]`` behind it holds more than the 16 slots' (576 rows of it would be
    1.27 GB; a loop over positions that kept its carries would make them),
    and nothing is shaped like a slot's whole context of 12,800.  A tile of
    the full layers' read is scored as the gather left it: no op of its own
    rewrites, copies or transposes anything of a gathered tile's size
    (``reshape bf16[2,256,30,128]`` was the cell's fifth costliest device
    op, PERF.md section 6, PR 50).  The
    recurrence is the chunked form in both: a verify row's chunk of 9 a
    slot, a prompt's chunks of 64, eight at a time, and no solver's custom
    call (``lax.linalg.triangular_solve`` was 47 of a 139 ms wide tick on
    the chip, PERF.md section 6, PR 48: the inverse is forward substitution
    in blocks of 16, joined by products)."""
    import re
    texts, _, pools, layouts = _tick_programs("serve-gdn-mixedlen", one_chip)
    ops = re.findall(_OPS, texts[C])
    assert pools == {"conv/u": "[12,16,8,11520]",
                     "delta/S": "[12,16,1,30,192,96]",
                     "delta/at": "[12,16,1,1]",
                     "delta/row": "[12,16,4,8700]",
                     "kv/k": "[4,3072,30,16,128]",
                     "kv/v": "[4,3072,30,16,128]"}
    # (``at``'s two axes of one element lie where the device likes: 768 B)
    assert {k: v for k, v in layouts.items() if k != "delta/at"} == {
        leaf: tuple(range(pool.count(",") + 1))
        for leaf, pool in pools.items() if leaf != "delta/at"}
    state = "[12,16,30,192,96]"
    for whole, how in ((pools["kv/k"], "scatter"), (state, "scatter"),
                       ("[12,16,8,11520]", _BY_SLOT),
                       ("[12,16,4,8700]", _BY_SLOT)):
        assert (whole, how) in ops, whole
        assert not [op for op in ops if op[0] == whole
                    and op[1] in ("concatenate", "copy")], whole
    assert not [op for op in ops if op[0] == pools["delta/S"]
                and op[1] == "copy"]
    assert not re.search(r"\[12,16,4,8700\]\{(?!3,2,1,0)", texts[C])
    behind = [tuple(map(int, d[1:-1].split(","))) for d, _ in ops
              if d.endswith(",30,192,96]")]
    # the stacked pool, and never more states at once than the 16 slots'
    assert behind and not [d for d in behind if d[0] != 12
                           and math.prod(d[:-3]) > 16], sorted(set(behind))
    assert not [op for op in ops if op[0].endswith((",12800,3840]",
                                                    ",12800,30,128]",
                                                    ",800,30,16,128]"))]
    # a tile of 16 table entries of 16 positions, two slots' in the first
    # pass and one slot's in the chunk's, in any order of its axes; side by
    # side it was [.., 256, 3840] -> [.., 256, 30, 128]
    tiles = {tuple(sorted(d)) for slots in ((2,), (), (32,), (16,))
             for d in (slots + (16, 16, 30, 128), slots + (256, 30, 128),
                       slots + (16, 30, 128), slots + (16, 3840),
                       slots + (16, 16, 3840), slots + (256, 3840))}
    shape = lambda d: tuple(sorted(int(n) for n in d[1:-1].split(",")
                                   if n not in ("", "1")))
    assert not [(d, op) for d, op in _top_level(texts[C])
                if op in ("reshape", "copy", "transpose")
                and shape(d) in tiles]
    # a verify row's chunk a slot; eight of a prompt's chunks at a time
    chunk = {512: "[8,30,64,288]", 5: "[16,30,9,288]"}[C]
    assert [op for op in ops if op[0] == chunk], chunk
    assert not [op for op in ops if op[1] == "triangular-solve"]
    # (the solver was ``custom-call f32[26,30,1,64,64]`` / ``[16,30,1,9,9]``)
    assert not [op for op in ops if op[1] == "custom-call"
                and op[0].endswith((",1,64,64]", ",1,9,9]"))]


@pytest.mark.parametrize("C", [256, 4])
def test_the_block_tick_samples_on_its_rows_and_reads_tiles(one_chip, C):
    """``serve-moe-blockdiff-gen``'s two programs: the pool (a position's
    heads side by side, ``[7, 4096, 16, 512]``) is scattered into in place
    and never copied whole, the block-masked attention reads a tile of
    context inside the shared loop (nothing is shaped like a slot's whole
    context), the expert tile is Mosaic's, and the float32 logits exist on
    the tick's rows alone: no ``[.., 151936]`` slab wider than them at
    either width."""
    import re
    text, pool = _tick_program("serve-moe-blockdiff-gen", C, one_chip)
    ops = re.findall(_OPS, text)
    assert pool == "[7,4096,16,512]" and (pool, "scatter") in ops
    assert not [op for op in ops if op[0] == pool
                and op[1] in ("copy", "concatenate")]
    assert not [op for op in ops if op[0].endswith(",2048,512]")]
    assert "tpu_custom_call" in text and "expert_tile_ffn" in text
    # the wide tick's 384 packed rows; the narrow one's slab is its rows
    rows = {256: ("[1,384,151936]", "[384,151936]"),
            4: ("[32,4,151936]", "[128,151936]")}[C]
    logits = {op[0] for op in ops if op[0].endswith(",151936]")
              } - {"[2048,151936]"}       # the head's own matrix
    assert logits and logits <= set(rows)


@pytest.mark.parametrize("C", [64, 5])
def test_the_latent_tick_holds_no_whole_context_and_no_third_pool_copy(
        one_chip, C):
    """``serve-moe-mla-decode``'s two programs: the attention reads a tile
    of context at a time inside the shared loop, so nothing is shaped like a
    slot's whole context (``[slots, 2560, 576]``, whole or split by blocks
    of slots, which the once-gathered form made and copied a layer); the
    loops hold the pool without copying it; and the pool, a position's 576
    values padded to 640, lies row-major by default, so the two relayouts a
    tick of the ``[5, 5120, 16, 576]`` pool began and ended with are gone
    (ROADMAP S10 (b)): no copy of it at all.  The tiles' gathers read the
    pool through its 576-wide view, which is the same bytes and no op of its
    own, and bring back blocks of ``[16, 576]`` (what ``mla.cache_ops_ms.
    serve`` finds them by)."""
    import re
    text, pool = _tick_program("serve-moe-mla-decode", C, one_chip)
    ops = re.findall(_OPS, text)
    assert pool == "[5,5120,16,640]" and (pool, "scatter") in ops
    assert not [op for op in ops if op[0].endswith(",2560,576]")]
    assert not [op for op in ops if op[0] == pool
                and op[1] in ("copy", "concatenate")]
    seen = {op[1] for op in ops if op[0] == "[5,5120,16,576]"}
    assert seen and seen <= {"bitcast", "parameter", "get-tuple-element"}
    assert ("[32,16,576]", "fusion") in ops


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
