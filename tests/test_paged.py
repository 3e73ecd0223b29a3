"""How a block table addresses a paged pool (horovod_tpu/models/paged.py),
on the kinds of pool the served models keep — llama's five axes, the latent
decoder's four and gdn_hybrid's keys and values BY HEAD inside a block —,
how a fixed state a slot is addressed beside it, and
the contract ServeEngine holds a model module to
(docs/serving.md#what-a-served-model-module-exports)."""

import dataclasses
import importlib
import itertools
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import gdn_hybrid, latent_moe, layers, llama, paged
from horovod_tpu.serve.config import ServeConfig
from horovod_tpu.serve.engine import (_MODEL_MODULES, HostSpillPool,
                                      ServeEngine, _PrefixNode, block_length,
                                      decode_block_payload,
                                      encode_block_payload)

BLOCKS, BS = 12, 4
# (two heads where a block holds four positions: a swapped axis shows)
KINDS = {"llama": (llama, llama.CONFIGS["tiny"]),
         "latent": (latent_moe, latent_moe.CONFIGS["tiny"]),
         "by-head": (gdn_hybrid, dataclasses.replace(
             gdn_hybrid.CONFIGS["tiny"], n_heads=2))}


def _by_head(model):
    """Whether the module's paged pool lies by head inside a block."""
    return model is gdn_hybrid


@pytest.fixture(params=sorted(KINDS))
def kind(request):
    """(model module, its tiny config, a pool of noise as host numpy: the
    module's paged pool)."""
    model, cfg = KINDS[request.param]
    rng = np.random.default_rng(3)
    cache = (model.init_cache(cfg, {gdn_hybrid.KV: BLOCKS,
                                    gdn_hybrid.CONV: (3, 4),
                                    gdn_hybrid.DELTA: (3, 1)}, BS)[
        gdn_hybrid.KV] if _by_head(model)
        else model.init_cache(cfg, BLOCKS, BS))
    pool = {k: rng.normal(size=v.shape).astype(np.float32)
            for k, v in cache.items()}
    return model, cfg, pool


def _device(pool):
    return {k: jnp.asarray(v) for k, v in pool.items()}


def _write(model, leaf, layer, blk, off, values):
    """The rows at (blk, off) into a leaf, by the write its kind gets."""
    if _by_head(model):
        return paged.write_blocks(leaf, layer, *paged.block_lands(
            blk, off, BLOCKS, BS, blk.size), values)
    return paged.write(leaf, layer, blk, off, values)


def _position(model, leaf, layer, block, offset):
    """Where a position's values lie in a host leaf (an index)."""
    return ((layer, block, slice(None), offset) if _by_head(model)
            else (layer, block, offset))


# slot 0 holds 2 positions and takes 3 more (crossing into its second block),
# slot 1 is dead (n_new 0, a stale table row), slot 2 takes one: its padding
# columns fall under the table's unassigned (-1) entries
TABLES = np.array([[7, 2, 9], [4, 4, 4], [5, -1, -1]], np.int32)
LENGTHS = np.array([2, 6, 3], np.int32)
N_NEW = np.array([3, 0, 1], np.int32)
C = 4


def test_slot_positions_and_write_index_by_a_plain_loop():
    positions, valid = paged.slot_positions(jnp.asarray(LENGTHS),
                                            jnp.asarray(N_NEW), C)
    blk, off = paged.write_index(jnp.asarray(TABLES), positions, valid,
                                 BLOCKS, BS)
    for s in range(3):
        for j in range(C):
            P = LENGTHS[s] + j
            assert positions[s, j] == P and valid[s, j] == (j < N_NEW[s])
            assert off[s, j] == P % BS
            want = TABLES[s, min(P // BS, 2)] if j < N_NEW[s] else BLOCKS
            assert blk[s, j] == want, (s, j)


def test_a_position_lands_in_its_block_and_nothing_else_moves(kind):
    model, cfg, pool = kind
    positions, valid = paged.slot_positions(jnp.asarray(LENGTHS),
                                            jnp.asarray(N_NEW), C)
    blk, off = paged.write_index(jnp.asarray(TABLES), positions, valid,
                                 BLOCKS, BS)
    rng = np.random.default_rng(5)
    for name, leaf in pool.items():
        behind = ((leaf.shape[2], leaf.shape[4]) if _by_head(model)
                  else leaf.shape[3:])
        for layer in (0, leaf.shape[0] - 1):
            values = rng.normal(size=(3, C) + behind).astype(np.float32)
            got = np.asarray(_write(model, jnp.asarray(leaf), layer, blk,
                                    off, jnp.asarray(values)))
            want = leaf.copy()
            for s in range(3):
                for j in range(N_NEW[s]):
                    P = LENGTHS[s] + j
                    want[_position(model, leaf, layer, TABLES[s, P // BS],
                                   P % BS)] = values[s, j]
            assert np.array_equal(got, want), (name, layer)


def test_gathered_index_t_is_position_t(kind):
    model, cfg, pool = kind
    for name, leaf in pool.items():
        layer = leaf.shape[0] - 1
        ctx = np.asarray(paged.gather(jnp.asarray(leaf), layer,
                                      jnp.asarray(TABLES), _by_head(model)))
        # by head the blocks as they lie, an entry a block
        assert ctx.shape == ((3, 3) + leaf.shape[2:] if _by_head(model)
                             else (3, 3 * BS) + leaf.shape[3:])
        for s in range(3):
            for t in range(3 * BS):
                b = max(TABLES[s, t // BS], 0)      # -1 reads block 0
                at = (ctx[s, t // BS, :, t % BS] if _by_head(model)
                      else ctx[s, t])
                assert np.array_equal(
                    at, leaf[_position(model, leaf, layer, b, t % BS)])


def test_context_mask_by_a_plain_loop():
    positions, _ = paged.slot_positions(jnp.asarray(LENGTHS),
                                        jnp.asarray(N_NEW), C)
    mask = np.asarray(paged.context_mask(positions, 3 * BS))
    assert mask.shape == (3, 1, C, 3 * BS)
    for s in range(3):
        for j in range(C):
            for t in range(3 * BS):
                assert mask[s, 0, j, t] == (t <= LENGTHS[s] + j)


# ------------------------------------------------------- the bounded read
# Six slots over tables of 8 blocks of 4 (32 positions), read in tiles of 8
# positions, the first pass in blocks of two slots, the second in blocks of
# two with 4 narrow columns.  A plan is (columns, lengths, n_new).
READ = dict(S=6, max_blocks=8, tile=8, slots=2, narrow=4)
PLANS = {
    # decode rows of 1 + spec_k columns over ragged contexts
    "ragged-verify-rows": (5, [3, 17, 9, 0, 25, 12], [5, 1, 3, 1, 2, 1]),
    # slots 0 and 1 are a whole dead block, slot 3 is dead beside a live one
    # (their stale lengths reach farther than any live slot's)
    "dead-block-and-dead-slot": (5, [30, 31, 6, 29, 2, 11], [0, 0, 1, 0, 4, 1]),
    # slot 0 ends at max_blocks x block_size, the table's last position
    "a-full-table": (5, [29, 4, 27, 8, 0, 0], [3, 1, 5, 1, 0, 1]),
    # slot 0 ends exactly on a tile's edge (16), slot 2 one position past it
    "a-tiles-edge-and-one-past": (5, [15, 2, 16, 3, 7, 7], [1, 1, 1, 1, 1, 1]),
    # a chunk in block 0, a tail longer than the narrow columns in block 2,
    # decode rows beside both and alone in block 1
    "a-chunk-beside-decode-rows": (12, [8, 21, 13, 5, 30, 4],
                                   [12, 1, 1, 1, 0, 7]),
    # every block holds a chunk: the worst plan
    "chunks-everywhere": (12, [0, 12, 4, 20, 16, 8], [12, 9, 12, 12, 5, 12]),
}


def _plan(name):
    C, lengths, n_new = PLANS[name]
    lengths, n_new = np.array(lengths, np.int32), np.array(n_new, np.int32)
    S, mb = READ["S"], READ["max_blocks"]
    rng = np.random.default_rng(17)
    perm = rng.permutation(S * mb).reshape(S, mb).astype(np.int32)
    # -1 past what a live slot holds; a dead slot keeps a stale row
    held = -(-(lengths + n_new) // BS)
    tables = np.where((np.arange(mb)[None] < held[:, None])
                      | (n_new == 0)[:, None], perm, -1).astype(np.int32)
    return C, lengths, n_new, tables


@pytest.fixture
def small_tiles(monkeypatch):
    monkeypatch.setattr(paged, "TILE", READ["tile"])
    monkeypatch.setattr(paged, "NARROW_SLOTS", READ["slots"])


def _attends(model, cfg):
    """(the model's ``attend`` for one tile, the same attention over a whole
    gathered table [s, c, heads, d], the queries' trailing shape, the way
    back from the scores' layout, the pool as the model hands it to the
    read: latent_moe pads a position to 128 lanes and reads through the
    latent's own columns)."""
    if model is latent_moe:
        def whole(q, pos, ctx):
            lat = ctx["latent"]
            s = jnp.einsum("schx,skx->shck", q, lat) / np.sqrt(cfg.qk_dim)
            s = jnp.where(paged.context_mask(pos, lat.shape[1]), s, -jnp.inf)
            return jnp.einsum("shck,skl->schl", jax.nn.softmax(s, -1),
                              lat[..., :cfg.kv_rank])
        return (latent_moe.latent_attend(cfg), whole,
                (cfg.n_heads, cfg.latent_dim),
                lambda o: jnp.swapaxes(o, 1, 2),
                lambda pool: {"latent": pool["latent"][..., :cfg.latent_dim]})

    # gathered by head [s, entries, heads, bs, d] -> by position
    flat = (lambda a: jnp.swapaxes(a, 2, 3).reshape(
        a.shape[0], -1, a.shape[2], a.shape[4])) if _by_head(model) else (
            lambda a: a)

    def whole(q, pos, ctx):
        k, v = flat(ctx["k"]), flat(ctx["v"])
        return layers.causal_attention(
            q, k, v, causal=False, mask=paged.context_mask(pos, k.shape[1]))
    return (gdn_hybrid._attend_tile if _by_head(model)
            else llama._attend_tile, whole, (cfg.n_heads, cfg.head_dim),
            lambda o: jnp.moveaxis(o, 3, 1).reshape(
                o.shape[0], o.shape[3], cfg.n_heads, -1), lambda pool: pool)


def _bound(lengths, pool, layer=1, by_head=False):
    """A bound over ``pool`` for queries that are not packed."""
    return paged.Bound(jnp.asarray(lengths), pool, layer,
                       paged.Slab(None, None), by_head)


@pytest.mark.parametrize("plan", sorted(PLANS))
def test_the_bounded_read_is_the_whole_table_read(kind, plan, small_tiles):
    """``attend_by_blocks`` with a bound — tiles of 8 positions as far as a
    block's longest live context, an online softmax across them, dead blocks
    skipped — against the whole table gathered, masked and softmaxed at
    once, float32, at every valid position of the plan."""
    model, cfg, pool = kind
    S, mb = READ["S"], READ["max_blocks"]
    pool = {k: jnp.asarray(np.random.default_rng(4).normal(
        size=v.shape[:1] + (S * mb,) + v.shape[2:]).astype(np.float32))
        for k, v in pool.items()}
    C, lengths, n_new, tables = _plan(plan)
    attend, whole, width, back, seen = _attends(model, cfg)
    q = jnp.asarray(np.random.default_rng(6).normal(
        size=(S, C) + width).astype(np.float32))
    positions, valid = paged.slot_positions(jnp.asarray(lengths),
                                            jnp.asarray(n_new), C)
    got = back(jax.jit(lambda q: paged.attend_by_blocks(
        attend, (q, positions, jnp.asarray(tables)), jnp.asarray(n_new),
        READ["slots"], READ["narrow"],
        bound=_bound(lengths, seen(pool), by_head=_by_head(model))))(q))
    want = whole(q, positions, paged.gather(
        seen(pool), 1, jnp.asarray(tables), _by_head(model)))
    assert got.shape == want.shape and bool(jnp.isfinite(got).all())
    assert np.asarray(valid).any()
    err = np.abs(np.asarray(got) - np.asarray(want))[np.asarray(valid)]
    assert float(err.max()) < 2e-6, plan
    # a block with no live slot read nothing and comes back zero
    dead = np.repeat((n_new.reshape(-1, 2) == 0).all(axis=1), 2)
    assert not np.asarray(got)[dead].any()


@pytest.mark.parametrize("plan", sorted(PLANS))
def test_the_hosts_tile_counts_are_the_trip_counts_the_device_ran(
        plan, small_tiles):
    """``read_counts`` (``context_read_share``'s arithmetic) against the
    loops themselves: an ``attend`` that scores one key and adds 1 a tile
    returns, a slot, the number of tiles its block's loop ran."""
    S, mb, tile = READ["S"], READ["max_blocks"], READ["tile"]
    slots, narrow = READ["slots"], READ["narrow"]
    C, lengths, n_new, tables = _plan(plan)
    fill = jnp.finfo(jnp.float32).min

    def attend(q, pos, ctx, start):
        s = jnp.full(q.shape[:2] + (tile,), fill).at[:, :, 0].set(
            jnp.where(start == 0, 0.0, fill))
        return s, lambda p: jnp.ones(p.shape[:-1] + (1,), jnp.float32)
    pool = {"x": jnp.zeros((1, S * mb, BS, 1))}

    def ran(cols):
        q = jnp.zeros((S, cols))
        o = jax.jit(lambda q: paged.attend_by_blocks(
            attend, (q, q.astype(jnp.int32), jnp.asarray(tables)),
            jnp.asarray(n_new), slots, narrow,
            bound=_bound(lengths, pool, 0)))(q)
        return np.asarray(o)[:, 0, 0].astype(np.int64)
    first = ran(narrow)             # the first pass alone
    both = ran(C)
    chunk = np.repeat(n_new.reshape(-1, slots).max(axis=1) > narrow,
                      slots) & (C > narrow)
    assert np.array_equal(both[~chunk], first[~chunk])
    want = -(-np.where(n_new > 0, lengths + n_new, 0).reshape(
        -1, slots).max(axis=1) // tile)
    assert np.array_equal(first, np.repeat(want, slots))
    assert np.array_equal(both[chunk], np.repeat(want, slots)[chunk])
    counts = paged.read_counts(lengths, n_new, C, slots, narrow, BS, mb)
    assert counts[0] == (first.sum() + both[chunk].sum()) * tile
    assert counts[1] == S * mb * BS
    assert tuple(counts[2:]) == (int((want == 0).sum()), S // slots)
    # a module that hands no bound reads every table whole, chunks twice
    whole = paged.read_counts(lengths, n_new, C, slots, narrow, BS, mb,
                              bounded=False)
    assert whole[0] == (S + chunk.sum()) * mb * BS and whole[2] == 0


def test_a_table_that_is_no_whole_number_of_tiles(kind, monkeypatch):
    """Tables of 3 blocks read in tiles of 2: the fourth entry is padding
    that covers positions no query sees."""
    model, cfg, pool = kind
    monkeypatch.setattr(paged, "TILE", 2 * BS)
    attend, whole, width, back, seen = _attends(model, cfg)
    q = jnp.asarray(np.random.default_rng(8).normal(
        size=(3, C) + width).astype(np.float32))
    lengths, n_new = jnp.asarray([7, 6, 0]), jnp.asarray([4, 0, 3])
    tables = jnp.asarray(np.array([[7, 2, 9], [4, 4, 4], [5, -1, -1]]))
    positions, valid = paged.slot_positions(lengths, n_new, C)
    got = back(paged.attend_by_blocks(
        attend, (q, positions, tables), n_new, 1, C,
        bound=_bound(lengths, seen(_device(pool)), by_head=_by_head(model))))
    want = whole(q, positions, paged.gather(seen(_device(pool)), 1, tables,
                                            _by_head(model)))
    err = np.abs(np.asarray(got) - np.asarray(want))[np.asarray(valid)]
    assert float(err.max()) < 2e-6


def test_copy_blocks_padding_and_a_source_recycled_in_the_same_call(kind):
    model, cfg, pool = kind
    # 3 -> 5; 5 -> 8 reads what 5 held BEFORE the call; (0 -> BLOCKS) and
    # (-1 -> BLOCKS) are padding pairs: dropped, their source clamped
    src = jnp.array([3, 5, 0, -1], jnp.int32)
    dst = jnp.array([5, 8, BLOCKS, BLOCKS], jnp.int32)
    # (gdn_hybrid's own clones nothing: no prefix over its state kinds)
    for copy in (paged.copy_blocks,) + (
            () if _by_head(model) else (model.copy_blocks,)):
        got = copy(_device(pool), src, dst)
        assert set(got) == set(pool)
        for name, leaf in pool.items():
            want = leaf.copy()
            want[:, 5], want[:, 8] = leaf[:, 3], leaf[:, 5]
            assert np.array_equal(np.asarray(got[name]), want), name


def test_copy_blocks_jitted_on_a_bare_leaf_and_all_padding_is_identity():
    """As the tick calls it: under jit, on every tick — most of which carry
    padding pairs alone and must leave the pool as it was.  A pool is any
    pytree, a bare array included, whatever lies behind the block axes."""
    rng = np.random.default_rng(9)
    copy = jax.jit(paged.copy_blocks)
    for behind in ((128,), (40,), (2, 8)):
        leaf = rng.normal(size=(3, BLOCKS, BS) + behind).astype(np.float32)
        nothing = copy(jnp.asarray(leaf), jnp.zeros(3, jnp.int32),
                       jnp.full(3, BLOCKS, jnp.int32))
        assert np.array_equal(np.asarray(nothing), leaf), behind
        want = leaf.copy()
        want[:, 5], want[:, 8] = leaf[:, 3], leaf[:, 5]
        got = copy(jnp.asarray(leaf), jnp.array([3, 5, -1], jnp.int32),
                   jnp.array([5, 8, BLOCKS], jnp.int32))
        assert np.array_equal(np.asarray(got), want), behind


def test_read_block_write_block_round_trip(kind):
    """A block's payload, whatever lies behind the block axis (by head:
    ``[L, heads, block_size, head_dim]``), is the same on both sides of a
    spill (HostSpillPool over the two accessors) and of a hand-off's
    record (the payload's codec)."""
    model, cfg, pool = kind
    payload = paged.read_block(_device(pool), 7)
    assert list(payload) == sorted(pool)
    for name, leaf in pool.items():
        assert isinstance(payload[name], np.ndarray)
        assert np.array_equal(payload[name], leaf[:, 7])
    if _by_head(model):
        assert payload["k"].shape == (cfg.count(gdn_hybrid.FULL), cfg.n_heads,
                                      BS, cfg.head_dim)
    sent = decode_block_payload(encode_block_payload(payload))
    got = paged.write_block(_device(pool), 9, sent)
    for name, leaf in pool.items():
        want = leaf.copy()
        want[:, 9] = leaf[:, 7]
        assert np.array_equal(np.asarray(got[name]), want)
    # spilled from block 7, reloaded into block 2
    held = {"cache": _device(pool)}
    tier = HostSpillPool(
        1, lambda b: paged.read_block(held["cache"], b),
        lambda b, p: held.update(cache=paged.write_block(held["cache"], b, p)))
    node = _PrefixNode((1, 2, 3, 4), 7, None)
    assert tier.spill(node) and tier.holds(node)
    tier.reload(node, 2)
    for name, leaf in pool.items():
        want = leaf.copy()
        want[:, 2] = leaf[:, 7]
        assert np.array_equal(np.asarray(held["cache"][name]), want)


# ------------------------------------------------- a kind by head
def _pair(layers=2, heads=2, width=8):
    """The same keys and values declared side by side in a block, a
    position's heads apart, and BY HEAD inside it."""
    leaves = {"k": (heads, width), "v": (heads, width)}
    return (paged.CacheKind("kv", layers, leaves=leaves),
            paged.CacheKind("kv", layers, leaves=leaves, by_head=True))


@pytest.mark.parametrize("by_head", [False, True], ids=["side", "by-head"])
def test_init_pools_and_pool_shardings_follow_the_declaration(by_head):
    kind = _pair(layers=3, heads=4)[by_head]
    pool = jax.eval_shape(lambda: paged.init_pools(
        (kind,), {"kv": 64}, BS, jnp.bfloat16))["kv"]
    want = (3, 64, 4, BS, 8) if by_head else (3, 64, BS, 4, 8)
    assert {k: (v.shape, v.dtype) for k, v in pool.items()} == {
        k: (want, jnp.bfloat16) for k in ("k", "v")}
    assert paged.block_size(pool, by_head) == BS
    PS = jax.sharding.PartitionSpec
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:8]).reshape(4, 2),
                             ("data", "model"))
    # the heads over the model axis wherever they lie, the blocks over data
    spec = paged.pool_shardings(mesh, (kind,), {"kv": 64})["kv"].spec
    assert spec == (PS(None, "data", "model", None, None) if by_head
                    else PS(None, "data", None, "model", None))
    # three heads, which the model axis does not divide, stay whole
    odd = _pair(heads=3)[by_head]
    assert paged.pool_shardings(mesh, (odd,), {"kv": 64})["kv"].spec == PS(
        None, "data", None, None, None)


@pytest.mark.parametrize("bs", [1, 2, 3, 16])
def test_touched_blocks_bounds_every_plan(bs):
    """No plan of ``rows`` rows touches more blocks than ``touched_blocks``
    says — a slot's n positions from the offset that splits them worst —,
    and some plan touches that many."""
    touch = lambda n: -(-(bs - 1 + n) // bs) if n else 0
    for S, C, rows in ((3, 5, 15), (3, 5, 7), (2, 9, 12), (4, 1, 4),
                       (3, 6, 4), (1, 7, 7)):
        worst = max(sum(map(touch, plan))
                    for plan in itertools.product(range(C + 1), repeat=S)
                    if sum(plan) <= rows)
        assert paged.touched_blocks(S, C, rows, bs) == worst, (S, C, rows)


@pytest.mark.parametrize("budget", [0, 14], ids=["slab", "packed"])
@pytest.mark.parametrize("plan", sorted(PLANS))
def test_a_pool_by_head_reads_what_one_side_by_side_reads(plan, budget,
                                                          small_tiles):
    """The same rows through one tick into a pool side by side (``write``
    by row) and into one by head (``write_blocks`` by block), then read
    through ``attend_by_blocks`` with a bound: every block of the two pools
    holds the same values — padding rows, a dead slot's stale table row and
    rows out of range dropped in both — and the reads agree in float32 to
    the tiled read's tolerance."""
    S, mb = READ["S"], READ["max_blocks"]
    C, lengths, n_new, tables = _plan(plan)
    if budget and n_new.sum() > budget:
        budget = int(n_new.sum())
    side, head = _pair()
    H, W = side.leaves["k"]
    rng = np.random.default_rng(11)
    noise = rng.normal(size=(2, S * mb, BS, H, W)).astype(np.float32)
    rows = {n: jnp.asarray(rng.normal(size=(S, C, H * W)).astype(np.float32))
            for n in ("k", "v")}
    q = jnp.asarray(rng.normal(size=(S, C, H, W)).astype(np.float32))
    lens, new = jnp.asarray(lengths), jnp.asarray(n_new)

    def run(kind, tile, pool):
        pool = {"k": pool, "v": pool + 1.0}
        t = paged.tick((kind,), {"kv": pool}, {"kv": jnp.asarray(tables)},
                       lens, new, C, rows=budget)
        # (a row's heads apart for the write by row; by block either way)
        values = {n: t.take(a if kind.by_head else a.reshape(S, C, H, W))
                  for n, a in rows.items()}
        pool = (paged.write_blocks if kind.by_head else paged.write)(
            pool, 1, *t.where["kv"], values)
        o = paged.attend_by_blocks(
            tile, (t.take(q), t.positions, jnp.asarray(tables)), new,
            READ["slots"], READ["narrow"],
            bound=paged.Bound(lens, pool, 1, t.slab, kind.by_head))
        return pool, jnp.moveaxis(o, 3, 1)      # [S, C, heads, 1, width]
    got_side, o_side = jax.jit(lambda p: run(side, llama._attend_tile, p))(
        jnp.asarray(noise))
    got_head, o_head = jax.jit(
        lambda p: run(head, gdn_hybrid._attend_tile, p))(
            jnp.asarray(np.swapaxes(noise, 2, 3)))
    live = np.arange(C)[None] < n_new[:, None]
    assert live.any()
    for n in ("k", "v"):
        a = np.asarray(got_side[n])
        assert np.array_equal(np.swapaxes(np.asarray(got_head[n]), 2, 3), a)
        # layer 0 and every block that no live position fell into are as
        # they were; the rows that landed are in layer 1
        was = noise + (n == "v")
        assert np.array_equal(a[0], was[0])
        for s, j in zip(*np.nonzero(live)):
            P = lengths[s] + j
            at = (1, tables[s, P // BS], P % BS)
            assert np.array_equal(a[at].reshape(-1), np.asarray(rows[n])[s, j])
            was[at] = a[at]
        assert np.array_equal(a, was), n
    err = np.abs(np.asarray(o_head) - np.asarray(o_side))[live]
    assert float(err.max()) < 2e-6, plan


def test_shardings_ride_the_meshs_own_axes():
    """tests/test_serve.py test_cache_shardings_ride_existing_axes' meshes,
    for a pool with a head axis and for one without."""
    PS = jax.sharding.PartitionSpec
    devs = np.array(jax.devices()[:8])
    mesh2 = jax.sharding.Mesh(devs.reshape(4, 2), ("data", "model"))
    mesh1 = jax.sharding.Mesh(devs, ("hvd",))
    assert paged.shardings(mesh2, 64, 4).spec == PS(
        None, "data", None, "model", None)
    assert paged.shardings(mesh2, 64, 3).spec == PS(
        None, "data", None, None, None)
    assert paged.shardings(mesh1, 64, 4).spec == PS(
        None, "hvd", None, None, None)
    # blocks that no axis divides stay whole
    assert paged.shardings(mesh1, 63, 4).spec == PS(None, None, None, None,
                                                    None)
    # no head axis: four axes, blocks over the first axis that divides them
    assert paged.shardings(mesh2, 64).spec == PS(None, "data", None, None)
    assert paged.shardings(mesh2, 6).spec == PS(None, "model", None, None)
    assert latent_moe.cache_shardings(
        mesh1, latent_moe.CONFIGS["tiny"], 64).spec == PS(
            None, "hvd", None, None)


# ------------------------------------------------ the engine's contract
CONTRACT = ("init_cache", "copy_blocks", "apply_cached", "cache_shardings",
            "attn_blocks", "TICK_COUNTERS")
#: what a module MAY declare beside them: its cache kinds (swa_moe, conv_moe)
#: and the tick's greedy tokens in place of its logits — of every column, or
#: with ``read`` of the columns the tick reads (tests/test_greedy_read.py)
OPTIONAL = ("cache_kinds", "greedy_cached")


def _engine(model, cfg, params):
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("hvd",))
    scfg = ServeConfig(max_slots=2, block_size=4, cache_blocks=32,
                       max_seq_len=32, max_batch_tokens=12, prefill_chunk=8,
                       # refused over a window cache kind, and like the
                       # drafts for a model that denoises blocks
                       prefix_cache=not (hasattr(model, "cache_kinds")
                                         or block_length(cfg)),
                       spec_decode=not block_length(cfg))
    return ServeEngine(model, cfg, params, scfg, mesh=mesh)


@pytest.mark.parametrize("name", sorted(_MODEL_MODULES))
def test_every_served_module_keeps_the_contract(name):
    """The six names, a config that carries the tick's token budget, and a
    request served through them with no probe of
    the module: a stand-in that lacks one fails loudly."""
    model = importlib.import_module(_MODEL_MODULES[name])
    for attr in CONTRACT:
        assert hasattr(model, attr), (name, attr)
    assert isinstance(model.TICK_COUNTERS, tuple)
    cfg = model.CONFIGS["tiny"]
    params = model.init(jax.random.PRNGKey(0), cfg)
    engine = _engine(model, cfg, params)
    assert cfg.max_tick_tokens == 0
    assert engine.model_cfg.max_tick_tokens == engine.cfg.max_batch_tokens
    req = engine.submit([3, 1, 4, 1, 5, 9, 2, 6, 5, 3], 4, req_id="r")
    engine.flush()
    assert len(req.out_tokens) == 4
    counted = engine.stats().get("moe", {})
    assert sorted(counted) == sorted(model.TICK_COUNTERS)
    engine.close()
    for attr in ("TICK_COUNTERS", "cache_shardings", "attn_blocks"):
        stub = types.SimpleNamespace(**{
            a: getattr(model, a) for a in CONTRACT + OPTIONAL
            if a != attr and hasattr(model, a)})
        with pytest.raises(AttributeError, match=attr):
            _engine(stub, cfg, params)


# ------------------------------------------------ a fixed state a slot
def _state_tick(kind, pool, lengths, n_new, C, budget=0,
                reads=("slot", "row")):
    """The tick of ONE state kind's pool (a dict of its leaves)."""
    return paged.tick((kind,), {kind.name: pool}, {}, jnp.asarray(lengths),
                      jnp.asarray(n_new), C, rows=budget, reads=reads)


def test_the_state_kinds_addressing_on_a_tick():
    """paged.state_lands / state_read by hand: which of a tick's rows land
    in a slot's columns (the last ``cols`` of a long chunk only), and what
    each row reads back: its own slot's earlier rows, the state as it was
    before the tick, zero before position 0 — never the neighbour's row."""
    cols, S, C = 4, 3, 6
    lengths = jnp.asarray([0, 5, 9], jnp.int32)
    n_new = jnp.asarray([6, 1, 0], jnp.int32)
    # pool[l, s, c] = 100 s + the position that column held before the tick
    held = np.zeros((1, S, cols, 1), np.float32)
    for s, L in enumerate(lengths.tolist()):
        for p in range(max(0, L - cols), L):
            held[0, s, p % cols, 0] = 100 * s + p
    kind = paged.CacheKind("conv", 1, state=2, leaves={"u": (1,)})
    t = _state_tick(kind, {"u": jnp.asarray(held)}, lengths, n_new, C, 8)
    row, keeps = t.lands["conv"]
    # slot 0 writes positions 2..5 of its six (the last four: rows 2..5, at
    # columns 2, 3, 0, 1), slot 1 its one (row 6, column 5 % 4), slot 2
    # nothing; what does not land keeps what it held
    assert keeps.tolist() == [[True] * 4, [False, True, False, False],
                              [False] * 4]
    assert row[0].tolist() == [4, 5, 2, 3] and int(row[1, 1]) == 6
    own = t.take(1000.0 + 10 * jnp.arange(S)[:, None]
                 + jnp.arange(C)[None].astype(jnp.float32))[0][:, None]
    back1, back2 = (e[:7, 0] for e in paged.state_read(
        jnp.asarray(held), 0, own, t, 2))
    # rows: slot 0's six (positions 0..5), then slot 1's one (position 5)
    assert back1.tolist() == [0, 1000, 1001, 1002, 1003, 1004, 104]
    assert back2.tolist() == [0, 0, 1000, 1001, 1002, 1003, 103]
    out = np.asarray(paged.write_slots(
        {"u": jnp.asarray(held)}, 0, row, keeps, {"u": own[None]})["u"])
    assert out[0, 0, :, 0].tolist() == [1004, 1005, 1002, 1003]
    assert out[0, 1, :, 0].tolist() == [104, 1010, 102, 103]
    assert (out[0, 2] == held[0, 2]).all()


# ------- a slot at a time against a row at a time: the reference kept here
def _by_row_read(pool, layer, own, slot, positions, lengths, back):
    """``paged.state_read`` as it was by ROW: every row gathers its slot's
    column of the pool, then the tick's own rows are laid over."""
    at = positions - back
    kept = pool[layer, slot, at % pool.shape[2]]
    mine = jnp.pad(own, ((back, 0), (0, 0)))[:own.shape[0]]
    return jnp.where((at >= 0)[:, None],
                     jnp.where((at >= lengths)[:, None], mine, kept),
                     jnp.zeros((), own.dtype))


def _by_row_lands(lengths, n_new, valid, positions, cols, replay):
    """``paged.state_index`` / ``replay_index`` as they were: (slot, col)
    [S, C] by row, what does not land at slot ``S``, off the axis."""
    S = lengths.shape[0]
    if replay:
        lands = (valid & (n_new <= cols + 1)[:, None]
                 & (positions > lengths[:, None]))
    else:
        lands = valid & (positions >= (lengths + n_new)[:, None] - cols)
    slot = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[:, None],
                            positions.shape)
    return jnp.where(lands, slot, S), positions % cols


#: name -> (lengths, n_new) of five slots in a tick of twelve columns over a
#: state of 3 with a verify row of 5: eight columns, or a ring of four rows
SLOT_PLANS = {
    "a_chunk_longer_than_the_ring": ([16, 3, 40, 0, 7], [12, 1, 10, 0, 9]),
    "a_chunk_shorter_than_the_state": ([5, 0, 11, 2, 0], [2, 1, 2, 1, 2]),
    "a_dead_slot": ([9, 7, 0, 30, 4], [5, 0, 0, 1, 0]),
    "a_new_tenant": ([0, 0, 0, 6, 0], [12, 3, 1, 4, 5]),
    "a_verify_row": ([9, 21, 2, 0, 100], [5, 5, 5, 5, 5]),
}


@pytest.mark.parametrize("replay", [False, True], ids=["state", "replay"])
@pytest.mark.parametrize("packed", [False, True], ids=["slab", "packed"])
@pytest.mark.parametrize("plan", sorted(SLOT_PLANS))
def test_a_state_kind_by_slot_is_the_addressing_by_row(plan, packed, replay):
    """What a tick reads of a state kind's pool and leaves in it, a SLOT at
    a time (paged.state_read, state_lands, write_slots), is bit for bit
    what it read and left a ROW at a time — every row's gather of
    ``pool[layer, slot, at % cols]`` and the scatter of all rows with the
    ones that do not land dropped —: ``earlier[back]`` at every row that
    holds a token, and the pool's contents whole, the other layer and the
    slots that run no row included.  Over a pool of noise that another
    stream left in every slot."""
    state, spec, S, C, d = 3, 5, 5, 12, 3
    lengths, n_new = (jnp.asarray(x, jnp.int32) for x in SLOT_PLANS[plan])
    cols = (paged.replay_rows(spec) if replay
            else paged.state_columns(state, spec))
    kind = (paged.CacheKind("k", 2, state=1, leaves={"S": (2, 2)},
                            replay={"u": (d,)}) if replay else
            paged.CacheKind("k", 2, state=state, leaves={"u": (d,)}))
    rng = np.random.default_rng(sorted(SLOT_PLANS).index(plan))
    pool = paged.init_pools((kind,), {"k": (S, cols)}, 4, jnp.bfloat16)["k"]
    pool = dict(pool, u=jnp.asarray(
        rng.normal(size=pool["u"].shape), jnp.bfloat16))
    budget = int(n_new.sum()) + 2 if packed else 0
    t = _state_tick(kind, pool, lengths, n_new, C, budget)
    assert (t.slab.rows is not None) == packed
    own = t.take(jnp.asarray(rng.normal(size=(S, C, d)), jnp.bfloat16))
    flat = own.reshape(-1, d)
    valid = np.asarray(t.take(paged.slot_positions(lengths, n_new, C)[1])
                       ).reshape(-1)
    assert valid.sum() == int(n_new.sum())
    if not replay:
        got = paged.state_read(pool["u"], 1, flat, t, state)
        for back in range(1, state + 1):
            want = _by_row_read(pool["u"], 1, flat, *t.row, back)
            assert np.array_equal(np.asarray(got[back - 1])[valid],
                                  np.asarray(want)[valid]), back
    positions, slab_valid = paged.slot_positions(lengths, n_new, C)
    slot, col = _by_row_lands(lengths, n_new, slab_valid, positions, cols,
                              replay)
    want = paged.write({"u": pool["u"]}, 1, t.take(slot), t.take(col),
                       {"u": own})["u"]
    got = paged.write_slots({"u": pool["u"]}, 1, *t.lands["k"],
                            {"u": own})["u"]
    assert got.dtype == want.dtype and np.array_equal(
        np.asarray(got, np.float32), np.asarray(want, np.float32))
    assert np.array_equal(np.asarray(got[0], np.float32),
                          np.asarray(pool["u"][0], np.float32))


@pytest.mark.parametrize("state,tick_cols", [(2, 5), (2, 1), (3, 9), (1, 2)])
def test_a_states_ring_keeps_what_the_tick_after_a_verify_row_reads(
        state, tick_cols):
    """paged.state_columns' bound, position by position: a verify row writes
    ``L .. L+n-1``; with any ``a < n`` of them accepted the next tick reads
    the ``state`` positions before ``L+a+1``, and each still lies in its own
    column; a ring two columns shorter loses one when nothing is accepted
    and the row is as wide as it may be."""
    R, L = paged.state_columns(state, tick_cols), 1000
    for n in range(1, tick_cols + 1):
        for a in range(n):
            read = range(L + a + 1 - state, L + a + 1)
            live = set(read) | set(range(L, L + n))
            assert len({p % R for p in live}) == len(live)
    short = R - 2
    live = set(range(L + 1 - state, L + tick_cols))
    assert short < 1 or len({p % short for p in live}) < len(live)



# ------------------------------------ a state that folds its past: a carry
def test_the_carrys_read_and_its_snapshots_on_a_tick():
    """paged.carry_read / carry_index / write by hand, on a carry with a
    shape behind its columns: a tick reads the ONE column of each slot's
    last position as it was before the tick (zero for a slot that holds
    nothing), and the carries after its rows land at ``[s, P % cols]``, the
    last ``cols`` of a long chunk only, nothing of a slot that runs no
    row."""
    cols, S, C = 4, 4, 6
    lengths = jnp.asarray([0, 5, 9, 0], jnp.int32)
    n_new = jnp.asarray([6, 2, 0, 0], jnp.int32)
    # pool[l, s, c] = 100 s + the position whose carry the column holds, on
    # a [2, 3] shape behind; slot 3 holds a stream that left
    held = np.zeros((2, S, cols, 2, 3), np.float32)
    for s, L in enumerate([0, 5, 9, 7]):
        for p in range(max(0, L - cols), L):
            held[1, s, p % cols] = 100 * s + p
    pool = jnp.asarray(held)
    got = paged.carry_read(pool, 1, lengths)
    assert got.shape == (S, 2, 3)
    assert got[:, 0, 0].tolist() == [0.0, 104.0, 208.0, 0.0]
    assert bool((got == got[:, :1, :1]).all())
    # the carries after the tick's rows: 1000 + 10 s + column, time-major as
    # a scan hands them out
    kind = paged.CacheKind("carry", 2, state=1, leaves={"h": (2, 3)})
    slot, col = paged.carry_index(
        _state_tick(kind, {"h": pool}, lengths, n_new, C), cols)
    after = jnp.broadcast_to(
        (1000.0 + 10 * jnp.arange(S)[None, :] + jnp.arange(C)[:, None]
         )[..., None, None], (C, S, 2, 3))
    out = np.asarray(paged.write({"h": pool}, 1, slot.T, col.T,
                                 {"h": after})["h"])
    assert (out[0] == held[0]).all()                     # the other layer
    # slot 0 keeps the carries after its positions 2..5 (rows 2..5), slot 1
    # those after 5 and 6 beside the two it held, slots 2 and 3 what they had
    assert out[1, 0, :, 0, 0].tolist() == [1004, 1005, 1002, 1003]
    assert out[1, 1, :, 0, 0].tolist() == [104, 1010, 1011, 103]
    assert (out[1, 2:] == held[1, 2:]).all()
    # the tick after reads what the last ACCEPTED row left: of slot 1's two
    # rows one accepted -> length 6 -> the carry after position 5
    nxt = paged.carry_read(jnp.asarray(out), 1, jnp.asarray([6, 6, 9, 0]))
    assert nxt[:, 0, 0].tolist() == [1005.0, 1010.0, 208.0, 0.0]


# ------------- a state too large for a column a row: ONE a slot, and a replay
def test_the_replay_kinds_addressing_on_a_tick():
    """paged.state_lands / committed / replay_read / commit by hand, on a
    state with a shape behind it: a row that fits the ring behind its first
    column commits after that column and leaves the rest to the ring at
    ``[s, P % rows]``; a longer one (a prompt's chunk) commits after its
    last column and keeps nothing; a tick reads ONE state a slot as it was
    before the tick with the position it stands after (zero and 0 for a
    slot that holds nothing) and the ring in the order it is replayed."""
    rows, S, C = paged.replay_rows(5), 4, 8
    assert rows == 4 and paged.replay_rows(1) == 1
    kind = paged.CacheKind("delta", 2, state=1, dtype=jnp.float32,
                           leaves={"S": (2, 3)}, replay={"x": (2,)})
    pool = paged.init_pools((kind,), {"delta": (S, rows)}, 4,
                            jnp.bfloat16)["delta"]
    assert {k: (v.shape, str(v.dtype)) for k, v in pool.items()} == {
        "S": ((2, S, 1, 2, 3), "float32"), "x": ((2, S, rows, 2), "float32"),
        paged.AT: ((2, S, 1, 1), "int32")}
    # slot 0 a verify row of five at 9, slot 1 a chunk of eight at 16, slot
    # 2 nothing, slot 3 a new tenant's tail of three where a stream left
    lengths = jnp.asarray([9, 16, 0, 0], jnp.int32)
    n_new = jnp.asarray([5, 8, 0, 3], jnp.int32)
    assert paged.commit_row(n_new, rows).tolist() == [0, 7, 0, 0]
    # the ring's entries by slot: slot 0's rows 1..4 (positions 10..13 at
    # entries 2, 3, 0, 1), slot 3's rows 14 and 15 (positions 1 and 2),
    # nothing of the chunk and of the idle slot
    t = paged.tick((kind,), {"delta": pool}, {}, lengths, n_new, C)
    row, keeps = t.lands["delta"]
    assert t.start.tolist() == [0, 8, 16, 24]
    assert keeps.tolist() == [[True] * 4, [False] * 4, [False] * 4,
                              [False, True, True, False]]
    assert row[0].tolist() == [3, 4, 1, 2] and row[3, 1:3].tolist() \
        == [25, 26]
    # what the pool held: state 100 s, standing after 7, 16, 5 and 11; the
    # ring's entry e of slot s holds 10 s + e
    held = dict(pool)
    held["S"] = held["S"].at[1].set(
        100.0 * jnp.arange(S)[:, None, None, None] + jnp.ones((S, 1, 2, 3)))
    held[paged.AT] = held[paged.AT].at[1, :, 0, 0].set(
        jnp.asarray([7, 16, 5, 11]))
    held["x"] = held["x"].at[1].set(
        (10.0 * jnp.arange(S)[:, None] + jnp.arange(rows)[None])[..., None]
        * jnp.ones(2))
    state, at = paged.committed(held, 1, lengths, "S")
    assert state.shape == (S, 2, 3) and at.tolist() == [7, 16, 0, 0]
    assert state[:, 0, 0].tolist() == [1.0, 101.0, 0.0, 0.0]
    # slot 0 replays positions 7 and 8 (entries 3 and 0), then would 9, 10
    ring = paged.replay_read(held["x"], 1, at)
    assert ring.shape == (S, rows, 2)
    assert ring[0, :, 0].tolist() == [3.0, 0.0, 1.0, 2.0]
    assert ring[1, :, 0].tolist() == [10.0, 11.0, 12.0, 13.0]
    # the commit: the new state and where it stands; slot 2 keeps its own
    new = 1000.0 + jnp.arange(S)[:, None, None] * jnp.ones((S, 2, 3))
    out = paged.commit(held, 1, lengths, n_new, rows, S=new)
    assert out[paged.AT][1, :, 0, 0].tolist() == [10, 24, 5, 1]
    assert out["S"][1, :, 0, 0, 0].tolist() == [1000.0, 1001.0, 201.0, 1003.0]
    assert bool((out["S"][0] == held["S"][0]).all())     # the other layer
    assert out["x"] is held["x"]


@pytest.mark.parametrize("tick_cols", [1, 2, 5, 9])
def test_a_replay_ring_keeps_what_the_tick_after_a_verify_row_replays(
        tick_cols):
    """paged.replay_rows' bound, position by position: a verify row runs
    ``L .. L+n-1`` and commits after ``L``; with any ``a < n`` drafts
    accepted the next tick replays ``L+1 .. L+a``, each still in its own
    entry of the ring, before its own rows overwrite any; a ring one row
    shorter loses one when the row is as wide as it may be."""
    R, L = paged.replay_rows(tick_cols), 1000
    for n in range(1, tick_cols + 1):
        assert int(paged.commit_row(jnp.asarray(n), R)) == 0
        kept = range(L + 1, L + n)
        assert len({p % R for p in kept}) == len(kept)
        for a in range(n):
            assert set(range(L + 1, L + 1 + a)) <= set(kept)
    # a row wider than the ring holds is a chunk: it commits after its last
    assert int(paged.commit_row(jnp.asarray(R + 2), R)) == R + 1
    if tick_cols > 2:
        short = R - 1
        kept = range(L + 1, L + tick_cols)
        assert len({p % short for p in kept}) < len(kept)
