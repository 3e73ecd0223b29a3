"""The shared half of a served decoder (models/paged.py ``tick``,
``init_pools``, ``pool_shardings``; models/decoder.py ``cached_pair``;
docs/serving.md#what-a-served-model-module-exports), at `tiny` and with no
model compiled: the tick's slot arithmetic against the addressing worked
out by hand for every served module's declared cache kinds, each module's
pools and their shardings against shapes written out here from the modules
as they were before the frame was shared, and the cached pair in both its
forms over a stand-in family."""

import importlib
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import decoder, paged

MODULES = ["llama", "moe_llama", "latent_moe", "swa_moe", "conv_moe",
           "blockdiff_moe", "sambay", "gdn_hybrid"]
BS, TICK_COLS, MAX_BLOCKS, POOL = 4, 5, 8, 40
# One plan, a slot a row — (columns, length before the tick): a decode row,
# a verify row with two drafts, a chunk in the middle of a prompt, a
# prompt's last chunk and an idle slot.  The packed budget holds every valid
# token and fewer rows than the slab has positions.
C, ROWS = 6, 16
PLAN = [(1, 9), (3, 14), (6, 6), (4, 12), (0, 0)]
N_NEW, LENGTHS = (np.asarray(x, np.int32) for x in zip(*PLAN))
S = len(PLAN)
ALL = ("valid", "pos", "top", "slot", "row", "first")


def _part(tree, kind):
    """A kind's part of a cache, of tables or of block counts, by its name."""
    return tree if kind is None else tree[kind]


def _module(name):
    model = importlib.import_module("horovod_tpu.models." + name)
    cfg = model.CONFIGS["tiny"]
    kinds = (model.cache_kinds(cfg) if hasattr(model, "cache_kinds")
             else model._pool(cfg))
    return model, cfg, kinds


def _sizes(kinds):
    """(num_blocks, block tables) of each kind as the engine's scheduler
    sizes them: a whole context's table, a window's ring, a state's (slots,
    columns); every slot its own blocks, in an order that is not 0, 1, 2."""
    rng = np.random.default_rng(7)
    blocks, tables = {}, {}
    for k in kinds:
        if k.state is not None:
            blocks[k.name] = (S, paged.replay_rows(TICK_COLS) if k.replay
                              else paged.state_columns(k.state, TICK_COLS))
            continue
        entries = (MAX_BLOCKS if k.window is None else paged.ring_blocks(
            k.window, TICK_COLS, BS, MAX_BLOCKS))
        blocks[k.name] = POOL
        tables[k.name] = rng.permutation(POOL)[:S * entries].reshape(
            S, entries).astype(np.int32)
    if [k.name for k in kinds] == [None]:
        return blocks[None], tables[None]
    return blocks, tables


def _by_hand(kind, table, cols):
    """Where each packed row lands in a paged or a ring ``kind``'s pool, a
    row a valid position in slab order; in a state kind's, by slot: {(slot,
    column): the row that lands there}, every other column keeping what it
    held."""
    out, by_slot, row = [], {}, 0
    for s, (n, L) in enumerate(PLAN):
        for P in range(L, L + n):
            if kind.replay:     # behind a row's first column, if it fits
                if n <= cols + 1 and P > L:
                    by_slot[s, P % cols] = row
            elif kind.state is not None:
                if P >= L + n - cols:
                    by_slot[s, P % cols] = row
            elif kind.window is not None:
                out.append((table[s, (P // BS) % table.shape[1]], P % BS))
            else:
                out.append((table[s, P // BS], P % BS))
            row += 1
    return by_slot if kind.state is not None else np.asarray(out)


@pytest.mark.parametrize("name", MODULES)
def test_the_tick_is_the_addressing_by_hand(name):
    """``paged.tick`` over a module's declared kinds: the rows' positions,
    where they land in a paged kind's pool, in a ring's and in a state's
    columns by slot, what a family reads by row — and nothing for a kind
    the module did not declare."""
    model, cfg, kinds = _module(name)
    blocks, tables = _sizes(kinds)
    cache = jax.eval_shape(lambda: model.init_cache(cfg, blocks, BS))
    t = paged.tick(kinds, cache, jax.tree_util.tree_map(jnp.asarray, tables),
                   jnp.asarray(LENGTHS), jnp.asarray(N_NEW), C, rows=ROWS,
                   max_seq=cfg.max_seq, reads=ALL)
    n = int(N_NEW.sum())
    slot_of = np.repeat(np.arange(S), N_NEW)
    pos = np.concatenate([np.arange(L, L + m) for m, L in PLAN])
    assert t.positions.shape == (S, C) and t.slab.kept == ROWS
    assert np.array_equal(np.asarray(t.valid)[0], np.arange(ROWS) < n)
    assert np.array_equal(np.asarray(t.pos)[0, :n], pos)
    assert np.array_equal(np.asarray(t.top), LENGTHS + N_NEW - 1)
    assert np.array_equal(np.asarray(t.slot)[0, :n], slot_of)
    for got, want in zip(t.row, (slot_of, pos, LENGTHS[slot_of])):
        assert got.shape == (ROWS,) and np.array_equal(np.asarray(got)[:n],
                                                       want)
    # a slot's rows begin where its position is its length before the tick
    assert np.array_equal(np.asarray(t.first)[0, :n],
                          pos == LENGTHS[slot_of])
    # the rows go back to their places
    back = np.asarray(t.slab(t.take(t.positions)[..., None]))[..., 0]
    held = np.arange(C)[None] < N_NEW[:, None]
    assert np.array_equal(back[held], np.asarray(t.positions)[held])
    paged_kinds = [k for k in kinds if k.state is None]
    assert sorted(t.where, key=str) == sorted(
        (k.name for k in paged_kinds), key=str)
    assert sorted(t.lands) == sorted(
        k.name for k in kinds if k.state is not None)
    # a slot's rows begin where the packed rows before it end
    assert np.array_equal(np.asarray(t.start)[N_NEW > 0],
                          (np.cumsum(N_NEW) - N_NEW)[N_NEW > 0])
    for k in kinds:
        pool = jax.tree_util.tree_leaves(_part(cache, k.name))[0]
        if k.state is None and k.by_head:
            # by block: the blocks the rows touch, and for each offset of
            # each the row that lands there
            want = _by_hand(k, _part(tables, k.name), None)
            blk, row, keeps = (np.asarray(a) for a in t.where[k.name])
            most = paged.touched_blocks(S, C, ROWS, BS)
            assert blk.shape == (most,) and row.shape == keeps.shape == (
                most, BS)
            assert {(blk[u], o): row[u, o]
                    for u, o in zip(*np.nonzero(keeps))} == {
                tuple(at): r for r, at in enumerate(want)}, k
            touched = len({b for b, _ in want})
            assert (blk[touched:] == pool.shape[1]).all(), k
            continue
        if k.state is None:
            want = _by_hand(k, _part(tables, k.name), None)
            got = np.stack([np.asarray(a)[0] for a in t.where[k.name]],
                           axis=1)
            assert np.array_equal(got[:n], want), k
            assert (got[n:, 0] == pool.shape[1]).all(), k
            continue
        if k.replay:    # the ring's rows, not the ONE state's column
            pool = _part(cache, k.name)[next(iter(k.replay))]
        row, keeps = (np.asarray(a) for a in t.lands[k.name])
        assert row.shape == keeps.shape == (S, pool.shape[2])
        want = _by_hand(k, None, pool.shape[2])
        assert {(s, c): row[s, c] for s, c in zip(*np.nonzero(keeps))} \
            == want, k


def test_the_tick_holds_only_what_the_family_reads():
    model, cfg, kinds = _module("llama")
    blocks, tables = _sizes(kinds)
    cache = jax.eval_shape(lambda: model.init_cache(cfg, blocks, BS))
    args = (kinds, cache, jnp.asarray(tables), jnp.asarray(LENGTHS),
            jnp.asarray(N_NEW), C)
    t = paged.tick(*args, rows=ROWS, max_seq=cfg.max_seq, reads=("pos",))
    assert t.pos is not None and t.lands == {}
    assert all(getattr(t, f) is None for f in ALL if f != "pos")
    # a budget that holds the slab packs nothing: the rows are the slab
    t = paged.tick(*args)
    assert t.slab.rows is None and t.take(t.positions) is t.positions
    with pytest.raises(KeyError):
        paged.tick(*args, reads=("block",))


F32, BF16 = jnp.float32, jnp.bfloat16
#: name -> ({kind: {leaf: (shape behind [layers, blocks, BS] or [layers,
#: slots, columns], dtype under a bfloat16 cache[, its columns where they
#: are not the kind's: a ``replay`` kind keeps its state ONCE a slot])}},
#: {kind: layers}, whether the pools have a head axis to shard — "by head"
#: where it lies BEFORE the block's positions, the shape then written out
#: behind [layers, blocks]), from the modules' own ``init_cache`` and
#: ``cache_shardings`` before they shared one
POOLS = {
    "llama": ({None: {"k": ((2, 16), BF16), "v": ((2, 16), BF16)}},
              {None: 2}, True),
    "moe_llama": ({None: {"k": ((2, 16), BF16), "v": ((2, 16), BF16)}},
                  {None: 2}, True),
    "latent_moe": ({None: {"latent": ((128,), BF16)}}, {None: 3}, False),
    "swa_moe": ({"global": {"k": ((2, 16), BF16), "v": ((2, 16), BF16)},
                 "window": {"k": ((2, 16), BF16), "v": ((2, 16), BF16)}},
                {"global": 1, "window": 3}, True),
    "conv_moe": ({"attn": {"k": ((32,), BF16), "v": ((32,), BF16)},
                  "conv": {"u": ((64,), BF16)}},
                 {"attn": 1, "conv": 5}, False),
    "blockdiff_moe": ({None: {"k": ((32,), BF16), "v": ((32,), BF16)}},
                      {None: 3}, False),
    "sambay": ({"kv": {"k": ((32,), BF16), "v": ((32,), BF16)},
                "window": {"k": ((32,), BF16), "v": ((32,), BF16)},
                "conv": {"u": ((128,), BF16)},
                "carry": {"h": ((16, 128), F32)}},
               {"kv": 1, "window": 2, "conv": 3, "carry": 3}, False),
    "gdn_hybrid": ({"kv": {"k": ((4, BS, 16), BF16), "v": ((4, BS, 16), BF16)},
                    "conv": {"u": ((128,), BF16)},
                    "delta": {"S": ((4, 16, 8), F32, 1),
                              "at": ((1,), jnp.int32, 1),
                              "row": ((4 * (8 + 16 + 2),), F32)}},
                   {"kv": 2, "conv": 6, "delta": 6}, "by head"),
}
STATE = {"conv_moe": {"conv": 2}, "sambay": {"conv": 3, "carry": 1},
         "gdn_hybrid": {"conv": 3, "delta": 1}}


@pytest.mark.parametrize("name", MODULES)
def test_every_modules_pools_and_their_shardings(name):
    model, cfg, kinds = _module(name)
    leaves, layers, heads = POOLS[name]
    states = STATE.get(name, {})
    assert {k.name: k.layers for k in kinds} == layers
    assert {k.name: k.state for k in kinds if k.state is not None} == states
    blocks = {k: (S, 7) if k in states else 64 - 10 * i
              for i, k in enumerate(layers)}
    if None in blocks:
        blocks = blocks[None]
    cache = jax.eval_shape(lambda: model.init_cache(cfg, blocks, BS, BF16))
    for kind, want in leaves.items():
        pool, n = _part(cache, kind), _part(blocks, kind)
        lead = lambda cols=7: (layers[kind],) + (
            (S, cols) if kind in states
            else (n,) if heads == "by head" else (n, BS))
        assert {k: (v.shape, v.dtype) for k, v in pool.items()} == {
            k: (lead(*cols) + behind, dtype)
            for k, (behind, dtype, *cols) in want.items()}
    # without a dtype the pools are the config's (a position is an integer)
    own = jax.tree_util.tree_leaves(jax.eval_shape(
        lambda: model.init_cache(cfg, blocks, BS)))
    assert {x.dtype for x in own} - {jnp.dtype(jnp.int32)} == {
        jnp.dtype(cfg.dtype)}
    PS = jax.sharding.PartitionSpec
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:8]).reshape(4, 2),
                             ("data", "model"))
    got = model.cache_shardings(mesh, cfg, blocks)
    for kind in leaves:
        n, spec = _part(blocks, kind), _part(got, kind).spec
        if kind in states:      # the slots: 5 of them, which no axis divides
            assert spec == PS(None, None, None, None)
        elif heads == "by head":    # four heads, the pool's axis 2
            assert spec == PS(None, "data" if n % 4 == 0 else None, "model",
                              None, None)
        elif heads:             # two kv heads over the model axis
            assert spec == PS(None, "data" if n % 4 == 0 else None, None,
                              "model", None)
        else:
            assert spec == PS(None, "data" if n % 4 == 0 else "model", None,
                              None)


# ------------------------------------------------------- the cached pair
VOCAB, DIM = 11, 8


def _stand_in(params, tokens, cfg, cache, tables, lengths, n_new, head,
              scale=1.0):
    """A family's forward with no layers: the rows' embeddings are the last
    hidden states, and the cache comes back with one more tick counted."""
    t = paged.tick((paged.CacheKind(None, 1),), cache, tables, lengths,
                   n_new, tokens.shape[1], rows=cfg.max_tick_tokens)
    x = params["embed"][t.take(tokens)] * scale
    return head(t, lambda h: h @ params["head"], x), {"k": cache["k"] + 1}


@pytest.mark.parametrize("rows", [0, ROWS], ids=["slab", "packed"])
def test_the_cached_pair_in_both_forms(rows):
    """``greedy_cached``'s tokens are the float32 argmax of
    ``apply_cached``'s logits — at the columns ``read`` on those rows alone,
    or at every valid position —, a family's own ``sample`` stands in the
    argmax's place, what the family takes beside the contract goes through
    (``**kw``), and ``read`` is in the signature of the form that takes it
    (serve/engine.py ``samples_read``)."""
    from horovod_tpu.serve.engine import samples_read
    rng = np.random.default_rng(11)
    params = {"embed": jnp.asarray(rng.normal(size=(VOCAB, DIM)), F32),
              "head": jnp.asarray(rng.normal(size=(DIM, VOCAB)), F32)}
    tokens = jnp.asarray(rng.integers(0, VOCAB, (S, C)), jnp.int32)
    cache = {"k": jnp.zeros((1, POOL, BS, 1), F32)}
    tables = jnp.asarray(_sizes((paged.CacheKind(None, 1),))[1])
    args = (params, tokens, types.SimpleNamespace(max_tick_tokens=rows),
            cache, tables, jnp.asarray(LENGTHS), jnp.asarray(N_NEW))
    held = np.arange(C)[None] < N_NEW[:, None]

    apply_cached, greedy_cached = decoder.cached_pair(_stand_in)
    logits, after = apply_cached(*args)
    assert logits.shape == (S, C, VOCAB) and float(after["k"][0, 0, 0, 0]) == 1
    want = np.argmax(np.asarray(logits, np.float32), -1)
    tok, _ = greedy_cached(*args)
    assert tok.dtype == jnp.int32
    assert np.array_equal(np.asarray(tok)[held], want[held])
    assert not samples_read(types.SimpleNamespace(greedy_cached=greedy_cached))
    flipped, _ = apply_cached(*args, scale=-1.0)
    assert np.allclose(np.asarray(flipped), -np.asarray(logits))

    _, greedy_at = decoder.cached_pair(_stand_in, read=True)
    assert samples_read(types.SimpleNamespace(greedy_cached=greedy_at))
    read = np.clip((N_NEW - 1)[:, None] + np.arange(2)[None], 0, C - 1)
    tok, _ = greedy_at(*args, jnp.asarray(read, jnp.int32))
    assert tok.shape == (S, 2) and tok.dtype == jnp.int32
    live = np.take_along_axis(held, read, 1)
    assert np.array_equal(np.asarray(tok)[live],
                          np.take_along_axis(want, read, 1)[live])

    # a family's own rule in the greedy token's place: a pytree a position
    top2 = lambda z, cfg: (jnp.argsort(-z, axis=-1)[..., 1].astype(jnp.int32),
                           jnp.max(z, axis=-1))
    _, sampled = decoder.cached_pair(_stand_in, sample=top2)
    (second, best), _ = sampled(*args)
    assert second.shape == best.shape == (S, C)
    z = np.asarray(logits)
    assert np.array_equal(np.asarray(second)[held],
                          np.argsort(-z, -1)[..., 1][held])
    assert np.allclose(np.asarray(best)[held], z.max(-1)[held])
