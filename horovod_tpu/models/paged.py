"""How a block table addresses a paged pool — the one place that knows.

A pool is any pytree of arrays ``[n_layers, num_blocks, block_size, ...]``
(models/llama.py: ``{"k","v"}`` with ``[kv_heads, head_dim]`` behind;
models/latent_moe.py: ``{"latent"}`` with ``[kv_rank + rope]`` behind): one
PREALLOCATED buffer of fixed-size blocks a layer, shared by every in-flight
sequence.  A sequence owns whole blocks through its row of ``block_tables``
``[S, max_blocks]`` (-1 = unassigned): table slot j covers positions
``[j*bs, (j+1)*bs)``, so sequences of different lengths coexist in static
shapes.  The stacked pool is indexed by layer, never unstacked: a donated
cache stays one buffer through a tick (docs/serving.md).

A model whose layers keep state of several KINDS (models/swa_moe.py: layers
that read the whole context beside layers that read a window of it) declares
them (:class:`CacheKind`) and holds one pool a kind.  A kind with a window
is addressed as a RING: its table row has :func:`ring_blocks` entries and
position P lands in entry ``(P // bs) % entries``, so a slot holds the last
``entries * bs`` positions whatever its context's length
(docs/serving.md#cache-kinds).
"""

from __future__ import annotations

from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P


class CacheKind(NamedTuple):
    """One kind of cached state: ``layers`` layers of the model keep it, each
    the slot's whole context (``window`` None) or its last ``window``
    positions.  A module's ``cache_kinds(cfg)`` lists its kinds; its cache
    and the block tables it is handed are dicts by ``name``."""
    name: str
    layers: int
    window: Optional[int] = None


def ring_blocks(window: int, tick_cols: int, block_size: int,
                max_blocks: int) -> int:
    """Table entries of a slot's ring in a kind with ``window``: the window
    plus the widest tick's columns, rounded up to blocks, and never more
    than a whole context's.  A tick writes its ``n <= tick_cols`` positions
    ``L .. L+n-1`` BEFORE its queries read ``L-window+1 ..``: the write of
    ``L+n-1`` lands on position ``L+n-1-R``, which no query of the tick may
    still see, so ``R >= window + n - 1``.  The same margin covers a
    rejected speculative draft: its stale write at ``L+j`` (``j <
    tick_cols``) lands on ``L+j-R < L-window+1``, outside every later
    accepted query's window too."""
    return min(-(-(window + tick_cols) // block_size), max_blocks)


def slot_positions(lengths: jax.Array, n_new: jax.Array, C: int
                   ) -> Tuple[jax.Array, jax.Array]:
    """(positions, valid) [S, C]: slot s's column j is position ``lengths[s]
    + j`` and holds a token when ``j < n_new[s]`` (0 = an inactive slot)."""
    positions = lengths[:, None] + jnp.arange(C, dtype=lengths.dtype)[None]
    return positions, jnp.arange(C)[None, :] < n_new[:, None]


def pack(valid: jax.Array, budget: int) -> Tuple[Callable, Callable]:
    """(take, slab) for a tick whose plan holds at most ``budget`` tokens
    (the scheduler's ``max_batch_tokens``; 0 = no promise): what is a
    token's own — embedding, norms, projections, rotary, the FFN, the head,
    the pool's write — runs on ``R = min(S * C, budget)`` ROWS, the valid
    positions packed to the front in slab order (a stable sort), and only
    the attention's core sees slots.  ``take`` brings ``[S, C, ...]`` to
    ``[1, R, ...]``; ``slab`` brings rows back to their places in
    ``[S, C, ...]``, ZERO where a position was left out, so what comes back
    is defined at valid positions only.  A slab no larger than the budget
    is not packed: both are the identity and the rows are ``[S, C, ...]``."""
    S, C = valid.shape
    T = S * C
    R = min(T, budget or T)
    if R == T:
        return (lambda a: a), (lambda a: a)
    flat = lambda a: a.reshape((T,) + a.shape[2:])
    order = jnp.argsort(~flat(valid), stable=True)     # positions, by row
    row_of = jnp.argsort(order).reshape(S, C)           # rows, by position

    def slab(a):
        back = a[0][jnp.minimum(row_of, R - 1)]
        kept = (row_of < R).reshape((S, C) + (1,) * (a.ndim - 2))
        return jnp.where(kept, back, jnp.zeros((), a.dtype))
    return (lambda a: flat(a)[order[:R]][None]), slab


def write_index(block_tables: jax.Array, positions: jax.Array,
                valid: jax.Array, num_blocks: int, block_size: int,
                ring: bool = False) -> Tuple[jax.Array, jax.Array]:
    """(blk, off): position P of slot s lands in ``block_tables[s, P // bs]``
    at offset ``P % bs`` — in a ``ring``, in entry ``(P // bs) % entries``.
    Invalid (padding / inactive-slot) positions go to ``num_blocks``, off
    the block axis, where :func:`write` drops them — a dead slot's stale
    table row is never written."""
    entry = positions // block_size
    slot_idx = (entry % block_tables.shape[1] if ring else
                jnp.minimum(entry, block_tables.shape[1] - 1))
    blk = jnp.take_along_axis(block_tables, slot_idx, axis=1)
    blk = jnp.where(valid, jnp.maximum(blk, 0), num_blocks)
    return blk, positions % block_size


def write(pool: Any, layer: int, blk: jax.Array, off: jax.Array,
          values: Any) -> Any:
    """Scatter ``values`` (a pytree like ``pool``) into layer ``layer`` of
    the stacked pool at (blk, off), in place; out of range is dropped."""
    with jax.named_scope("kv_write"):
        return jax.tree_util.tree_map(
            lambda p, v: p.at[layer, blk, off].set(v, mode="drop"),
            pool, values)


def gather(pool: Any, layer: int, block_tables: jax.Array) -> Any:
    """Every slot's whole context of layer ``layer``, ``[S, max_blocks *
    block_size, ...]`` a leaf: index t IS position t.  Unassigned entries
    (-1 -> block 0) only cover positions :func:`context_mask` excludes."""
    S, max_blocks = block_tables.shape
    bt = jnp.maximum(block_tables, 0)
    with jax.named_scope("kv_gather"):
        return jax.tree_util.tree_map(
            lambda p: p[layer, bt].reshape(
                (S, max_blocks * p.shape[2]) + p.shape[3:]), pool)


def context_mask(positions: jax.Array, ctx: int) -> jax.Array:
    """[S, 1, C, ctx] bool: the query at ``positions[s, c]`` sees gathered
    keys ``0 .. positions[s, c]`` (its own, written first, included)."""
    return (jnp.arange(ctx)[None, None, :] <= positions[:, :, None])[:, None]


def ring_positions(top: jax.Array, ring: int) -> jax.Array:
    """[S, ring] int32: the position that index j of slot s's gathered ring
    holds once the tick's writes are in, ``top[s]`` being the slot's last
    written position (``lengths + n_new - 1``): the largest ``p <= top``
    with ``p % ring == j``.  Negative = never written."""
    j = jnp.arange(ring, dtype=top.dtype)[None, :]
    return top[:, None] - (top[:, None] - j) % ring


def window_mask(positions: jax.Array, key_pos: jax.Array,
                window: int) -> jax.Array:
    """[S, 1, C, K] bool: the query at ``positions[s, c]`` sees the gathered
    key at position ``key_pos[s, k]`` when ``0 <= q - k < window`` (its own
    included: ``window`` keys) and the key was written (``k >= 0``)."""
    d = positions[:, :, None] - key_pos[:, None, :]
    return ((d >= 0) & (d < window) & (key_pos[:, None, :] >= 0))[:, None]


def slots_per_block(S: int, per_slot_bytes: int, budget: int) -> int:
    """The largest divisor of S whose scores fit the budget (at least 1)."""
    want = max(1, budget // max(per_slot_bytes, 1))
    return max(b for b in range(1, S + 1) if S % b == 0 and b <= want)


def attend_by_blocks(attend: Callable, args: Tuple[jax.Array, ...],
                     n_new: jax.Array, slots: int, narrow: int) -> jax.Array:
    """``attend(q, positions, *context)`` -> ``[S, C, ...]`` at the width each
    block of ``slots`` slots needs; every array of ``args`` is led by the
    slot axis, the first two by ``[S, C]``.  A tick wider than ``narrow``
    columns attends every slot's first ``narrow`` columns at once, then a
    block after another: one that holds a slot with more than ``narrow``
    tokens attends again with all C columns, the others are done — the
    columns past a slot's ``n_new`` are padding that nothing reads, and
    come back zero.  Any plan is served: every block may hold a chunk.
    ``attend`` may gather its slots' context from the pool itself (llama.py
    does): the loop then holds the pool, which XLA neither copies nor
    restacks for it (tests/test_serve.py; PERF.md §6, PR 30)."""
    S, C = args[0].shape[:2]
    if C <= narrow:
        return attend(*args)
    q, pos, *context = args
    few = attend(q[:, :narrow], pos[:, :narrow], *context)
    o = jnp.pad(few, ((0, 0), (0, C - narrow)) + ((0, 0),) * (few.ndim - 2))

    def block(i, o):
        cut = lambda a: lax.dynamic_slice_in_dim(a, i * slots, slots)
        return lax.cond(
            jnp.max(cut(n_new)) > narrow,
            lambda o, *own: lax.dynamic_update_slice_in_dim(
                o, attend(*own), i * slots, 0),
            lambda o, *own: o, o, *map(cut, args))
    return lax.fori_loop(0, S // slots, block, o)


def wide_blocks(n_new: np.ndarray, slots: int, narrow: int) -> Tuple[int, int]:
    """:func:`attend_by_blocks`'s choice read off a plan on the host: (blocks
    that attend at the tick's full width, blocks) for ``n_new`` tokens a
    slot."""
    top = np.asarray(n_new).reshape(-1, slots).max(axis=1)
    return int((top > narrow).sum()), int(top.size)


def copy_blocks(cache: Any, src: jax.Array, dst: jax.Array) -> Any:
    """Copy-on-write for the serving prefix cache (serve/engine.py
    PrefixCache): clone whole blocks ``src[i] -> dst[i]`` in every layer of
    every leaf, BEFORE the tick's writes.  Padding pairs route ``dst`` out
    of range and are dropped, their ``src`` clamped.  A source recycled as a
    destination in the same call still copies its old content."""
    def cp(pool):
        # a layer at a time, as the tick's own writes index the pool: one
        # scatter across the layers relaid the [5,5120,16,576] pool whole
        # every tick (PR 27); on [24,2560,16,8,128] the two forms differ by
        # 0.06 ms of a 13.3 ms tick and nothing end to end (PERF.md §6, PR 29)
        safe = jnp.clip(src, 0, pool.shape[1] - 1)
        for i in range(pool.shape[0]):
            pool = pool.at[i, dst].set(pool[i, safe], mode="drop")
        return pool
    return jax.tree_util.tree_map(cp, cache)


def shardings(mesh, num_blocks: int, head_axis_size: Optional[int] = None):
    """NamedSharding for a pool ``[L, blocks, bs, ...]`` along the training
    mesh's own axes: a head axis (``[.., heads, head_dim]`` behind, of
    ``head_axis_size``) over a model/tp axis that divides it, blocks over
    the first remaining axis that divides them.  A pool without a head axis
    (the latent is every head's) shards its blocks alone."""
    head_axis = None
    if head_axis_size is not None:
        head_axis = next(
            (a for a in mesh.axis_names
             if str(a).split(".")[-1] in ("model", "tp")
             and head_axis_size % mesh.shape[a] == 0), None)
    block_axis = next(
        (a for a in mesh.axis_names
         if a != head_axis and num_blocks % mesh.shape[a] == 0), None)
    if head_axis_size is None:
        return NamedSharding(mesh, P(None, block_axis, None, None))
    return NamedSharding(mesh, P(None, block_axis, None, head_axis, None))


def _leaf_key(path) -> str:
    return "/".join(str(getattr(p, "key", p)) for p in path)


def read_block(cache: Any, block: int) -> Dict[str, np.ndarray]:
    """One block across all layers as host numpy, keyed by leaf path (spill
    and the prefill hand-off's export): ``[L, bs, ...]`` a leaf."""
    leaves, _ = jax.tree_util.tree_flatten_with_path(cache)
    return {_leaf_key(path): np.asarray(leaf[:, block])
            for path, leaf in leaves}


def write_block(cache: Any, block: int, payload: Dict[str, Any]) -> Any:
    """The cache with :func:`read_block`'s payload written into ``block``
    (spill reload / hand-off import)."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(cache)
    return jax.tree_util.tree_unflatten(treedef, [
        leaf.at[:, block].set(
            np.asarray(payload[_leaf_key(path)]).astype(leaf.dtype))
        for path, leaf in leaves])
