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

A paged kind may instead keep its keys and values BY HEAD inside a block
(``CacheKind.by_head``; models/gdn_hybrid.py): the pool is ``[n_layers,
num_blocks, heads, block_size, head_dim]``, a block the same bytes in
another order.  Its read hands the model a tile as the gather leaves it,
``[slots, entries, heads, block_size, head_dim]`` (:func:`gather`), to be
scored with the entries as an axis of the products (models/layers.py
``attention_tile_by_head``): no pass rewrites a tile of positions ``[..,
heads x head_dim]`` into heads before its scores.  Its write goes BY BLOCK
(:func:`block_lands`, :func:`write_blocks`): the blocks a tick touches are
gathered, its rows laid in, the blocks scattered back whole — a scatter by
row at ``[blk, :, off]``, whose window spans the head axis round a scattered
position, makes the compiler relay the whole pool into and out of every
tick (docs/serving.md#where-the-pool-lies).

A model whose layers keep state of several KINDS declares them
(:class:`CacheKind`) and holds one pool a kind.  There are three, the
third in three forms (docs/serving.md#cache-kinds):

  * the WHOLE CONTEXT, as above: a block table over the paged pool;
  * a RING of a window (models/swa_moe.py: layers that read a window of the
    context beside layers that read all of it): the table row has
    :func:`ring_blocks` entries and position P lands in entry ``(P // bs) %
    entries``, so a slot holds the last ``entries * bs`` positions whatever
    its context's length;
  * a FIXED STATE a slot (models/conv_moe.py: a short convolution's last
    inputs): no blocks, no table, no allocator.  The pool is ``[layers,
    slots, columns, ...]``, position P of slot s lies at ``[s, P %
    columns]``, and a tick reads and writes it a SLOT, not a row: a row's
    predecessors are the tick's own rows above it, and only a slot's first
    rows need the pool, which gives the ``state`` columns of the slot's
    last positions ONCE a layer (:func:`state_head`; :func:`state_read`
    lays them before the slot's rows); of a slot's rows its LAST
    ``columns`` land, gathered from the tick's rows and laid over the
    layer's columns (:func:`state_lands`, :func:`write_slots`);
    :func:`state_columns` says how many columns keep those through a
    rejected draft.  Where what a layer keeps FOLDS all earlier positions
    (models/sambay.py: a selective scan's carry) a column is no input of a
    position but the state AFTER it: a tick reads the ONE column at its
    slot's last position (:func:`carry_read`, ``state`` = 1) and writes one
    after each of its last rows at the same ``[s, P % columns]``
    (:func:`carry_index`: the scan hands them out a row at a time), so
    that a verify row of which ``a`` drafts are accepted leaves the carry
    after row ``a`` where the next tick looks for it.  What lies behind the
    columns is the model's: ``[.., d]``, or a carry's ``[.., d_state, d]``.
    Where that folded state is too large for a column a row
    (models/gdn_hybrid.py: a delta rule's MATRIX a head) the kind declares
    what a row feeds the recurrence (``replay``) and keeps ONE committed
    state a slot, the position it stands after (``at``) and a ring of the
    last verify row's inputs: a tick reads the state (:func:`committed`),
    REPLAYS the ring's rows from ``at`` up to its slot's length
    (:func:`replay_read`), runs its own, commits the state after its last
    row that no later tick can take back (:func:`commit_row`) and lays the
    rows behind it over the ring, a slot at a time as well
    (:func:`state_lands` with ``replay``).

What a module declares of its kinds is enough for what every module needs of
them: :func:`tick` does one tick's slot arithmetic for all of them, once
before the layers (:class:`Tick`), and :func:`init_pools` and
:func:`pool_shardings` make each kind's pool and its sharding from its
``leaves`` (models/decoder.py has the frame round the layers).
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P


class CacheKind(NamedTuple):
    """One kind of cached state: ``layers`` layers of the model keep it, each
    the slot's whole context (``window`` and ``state`` None), its last
    ``window`` positions in a ring of blocks, or a fixed state a slot of
    which a tick reads back the ``state`` columns before its own (the
    module docstring sets the three side by side).  A module's
    ``cache_kinds(cfg)`` lists its kinds; its cache is a dict by ``name``,
    and so are the block tables it is handed (a state kind has none).  A
    module with ONE pool and no ``cache_kinds`` is the case of one kind
    without a name (``name`` None): its cache is the pool itself and its
    table the array (models/llama.py).

    ``leaves`` says what the kind's pool holds, name -> the shape BEHIND
    ``[layers, blocks, block_size]`` (a state kind: behind ``[layers, slots,
    columns]``), in ``dtype`` where that is not the cache's own
    (:func:`init_pools`).  A paged pool whose leaves are ``[heads,
    head_dim]`` behind has a head axis to shard (:func:`pool_shardings`).

    ``by_head`` (a paged or ring kind whose leaves are all ``[heads,
    head_dim]``) puts the heads BEFORE a block's positions: behind
    ``[layers, blocks]`` a leaf is ``[heads, block_size, head_dim]``, and
    what :func:`gather` returns of it is scored as it lies.  Such a pool is
    written by :func:`write_blocks` alone, whole blocks at ``[layer, blk]``
    (:func:`tick` hands ``where`` by block for it, :func:`block_lands`): a
    scatter by row would address ``[blk, :, off]``, a window across the
    heads round a scattered position, which the TPU's compiler serves by
    relaying the WHOLE pool into another order and back in every tick (PR
    48: two copies of 1.5 GB a leaf), and rows of one head scattered into
    the flat view ``[blocks x heads x block_size, head_dim]`` cost thirty
    scattered rows for one (PR 50: 9.7 ms against 0.7 in a chunk-wide tick
    of models/gdn_hybrid.py's four layers).  Every reader of a block's
    length asks :func:`block_size`; a ring by head needs ``window >=
    block_size``, so that no tick enters one block twice.

    ``replay`` (a state kind of ``state`` 1 alone) names what ONE ROW feeds
    the recurrence that made the state, name -> shape: the pool then holds
    ``leaves`` ONCE a slot (one column), the position that state stands
    after (``at``, int32) and ``replay``'s leaves for the :func:`replay_rows`
    rows of a verify row that a later tick may take back, and the kind's
    ``num_blocks`` is ``(slots, those rows)``."""
    name: Optional[str]
    layers: int
    window: Optional[int] = None
    state: Optional[int] = None
    leaves: Optional[Dict[str, Tuple[int, ...]]] = None
    dtype: Any = None
    replay: Optional[Dict[str, Tuple[int, ...]]] = None
    by_head: bool = False


def state_columns(state: int, tick_cols: int) -> int:
    """Columns of a slot's ring in a kind with a fixed ``state``: what a tick
    reads back plus the widest row a tick may have to take back
    (``tick_cols``, a verify row's ``1 + spec_k``).  A verify row writes
    ``L .. L+n-1`` (``n <= tick_cols``); with ``a < n`` of them accepted the
    next tick reads ``L+a-state+1 .. L+a``, at the least ``L-state+1``: the
    positions ``L-state+1 .. L+n-1`` must lie in different columns, ``state
    + n - 1`` of them, and one more column than that is kept.  A rejected
    draft's column is stale data at a position that the next accepted token
    overwrites before anything reads it (:func:`ring_blocks` makes the same
    argument for a window's keys)."""
    return state + tick_cols


def state_lands(lengths: jax.Array, n_new: jax.Array, start: jax.Array,
                cols: int, rows: int, replay: bool = False
                ) -> Tuple[jax.Array, jax.Array]:
    """(row, keeps) [S, cols] for :func:`write_slots` into a state kind's
    pool ``[layers, S, cols, ...]``, BY SLOT: column c of slot s takes the
    tick's row ``row[s, c]`` — a slot's rows lie side by side in slab order
    from ``start[s]`` on (:func:`pack`) — where ``keeps[s, c]``, and keeps
    what it held elsewhere.  Position P of slot s lands in ``[s, P %
    cols]``, so the column holds the LAST of the slot's positions that
    falls into it: of a chunk longer than the ring only the last ``cols``
    positions land, and a slot that runs no row keeps all it held.  With
    ``replay`` (the ring ``[layers, S, rows, ...]`` of a ``replay`` kind)
    every column BEHIND the first of a row that commits after its first
    (:func:`commit_row`) lands, and a longer row leaves the ring alone.  A
    position that the tick's ``rows`` rows do not hold does not land."""
    at = ring_positions(lengths + n_new - 1, cols)
    own = at - lengths[:, None]         # the position's column in the tick
    keeps = own >= int(replay)
    if replay:
        keeps &= (n_new <= cols + 1)[:, None]
    row = start[:, None] + own
    return jnp.clip(row, 0, rows - 1), keeps & (row < rows)


def state_head(pool: jax.Array, layer: int, lengths: jax.Array, state: int
               ) -> jax.Array:
    """What each slot held at its last ``state`` positions, ``[S, state,
    d]``: index i is position ``lengths - state + i`` of ``pool[layer]``
    ``[S, cols, d]`` AS IT WAS BEFORE THE TICK — read before
    :func:`write_slots` —, and zero below position 0: a slot's new tenant
    reads nothing of the stream that left it.  The one read of the pool a
    layer makes: ``S * state`` rows whatever the tick's width."""
    at = lengths[:, None] - state + jnp.arange(state)[None, :]
    kept = jnp.take_along_axis(
        pool[layer], (at % pool.shape[2])[..., None], axis=1)
    return jnp.where((at >= 0)[..., None], kept, jnp.zeros((), pool.dtype))


def state_read(pool: jax.Array, layer: int, own: jax.Array, t: "Tick",
               state: int) -> List[jax.Array]:
    """What lay ``back`` = 1 .. ``state`` positions before each of a tick's
    rows (index ``back - 1``, each shaped like ``own``), ``own`` ``[.., d]``
    being the rows' own values in slab order (:func:`pack` keeps a slot's
    tokens side by side): a tick's own rows are their own predecessors —
    the row ``back`` rows up — but for the first ``back`` rows of each live
    slot, before which :func:`state_head`'s rows are laid (never a
    neighbour slot's row, and nothing over a row that is not the slot's
    own).  The ``back``s share the one read of the pool."""
    rows = own.reshape(-1, own.shape[-1])
    N = rows.shape[0]
    head = state_head(pool, layer, t.lengths, state).astype(own.dtype)
    col = jnp.arange(state)[None, :]
    at = jnp.where(col < t.n_new[:, None], t.start[:, None] + col, N)
    return [jnp.pad(rows, ((back, 0), (0, 0)))[:N].at[at[:, :back]].set(
        head[:, state - back:], mode="drop").reshape(own.shape)
        for back in range(1, state + 1)]


def carry_index(t: "Tick", cols: int) -> Tuple[jax.Array, jax.Array]:
    """(slot, col) BY ROW, shaped like the tick's rows (``t.slot``), for
    :func:`write` of the carries a scan leaves after EACH row into
    ``[layers, S, cols, ...]``: the carry after position P of slot s lands
    in ``[s, P % cols]``, the last ``cols`` of a long chunk only; what does
    not land, and every row that holds no token, goes to slot ``S``, off
    the axis, where :func:`write` drops it.  Needs ``t.slot`` and
    ``t.row``."""
    slot, pos, length = t.row
    held = length + t.n_new[slot]
    lands = (pos < held) & (pos >= held - cols)
    shaped = lambda a: a.reshape(t.slot.shape)
    return (shaped(jnp.where(lands, slot, t.lengths.shape[0])),
            shaped(pos % cols))


def carry_read(pool: jax.Array, layer: int, lengths: jax.Array) -> jax.Array:
    """The state that each slot's LAST position left, ``[S, ...]``: the
    column of position ``lengths - 1`` of ``pool[layer]`` ``[S, cols, ...]``
    AS IT WAS BEFORE THE TICK, and zero for a slot that holds nothing (a
    slot's new tenant starts from nothing; so does a slot that runs no row,
    whose length the tick is handed as 0).  The counterpart of
    :func:`state_read` for a state that folds its past: the tick's own rows
    are scanned from it, and :func:`write` at :func:`carry_index` keeps the
    state after each of the last ``cols`` of them."""
    S = lengths.shape[0]
    kept = pool[layer, jnp.arange(S), (lengths - 1) % pool.shape[2]]
    held = (lengths > 0).reshape((S,) + (1,) * (kept.ndim - 1))
    return jnp.where(held, kept, jnp.zeros((), pool.dtype))


#: the leaf of a ``replay`` kind's pool that holds the position the committed
#: state stands after, ``[layers, slots, 1, 1]`` int32
AT = "at"


def replay_rows(tick_cols: int) -> int:
    """Rows of a slot's ring in a kind with ``replay``: the widest row a
    tick may have to take back (``tick_cols``, a verify row's ``1 +
    spec_k``) less its first.  A verify row runs ``L .. L+n-1`` (``n <=
    tick_cols``) and the tick after starts at ``L+1+a`` with ``a < n`` of its
    drafts accepted: row ``L`` — the token the tick before emitted — stands
    whatever is accepted, so the state AFTER it is committed
    (:func:`commit_row`), and rows ``L+1 .. L+n-1`` are the ``n - 1 <=
    tick_cols - 1`` that may be taken back: their inputs go to the ring at
    ``position % rows`` (:func:`state_lands`), no two of them in one entry,
    and the next tick replays ``L+1 .. L+a`` of them (:func:`replay_read`)
    before anything overwrites them — its own write comes after its read.
    At least one row, so that no pool is empty without speculation."""
    return max(tick_cols - 1, 1)


def commit_row(n_new: jax.Array, rows: int) -> jax.Array:
    """[S] the column of its own rows after which a slot's state is
    committed in a kind with ``replay``: a row that fits the ring behind its
    first column (``n_new <= rows + 1``: a verify row, a decode row, a short
    tail of a prompt) commits after column 0 and leaves the rest to the
    ring; a longer one is a prompt's chunk, which nothing takes back, and
    commits after its last column, the ring left alone (:func:`replay_rows`
    has the argument).  The tick cannot tell a verify row from a prompt's
    tail of the same width and need not: a tail's rows are replayed all."""
    return jnp.where(n_new <= rows + 1, 0, n_new - 1)


def committed(pool: Any, layer: int, lengths: jax.Array, leaf: str
              ) -> Tuple[jax.Array, jax.Array]:
    """(each slot's committed ``leaf`` ``[S, ...]``, the position ``at`` [S]
    it stands after) of a ``replay`` kind's ``pool[layer]`` AS IT WAS BEFORE
    THE TICK: zero and 0 for a slot that holds nothing (a new tenant starts
    from nothing, as :func:`carry_read` has it).  ``lengths - at`` rows of
    the ring are the tick's to replay."""
    held = lengths > 0
    kept = pool[leaf][layer, :, 0]
    return (jnp.where(held.reshape((-1,) + (1,) * (kept.ndim - 1)), kept,
                      jnp.zeros((), kept.dtype)),
            jnp.where(held, pool[AT][layer, :, 0, 0], 0))


def replay_read(ring: jax.Array, layer: int, at: jax.Array) -> jax.Array:
    """A slot's ring in the order it is replayed, ``[S, rows, ...]``: index
    ``e`` is what position ``at + e`` left in ``ring[layer]`` ``[S, rows,
    ...]`` (:func:`state_lands`); the first ``lengths - at`` of them were
    accepted."""
    S, rows = ring.shape[1:3]
    entry = (at[:, None] + jnp.arange(rows)[None, :]) % rows
    return ring[layer, jnp.arange(S)[:, None], entry]


def commit(pool: Any, layer: int, lengths: jax.Array, n_new: jax.Array,
           rows: int, **state: jax.Array) -> Any:
    """``pool`` with the ``state`` (leaf = ``[S, ...]``) that each slot's
    tick left after its :func:`commit_row` written into ``pool[layer]``, and
    with it the position it stands after; a slot that ran no row keeps what
    it held."""
    S = lengths.shape[0]
    slot = jnp.where(n_new > 0, jnp.arange(S), S)
    at = lengths + commit_row(n_new, rows) + 1
    out = dict(pool, **{AT: pool[AT].at[layer, slot, 0, 0].set(
        at.astype(pool[AT].dtype), mode="drop")})
    for name, value in state.items():
        out[name] = pool[name].at[layer, slot, 0].set(
            value.astype(pool[name].dtype), mode="drop")
    return out


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


def block_of(a: jax.Array, i, per: int, cols: int) -> jax.Array:
    """Slots ``i * per .. (i + 1) * per - 1`` of ``a`` [S, C, ...] and their
    first ``cols`` columns (``i`` may be a device value)."""
    return lax.dynamic_slice(a, (i * per, 0) + (0,) * (a.ndim - 2),
                             (per, cols) + a.shape[2:])


class Slab(NamedTuple):
    """:func:`pack`'s ``slab``: ``slab(a)`` brings rows ``[1, R, ...]`` back
    to their places in ``[S, C, ...]``, ZERO where a position was left out;
    ``slab(a, i, per, cols)`` only to :func:`block_of` that slab,
    ``slab.at(a, read)`` only the columns ``read`` of each slot.  ``rows``
    [S, C] is the row of each position (None where nothing is packed: the
    rows ARE the slab), ``kept`` the number of rows."""
    rows: Optional[jax.Array]
    kept: Optional[int]

    def __call__(self, a: jax.Array, *block) -> jax.Array:
        if self.rows is None:
            return block_of(a, *block) if block else a
        rows = block_of(self.rows, *block) if block else self.rows
        back = a[0][jnp.minimum(rows, self.kept - 1)]
        kept = (rows < self.kept).reshape(rows.shape + (1,) * (a.ndim - 2))
        return jnp.where(kept, back, jnp.zeros((), a.dtype))

    def at(self, a: jax.Array, read: jax.Array) -> jax.Array:
        """The rows of ``a`` ``[1, R, ...]`` that hold each slot's columns
        ``read`` [S, W] (inside ``0 .. C-1``), as ``[S, W, ...]``: ``take``
        undone for those positions alone, so that what only they need — a
        tick's output head (``greedy_cached(.., read)``) — runs on ``S * W``
        rows.  A position that was left out comes back as the last row's:
        defined where the position was packed, like ``slab(a)``."""
        if self.rows is None:
            return a[jnp.arange(a.shape[0])[:, None], read]
        rows = jnp.take_along_axis(self.rows, read, axis=1)
        return a[0][jnp.minimum(rows, self.kept - 1)]


def pack(valid: jax.Array, budget: int) -> Tuple[Callable, Slab]:
    """(take, slab) for a tick whose plan holds at most ``budget`` tokens
    (the scheduler's ``max_batch_tokens``; 0 = no promise): what is a
    token's own — embedding, norms, projections, rotary, the FFN, the head,
    the pool's write — runs on ``R = min(S * C, budget)`` ROWS, the valid
    positions packed to the front in slab order (a stable sort), and only
    the attention's core sees slots.  ``take`` brings ``[S, C, ...]`` to
    ``[1, R, ...]``; ``slab`` (:class:`Slab`) brings rows back to their
    places, whole or a block of slots' columns at a time, so that a loop
    over blocks never makes the whole of a wide one; what comes back is
    defined at valid positions only.  A slab no larger than the budget is
    not packed: both are the identity and the rows are ``[S, C, ...]``."""
    S, C = valid.shape
    T = S * C
    R = min(T, budget or T)
    if R == T:
        return (lambda a: a), Slab(None, None)
    flat = lambda a: a.reshape((T,) + a.shape[2:])
    order = jnp.argsort(~flat(valid), stable=True)     # positions, by row
    row_of = jnp.argsort(order).reshape(S, C)           # rows, by position
    return (lambda a: flat(a)[order[:R]][None]), Slab(row_of, R)


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


def block_size(pool: Any, by_head: bool = False) -> int:
    """Positions a block of a paged or ring kind's ``pool`` holds: the axis
    behind the blocks, or behind the heads where the kind declares that it
    lies ``by_head`` (:class:`CacheKind`) — the shape alone cannot say."""
    return jax.tree_util.tree_leaves(pool)[0].shape[3 if by_head else 2]


def touched_blocks(S: int, C: int, rows: int, block_size: int) -> int:
    """The most blocks that a ``[S, C]`` tick of ``rows`` rows writes into:
    a slot's ``n`` positions in a row begin anywhere in a block and touch
    at most ``n`` of them, ``1 + ceil((n - 1) / bs)``, which is ``2 + (n -
    2) // bs`` from two rows on — so the plan that touches most gives every
    slot two rows and a block more for every ``bs`` after."""
    return min(rows, S * (1 + -(-(C - 1) // block_size)),
               2 * S + (rows - 2 * S) // block_size)


def block_lands(blk: jax.Array, off: jax.Array, num_blocks: int,
                block_size: int, most: int
                ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """(blk [U], row [U, bs], keeps [U, bs]) for :func:`write_blocks` into
    a pool by head, BY BLOCK, of :func:`write_index`'s (blk, off) by row in
    slab order (:func:`pack` keeps a slot's positions side by side, so the
    rows of one block follow one another): the ``U = most`` blocks at most
    that the tick touches (:func:`touched_blocks`; ``num_blocks``, off the
    axis, past the last that it does), and for offset o of each the row
    ``row[u, o]`` that lands there where ``keeps[u, o]``; elsewhere the
    block keeps what it held."""
    blk, off = blk.reshape(-1), off.reshape(-1)
    N = blk.shape[0]
    before = jnp.pad(blk, (1, 0), constant_values=num_blocks)[:N]
    first, = jnp.nonzero((blk < num_blocks) & (blk != before), size=most,
                         fill_value=N)          # a block's first row
    blk = jnp.pad(blk, (0, 1), constant_values=num_blocks)
    ublk = blk[first]
    row = (first - jnp.pad(off, (0, 1))[first])[:, None] + jnp.arange(
        block_size)[None, :]
    keeps = ((row >= first[:, None]) & (ublk < num_blocks)[:, None]
             & (blk[jnp.clip(row, 0, N)] == ublk[:, None]))
    return ublk, jnp.clip(row, 0, N - 1), keeps


def _of(tree: Any, kind: CacheKind) -> Any:
    """``kind``'s part of a cache, of block tables or of block counts: the
    entry by its name, or all of it for the one kind without a name."""
    return tree if kind.name is None else tree[kind.name]


class Tick(NamedTuple):
    """What the layers of one tick share (:func:`tick`).  The last six are
    None unless the family reads them."""
    positions: jax.Array    # [S, C] (slot_positions)
    lengths: jax.Array      # [S] positions a slot held before the tick
    n_new: jax.Array        # [S]
    take: Callable          # [S, C, ...] -> rows [1, R, ...] (pack)
    slab: Slab              # rows -> [S, C, ...] (or a block of it, or the
                            # columns a tick reads), zero where left out
    start: jax.Array        # [S] where a slot's rows begin among the rows
    # by the name of a kind: where the rows land in a paged or a ring kind's
    # pool, (blk, off) by row for write (write_index) or, where the kind
    # lies by head, (blk, row, keeps) by block for write_blocks
    # (block_lands), and which of the rows land in a state kind's, (row,
    # keeps) by slot for write_slots (state_lands)
    where: Dict[Optional[str], Tuple[jax.Array, jax.Array]]
    lands: Dict[Optional[str], Tuple[jax.Array, jax.Array]]
    valid: Optional[jax.Array] = None   # the rows that hold a token
    pos: Optional[jax.Array] = None     # the rows' positions, inside the
                                        # rope table
    top: Optional[jax.Array] = None     # [S] a slot's last written position
    # by row: whose slot a row is, (slot, position, the slot's length) flat
    # [N] (carry_index and a family's own arithmetic ask them), and whether
    # a row is its slot's first
    slot: Optional[jax.Array] = None
    row: Optional[Tuple[jax.Array, jax.Array, jax.Array]] = None
    first: Optional[jax.Array] = None


def tick(kinds: Tuple[CacheKind, ...], cache: Any, tables: Any,
         lengths: jax.Array, n_new: jax.Array, C: int, rows: int = 0,
         max_seq: Optional[int] = None, reads: Tuple[str, ...] = ()) -> Tick:
    """One ``[S, C]`` tick's slot arithmetic, done once for all layers and
    for every kind of cache the module declares: where each position lies
    (:func:`slot_positions`), the ``rows`` the tick's tokens are packed to
    (:func:`pack`; the config's ``max_tick_tokens``), and where the rows
    land in each of ``kinds`` — a ring where ``kind.window``; where
    ``kind.state``, which rows land in a slot's columns, or in the ring's
    entries where ``kind.replay`` (a kind whose writer goes by row, a
    carry's snapshots through :func:`carry_index`, leaves its entry unread,
    and nothing of it is lowered).  ``cache`` and ``tables`` are the
    module's own, dicts by kind or the one pool and its table.

    ``reads`` names what else the family's mixers read of :class:`Tick`,
    and is worked out here too, in that order, before any layer: a field
    first made inside a layer's loop or branch would be a value of that
    trace alone.  ``pos`` needs ``max_seq``, the rope table's length."""
    positions, valid = slot_positions(lengths, n_new, C)
    take, slab = pack(valid, rows)
    S = lengths.shape[0]
    start = (slab.rows[:, 0] if slab.rows is not None
             else jnp.arange(S, dtype=jnp.int32) * C)
    where, lands = {}, {}
    for kind in kinds:
        pool = jax.tree_util.tree_leaves(_of(cache, kind))[0]
        if kind.state is None:
            n, bs = pool.shape[1], block_size(pool, kind.by_head)
            blk, off = write_index(_of(tables, kind), positions, valid,
                                   n, bs, ring=kind.window is not None)
            by_row = (take(blk), take(off))
            where[kind.name] = by_row if not kind.by_head else block_lands(
                *by_row, n, bs, touched_blocks(S, C, slab.kept or S * C, bs))
        else:
            if kind.replay:     # the ring's rows, not the ONE state's column
                pool = _of(cache, kind)[next(iter(kind.replay))]
            lands[kind.name] = state_lands(
                lengths, n_new, start, pool.shape[2], slab.kept or S * C,
                replay=bool(kind.replay))
    wide = lambda a: take(jnp.broadcast_to(a[:, None], positions.shape))
    slots = lambda: wide(jnp.arange(S, dtype=jnp.int32))
    read: Dict[str, Any] = {}
    how = {
        "valid": lambda: take(valid),
        "pos": lambda: take(jnp.minimum(positions, max_seq - 1)),
        "top": lambda: lengths + n_new - 1,
        "slot": slots,
        "row": lambda: ((read["slot"] if "slot" in read else slots()
                         ).reshape(-1), take(positions).reshape(-1),
                        wide(lengths).reshape(-1)),
        "first": lambda: take(positions) == wide(lengths),
    }
    for name in reads:
        read[name] = how[name]()
    return Tick(positions, lengths, n_new, take, slab, start, where, lands,
                **read)


def write(pool: Any, layer: int, blk: jax.Array, off: jax.Array,
          values: Any) -> Any:
    """Scatter ``values`` (a pytree like ``pool``) into layer ``layer`` of
    the stacked pool at (blk, off), in place; out of range is dropped."""
    with jax.named_scope("kv_write"):
        return jax.tree_util.tree_map(
            lambda p, v: p.at[layer, blk, off].set(v, mode="drop"),
            pool, values)


def write_blocks(pool: Any, layer: int, blk: jax.Array, row: jax.Array,
                 keeps: jax.Array, values: Any) -> Any:
    """``values`` (a pytree like ``pool``; the tick's rows ``[1, R, ...]``
    or ``[S, C, ...]``, a row's heads side by side or apart) into layer
    ``layer`` of a stacked pool BY HEAD ``[layers, blocks, heads,
    block_size, head_dim]``, BY BLOCK (:func:`block_lands`): the blocks the
    tick touches are gathered, the rows that land are laid over their
    offsets, and the blocks go back whole, in place; out of range is
    dropped.  The one write such a pool gets (:class:`CacheKind`)."""
    def lay(p, v):
        H, _, hd = p.shape[2:]
        got = jnp.swapaxes(v.reshape((-1, H, hd))[row], 1, 2)
        held = p[layer, jnp.minimum(blk, p.shape[1] - 1)]
        return p.at[layer, blk].set(
            jnp.where(keeps[:, None, :, None], got.astype(p.dtype), held),
            mode="drop")
    with jax.named_scope("kv_write"):
        return jax.tree_util.tree_map(lay, pool, values)


def write_slots(pool: Any, layer: int, row: jax.Array, keeps: jax.Array,
                values: Any) -> Any:
    """``values`` (a pytree like ``pool``; the tick's rows ``[1, R, ...]``
    or ``[S, C, ...]``) into layer ``layer`` of a state kind's stacked pool,
    BY SLOT (:func:`state_lands`): the ``S * cols`` rows that may land are
    gathered from the tick's rows and laid over the layer's ``[S, cols,
    ...]`` where ``keeps``; every other column, and a slot that runs no
    row, keeps what it held."""
    def lay(p, v):
        got = v.reshape((-1,) + v.shape[2:])[row].astype(p.dtype)
        keep = keeps.reshape(keeps.shape + (1,) * (got.ndim - 2))
        return p.at[layer].set(jnp.where(keep, got, p[layer]))
    with jax.named_scope("kv_write"):
        return jax.tree_util.tree_map(lay, pool, values)


def gather(pool: Any, layer: int, block_tables: jax.Array,
           by_head: bool = False) -> Any:
    """Every slot's whole context of layer ``layer``, ``[S, max_blocks *
    block_size, ...]`` a leaf: index t IS position t.  Unassigned entries
    (-1 -> block 0) only cover positions :func:`context_mask` excludes.
    Of a pool ``by_head`` the blocks AS THEY LIE, ``[S, max_blocks, heads,
    block_size, head_dim]``: position t is ``[t // bs, :, t % bs]``, and
    nothing of the context's size is made between the gather and the
    products that keep the entries as an axis
    (models/layers.py ``attention_tile_by_head``)."""
    S, max_blocks = block_tables.shape
    bt = jnp.maximum(block_tables, 0)
    with jax.named_scope("kv_gather"):
        if by_head:
            return jax.tree_util.tree_map(lambda p: p[layer, bt], pool)
        return jax.tree_util.tree_map(
            lambda p: p[layer, bt].reshape(
                (S, max_blocks * p.shape[2]) + p.shape[3:]), pool)


def context_mask(positions: jax.Array, ctx: int, block: int = 1,
                 held: Optional[jax.Array] = None) -> jax.Array:
    """[S, 1, C, ctx] bool: the query at ``positions[s, c]`` sees gathered
    keys ``0 .. positions[s, c]`` (its own, written first, included).  Over
    a tile of a bounded read that begins at position ``start``, hand it
    ``positions - start``.

    With a ``block`` length B above 1 the mask is BLOCK-CAUSAL
    (models/blockdiff_moe.py): key ``j`` is visible iff ``j // B <=
    positions[s, c] // B`` — a query sees every position of its own block
    of B and of earlier blocks — and ``j`` lies below ``held[s]``, the
    slot's length once the tick's rows are in (a block that the tick fills
    only in part ends there).  A tile's ``start`` is a multiple of B, so
    ``positions - start`` and ``held - start`` keep the blocks.  ``block``
    1 is the causal mask above, the same expression as before there was a
    block length."""
    keys = jnp.arange(ctx)[None, None, :]
    if block == 1:
        return (keys <= positions[:, :, None])[:, None]
    return ((keys // block <= positions[:, :, None] // block)
            & (keys < held[:, None, None]))[:, None]


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


def _divisor(S: int, want: int) -> int:
    """The largest divisor of S that is at most ``want`` (at least 1)."""
    return max(b for b in range(1, S + 1) if S % b == 0 and b <= max(want, 1))


def slots_per_block(S: int, per_slot_bytes: int, budget: int) -> int:
    """The largest divisor of S whose scores fit the budget (at least 1)."""
    return _divisor(S, budget // max(per_slot_bytes, 1))


#: Positions of context that one step of a bounded read gathers, masks and
#: scores (a whole number of pool blocks, :func:`tile_blocks`), and the slots
#: whose first ``narrow`` columns read together, the longest context among
#: them bounding all.  Both by a probe at the serving cells' shapes (PERF.md
#: §6, PR 32): a smaller step reads less that nobody holds, and pays for
#: itself more often.
TILE = 256
NARROW_SLOTS = 2
#: columns a block of decode rows attends with in a chunk-wide tick: the
#: number every served module declares as its ``NARROW_COLS``
NARROW_COLS = 8


def attn_blocks(heads: int, S: int, C: int, ctx: int, score_bytes: int,
                narrow_cols: int) -> Tuple[int, int]:
    """(slots a block, narrow columns) of a cached attention of ``heads``
    query heads in a ``[S, C]`` tick over ``ctx`` gathered positions
    (:func:`attend_by_blocks`; the engine's ``wide_blocks_share`` reads a
    plan by the same two): as many slots as keep a block's float32 scores
    (heads x columns x context x 4 B a slot) within ``score_bytes``.  A
    module's ``attn_blocks`` hands in its own ``SCORE_BYTES`` and
    ``NARROW_COLS`` as they stand when it is called."""
    return slots_per_block(S, heads * C * ctx * 4, score_bytes), narrow_cols


def tile_blocks(block_size: int, max_blocks: int) -> int:
    """Table entries that a tile of a bounded read covers."""
    return min(max(TILE // block_size, 1), max_blocks)


def narrow_slots(S: int) -> int:
    """Slots a block of a bounded read's first pass (a divisor of S)."""
    return _divisor(S, NARROW_SLOTS)


def block_tiles(lengths, n_new, slots: int, tile: int):
    """Tiles of ``tile`` positions that each block of ``slots`` slots reads
    in a bounded read: as far as the longest context a LIVE slot of the
    block (``n_new > 0``) holds once the tick's writes are in, and none
    where no slot is live.  The device's trip counts and the host's count of
    them (:func:`read_counts`) are this one function, on jax or numpy
    arrays."""
    held = ((lengths + n_new) * (n_new > 0)).reshape(-1, slots).max(axis=1)
    return -(-held // tile)


def _bounded(tile: Callable, tiles: jax.Array, stat: Tuple[int, ...],
             acc: Tuple[int, ...], dtype) -> jax.Array:
    """One block of slots over its first ``tiles`` tiles, an online softmax:
    ``tile(t)`` -> (float32 scores ``stat + (keys,)``, ``weigh``) is folded,
    a tile after another, into a running float32 maximum, denominator and
    value sum, so the scores of one tile are all that exists at a time.
    ``acc``-shaped, in ``dtype``; zero where nothing was read."""
    def step(t, carry):
        m, l, acc = carry
        s, weigh = tile(t)
        top = jnp.maximum(m, jnp.max(s, -1))
        p = jnp.exp(s - jnp.expand_dims(top, -1))
        keep = jnp.exp(m - top)
        return (top, keep * l + jnp.sum(p, -1),
                jnp.expand_dims(keep, -1) * acc + weigh(p))
    _, l, acc = lax.fori_loop(
        0, tiles, step,
        (jnp.full(stat, jnp.finfo(jnp.float32).min, jnp.float32),
         jnp.zeros(stat, jnp.float32), jnp.zeros(acc, jnp.float32)))
    return (acc / jnp.expand_dims(jnp.where(l > 0, l, 1.0), -1)).astype(dtype)


class Bound(NamedTuple):
    """What a caller hands :func:`attend_by_blocks` to have its read bounded
    by what the slots hold: the positions each slot held before the tick
    (``lengths`` [S]), the stacked ``pool`` and the ``layer`` of it to read,
    and the tick's ``slab`` (:func:`pack`), through which a block's queries
    come back from the rows; ``by_head`` where the pool's kind declares it
    (``attend`` is then handed its tile as :func:`gather` leaves it)."""
    lengths: jax.Array
    pool: Any
    layer: int
    slab: Slab
    by_head: bool = False


def attend_by_blocks(attend: Callable, args: Tuple[jax.Array, ...],
                     n_new: jax.Array, slots: int, narrow: int,
                     bound: Optional[Bound] = None) -> jax.Array:
    """The cached attention at the width each block of ``slots`` slots needs
    and, given a ``bound``, only as far as its slots' contexts reach.  Every
    array of ``args`` is led by the slot axis, the first two (queries, their
    positions) by ``[S, C]``.  A tick wider than ``narrow`` columns attends
    every slot's first ``narrow`` columns, then a block after another: one
    that holds a slot with more than ``narrow`` tokens attends again with
    all C columns, the others are done — the columns past a slot's ``n_new``
    are padding that nothing reads.  Any plan is served: every block may
    hold a chunk.

    WITHOUT a bound every slot's whole table is read (models/swa_moe.py):
    ``attend(q, positions, *context)`` -> ``[S, C, ...]``, the first
    ``narrow`` columns of all slots at once.  ``attend`` may gather its
    slots' context from the pool itself: the loop then holds the pool, which
    XLA neither copies nor restacks for it (tests/test_serve.py; PERF.md §6,
    PR 30).

    WITH one the read is tiled (models/llama.py, models/latent_moe.py): the
    first of ``args`` is the queries as ROWS (what :func:`pack`'s ``take``
    made; a block's come back through ``bound.slab`` inside the loop, so the
    whole slab of a wide tick is never made), the third the block table,
    and ``attend(q, positions, ctx, *rest, start)`` is handed ONE TILE of
    context — ``ctx`` = :func:`gather` of :func:`tile_blocks` entries of the
    block's tables from ``bound.pool`` at ``bound.layer``, whose first key
    is position ``start`` — and returns ``(scores, weigh)``: the tile's
    float32 scores ``[s, .., c, keys]``, scaled and masked with float32's
    minimum, and the value product ``weigh(p)`` -> float32 ``[s, .., c, d]``
    of unnormalised probabilities shaped like the scores.  What is the
    model's own stops there; the tiling, the trip count (:func:`block_tiles`,
    a device value read off ``lengths`` and ``n_new``), the block with no
    live slot, which reads nothing, and the softmax across tiles are here,
    once.  ``attend`` must be the SAME function object for every layer of a
    tick and close over no array: the read is traced once a tick, not once
    a layer (:func:`_attend_tiled`).  The first pass runs in blocks of
    :func:`narrow_slots` slots.  Returns ``[S, .., C, d]`` in the scores'
    layout: the model brings it to its own."""
    if bound is None:
        return _attend_whole(attend, args, n_new, slots, narrow)
    q, pos, tables, *rest = args
    return _attend_tiled(
        attend, bound.pool, jnp.int32(bound.layer), q, bound.slab.rows, pos,
        tables, tuple(rest), bound.lengths, n_new, slots=slots,
        narrow=narrow, kept=bound.slab.kept, per=narrow_slots(pos.shape[0]),
        tb=tile_blocks(block_size(bound.pool, bound.by_head),
                       tables.shape[1]), by_head=bound.by_head)


def _attend_whole(attend, args, n_new, slots, narrow):
    """:func:`attend_by_blocks` without a bound."""
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


@functools.partial(jax.jit, static_argnums=(0,), static_argnames=(
    "slots", "narrow", "kept", "per", "tb", "by_head"))
def _attend_tiled(attend, pool, layer, q, rows, pos, tables, rest, lengths,
                  n_new, *, slots, narrow, kept, per, tb, by_head):
    """:func:`attend_by_blocks` with a bound: two passes of :func:`_bounded`
    blocks, each over the blocks that have something to read.  A function
    of arrays alone, jitted INSIDE the tick's program with the layer as a
    value: the layers of a tick share one trace and one lowering (24 layers
    traced one by one cost ``serve-decode`` 2.8 s of set-up, PERF.md §6,
    PR 32); the compiler inlines the calls and sees each layer's constant.
    ``per`` slots a block in the first pass, ``tb`` table entries a tile."""
    slab = Slab(rows, kept)
    bs = block_size(pool, by_head)
    S, C = pos.shape
    # a table that is no whole number of tiles: the entries past it are
    # unassigned, and cover positions that no query sees
    tables = jnp.pad(tables, ((0, 0), (0, -tables.shape[1] % tb)),
                     constant_values=-1)

    def tile(i, per, cols):
        """``t`` -> (scores, weigh) of tile ``t`` for block ``i``."""
        cut = lambda a: lax.dynamic_slice_in_dim(a, i * per, per)
        own, p = slab(q, i, per, cols), block_of(pos, i, per, cols)
        tab, *more = map(cut, (tables, *rest))
        return lambda t: attend(
            own, p,
            gather(pool, layer, lax.dynamic_slice_in_dim(tab, t * tb, tb, 1),
                   by_head),
            *more, t * (tb * bs))

    # what a tile's scores and value product look like, traced once: a
    # block's are these with its slots in front and its columns second last
    def one_tile():
        s, weigh = tile(0, 1, C)(0)
        return s, weigh(s)
    scores, values = jax.eval_shape(one_tile)
    dtype = q.dtype
    sized = lambda like, per, cols: (
        (per,) + like.shape[1:-2] + (cols, like.shape[-1]))

    def read(o, cols, per, tiles):
        """Each block of ``per`` slots that has ``tiles[block]`` to read
        attends over them in its first ``cols`` columns, into ``o``; the
        loop visits those blocks alone, so one that reads nothing costs
        nothing and ``o`` keeps what it held there."""
        order = jnp.argsort(tiles == 0, stable=True)    # readers first

        def block(j, o):
            # (indexing by a device value through lax: numpy-style indexing
            # costs a millisecond a trace, and a tick traces this 48 times)
            i = lax.dynamic_index_in_dim(order, j, keepdims=False)
            got = _bounded(tile(i, per, cols),
                           lax.dynamic_index_in_dim(tiles, i, keepdims=False),
                           sized(scores, per, cols)[:-1],
                           sized(values, per, cols), dtype)
            return lax.dynamic_update_slice(
                o, got, (i * per,) + (0,) * (o.ndim - 1))
        return lax.fori_loop(0, jnp.sum(tiles > 0), block, o)

    o = read(jnp.zeros(sized(values, S, C), dtype), min(C, narrow), per,
             block_tiles(lengths, n_new, per, tb * bs))
    if C <= narrow:
        return o
    return read(o, C, slots,
                block_tiles(lengths, n_new, slots, tb * bs)
                * holds_chunk(n_new, slots, narrow))


def holds_chunk(n_new, slots: int, narrow: int):
    """[blocks] bool: the blocks of ``slots`` slots that hold a slot with
    more than ``narrow`` new tokens, and so attend at the tick's full width
    (on jax or numpy arrays)."""
    return n_new.reshape(-1, slots).max(axis=1) > narrow


def wide_blocks(n_new: np.ndarray, slots: int, narrow: int) -> Tuple[int, int]:
    """:func:`attend_by_blocks`'s choice read off a plan on the host: (blocks
    that attend at the tick's full width, blocks) for ``n_new`` tokens a
    slot."""
    wide = holds_chunk(np.asarray(n_new), slots, narrow)
    return int(wide.sum()), int(wide.size)


def read_counts(lengths: np.ndarray, n_new: np.ndarray, C: int, slots: int,
                narrow: int, block_size: int, max_blocks: int,
                bounded: bool = True) -> np.ndarray:
    """A ``[S, C]`` tick's attention read off its plan on the host, as
    :func:`attend_by_blocks` runs it: int64 [positions read, positions the
    tables cover, first-pass blocks with no live slot, first-pass blocks].
    A block reads ``its slots x its tiles x a tile's positions`` in either
    pass (:func:`block_tiles`, the device's own trip counts); not
    ``bounded``, every slot's whole table in the first pass and a chunk
    block's again."""
    lengths, n_new = np.asarray(lengths), np.asarray(n_new)
    S = n_new.size
    chunk = holds_chunk(n_new, slots, narrow) * (C > narrow)
    if not bounded:
        whole = max_blocks * block_size
        return np.array([(S + slots * chunk.sum()) * whole, S * whole, 0, 1],
                        np.int64)
    tb = tile_blocks(block_size, max_blocks)
    per = narrow_slots(S)
    first = block_tiles(lengths, n_new, per, tb * block_size)
    second = block_tiles(lengths, n_new, slots, tb * block_size) * chunk
    return np.array([(per * first.sum() + slots * second.sum())
                     * tb * block_size, S * max_blocks * block_size,
                     (first == 0).sum(), first.size], np.int64)


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


def no_prefix_blocks(cache: Any, src: jax.Array, dst: jax.Array) -> Any:
    """``copy_blocks`` of a model with a window or a state kind: nothing to
    clone.  A block is a prefix's to share only with every layer's state at
    its end: a window layer's block stops being a prefix's once the stream
    has passed it, and a fixed state keeps its slot's last columns alone —
    so ServeEngine refuses prefix sharing (and with it copy-on-write) over
    such kinds."""
    return cache


def shardings(mesh, num_blocks: int, head_axis_size: Optional[int] = None,
              by_head: bool = False):
    """NamedSharding for a pool ``[L, blocks, bs, ...]`` along the training
    mesh's own axes: a head axis (``[.., heads, head_dim]`` behind, of
    ``head_axis_size``; ``by_head``, the pool's axis 2, before the block's
    positions) over a model/tp axis that divides it, blocks over the first
    remaining axis that divides them.  A pool without a head axis (the
    latent is every head's) shards its blocks alone."""
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
    heads = (head_axis, None) if by_head else (None, head_axis)
    return NamedSharding(mesh, P(None, block_axis, *heads, None))


def init_pools(kinds: Tuple[CacheKind, ...], num_blocks: Any,
               block_size: int, dtype) -> Any:
    """One preallocated pool a kind, by what each declares
    (``CacheKind.leaves``): a paged or a ring kind's leaves are ``[kind's
    layers, num_blocks[kind], block_size, ...]`` — ``[.., num_blocks[kind],
    heads, block_size, head_dim]`` where it lies ``by_head`` —, a state
    kind's ``[kind's layers, slots, columns, ...]``, its ``num_blocks``
    being ``(slots, columns)`` — with ``replay``, the leaves at ONE column
    beside ``at`` and the ring ``[kind's layers, slots, rows, ...]``,
    ``num_blocks`` ``(slots, rows)`` —; in ``dtype`` unless the kind names
    its own.  ``{kind: {leaf: array}}``, or the one pool of a kind without a
    name (whose ``num_blocks`` is the number)."""
    def pool(kind):
        n = _of(num_blocks, kind)
        lead = (kind.layers,) + (tuple(n) if kind.state is not None
                                 else (n, block_size))
        zeros = lambda lead, leaves, dtype: {
            name: jnp.zeros(lead + tuple(behind), dtype)
            for name, behind in leaves.items()}
        if kind.by_head:    # the heads before the block's positions
            return zeros(lead[:2], {
                name: (heads, block_size, width)
                for name, (heads, width) in kind.leaves.items()},
                kind.dtype or dtype)
        if kind.replay is None:
            return zeros(lead, kind.leaves, kind.dtype or dtype)
        # ONE state a slot and where it stands, beside the ring of rows
        one = lead[:2] + (1,)
        return {**zeros(one, kind.leaves, kind.dtype or dtype),
                **zeros(one, {AT: (1,)}, jnp.int32),
                **zeros(lead, kind.replay, kind.dtype or dtype)}
    pools = {kind.name: pool(kind) for kind in kinds}
    return pools[None] if None in pools else pools


def pool_shardings(mesh, kinds: Tuple[CacheKind, ...], num_blocks: Any):
    """:func:`shardings` of each of ``kinds``' pools (``{kind: sharding}``,
    or the one of a kind without a name): a paged pool's blocks and a
    state's slots over the data axis, each pool by its own number of them,
    and the heads of a paged pool that has a head axis (``[heads,
    head_dim]`` behind, or the pool's axis 2 where the kind lies by head)
    over a model axis."""
    def one(kind):
        n = _of(num_blocks, kind)
        if kind.state is not None:
            return shardings(mesh, n[0])
        behind = list(kind.leaves.values())
        return shardings(mesh, n, behind[0][0] if all(
            len(b) == 2 for b in behind) else None, kind.by_head)
    out = {kind.name: one(kind) for kind in kinds}
    return out[None] if None in out else out


def layer_of_kind(kind_of: Callable[[int], str], i: int) -> Tuple[str, int]:
    """(cache kind of layer i, its index among that kind's layers, which is
    its layer in the kind's pool), ``kind_of(j)`` naming layer j's kind."""
    kind = kind_of(i)
    return kind, sum(kind_of(j) == kind for j in range(i))


def leaf_key(path) -> str:
    """A cache leaf's name from its pytree path (block payloads and the
    engine's record of the pool's layout are keyed by it)."""
    return "/".join(str(getattr(p, "key", p)) for p in path)


def read_block(cache: Any, block: int) -> Dict[str, np.ndarray]:
    """One block across all layers as host numpy, keyed by leaf path (spill
    and the prefill hand-off's export): ``[L, bs, ...]`` a leaf."""
    leaves, _ = jax.tree_util.tree_flatten_with_path(cache)
    return {leaf_key(path): np.asarray(leaf[:, block])
            for path, leaf in leaves}


def write_block(cache: Any, block: int, payload: Dict[str, Any]) -> Any:
    """The cache with :func:`read_block`'s payload written into ``block``
    (spill reload / hand-off import)."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(cache)
    return jax.tree_util.tree_unflatten(treedef, [
        leaf.at[:, block].set(
            np.asarray(payload[leaf_key(path)]).astype(leaf.dtype))
        for path, leaf in leaves])
