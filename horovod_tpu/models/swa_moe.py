"""Grouped-query decoder whose attention is of two kinds in one stack and
whose experts are routed BEFORE the attention — written by its mechanisms,
so that any model built from them is a config away:

  * **Window and global attention, layer by layer.**  ``window_layout[i]``
    = 1 gives layer i a sliding window: the query at position t sees keys
    ``t - window < j <= t`` (``window`` keys, its own included); 0 gives it
    the whole causal context.
  * **Rotary only where ``rope_layout[i]`` = 1.**  A layer without it has
    no positional encoding at all: its scores depend on positions through
    the mask alone.
  * **Two kinds of cache** (models/paged.py ``CacheKind``): the global
    layers keep a slot's whole context in one paged pool, the window layers
    keep a RING of ``window + one tick's columns`` positions a slot in
    another, whatever the context's length, and gather and score that ring
    and not ``max_seq`` positions.
  * **Many small ReLU-gated experts, routed from the attention's input.**
    The router reads ``norm(x)`` — what the attention reads — and its
    decision (the ``top_k`` largest logits, gates their softmax) is applied
    after the attention to ``norm(x + attention)``: ``sum_e g_e
    (relu(h W_gate,e) * h W_up,e) W_down,e``.  No dense FFN, no shared
    expert.  This chip holds ``experts_held`` of ``n_experts`` starting at
    ``first_expert`` (parallel/expert.py ``held_experts``).

Serving contract as models/llama.py, with one pool a cache kind:
``cache_kinds`` declares them, ``init_cache`` takes the blocks of each
(``{kind: blocks}``) and ``apply_cached`` the block tables of each.  The
vocabulary is wide enough that ``[slots, chunk, vocab]`` logits would be
the tick's largest array, so the module gives the engine
``greedy_cached``: the argmax taken on the tick's packed rows.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from . import decoder
from . import layers as L
from . import paged
from .paged import NARROW_COLS
from ..parallel import expert as X
from ..parallel.expert import EXPERT_TILE


@dataclasses.dataclass(frozen=True)
class SwaMoeConfig:
    vocab: int = 4096
    dim: int = 256
    n_layers: int = 4
    n_heads: int = 8
    n_kv_heads: int = 2
    head_dim: int = 32
    window: int = 64
    # one entry a layer (cycled when shorter): 1 = sliding window / rotary
    window_layout: Tuple[int, ...] = (0, 1, 1, 1)
    rope_layout: Tuple[int, ...] = (0, 1, 1, 1)
    moe_hidden: int = 64         # one expert
    n_experts: int = 16          # the router's width
    experts_held: int = 16       # ... of which this chip holds
    first_expert: int = 0        # ... starting here
    top_k: int = 4
    norm_eps: float = 1e-6
    max_seq: int = 512
    rope_theta: float = 10000.0
    dtype: Any = jnp.float32
    # The most valid tokens one call of apply_cached holds (models/paged.py
    # pack); ServeEngine sets it to its own max_batch_tokens; 0 = every
    # position of the slab.
    max_tick_tokens: int = 0

    def __post_init__(self):
        # a manifest's JSON gives lists: the config stays hashable
        for name in ("window_layout", "rope_layout"):
            object.__setattr__(self, name, tuple(getattr(self, name)))

    def windowed(self, i: int) -> bool:
        return bool(self.window_layout[i % len(self.window_layout)])

    def rotary(self, i: int) -> bool:
        return bool(self.rope_layout[i % len(self.rope_layout)])


CONFIGS = {
    "tiny": SwaMoeConfig(vocab=256, dim=64, n_layers=4, n_heads=4,
                         n_kv_heads=2, head_dim=16, window=16,
                         moe_hidden=32, n_experts=8, experts_held=8,
                         top_k=2, max_seq=128),
}

#: float32 scores one block of slots may hold (heads x columns x keys x 4 B
#: a slot): a chunk-wide tick attends a slot at a time in either kind
SCORE_BYTES = 256 << 20
#: the names of the two cache kinds
GLOBAL, WINDOW = "global", "window"

#: what the third value of apply_cached counts, summed over the layers
TICK_COUNTERS = ("ticks",) + X.HELD_COUNTERS


# ----------------------------------------------------------------- weights
def init_layer(key, cfg: SwaMoeConfig) -> Dict[str, Any]:
    k = jax.random.split(key, 5)
    d, hd = cfg.dim, cfg.head_dim
    dense = lambda key, i, o: L.dense_init(key, i, o, use_bias=False,
                                           dtype=cfg.dtype)
    return {"input_norm": L.rmsnorm_init(d, cfg.dtype),
            "post_attn_norm": L.rmsnorm_init(d, cfg.dtype),
            "attn": {"wq": dense(k[0], d, cfg.n_heads * hd),
                     "wk": dense(k[1], d, cfg.n_kv_heads * hd),
                     "wv": dense(k[2], d, cfg.n_kv_heads * hd),
                     "wo": dense(k[3], cfg.n_heads * hd, d)},
            "moe": X.init_held_experts(k[4], d, cfg.moe_hidden, cfg.n_experts,
                                       cfg.experts_held, cfg.dtype)}


def init(key, cfg: SwaMoeConfig) -> Dict[str, Any]:
    keys = jax.random.split(key, cfg.n_layers + 2)
    return {"embed": L.embedding_init(keys[0], cfg.vocab, cfg.dim, cfg.dtype),
            "final_norm": L.rmsnorm_init(cfg.dim, cfg.dtype),
            "lm_head": L.dense_init(keys[1], cfg.dim, cfg.vocab,
                                    use_bias=False, dtype=cfg.dtype),
            "layers": [init_layer(keys[2 + i], cfg)
                       for i in range(cfg.n_layers)]}


# ------------------------------------------------------------------ pieces
def _route(p, h, cfg):
    """The routing decision, from the ATTENTION's input h [.., D]:
    (idx [T, k], gates [T, k])."""
    with jax.named_scope("moe/route"):
        return X.route_softmax_topk(h.reshape(-1, cfg.dim),
                                    p["moe"]["router"]["kernel"], cfg.top_k)


def _experts(p, h2, valid, routing, cfg):
    """The held experts' part on h2 [B, S, D] under ``routing``:
    (y [B, S, D], counters)."""
    return X.held_ffn(p["moe"], h2, valid, first=cfg.first_expert,
                      routing=routing, act=jax.nn.relu, tile=EXPERT_TILE)


# ------------------------------------------------------- full-sequence path
def apply(params: Dict[str, Any], ids: jax.Array, cfg: SwaMoeConfig,
          rope_positions=None) -> jax.Array:
    """Forward without a cache: ids [B, S] -> logits [B, S, vocab].  For
    tests and for checking the cached path against.  ``rope_positions``
    [B, S] (the tests') are what the ROTARY layers rotate by in place of
    0..S-1; the masks go by a token's place in the row whatever it says, so
    a layer without rotary cannot see it."""
    B, S = ids.shape
    cos, sin = L.rope_freqs(cfg.head_dim, cfg.max_seq, cfg.rope_theta)
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    rot = positions if rope_positions is None else rope_positions
    valid = jnp.ones((B, S), bool)
    x = L.embedding(params["embed"], ids).astype(cfg.dtype)
    for i, p in enumerate(params["layers"]):
        h = L.norm(p["input_norm"], x, cfg)
        routing = _route(p, h, cfg)
        q, k, v = L.qkv(p["attn"], h, cfg, cos, sin, rot,
                        rotary=cfg.rotary(i))
        mask = (paged.window_mask(positions, positions, cfg.window)
                if cfg.windowed(i) else None)
        o = L.causal_attention(q, k, v, causal=mask is None, mask=mask)
        x = x + L.dense(p["attn"]["wo"], o.reshape(B, S, -1))
        y, _ = _experts(p, L.norm(p["post_attn_norm"], x, cfg), valid,
                        routing, cfg)
        x = x + y
    return _logits(params, cfg, x)


# ------------------------------------------------------------- decode path
def cache_kinds(cfg: SwaMoeConfig) -> Tuple[paged.CacheKind, ...]:
    """The kinds of cache this stack keeps, those with layers only: the
    global layers' whole contexts, the window layers' rings."""
    n_win = sum(cfg.windowed(i) for i in range(cfg.n_layers))
    behind = (cfg.n_kv_heads, cfg.head_dim)
    leaves = {"k": behind, "v": behind}
    kinds = (paged.CacheKind(GLOBAL, cfg.n_layers - n_win, leaves=leaves),
             paged.CacheKind(WINDOW, n_win, cfg.window, leaves=leaves))
    return tuple(k for k in kinds if k.layers)


def init_cache(cfg: SwaMoeConfig, num_blocks: Dict[str, int],
               block_size: int, dtype=None) -> Dict[str, Dict[str, jax.Array]]:
    """One paged pool a kind, ``{kind: {"k", "v"}}`` of ``[kind's layers,
    num_blocks[kind], block_size, n_kv_heads, head_dim]``."""
    return paged.init_pools(cache_kinds(cfg), num_blocks, block_size,
                            dtype if dtype is not None else cfg.dtype)


def cache_shardings(mesh, cfg: SwaMoeConfig, num_blocks: Dict[str, int]):
    """{kind: sharding}: blocks over the data axis, kv heads over a model
    axis, each pool by its own number of blocks."""
    return paged.pool_shardings(mesh, cache_kinds(cfg), num_blocks)


copy_blocks = paged.no_prefix_blocks


def attn_blocks(cfg: SwaMoeConfig, S: int, C: int, ctx: int
                ) -> Tuple[int, int]:
    """(slots a block, narrow columns) of the cached attention in a
    ``[S, C]`` tick over ``ctx`` gathered positions, either kind."""
    return paged.attn_blocks(cfg.n_heads, S, C, ctx, SCORE_BYTES,
                             NARROW_COLS)


def _attn_cached(p, h, cfg, i, cos, sin, cache, tables, t: paged.Tick):
    """Layer i's attention over its kind's pool, in place (the pools stay
    stacked, as models/llama.py keeps its one): the rows' k/v are scattered
    in FIRST, then the queries go back to their slots and each attends over
    what its kind gathers — the slot's whole context under the causal mask,
    or its ring under the window's — a block of slots after another, only
    the blocks that hold a chunk at chunk width (paged.attend_by_blocks)."""
    kind, j = paged.layer_of_kind(
        lambda j: WINDOW if cfg.windowed(j) else GLOBAL, i)
    rows = h.shape[:2]
    q, k, v = L.qkv(p, h, cfg, cos, sin, t.pos, rotary=cfg.rotary(i))
    with jax.named_scope("attn/" + kind):
        pool = paged.write(cache[kind], j, *t.where[kind], {"k": k, "v": v})
        cache = dict(cache, **{kind: pool})
        n_keys = tables[kind].shape[1] * pool["k"].shape[2]

        def attend(q, pos, tab, top):
            ctx = paged.gather(pool, j, tab)
            mask = (paged.window_mask(
                        pos, paged.ring_positions(top, n_keys), cfg.window)
                    if kind == WINDOW else paged.context_mask(pos, n_keys))
            return L.causal_attention(q, ctx["k"], ctx["v"], causal=False,
                                      mask=mask)
        o = paged.attend_by_blocks(
            attend, (t.slab(q), t.positions, tables[kind], t.top), t.n_new,
            *attn_blocks(cfg, *t.positions.shape, n_keys))
    return L.dense(p["wo"], t.take(o).reshape(rows + (-1,))), cache


def _logits(params, cfg, x):
    """The final norm and the output head on hidden states ``[.., dim]``."""
    return L.dense(params["lm_head"], L.norm(params["final_norm"], x, cfg))


def _forward(params, tokens, cfg, cache, tables, lengths, n_new, head):
    """The tick's rows through the stack (decoder.forward): the router
    reads the attention's input, its decision is applied after the
    attention."""
    cos, sin = L.rope_freqs(cfg.head_dim, cfg.max_seq, cfg.rope_theta)

    def layer(i, p, x, cache, t):
        h = L.norm(p["input_norm"], x, cfg)
        routing = _route(p, h, cfg)
        a, cache = _attn_cached(p["attn"], h, cfg, i, cos, sin, cache,
                                tables, t)
        x = x + a
        y, c = _experts(p, L.norm(p["post_attn_norm"], x, cfg), t.valid,
                        routing, cfg)
        return x + y, cache, c
    return decoder.forward(
        layer, functools.partial(_logits, params, cfg), cache_kinds(cfg),
        params, tokens, cfg, cache, tables, lengths, n_new, head,
        counters=TICK_COUNTERS, max_seq=cfg.max_seq,
        reads=("top", "valid", "pos"))


#: decoder.cached_pair has the contract: ``cache`` and ``block_tables`` are
#: dicts by kind (the window kind's table is a ring), the third value the
#: counters summed over the layers, the greedy token every position's.
apply_cached, greedy_cached = decoder.cached_pair(_forward)


def param_count(cfg: SwaMoeConfig) -> int:
    d, hd = cfg.dim, cfg.head_dim
    attn = 2 * d * cfg.n_heads * hd + 2 * d * cfg.n_kv_heads * hd
    layer = (attn + d * cfg.n_experts
             + cfg.experts_held * 3 * d * cfg.moe_hidden + 2 * d)
    return cfg.n_layers * layer + 2 * cfg.vocab * d + d


__all__ = ["SwaMoeConfig", "CONFIGS", "TICK_COUNTERS", "GLOBAL", "WINDOW",
           "init", "apply", "cache_kinds", "init_cache", "cache_shardings",
           "copy_blocks", "apply_cached", "greedy_cached", "attn_blocks",
           "param_count"]
