"""Decoder whose token mixer is, layer by layer, a gated SHORT CONVOLUTION or
grouped-query attention, over dense and then routed feed-forward layers —
written by its mechanisms, so that any model built from them is a config
away:

  * **``layer_types[i]``** is ``"conv"`` or ``"full_attention"``.  Layer i:
    ``r = x + Op_i(norm(x))``, ``x' = r + FFN_i(norm(r))``.
  * **The short convolution** of position t: ``(B, C, X) = split3(h W_in)``,
    ``u = B * X``, ``v_t = sum_j w[:, j] * u_{t-K+1+j}`` over ``K =
    conv_taps`` taps (the last on the current position, ``u`` zero before
    position 0), ``Op = (C * v) W_out``.  No context is read: what a stream
    carries from one tick to the next is ``u`` at its last ``K - 1``
    positions.
  * **Attention** with an RMS norm with its own gain over the ``head_dim``
    of each query and key head BEFORE the rotary encoding, which every
    attention layer has; the whole causal context.
  * **Two kinds of cache** (models/paged.py ``CacheKind``): the attention
    layers' keys and values in the paged pool, read as far as a slot's
    context reaches (``attend_by_blocks`` with a ``Bound``), and the conv
    layers' ``u`` as a FIXED STATE a slot, a short ring of columns
    (``paged.state_columns``) with no blocks and no table.
  * **``n_dense_layers`` dense gated FFNs, then sigmoid-routed experts with
    a bias that picks and does not weigh** (parallel/expert.py
    ``route_sigmoid_topk``): the ``top_k`` largest of ``sigmoid(h W_r) +
    bias``, gates the unbiased scores over their sum.  No shared expert.
    This chip holds ``experts_held`` of ``n_experts`` from ``first_expert``.
  * **The head is the embedding**, after the final norm.

Serving contract as models/swa_moe.py: ``cache_kinds`` declares the kinds,
``init_cache`` sizes each, ``apply_cached`` takes the block table of the
attention kind (``{ATTN: table}``; the state kind has none), and the module
samples on the tick's packed rows (``greedy_cached``).
"""

from __future__ import annotations

import dataclasses
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
class ConvMoeConfig:
    vocab: int = 4096
    dim: int = 256
    n_layers: int = 6
    n_heads: int = 8
    n_kv_heads: int = 2
    head_dim: int = 32
    # one entry a layer, "conv" or "full_attention"; the first n_layers run
    layer_types: Tuple[str, ...] = ("conv", "conv", "full_attention", "conv",
                                    "conv", "conv")
    conv_taps: int = 3
    n_dense_layers: int = 2      # leading layers with a dense FFN
    ffn_dim: int = 512           # ... of this width
    moe_hidden: int = 64         # one expert
    n_experts: int = 16          # the router's width
    experts_held: int = 16       # ... of which this chip holds
    first_expert: int = 0        # ... starting here
    top_k: int = 4
    route_scale: float = 1.0
    norm_eps: float = 1e-5
    max_seq: int = 512
    rope_theta: float = 1000000.0
    dtype: Any = jnp.float32
    # The most valid tokens one call of apply_cached holds (models/paged.py
    # pack); ServeEngine sets it to its own max_batch_tokens; 0 = every
    # position of the slab.
    max_tick_tokens: int = 0

    def __post_init__(self):
        # a manifest's JSON gives lists: the config stays hashable
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        if len(self.layer_types) < self.n_layers or set(
                self.layer_types) - {"conv", "full_attention"}:
            raise ValueError(
                f"layer_types {self.layer_types!r}: {self.n_layers} entries "
                "of 'conv' or 'full_attention' are needed")

    def conv(self, i: int) -> bool:
        return self.layer_types[i] == "conv"

    def routed(self, i: int) -> bool:
        return i >= self.n_dense_layers


CONFIGS = {
    "tiny": ConvMoeConfig(vocab=256, dim=64, n_layers=6, n_heads=4,
                          n_kv_heads=2, head_dim=16, ffn_dim=96,
                          moe_hidden=32, n_experts=8, experts_held=8,
                          top_k=2, max_seq=128),
}

#: the epsilon under the gates' sum
GATE_EPS = 1e-6
#: float32 scores one block of slots may hold (heads x columns x one tile of
#: context x 4 B a slot)
SCORE_BYTES = 32 << 20
#: the names of the two cache kinds
ATTN, CONV = "attn", "conv"
#: the cached attention reads a slot's context as far as it reaches
BOUNDED_READ = True

#: what the third value of apply_cached counts, summed over the routed layers
TICK_COUNTERS = ("ticks",) + X.HELD_COUNTERS


# ----------------------------------------------------------------- weights
def init_layer(key, cfg: ConvMoeConfig, i: int) -> Dict[str, Any]:
    k = jax.random.split(key, 6)
    d, hd = cfg.dim, cfg.head_dim
    dense = lambda key, i, o: L.dense_init(key, i, o, use_bias=False,
                                           dtype=cfg.dtype)
    p = {"op_norm": L.rmsnorm_init(d, cfg.dtype),
         "ffn_norm": L.rmsnorm_init(d, cfg.dtype)}
    if cfg.conv(i):
        p["conv"] = {"in_proj": dense(k[0], d, 3 * d),
                     "taps": (jax.random.normal(k[1], (d, cfg.conv_taps))
                              * cfg.conv_taps ** -0.5).astype(cfg.dtype),
                     "out_proj": dense(k[2], d, d)}
    else:
        p["attn"] = {"wq": dense(k[0], d, cfg.n_heads * hd),
                     "wk": dense(k[1], d, cfg.n_kv_heads * hd),
                     "wv": dense(k[2], d, cfg.n_kv_heads * hd),
                     "wo": dense(k[3], cfg.n_heads * hd, d),
                     "q_norm": L.rmsnorm_init(hd, cfg.dtype),
                     "k_norm": L.rmsnorm_init(hd, cfg.dtype)}
    if cfg.routed(i):
        p["moe"] = dict(
            X.init_held_experts(k[4], d, cfg.moe_hidden, cfg.n_experts,
                                cfg.experts_held, cfg.dtype),
            bias=(0.1 * jax.random.normal(k[5], (cfg.n_experts,))
                  ).astype(cfg.dtype))
    else:
        p["ffn"] = {"w1": dense(k[4], d, cfg.ffn_dim),
                    "w3": dense(k[5], d, cfg.ffn_dim),
                    "w2": dense(k[3], cfg.ffn_dim, d)}
    return p


def init(key, cfg: ConvMoeConfig) -> Dict[str, Any]:
    keys = jax.random.split(key, cfg.n_layers + 1)
    return {"embed": L.embedding_init(keys[0], cfg.vocab, cfg.dim, cfg.dtype),
            "final_norm": L.rmsnorm_init(cfg.dim, cfg.dtype),
            "layers": [init_layer(keys[1 + i], cfg, i)
                       for i in range(cfg.n_layers)]}


# ------------------------------------------------------------------ pieces
def _conv_in(p, h, cfg):
    """(C, u) of the conv operator's input h [.., D]: the output gate and the
    gated input ``u = B * X`` that the taps run over."""
    with jax.named_scope("conv/in"):
        b, c, x = jnp.split(L.dense(p["in_proj"], h), 3, axis=-1)
        return c, b * x


def _conv_out(p, c, u, before, cfg):
    """``(C * sum_j w[:, j] u_{t-K+1+j}) W_out``: ``before(back)`` is ``u``
    ``back`` positions before each row's own.  The taps' sum is taken in
    float32 and rounded once, at the gate."""
    K, f32 = cfg.conv_taps, jnp.float32
    with jax.named_scope("conv/taps"):
        w = p["taps"].astype(f32)
        v = w[:, K - 1] * u.astype(f32)
        for back in range(1, K):
            v = v + w[:, K - 1 - back] * before(back).astype(f32)
        gated = (c.astype(f32) * v).astype(u.dtype)
    with jax.named_scope("conv/out"):
        return L.dense(p["out_proj"], gated)


def _ffn(p, h, valid, cfg, i):
    """Layer i's feed-forward part on h [B, S, D]: (y, counters) — the dense
    gated FFN, or this chip's experts under the biased router."""
    if not cfg.routed(i):
        with jax.named_scope("ffn"):
            f = p["ffn"]
            y = X.gated_ffn(f["w1"]["kernel"], f["w3"]["kernel"],
                            f["w2"]["kernel"], h)
        return y.astype(h.dtype), 0
    route = lambda rows: X.route_sigmoid_topk(
        rows, p["moe"]["router"]["kernel"], cfg.top_k, cfg.route_scale,
        bias=p["moe"]["bias"], eps=GATE_EPS)
    return X.held_ffn(p["moe"], h, valid, route=route,
                      first=cfg.first_expert, act=jax.nn.silu,
                      tile=EXPERT_TILE)


def _head(params, x, cfg):
    """Logits of hidden states x [.., D]: the final norm, then the embedding
    as the head."""
    return jnp.dot(L.norm(params["final_norm"], x, cfg),
                   params["embed"]["table"].T)


# ------------------------------------------------------- full-sequence path
def apply(params: Dict[str, Any], ids: jax.Array, cfg: ConvMoeConfig
          ) -> jax.Array:
    """Forward without a cache: ids [B, S] -> logits [B, S, vocab].  For
    tests and for checking the cached path against."""
    B, S = ids.shape
    cos, sin = L.rope_freqs(cfg.head_dim, cfg.max_seq, cfg.rope_theta)
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    valid = jnp.ones((B, S), bool)
    x = L.embedding(params["embed"], ids).astype(cfg.dtype)
    for i, p in enumerate(params["layers"][:cfg.n_layers]):
        h = L.norm(p["op_norm"], x, cfg)
        if cfg.conv(i):
            c, u = _conv_in(p["conv"], h, cfg)
            before = lambda back, u=u: jnp.pad(
                u, ((0, 0), (back, 0), (0, 0)))[:, :S]
            x = x + _conv_out(p["conv"], c, u, before, cfg)
        else:
            q, k, v = L.qkv(p["attn"], h, cfg, cos, sin, positions,
                            qk_norm=True)
            o = L.causal_attention(q, k, v)
            x = x + L.dense(p["attn"]["wo"], o.reshape(B, S, -1))
        y, _ = _ffn(p, L.norm(p["ffn_norm"], x, cfg), valid, cfg, i)
        x = x + y
    return _head(params, x, cfg)


# ------------------------------------------------------------- decode path
def cache_kinds(cfg: ConvMoeConfig) -> Tuple[paged.CacheKind, ...]:
    """The kinds of cache this stack keeps, those with layers only: the
    attention layers' whole contexts, the conv layers' fixed state a slot
    (``u`` at the last ``conv_taps - 1`` positions)."""
    n_conv = sum(cfg.conv(i) for i in range(cfg.n_layers))
    # a position's heads side by side: with a last axis of ``head_dim`` 64,
    # half a lane tile, the chip lays the pool out blocks-minor and relays it
    # on the way into and out of every tick (PERF.md §6, PR 33)
    heads = (cfg.n_kv_heads * cfg.head_dim,)
    kinds = (paged.CacheKind(ATTN, cfg.n_layers - n_conv,
                             leaves={"k": heads, "v": heads}),
             paged.CacheKind(CONV, n_conv, state=cfg.conv_taps - 1,
                             leaves={"u": (cfg.dim,)}))
    return tuple(k for k in kinds if k.layers)


def init_cache(cfg: ConvMoeConfig, num_blocks: Dict[str, Any],
               block_size: int, dtype=None) -> Dict[str, Dict[str, jax.Array]]:
    """One pool a kind: ``{ATTN: {"k", "v"}}`` of ``[attention layers,
    num_blocks[ATTN], block_size, n_kv_heads * head_dim]`` — a position's
    heads side by side (:func:`cache_kinds` says why) — and ``{CONV:
    {"u"}}`` of ``[conv layers, slots, columns, dim]``, ``num_blocks[CONV]``
    being the state kind's ``(slots, columns)``."""
    return paged.init_pools(cache_kinds(cfg), num_blocks, block_size,
                            dtype if dtype is not None else cfg.dtype)


def cache_shardings(mesh, cfg: ConvMoeConfig, num_blocks: Dict[str, Any]):
    """{kind: sharding}: the paged pool's blocks and the state's slots over
    the data axis."""
    return paged.pool_shardings(mesh, cache_kinds(cfg), num_blocks)


#: Nothing to clone (paged.no_prefix_blocks): the engine refuses prefix
#: sharing over a state kind.
copy_blocks = paged.no_prefix_blocks


def attn_blocks(cfg: ConvMoeConfig, S: int, C: int, ctx: int
                ) -> Tuple[int, int]:
    """(slots a block, narrow columns) of the cached attention in a
    ``[S, C]`` tick over ``ctx`` gathered positions."""
    return paged.attn_blocks(cfg.n_heads, S, C, ctx, SCORE_BYTES,
                             NARROW_COLS)


def _conv_cached(p, h, cfg, j, cache, t: paged.Tick):
    """Conv layer (the state kind's j-th) on the tick's rows h: a row's
    earlier ``u`` are the rows before it where those are its own slot's new
    tokens, else the slot's state as the LAST tick left it, read a slot at
    a time before this tick's last ``u`` are laid over it
    (paged.state_read, paged.write_slots)."""
    c, u = _conv_in(p, h, cfg)
    with jax.named_scope("conv/state"):
        earlier = paged.state_read(cache[CONV]["u"], j, u, t,
                                   cfg.conv_taps - 1)
        cache = dict(cache, **{CONV: paged.write_slots(
            cache[CONV], j, *t.lands[CONV], {"u": u})})
    return _conv_out(p, c, u, lambda back: earlier[back - 1], cfg), cache


def _attend_tile(q, pos, ctx, start):
    """One tile of a block of slots' cached attention (paged.attend_by_blocks
    with a bound), as models/llama.py ``_attend_tile`` with the tile's keys
    and values ``ctx`` [s, keys, kv heads * head_dim] cut into heads here;
    they begin at position ``start``."""
    heads = lambda a: a.reshape(a.shape[:2] + (-1, q.shape[-1]))
    return L.attention_tile(
        q, heads(ctx["k"]), heads(ctx["v"]),
        paged.context_mask(pos - start, ctx["k"].shape[1]))


def _attn_cached(p, h, cfg, j, cos, sin, cache, tables, t: paged.Tick):
    """Attention layer (the paged kind's j-th) over the pool, in place, as
    models/llama.py ``_attn_cached``: the rows' k/v are scattered in first,
    then each block of slots attends a tile of context after another as far
    as its slots' contexts reach."""
    rows = h.shape[:2]
    q, k, v = L.qkv(p, h, cfg, cos, sin, t.pos, qk_norm=True)
    with jax.named_scope("attn"):
        flat = lambda a: a.reshape(rows + (-1,))
        pool = paged.write(cache[ATTN], j, *t.where[ATTN],
                           {"k": flat(k), "v": flat(v)})
        o = paged.attend_by_blocks(
            _attend_tile, (q, t.positions, tables[ATTN]), t.n_new,
            *attn_blocks(cfg, *t.positions.shape,
                         tables[ATTN].shape[1] * pool["k"].shape[2]),
            bound=paged.Bound(t.lengths, pool, j, t.slab))
        # [S, Hkv, rep, C, head_dim] -> the rows
        o = t.take(jnp.moveaxis(o, 3, 1))
    return (L.dense(p["wo"], o.reshape(rows + (-1,))),
            dict(cache, **{ATTN: pool}))


def _forward(params, tokens, cfg, cache, tables, lengths, n_new, head):
    """The tick's rows through the stack (decoder.forward): the layer's
    operator by its kind, then its feed-forward part."""
    cos, sin = L.rope_freqs(cfg.head_dim, cfg.max_seq, cfg.rope_theta)

    def layer(i, p, x, cache, t):
        h = L.norm(p["op_norm"], x, cfg)
        kind, j = paged.layer_of_kind(
            lambda j: CONV if cfg.conv(j) else ATTN, i)
        if kind == CONV:
            a, cache = _conv_cached(p["conv"], h, cfg, j, cache, t)
        else:
            a, cache = _attn_cached(p["attn"], h, cfg, j, cos, sin, cache,
                                    tables, t)
        x = x + a
        y, c = _ffn(p, L.norm(p["ffn_norm"], x, cfg), t.valid, cfg, i)
        return x + y, cache, c
    return decoder.forward(
        layer, lambda x: _head(params, x, cfg), cache_kinds(cfg), params,
        tokens, cfg, cache, tables, lengths, n_new, head,
        counters=TICK_COUNTERS, max_seq=cfg.max_seq,
        reads=("valid", "pos"))


#: decoder.cached_pair has the contract: ``cache`` is a dict by kind,
#: ``block_tables`` the attention kind's alone (``{ATTN: table}``), the third
#: value the counters summed over the routed layers, the greedy token every
#: position's.
apply_cached, greedy_cached = decoder.cached_pair(_forward)


def param_count(cfg: ConvMoeConfig) -> int:
    d, hd = cfg.dim, cfg.head_dim
    conv = 4 * d * d + cfg.conv_taps * d
    attn = (2 * d * cfg.n_heads * hd + 2 * d * cfg.n_kv_heads * hd + 2 * hd)
    dense = 3 * d * cfg.ffn_dim
    routed = (d * cfg.n_experts + cfg.n_experts
              + cfg.experts_held * 3 * d * cfg.moe_hidden)
    return (cfg.vocab * d + d + sum(
        (conv if cfg.conv(i) else attn) + (routed if cfg.routed(i) else dense)
        + 2 * d for i in range(cfg.n_layers)))


__all__ = ["ConvMoeConfig", "CONFIGS", "TICK_COUNTERS", "ATTN", "CONV",
           "BOUNDED_READ", "init", "apply", "cache_kinds", "init_cache",
           "cache_shardings", "copy_blocks", "apply_cached", "greedy_cached",
           "attn_blocks", "param_count"]
