"""Latent-attention decoder with sandwich norms and routed experts beside a
shared one — the block of today's large sparse models, written by its
mechanisms so that any model built from them is a config away:

  * **Latent attention.**  Queries and keys/values go through low-rank
    projections with an inner norm each (``wq_a -> q_a_norm -> wq_b``,
    ``wkv_a -> kv_a_norm -> wkv_b``); a head's query and key are a
    position-free part (``qk_nope_dim``) and a rotary part (``qk_rope_dim``)
    whose KEY is one vector shared by all heads.  What is cached a position
    is therefore the latent ``[c_kv | k_rope]`` (``kv_rank + qk_rope_dim``
    values), never a per-head K or V.
  * **Absorbed form over the cache.**  With ``wkv_b`` split by head into
    ``W^K_h, W^V_h``: ``score_h = (q_nope_h W^K_h^T) . c_kv + q_rope_h .
    k_rope`` and ``out_h = (softmax_h c_kv) W^V_h`` — the same mathematics
    as expanding K and V (:func:`apply` does that, and the tests hold the
    two together), at the cost of the latent's width a cached position.
  * **Sandwich norms.**  ``x + norm(sublayer(norm(x)))``: the sublayer's
    OUTPUT is normed too, then added.
  * **Layers of two kinds in one stack.**  ``n_dense`` leading layers with a
    dense gated FFN, then layers whose FFN is a shared expert plus the
    ``top_k`` of ``n_experts`` routed ones, of which this chip holds
    ``experts_held`` starting at ``first_expert``
    (parallel/expert.py ``held_experts``; docs/serving.md#held-experts).

Serving contract as models/llama.py: ``init_cache`` / ``copy_blocks`` /
``apply_cached`` over ONE pool ``[n_layers, blocks, block, kv_rank +
qk_rope_dim]``, plus ``cache_shardings`` (the pool has no head axis to
shard) and ``TICK_COUNTERS`` (what the third value of ``apply_cached``
counts; ServeEngine sums it into ``stats()["moe"]``).
"""

from __future__ import annotations

import dataclasses
import functools
import math
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
class LatentMoeConfig:
    vocab: int = 4096
    dim: int = 256
    n_layers: int = 3
    n_dense: int = 1             # leading layers with a dense FFN
    n_heads: int = 8
    q_rank: int = 96             # low-rank query projection
    kv_rank: int = 64            # the cached latent, without its rotary part
    qk_nope_dim: int = 32
    qk_rope_dim: int = 16
    v_dim: int = 32
    ffn_dim: int = 512           # dense layers
    moe_hidden: int = 128        # one expert, routed or shared
    n_experts: int = 16          # the router's width
    experts_held: int = 16       # ... of which this chip holds
    first_expert: int = 0        # ... starting here
    top_k: int = 4
    n_shared: int = 1
    route_scale: float = 2.5
    norm_eps: float = 1e-5
    max_seq: int = 512
    rope_theta: float = 10000.0
    dtype: Any = jnp.float32
    # The most valid tokens one call of apply_cached holds: they are packed
    # into that many rows.  ServeEngine sets it to its own max_batch_tokens,
    # whatever is written here; 0, for a direct caller, is every position of
    # the slab.
    max_tick_tokens: int = 0

    @property
    def latent_dim(self) -> int:
        return self.kv_rank + self.qk_rope_dim

    @property
    def pool_dim(self) -> int:
        """A position's columns in the paged pool: the latent and zeros up
        to a whole number of ``POOL_LANES`` (:func:`_pool`)."""
        return -(-self.latent_dim // POOL_LANES) * POOL_LANES

    @property
    def qk_dim(self) -> int:
        return self.qk_nope_dim + self.qk_rope_dim


CONFIGS = {
    "tiny": LatentMoeConfig(vocab=256, dim=64, n_layers=3, n_dense=1,
                            n_heads=4, q_rank=48, kv_rank=32,
                            qk_nope_dim=16, qk_rope_dim=8, v_dim=16,
                            ffn_dim=128, moe_hidden=32, n_experts=8,
                            experts_held=8, top_k=2, max_seq=128),
}

#: the cached attention computes at most this many bytes of float32 scores
#: at a time, a block of slots after another
SCORE_BYTES = 256 << 20

#: the pool's last axis is a whole number of these (_pool): a TPU tiles
#: an array's two minor axes by (8, 128), and a last axis that is no
#: multiple of 128 is not kept minor by the device's default layout
POOL_LANES = 128

#: what apply_cached's third value counts, summed over the expert layers
TICK_COUNTERS = ("ticks",) + X.HELD_COUNTERS
#: the cached attention reads a slot's context as far as it reaches, no
#: farther (paged.attend_by_blocks with a Bound)
BOUNDED_READ = True


# ----------------------------------------------------------------- weights
def _dense(key, i, o, cfg, scale=None):
    return L.dense_init(key, i, o, use_bias=False, scale=scale,
                        dtype=cfg.dtype)


def _gated_init(key, d, f, cfg):
    k = jax.random.split(key, 3)
    return {"w_gate": _dense(k[0], d, f, cfg), "w_up": _dense(k[1], d, f, cfg),
            "w_down": _dense(k[2], f, d, cfg)}


def init_layer(key, cfg: LatentMoeConfig, routed: bool) -> Dict[str, Any]:
    k = jax.random.split(key, 8)
    d, H = cfg.dim, cfg.n_heads
    norm = lambda n: L.rmsnorm_init(n, cfg.dtype)
    p = {"input_norm": norm(d), "post_attn_norm": norm(d),
         "pre_mlp_norm": norm(d), "post_mlp_norm": norm(d),
         "attn": {
             "wq_a": _dense(k[0], d, cfg.q_rank, cfg),
             "q_a_norm": norm(cfg.q_rank),
             "wq_b": _dense(k[1], cfg.q_rank, H * cfg.qk_dim, cfg),
             "wkv_a": _dense(k[2], d, cfg.latent_dim, cfg),
             "kv_a_norm": norm(cfg.kv_rank),
             "wkv_b": _dense(k[3], cfg.kv_rank,
                             H * (cfg.qk_nope_dim + cfg.v_dim), cfg),
             "wo": _dense(k[4], H * cfg.v_dim, d, cfg)}}
    if routed:
        p["moe"] = X.init_held_experts(k[5], d, cfg.moe_hidden, cfg.n_experts,
                                       cfg.experts_held, cfg.dtype)
        p["moe"]["shared"] = _gated_init(k[6], d,
                                         cfg.moe_hidden * cfg.n_shared, cfg)
    else:
        p["ffn"] = _gated_init(k[7], d, cfg.ffn_dim, cfg)
    return p


def init(key, cfg: LatentMoeConfig) -> Dict[str, Any]:
    keys = jax.random.split(key, cfg.n_layers + 2)
    return {"embed": L.embedding_init(keys[0], cfg.vocab, cfg.dim, cfg.dtype),
            "final_norm": L.rmsnorm_init(cfg.dim, cfg.dtype),
            "lm_head": _dense(keys[1], cfg.dim, cfg.vocab, cfg),
            "layers": [init_layer(keys[2 + i], cfg, routed=i >= cfg.n_dense)
                       for i in range(cfg.n_layers)]}


# ------------------------------------------------------------------ pieces
def _gated(p, x):
    return L.dense(p["w_down"],
                   jax.nn.silu(L.dense(p["w_gate"], x)) * L.dense(p["w_up"], x))


def _project(p, h, cfg, cos, sin, positions):
    """The projections of one layer's attention at explicit positions:
    (q_nope [.., H, nope], q_rope [.., H, rope], latent [.., kv_rank + rope])
    with the rotary parts rotated; the latent is what the cache holds."""
    B, S, _ = h.shape
    with jax.named_scope("attn/q_lora"):
        q = L.dense(p["wq_b"],
                    L.norm(p["q_a_norm"], L.dense(p["wq_a"], h), cfg))
        q = q.reshape(B, S, cfg.n_heads, cfg.qk_dim)
        q_nope, q_rope = q[..., :cfg.qk_nope_dim], q[..., cfg.qk_nope_dim:]
        q_rope = L.apply_rope_at(q_rope, cos, sin, positions)
    with jax.named_scope("attn/kv_latent"):
        kv = L.dense(p["wkv_a"], h)
        c_kv = L.norm(p["kv_a_norm"], kv[..., :cfg.kv_rank], cfg)
        k_rope = L.apply_rope_at(kv[..., None, cfg.kv_rank:], cos, sin,
                                 positions)[..., 0, :]
        latent = jnp.concatenate([c_kv, k_rope], -1)
    return q_nope, q_rope, latent


def _wkv_b(p, cfg):
    """``wkv_b`` by head: (W^K [kv_rank, H, nope], W^V [kv_rank, H, v])."""
    w = p["wkv_b"]["kernel"].reshape(cfg.kv_rank, cfg.n_heads,
                                     cfg.qk_nope_dim + cfg.v_dim)
    return w[..., :cfg.qk_nope_dim], w[..., cfg.qk_nope_dim:]


def _mlp(p, h, valid, cfg):
    """A layer's FFN half on normed input h [B, S, D]: (m, counters)."""
    if "ffn" in p:
        with jax.named_scope("ffn"):
            return _gated(p["ffn"], h), jnp.zeros(len(X.HELD_COUNTERS),
                                                  jnp.int32)
    with jax.named_scope("moe/shared"):
        shared = _gated(p["moe"]["shared"], h)
    y, counters = X.held_ffn(
        p["moe"], h, valid, first=cfg.first_expert, k=cfg.top_k,
        scale=cfg.route_scale, tile=EXPERT_TILE)
    with jax.named_scope("moe/combine"):
        return shared + y, counters


# ------------------------------------------------------- full-sequence path
def apply(params: Dict[str, Any], ids: jax.Array,
          cfg: LatentMoeConfig) -> jax.Array:
    """Forward without a cache: ids [B, S] -> logits [B, S, vocab], with K
    and V EXPANDED per head from the latent (the form the cached path
    absorbs).  For tests and for checking the cached path against."""
    B, S = ids.shape
    cos, sin = L.rope_freqs(cfg.qk_rope_dim, cfg.max_seq, cfg.rope_theta)
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    valid = jnp.ones((B, S), bool)
    x = L.embedding(params["embed"], ids).astype(cfg.dtype)
    for p in params["layers"]:
        a = p["attn"]
        q_nope, q_rope, latent = _project(a, L.norm(p["input_norm"], x, cfg),
                                          cfg, cos, sin, positions)
        wk, wv = _wkv_b(a, cfg)
        c_kv, k_rope = latent[..., :cfg.kv_rank], latent[..., cfg.kv_rank:]
        k = jnp.concatenate(
            [jnp.einsum("bsl,lhn->bshn", c_kv, wk),
             jnp.broadcast_to(k_rope[:, :, None, :],
                              (B, S, cfg.n_heads, cfg.qk_rope_dim))], -1)
        v = jnp.einsum("bsl,lhv->bshv", c_kv, wv)
        o = L.causal_attention(jnp.concatenate([q_nope, q_rope], -1), k, v)
        o = L.dense(a["wo"], o.reshape(B, S, cfg.n_heads * cfg.v_dim))
        x = x + L.norm(p["post_attn_norm"], o, cfg)
        m, _ = _mlp(p, L.norm(p["pre_mlp_norm"], x, cfg), valid, cfg)
        x = x + L.norm(p["post_mlp_norm"], m, cfg)
    return _logits(params, cfg, x)


# ------------------------------------------------------------- decode path
def _pool(cfg) -> Tuple[paged.CacheKind, ...]:
    """The one kind of cache, without a name: ``[c_kv | k_rope | zeros]`` a
    position, whatever the number of heads.  The zeros make the pool's
    device layout the one the tick works in: the shape decides it
    (docs/serving.md#where-the-pool-lies), and with kv_rank + qk_rope_dim =
    576 columns a TPU keeps the BLOCK axis minor, while the tick addresses
    the pool by ``[layer, block]`` and so relaid all of it on the way into
    and out of every tick (PERF.md §6, PR 36).  At 640 it lies row-major, in
    the bytes 576 columns take there anyway (tiles of 128 lanes)."""
    return (paged.CacheKind(None, cfg.n_layers,
                            leaves={"latent": (cfg.pool_dim,)}),)


def init_cache(cfg: LatentMoeConfig, num_blocks: int, block_size: int,
               dtype=None) -> Dict[str, jax.Array]:
    """The latent paged pool: ``{"latent": [n_layers, num_blocks,
    block_size, pool_dim]}`` (:func:`_pool` says why that wide)."""
    return paged.init_pools(_pool(cfg), num_blocks, block_size,
                            dtype if dtype is not None else cfg.dtype)


def cache_shardings(mesh, cfg: LatentMoeConfig, num_blocks: int):
    """The latent is every head's: no axis of it goes over a model axis."""
    return paged.pool_shardings(mesh, _pool(cfg), num_blocks)


copy_blocks = paged.copy_blocks


def attn_blocks(cfg: LatentMoeConfig, S: int, C: int, ctx: int
                ) -> Tuple[int, int]:
    """(slots a block, narrow columns) of :func:`_latent_attention` in a
    ``[S, C]`` tick over ``ctx`` positions, for the engine's counters."""
    return paged.attn_blocks(cfg.n_heads, S, C, ctx, SCORE_BYTES,
                             NARROW_COLS)


def _latent_tile(kv_rank: int, scale: float, q, pos, ctx, start):
    """One tile of a block of slots' absorbed attention, as
    paged.attend_by_blocks runs it: q [s, c, H, kv_rank + rope] (``q_nope
    W^K`` beside the rotated ``q_rope``) against the tile's latent
    ``ctx["latent"]`` [s, keys, kv_rank + rope], whose first key is position
    ``start``: float32 scores [s, H, c, keys], masked, and the value product
    with the latent's ``c_kv`` part [s, H, c, kv_rank]."""
    lat = ctx["latent"]
    s = jnp.einsum("schx,skx->shck", q, lat,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(paged.context_mask(pos - start, lat.shape[1]), s,
                  jnp.finfo(jnp.float32).min)
    return s, lambda p: jnp.einsum(
        "shck,skl->shcl", p.astype(lat.dtype), lat[..., :kv_rank],
        preferred_element_type=jnp.float32)


def latent_attend(cfg: LatentMoeConfig):
    """:func:`_latent_tile` for ``cfg``: ONE object a tick, which every
    layer hands paged.attend_by_blocks (its trace is shared by the layers)."""
    return functools.partial(_latent_tile, cfg.kv_rank,
                             1.0 / math.sqrt(cfg.qk_dim))


def _logits(params, cfg, x):
    """The final norm and the output head on hidden states ``[.., dim]``."""
    return L.dense(params["lm_head"], L.norm(params["final_norm"], x, cfg))


def _forward(params, tokens, cfg, cache, block_tables, lengths, n_new, head):
    """The tick's rows through the stack (decoder.forward): sandwich norms
    round the absorbed attention over the latent pool and round the FFN
    half; only the absorbed queries go back to their slots."""
    S, C = tokens.shape
    cos, sin = L.rope_freqs(cfg.qk_rope_dim, cfg.max_seq, cfg.rope_theta)
    blocks = attn_blocks(cfg, S, C,
                         block_tables.shape[1] * cache["latent"].shape[2])
    attend = latent_attend(cfg)
    # the pool's zero columns (:func:`_pool`), as the pool in hand has them
    pad = cache["latent"].shape[-1] - cfg.latent_dim

    def layer(i, p, x, cache, t):
        a, rows = p["attn"], x.shape[:2]
        q_nope, q_rope, latent = _project(
            a, L.norm(p["input_norm"], x, cfg), cfg, cos, sin, t.pos)
        cache = paged.write(
            cache, i, *t.where[None],
            {"latent": jnp.pad(latent.astype(cache["latent"].dtype),
                               ((0, 0), (0, 0), (0, pad)))})
        wk, wv = _wkv_b(a, cfg)
        with jax.named_scope("attn/latent_scores"):
            q = jnp.concatenate(
                [jnp.einsum("brhn,lhn->brhl", q_nope, wk), q_rope], -1)
            # read through the pool's first latent_dim columns: on the
            # chip the same bytes under another shape, no op of its own
            o = paged.attend_by_blocks(
                attend, (q, t.positions, block_tables), t.n_new, *blocks,
                bound=paged.Bound(
                    t.lengths,
                    {"latent": cache["latent"][..., :cfg.latent_dim]},
                    i, t.slab))
            # [S, H, C, kv_rank] -> the rows
            o = t.take(jnp.swapaxes(o, 1, 2))
        with jax.named_scope("attn/out"):
            o = jnp.einsum("brhl,lhv->brhv", o, wv)
            o = L.dense(a["wo"], o.reshape(rows + (cfg.n_heads * cfg.v_dim,)))
            x = x + L.norm(p["post_attn_norm"], o, cfg)
        m, c = _mlp(p, L.norm(p["pre_mlp_norm"], x, cfg), t.valid, cfg)
        return x + L.norm(p["post_mlp_norm"], m, cfg), cache, c
    return decoder.forward(
        layer, functools.partial(_logits, params, cfg), _pool(cfg), params,
        tokens, cfg, cache, block_tables, lengths, n_new, head,
        counters=TICK_COUNTERS, max_seq=cfg.max_seq, reads=("valid", "pos"))


#: decoder.cached_pair has the contract: the third value is the counters
#: summed over the expert layers, the greedy tokens those of the columns the
#: tick reads.
apply_cached, greedy_cached = decoder.cached_pair(_forward, read=True)


def param_count(cfg: LatentMoeConfig) -> int:
    d, H = cfg.dim, cfg.n_heads
    attn = (d * cfg.q_rank + cfg.q_rank * H * cfg.qk_dim + d * cfg.latent_dim
            + cfg.kv_rank * H * (cfg.qk_nope_dim + cfg.v_dim)
            + H * cfg.v_dim * d + cfg.q_rank + cfg.kv_rank + 4 * d)
    expert = 3 * d * cfg.moe_hidden
    routed = d * cfg.n_experts + (cfg.experts_held + cfg.n_shared) * expert
    return (cfg.n_layers * attn + cfg.n_dense * 3 * d * cfg.ffn_dim
            + (cfg.n_layers - cfg.n_dense) * routed + 2 * cfg.vocab * d + d)


__all__ = ["LatentMoeConfig", "CONFIGS", "TICK_COUNTERS", "BOUNDED_READ",
           "init", "apply",
           "init_cache", "cache_shardings", "copy_blocks", "apply_cached",
           "greedy_cached", "attn_blocks", "param_count"]
