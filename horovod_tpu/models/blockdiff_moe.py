"""Sparse decoder that generates by DENOISING A BLOCK of positions at a time
— written by its mechanisms, so that any model built from them is a config
away:

  * **One kind of layer.**  ``r = x + Attn(norm(x))``, ``x' = r +
    MoE(norm(r))``.
  * **Attention under a block-causal mask**: a position sees every position
    of its own block of ``block_length`` and of earlier blocks (key ``j`` is
    visible to query ``i`` iff ``j // B <= i // B``; ``B = 1`` is the causal
    mask).  Grouped-query heads with an RMS norm with its own gain over the
    ``head_dim`` of each query and key head BEFORE the rotary encoding, the
    whole context, no window.
  * **Routed experts alone**: router logits over all ``n_experts`` in
    float32, the ``top_k`` largest, gates the softmax over the chosen
    (parallel/expert.py ``route_softmax_topk``).  No shared expert, no dense
    layer.  This chip holds ``experts_held`` of them from ``first_expert``.
  * **An untied head** after the final norm, and **no shift**: the logits at
    a position predict THAT position's token.  A position not yet known
    holds ``mask_token_id`` (M).  A masked position's CANDIDATE is its best
    id other than M, its CONFIDENCE that id's softmax probability among the
    ids other than M, in float32 (:func:`greedy_cached`).

How a block is filled from candidates and confidences — every masked position
whose confidence reaches ``unmask_threshold`` and in any case the
``block_length // denoising_steps`` most confident, pass after pass, then one
more pass over the full block whose keys and values stay in the pool — is the
serving tick's (serve/engine.py ``block_tick_program``; docs/serving.md
#block-denoising); :func:`denoise` is the same rule as a plain loop without a
cache, the oracle the tests hold the engine to.

Serving contract as models/llama.py: one paged pool of keys and values, read
as far as a slot's context reaches (``attend_by_blocks`` with a ``Bound``),
and the module samples on the tick's packed rows.  A tick's rows are written
into the pool before they are read, a denoising pass's like a committing
one's: the next pass of the block overwrites them, and no other reader sees
them, because the slot's length has not moved.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import decoder
from . import layers as L
from . import paged
from .paged import NARROW_COLS
from ..parallel import expert as X
from ..parallel.expert import EXPERT_TILE


@dataclasses.dataclass(frozen=True)
class BlockDiffMoeConfig:
    vocab: int = 4096
    dim: int = 256
    n_layers: int = 4
    n_heads: int = 8
    n_kv_heads: int = 2
    head_dim: int = 32
    moe_hidden: int = 64         # one expert
    n_experts: int = 16          # the router's width
    experts_held: int = 16       # ... of which this chip holds
    first_expert: int = 0        # ... starting here
    top_k: int = 4
    norm_eps: float = 1e-6
    max_seq: int = 512
    rope_theta: float = 1000000.0
    dtype: Any = jnp.float32
    # Generation (docs/serving.md#block-denoising): the block, the id a
    # position holds until it is known, the confidence from which a pass
    # fixes a position, and the passes a block takes at the most (a pass
    # fixes at least ``block_length // denoising_steps`` positions).
    block_length: int = 4
    mask_token_id: int = 4095
    unmask_threshold: float = 0.9
    denoising_steps: int = 4
    # The most valid tokens one call of apply_cached holds (models/paged.py
    # pack); ServeEngine sets it to its own max_batch_tokens; 0 = every
    # position of the slab.
    max_tick_tokens: int = 0

    def __post_init__(self):
        B, n = self.block_length, self.denoising_steps
        if B < 1 or n < 1 or B % n:
            raise ValueError(
                f"block_length {B} / denoising_steps {n}: a pass fixes "
                "block_length // denoising_steps positions at the least, so "
                "the steps must divide the block")
        if not 0 <= self.mask_token_id < self.vocab:
            raise ValueError(f"mask_token_id {self.mask_token_id} is no id "
                             f"of a vocabulary of {self.vocab}")


CONFIGS = {
    "tiny": BlockDiffMoeConfig(vocab=256, dim=64, n_layers=3, n_heads=4,
                               n_kv_heads=2, head_dim=16, moe_hidden=32,
                               n_experts=8, experts_held=8, top_k=2,
                               max_seq=128, mask_token_id=255,
                               unmask_threshold=0.05),
}

#: float32 scores one block of slots may hold (heads x columns x one tile of
#: context x 4 B a slot)
SCORE_BYTES = 32 << 20
#: the cached attention reads a slot's context as far as it reaches
BOUNDED_READ = True

#: what the third value of apply_cached counts, summed over the layers
TICK_COUNTERS = ("ticks",) + X.HELD_COUNTERS


# ----------------------------------------------------------------- weights
def init_layer(key, cfg: BlockDiffMoeConfig) -> Dict[str, Any]:
    k = jax.random.split(key, 5)
    d, hd = cfg.dim, cfg.head_dim
    dense = lambda key, i, o: L.dense_init(key, i, o, use_bias=False,
                                           dtype=cfg.dtype)
    return {"attn_norm": L.rmsnorm_init(d, cfg.dtype),
            "ffn_norm": L.rmsnorm_init(d, cfg.dtype),
            "attn": {"wq": dense(k[0], d, cfg.n_heads * hd),
                     "wk": dense(k[1], d, cfg.n_kv_heads * hd),
                     "wv": dense(k[2], d, cfg.n_kv_heads * hd),
                     "wo": dense(k[3], cfg.n_heads * hd, d),
                     "q_norm": L.rmsnorm_init(hd, cfg.dtype),
                     "k_norm": L.rmsnorm_init(hd, cfg.dtype)},
            "moe": X.init_held_experts(k[4], d, cfg.moe_hidden, cfg.n_experts,
                                       cfg.experts_held, cfg.dtype)}


def init(key, cfg: BlockDiffMoeConfig, head_std: float = None
         ) -> Dict[str, Any]:
    """``head_std``: the head's scale (``dim ** -0.5`` without one).  The
    confidences a seeded head gives grow with it: a test that wants passes
    to fix several positions draws a wide one."""
    keys = jax.random.split(key, cfg.n_layers + 2)
    head = L.dense_init(keys[1], cfg.dim, cfg.vocab, use_bias=False,
                        dtype=jnp.float32)
    if head_std is not None:
        head = {"kernel": head["kernel"] * (head_std * cfg.dim ** 0.5)}
    return {"embed": L.embedding_init(keys[0], cfg.vocab, cfg.dim, cfg.dtype),
            "final_norm": L.rmsnorm_init(cfg.dim, cfg.dtype),
            "head": {"kernel": head["kernel"].astype(cfg.dtype)},
            "layers": [init_layer(keys[2 + i], cfg)
                       for i in range(cfg.n_layers)]}


# ------------------------------------------------------------------ pieces
def _moe(p, h, valid, cfg):
    """This chip's experts on h [B, S, D] under the renormalised softmax
    router: (y, counters)."""
    route = lambda rows: X.route_softmax_topk(rows, p["router"]["kernel"],
                                              cfg.top_k)
    return X.held_ffn(p, h, valid, route=route, first=cfg.first_expert,
                      act=jax.nn.silu, tile=EXPERT_TILE)


def _logits(params, x, cfg):
    """Float32 logits of hidden states x [.., D]: the final norm, then the
    head, its products summed in float32 and never rounded to the rows'
    type (a confidence is compared with a threshold)."""
    return jnp.dot(L.norm(params["final_norm"], x, cfg),
                   params["head"]["kernel"],
                   preferred_element_type=jnp.float32)


def candidates(logits: jax.Array, cfg: BlockDiffMoeConfig
               ) -> Tuple[jax.Array, jax.Array]:
    """(candidate int32 [..], confidence float32 [..]) of logits [.., V]:
    the best id other than M and its softmax probability among the ids
    other than M."""
    with jax.named_scope("tick/unmask"):
        z = logits.astype(jnp.float32)
        z = jnp.where(jnp.arange(z.shape[-1]) == cfg.mask_token_id,
                      jnp.finfo(jnp.float32).min, z)
        top = jnp.max(z, axis=-1)
        conf = 1.0 / jnp.sum(jnp.exp(z - top[..., None]), axis=-1)
        return jnp.argmax(z, axis=-1).astype(jnp.int32), conf


def fix_positions(conf: jax.Array, masked: jax.Array,
                  cfg: BlockDiffMoeConfig) -> Tuple[jax.Array, jax.Array]:
    """The rule of one denoising pass over blocks ``[.., B]``: of the
    ``masked`` positions (bool) those whose confidence is at least
    ``unmask_threshold`` are fixed, and in any case the ``block_length //
    denoising_steps`` most confident of them (the earlier position where two
    are equal).  Returns bool (fixed, fixed by the threshold)."""
    B = conf.shape[-1]
    c = jnp.where(masked, conf, -1.0)
    # a position's rank among the masked ones, the most confident first
    ahead = (c[..., None, :] > c[..., :, None]) | (
        (c[..., None, :] == c[..., :, None])
        & (jnp.arange(B)[None, :] < jnp.arange(B)[:, None]))
    rank = jnp.sum(ahead, axis=-1)
    sure = masked & (conf >= cfg.unmask_threshold)
    return sure | (masked & (rank < B // cfg.denoising_steps)), sure


# ------------------------------------------------------- full-sequence path
def apply(params: Dict[str, Any], ids: jax.Array, cfg: BlockDiffMoeConfig,
          block: int = None) -> jax.Array:
    """Forward without a cache under the block-causal mask: ids [B, S] ->
    float32 logits [B, S, vocab].  For tests and for checking the cached
    path against; ``block`` = 1 is the causal mask in the block mask's
    place (what this model does NOT do)."""
    B, S = ids.shape
    Bk = block or cfg.block_length
    cos, sin = L.rope_freqs(cfg.head_dim, cfg.max_seq, cfg.rope_theta)
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    valid = jnp.ones((B, S), bool)
    see = (jnp.arange(S)[None, :] // Bk <= jnp.arange(S)[:, None] // Bk)
    x = L.embedding(params["embed"], ids).astype(cfg.dtype)
    for p in params["layers"][:cfg.n_layers]:
        q, k, v = L.qkv(p["attn"], L.norm(p["attn_norm"], x, cfg), cfg, cos,
                        sin, positions, qk_norm=True)
        o = L.causal_attention(q, k, v, causal=False, mask=see[None, None])
        x = x + L.dense(p["attn"]["wo"], o.reshape(B, S, -1))
        y, _ = _moe(p["moe"], L.norm(p["ffn_norm"], x, cfg), valid, cfg)
        x = x + y
    return _logits(params, x, cfg)


def denoise(params: Dict[str, Any], prompt: List[int], max_new_tokens: int,
            cfg: BlockDiffMoeConfig, forward: Callable = None
            ) -> Tuple[List[int], List[int]]:
    """The generation rule as a plain loop without a cache: (tokens, steps)
    of one prompt, ``steps`` the pass (0-based, counted a block) in which
    each served token was fixed.  The prompt's ``len(prompt) % B`` last
    tokens are known positions of the first generated block; every block is
    generated whole, the last one too, and the answer is cut to
    ``max_new_tokens``.  ``forward(ids [1, S]) -> logits`` defaults to
    :func:`apply`."""
    B, M = cfg.block_length, cfg.mask_token_id
    forward = forward or jax.jit(lambda ids: apply(params, ids, cfg))
    p, end = len(prompt), len(prompt) + max_new_tokens
    row = list(prompt)
    steps: List[int] = []
    for start in range(p - p % B, end, B):
        row = row[:start + B] + [M] * (start + B - len(row))
        masked = np.arange(start, start + B) >= p
        step = np.zeros(B, np.int64)
        n = 0
        while masked.any():
            # padded to the model's positions: one program however long
            ids = np.zeros((1, cfg.max_seq), np.int32)
            ids[0, :start + B] = row
            z = forward(jnp.asarray(ids))[0, start:start + B]
            cand, conf = candidates(z, cfg)
            fix, _ = fix_positions(conf, jnp.asarray(masked), cfg)
            for j in np.flatnonzero(np.asarray(fix)):
                row[start + j], step[j] = int(cand[j]), n
            masked &= ~np.asarray(fix)
            n += 1
        steps += [int(s) for j, s in enumerate(step) if p <= start + j < end]
    return row[p:end], steps


# ------------------------------------------------------------- decode path
def _pool(cfg) -> Tuple[paged.CacheKind, ...]:
    """The one kind of cache, without a name: every layer's keys and values,
    a position's heads side by side."""
    heads = (cfg.n_kv_heads * cfg.head_dim,)
    return (paged.CacheKind(None, cfg.n_layers,
                            leaves={"k": heads, "v": heads}),)


def init_cache(cfg: BlockDiffMoeConfig, num_blocks: int, block_size: int,
               dtype=None) -> Dict[str, jax.Array]:
    """``{"k", "v"}`` of ``[layers, num_blocks, block_size, n_kv_heads *
    head_dim]``, a position's heads side by side."""
    return paged.init_pools(_pool(cfg), num_blocks, block_size,
                            dtype if dtype is not None else cfg.dtype)


def cache_shardings(mesh, cfg: BlockDiffMoeConfig, num_blocks: int):
    """The pool's blocks over the data axis."""
    return paged.pool_shardings(mesh, _pool(cfg), num_blocks)


#: Prefix blocks' clones, as every whole-context pool has them; the engine
#: refuses prefix sharing for a model with a block length and asks for none.
copy_blocks = paged.copy_blocks


def attn_blocks(cfg: BlockDiffMoeConfig, S: int, C: int, ctx: int
                ) -> Tuple[int, int]:
    """(slots a block, narrow columns) of the cached attention in a
    ``[S, C]`` tick over ``ctx`` gathered positions."""
    return paged.attn_blocks(cfg.n_heads, S, C, ctx, SCORE_BYTES,
                             NARROW_COLS)


@functools.lru_cache(maxsize=None)
def _attend_tile(block: int) -> Callable:
    """One tile of a block of slots' cached attention under the block mask
    of ``block`` positions (paged.attend_by_blocks with a bound: ONE
    function object a block length, so that the layers of a tick share one
    trace), as models/llama.py ``_attend_tile`` with the tile's keys and
    values ``ctx`` [s, keys, kv heads * head_dim] cut into heads here; they
    begin at position ``start`` and ``held`` [s] is each slot's length once
    the tick's rows are in."""
    def attend(q, pos, ctx, held, start):
        heads = lambda a: a.reshape(a.shape[:2] + (-1, q.shape[-1]))
        return L.attention_tile(
            q, heads(ctx["k"]), heads(ctx["v"]),
            paged.context_mask(pos - start, ctx["k"].shape[1], block,
                               held - start))
    return attend


def _attn_cached(p, h, cfg, i, cos, sin, attend, cache, table,
                 t: paged.Tick):
    """Attention layer i over the pool, in place: the rows' k/v are
    scattered in first (``kv_commit``: a committing pass's stand, a
    denoising pass's are overwritten by the block's next pass), then each
    block of slots attends a tile of context after another as far as its
    slots' contexts reach, under the block mask (``attn/block``; ``attend``
    is :func:`_attend_tile` of the block length)."""
    rows = h.shape[:2]
    q, k, v = L.qkv(p, h, cfg, cos, sin, t.pos, qk_norm=True)
    flat = lambda a: a.reshape(rows + (-1,))
    with jax.named_scope("kv_commit"):
        pool = paged.write(cache, i, *t.where[None],
                           {"k": flat(k), "v": flat(v)})
    with jax.named_scope("attn/block"):
        o = paged.attend_by_blocks(
            attend, (q, t.positions, table, t.lengths + t.n_new), t.n_new,
            *attn_blocks(cfg, *t.positions.shape,
                         table.shape[1] * pool["k"].shape[2]),
            bound=paged.Bound(t.lengths, pool, i, t.slab))
        # [S, Hkv, rep, C, head_dim] -> the rows
        o = t.take(jnp.moveaxis(o, 3, 1))
    return L.dense(p["wo"], o.reshape(rows + (-1,))), pool


def _forward(params, tokens, cfg, cache, table, lengths, n_new, head):
    """The tick's rows through the stack (decoder.forward): attention under
    the block mask, then the routed experts; the head's logits float32."""
    cos, sin = L.rope_freqs(cfg.head_dim, cfg.max_seq, cfg.rope_theta)
    attend = _attend_tile(cfg.block_length)

    def layer(i, p, x, cache, t):
        a, cache = _attn_cached(p["attn"], L.norm(p["attn_norm"], x, cfg),
                                cfg, i, cos, sin, attend, cache, table, t)
        x = x + a
        y, c = _moe(p["moe"], L.norm(p["ffn_norm"], x, cfg), t.valid, cfg)
        return x + y, cache, c
    return decoder.forward(
        layer, lambda x: _logits(params, x, cfg), _pool(cfg), params, tokens,
        cfg, cache, table, lengths, n_new, head, counters=TICK_COUNTERS,
        max_seq=cfg.max_seq, reads=("valid", "pos"))


#: decoder.cached_pair has the contract, here under the block mask: a slot's
#: rows are positions ``lengths .. lengths + n_new - 1`` — whole blocks, from
#: a block's first position — and see each other and everything before them.
#: The logits are float32, the third value the counters summed over the
#: layers, and in the greedy token's place stand each position's candidate
#: and confidence (:func:`candidates`): ``((int32 [S, C], float32 [S, C]),
#: cache, counters)``.
apply_cached, greedy_cached = decoder.cached_pair(_forward, sample=candidates)


def param_count(cfg: BlockDiffMoeConfig) -> int:
    d, hd = cfg.dim, cfg.head_dim
    attn = 2 * d * cfg.n_heads * hd + 2 * d * cfg.n_kv_heads * hd + 2 * hd
    routed = d * cfg.n_experts + cfg.experts_held * 3 * d * cfg.moe_hidden
    return 2 * cfg.vocab * d + d + cfg.n_layers * (attn + routed + 2 * d)


__all__ = ["BlockDiffMoeConfig", "CONFIGS", "TICK_COUNTERS", "BOUNDED_READ",
           "init", "apply", "denoise", "candidates", "fix_positions",
           "init_cache", "cache_shardings", "copy_blocks", "apply_cached",
           "greedy_cached", "attn_blocks", "param_count"]
