"""MoE decoder: Llama attention blocks with switch-MoE FFNs.

Beyond-reference model family (the reference ships no models; its
examples use torchvision/keras zoos).  The expert layer shares its
parameter layout and routing math with ``parallel/expert.py`` — the SAME
``{"router", "wi", "wo"}`` pytree runs dense on one chip (this module's
default path, used for tests/inference) or expert-parallel over an
``ep`` mesh axis via :func:`horovod_tpu.parallel.expert.make_moe_fn`
(pass it as ``moe_fn``), so checkpoints move freely between layouts.

Serving here runs EVERY expert on every token (``dropfree_moe_fn``): fine
for a handful of small experts.  A model with many gated experts of which a
chip holds a share is served by ``parallel/expert.py`` ``held_experts``
(sorted dispatch, work that grows with the assignments held; used by
``models/latent_moe.py``, docs/serving.md#held-experts).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from . import decoder
from . import layers as L
from . import llama as Ll
from ..parallel.expert import init_moe_params, moe_dense_reference


@dataclasses.dataclass(frozen=True)
class MoeLlamaConfig:
    vocab: int = 4096
    dim: int = 256
    n_layers: int = 4
    n_heads: int = 8
    n_kv_heads: int = 4
    moe_hidden: int = 512
    n_experts: int = 8
    experts_per_token: int = 1  # 1 = Switch, 2 = Mixtral top-2
    capacity_factor: float = 1.25
    router_aux_coef: float = 0.01
    max_seq: int = 512
    rope_theta: float = 10000.0
    dtype: Any = jnp.float32
    max_tick_tokens: int = 0    # llama.LlamaConfig's: the engine's budget

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads


CONFIGS = {
    "tiny": MoeLlamaConfig(vocab=256, dim=64, n_layers=2, n_heads=4,
                           n_kv_heads=2, moe_hidden=128, n_experts=4,
                           max_seq=128),
    "mini": MoeLlamaConfig(),
    # Mixtral-style top-2 routing with renormalized gates
    "mixtral-tiny": MoeLlamaConfig(vocab=256, dim=64, n_layers=2,
                                   n_heads=4, n_kv_heads=2,
                                   moe_hidden=128, n_experts=8,
                                   experts_per_token=2, max_seq=128),
}


def _llama_cfg(cfg: MoeLlamaConfig) -> Ll.LlamaConfig:
    """The attention half of a layer is exactly llama's."""
    return Ll.LlamaConfig(vocab=cfg.vocab, dim=cfg.dim,
                          n_layers=cfg.n_layers, n_heads=cfg.n_heads,
                          n_kv_heads=cfg.n_kv_heads, ffn_dim=1,
                          max_seq=cfg.max_seq, rope_theta=cfg.rope_theta,
                          dtype=cfg.dtype,
                          max_tick_tokens=cfg.max_tick_tokens)


def init(key, cfg: MoeLlamaConfig) -> Dict[str, Any]:
    keys = jax.random.split(key, cfg.n_layers + 2)
    lcfg = _llama_cfg(cfg)
    layers = []
    for i in range(cfg.n_layers):
        ka, km = jax.random.split(keys[2 + i])
        lp = Ll.init_layer(ka, lcfg)
        # drop the dense FFN; the MoE block replaces it
        for k in ("w_gate", "w_up", "w_down"):
            lp.pop(k)
        lp["moe"] = init_moe_params(km, cfg.dim, cfg.moe_hidden,
                                    cfg.n_experts, dtype=cfg.dtype)
        layers.append(lp)
    return {
        "embed": L.embedding_init(keys[0], cfg.vocab, cfg.dim, cfg.dtype),
        "final_norm": L.rmsnorm_init(cfg.dim, cfg.dtype),
        "lm_head": L.dense_init(keys[1], cfg.dim, cfg.vocab,
                                use_bias=False,
                                scale=1.0 / math.sqrt(cfg.dim),
                                dtype=cfg.dtype),
        "layers": layers,
    }


def _moe_block(p_moe: Dict[str, Any], x: jax.Array,
               cfg: MoeLlamaConfig,
               moe_fn: Optional[Callable]) -> tuple[jax.Array, jax.Array]:
    """[B, S, D] -> ([B, S, D], aux).  Dense single-chip path by default;
    an injected ``moe_fn`` (from parallel/expert.make_moe_fn) runs the
    expert-parallel all_to_all path with the same params."""
    B, S, D = x.shape
    tokens = x.reshape(B * S, D)
    if moe_fn is not None:
        y, aux = moe_fn(p_moe, tokens)
    else:
        capacity = int(math.ceil(B * S * cfg.experts_per_token *
                                 cfg.capacity_factor / cfg.n_experts))
        y, aux = moe_dense_reference(p_moe, tokens, cfg.n_experts,
                                     capacity,
                                     experts_per_token=cfg.experts_per_token)
    return y.reshape(B, S, D), aux


def apply(params: Dict[str, Any], ids: jax.Array, cfg: MoeLlamaConfig,
          moe_fn: Optional[Callable] = None,
          attn_fn=None) -> tuple[jax.Array, jax.Array]:
    """Forward: ids [B, S] -> (logits [B, S, vocab], mean router aux)."""
    lcfg = _llama_cfg(cfg)
    cos, sin = L.rope_freqs(cfg.head_dim, cfg.max_seq, cfg.rope_theta)
    x = L.embedding(params["embed"], ids).astype(cfg.dtype)
    auxes = []
    for p in params["layers"]:
        x = x + Ll._attn(p, L.rmsnorm(p["attn_norm"], x), lcfg, cos, sin,
                         attn_fn)
        y, aux = _moe_block(p["moe"], L.rmsnorm(p["ffn_norm"], x), cfg,
                            moe_fn)
        x = x + y
        auxes.append(aux)
    x = L.rmsnorm(params["final_norm"], x)
    return L.dense(params["lm_head"], x), jnp.mean(jnp.stack(auxes))


def loss_fn(params: Dict[str, Any], ids: jax.Array, cfg: MoeLlamaConfig,
            moe_fn: Optional[Callable] = None) -> jax.Array:
    """Next-token cross-entropy + router load-balancing aux."""
    logits, aux = apply(params, ids[:, :-1], cfg, moe_fn=moe_fn)
    targets = ids[:, 1:]
    nll = L.softmax_cross_entropy(logits, targets)
    return jnp.mean(nll) + cfg.router_aux_coef * aux


# ----------------------------------------------------------- decode path
def dropfree_moe_fn(cfg: MoeLlamaConfig) -> Callable:
    """Batch-invariant dense MoE for serving: capacity equals the token
    count, so no token is ever capacity-dropped and a request's logits
    cannot depend on its batchmates.  Training's capacity-bounded
    routing drops tokens by batch position — under continuous batching
    that would make a sequence's output a function of which other
    requests share its tick, which serving must never allow (and which
    would break the prefill+decode ≡ full-forward equivalence).  Pass
    the same fn to :func:`apply` when comparing against the cached path
    (tests/test_serve.py; docs/serving.md).  The price is every expert on
    every token (capacity = T in ``moe_dense_reference``);
    ``parallel/expert.py`` ``held_experts`` is drop-free and
    batch-invariant at a cost that grows with the assignments instead."""
    def fn(p_moe: Dict[str, Any], tokens: jax.Array):
        return moe_dense_reference(p_moe, tokens, cfg.n_experts,
                                   capacity=tokens.shape[0],
                                   experts_per_token=cfg.experts_per_token)
    return fn


# The attention half IS llama's, so the paged pool, its copy-on-write clone
# and its sharding are too (they read n_layers, n_kv_heads, head_dim, dtype).
_pool, init_cache, copy_blocks = Ll._pool, Ll.init_cache, Ll.copy_blocks
cache_shardings, BOUNDED_READ = Ll.cache_shardings, Ll.BOUNDED_READ
#: ServeEngine ignores the third value of apply_cached, the router aux
TICK_COUNTERS = ()


def attn_blocks(cfg: MoeLlamaConfig, S: int, C: int, ctx: int):
    """llama.attn_blocks: the attention half is llama's."""
    return Ll.attn_blocks(_llama_cfg(cfg), S, C, ctx)


def _forward(params, tokens, cfg, cache, block_tables, lengths, n_new, head,
             moe_fn: Optional[Callable] = None):
    """llama._forward with the expert block for the FFN (decoder.forward):
    (``head``'s, cache, mean router aux over the rows).  ``moe_fn`` defaults
    to the drop-free dense path — the batch-invariant serving routing."""
    lcfg = _llama_cfg(cfg)
    moe_fn = moe_fn if moe_fn is not None else dropfree_moe_fn(cfg)
    cos, sin = L.rope_freqs(cfg.head_dim, cfg.max_seq, cfg.rope_theta)
    auxes = []

    def layer(i, p, x, cache, t):
        a, cache = Ll._attn_cached(
            p, L.rmsnorm(p["attn_norm"], x), lcfg, cos, sin,
            cache, i, block_tables, t)
        x = x + a
        y, aux = _moe_block(p["moe"], L.rmsnorm(p["ffn_norm"], x), cfg,
                            moe_fn)
        auxes.append(aux)
        return x + y, cache
    return decoder.forward(
        layer, functools.partial(Ll._logits, params), _pool(cfg), params,
        tokens, cfg, cache, block_tables, lengths, n_new, head,
        max_seq=cfg.max_seq, reads=("pos",)) + (jnp.mean(jnp.stack(auxes)),)


#: llama's pair (decoder.cached_pair has the contract) with the mean router
#: aux over the rows behind the cache; each takes ``moe_fn``.
apply_cached, greedy_cached = decoder.cached_pair(_forward, read=True)


def param_count(cfg: MoeLlamaConfig) -> int:
    attn = (cfg.dim * cfg.n_heads * cfg.head_dim
            + 2 * cfg.dim * cfg.n_kv_heads * cfg.head_dim
            + cfg.n_heads * cfg.head_dim * cfg.dim + 2 * cfg.dim)
    moe = (cfg.dim * cfg.n_experts
           + 2 * cfg.n_experts * cfg.dim * cfg.moe_hidden)
    return (cfg.n_layers * (attn + moe)
            + 2 * cfg.vocab * cfg.dim + cfg.dim)


__all__ = ["MoeLlamaConfig", "CONFIGS", "init", "apply", "loss_fn",
           "param_count", "init_cache", "apply_cached", "greedy_cached",
           "copy_blocks",
           "cache_shardings", "TICK_COUNTERS", "BOUNDED_READ", "attn_blocks",
           "dropfree_moe_fn"]
