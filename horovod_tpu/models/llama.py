"""Llama-3-style decoder transformer — the flagship model.

BASELINE config 3: "Llama-3 8B torch FSDP-style shard with
hvd.allgather/reduce_scatter + Adasum"; metric tokens/sec/chip.  This is a
faithful Llama-3 architecture (RMSNorm pre-norm, RoPE theta=500000, GQA,
SwiGLU), written pure-JAX so parallelism is applied from outside:

  * DP:  shard batch over the mesh, sync grads via DistributedOptimizer.
  * FSDP: shard params over the mesh axis; jax sharding constraints make XLA
    insert all_gather on use + reduce_scatter on grads (parallel/fsdp.py).
  * TP: head- and ffn-dim shardings (parallel/tensor.py).
  * SP: sequence-sharded inputs with ulysses all_to_all or ring attention
    (parallel/sequence.py).

Sizes follow the published Llama-3 family; ``tiny``/``mini`` configs exist
for tests and the single-chip bench.
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


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab: int = 128256
    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    ffn_dim: int = 14336
    max_seq: int = 8192
    rope_theta: float = 500000.0
    dtype: Any = jnp.bfloat16
    # Concatenate the q/k/v (and gate/up) kernels at apply time and issue ONE
    # matmul per site: the residual stream is read once instead of 3x (2x for
    # the ffn) per layer, and the MXU sees a larger N dim.  Bit-identical to
    # the unfused path (each output column contracts the same weight column);
    # off by default because TP shards the individual kernels along their
    # output dims and the concat would cross that sharding.  Ignored (falls
    # back to separate matmuls) when a projection carries a bias term.
    fuse_proj: bool = False
    # Serving only: the most tokens one tick's plan holds.  ServeEngine
    # writes the scheduler's ``max_batch_tokens`` here, and apply_cached then
    # runs what is a token's own on that many packed rows where the tick's
    # slab has more positions (paged.pack).  0 = no promise, nothing packed.
    max_tick_tokens: int = 0

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads


CONFIGS = {
    "tiny": LlamaConfig(vocab=256, dim=64, n_layers=2, n_heads=4,
                        n_kv_heads=2, ffn_dim=128, max_seq=128,
                        dtype=jnp.float32),
    "mini": LlamaConfig(vocab=4096, dim=512, n_layers=4, n_heads=8,
                        n_kv_heads=4, ffn_dim=1024, max_seq=1024),
    "1b": LlamaConfig(vocab=128256, dim=2048, n_layers=16, n_heads=32,
                      n_kv_heads=8, ffn_dim=8192, max_seq=8192),
    "8b": LlamaConfig(),  # Llama-3-8B
}


def init_layer(key, cfg: LlamaConfig) -> Dict[str, Any]:
    ks = jax.random.split(key, 7)
    d, hd = cfg.dim, cfg.head_dim
    scale = 1.0 / math.sqrt(d)
    return {
        "attn_norm": L.rmsnorm_init(d, cfg.dtype),
        "wq": L.dense_init(ks[0], d, cfg.n_heads * hd, use_bias=False,
                           scale=scale, dtype=cfg.dtype),
        "wk": L.dense_init(ks[1], d, cfg.n_kv_heads * hd, use_bias=False,
                           scale=scale, dtype=cfg.dtype),
        "wv": L.dense_init(ks[2], d, cfg.n_kv_heads * hd, use_bias=False,
                           scale=scale, dtype=cfg.dtype),
        "wo": L.dense_init(ks[3], cfg.n_heads * hd, d, use_bias=False,
                           scale=scale, dtype=cfg.dtype),
        "ffn_norm": L.rmsnorm_init(d, cfg.dtype),
        "w_gate": L.dense_init(ks[4], d, cfg.ffn_dim, use_bias=False,
                               scale=scale, dtype=cfg.dtype),
        "w_up": L.dense_init(ks[5], d, cfg.ffn_dim, use_bias=False,
                             scale=scale, dtype=cfg.dtype),
        "w_down": L.dense_init(ks[6], cfg.ffn_dim, d, use_bias=False,
                               scale=1.0 / math.sqrt(cfg.ffn_dim),
                               dtype=cfg.dtype),
    }


def init(key, cfg: LlamaConfig) -> Dict[str, Any]:
    keys = jax.random.split(key, cfg.n_layers + 3)
    params: Dict[str, Any] = {
        "embed": L.embedding_init(keys[0], cfg.vocab, cfg.dim, cfg.dtype),
        "final_norm": L.rmsnorm_init(cfg.dim, cfg.dtype),
        "lm_head": L.dense_init(keys[1], cfg.dim, cfg.vocab, use_bias=False,
                                scale=1.0 / math.sqrt(cfg.dim),
                                dtype=cfg.dtype),
        "layers": [init_layer(keys[2 + i], cfg)
                   for i in range(cfg.n_layers)],
    }
    return params


def _attn(p: Dict[str, Any], x: jax.Array, cfg: LlamaConfig,
          cos: jax.Array, sin: jax.Array,
          attn_fn=None, pos_offset=0) -> jax.Array:
    B, S, _ = x.shape
    nq, nkv = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
    fuse = cfg.fuse_proj and not any(
        "bias" in p[k] for k in ("wq", "wk", "wv"))
    if fuse:
        wqkv = jnp.concatenate([p["wq"]["kernel"], p["wk"]["kernel"],
                                p["wv"]["kernel"]], axis=1)
        qkv = jnp.einsum("...i,io->...o", x, wqkv)
        q, k, v = jnp.split(qkv, (nq, nq + nkv), axis=-1)
    else:
        q, k, v = L.dense(p["wq"], x), L.dense(p["wk"], x), L.dense(p["wv"], x)
    q = q.reshape(B, S, cfg.n_heads, cfg.head_dim)
    k = k.reshape(B, S, cfg.n_kv_heads, cfg.head_dim)
    v = v.reshape(B, S, cfg.n_kv_heads, cfg.head_dim)
    q = L.apply_rope(q, cos, sin, offset=pos_offset)
    k = L.apply_rope(k, cos, sin, offset=pos_offset)
    if attn_fn is None:
        o = L.causal_attention(q, k, v, causal=True)
    else:
        o = attn_fn(q, k, v)
    return L.dense(p["wo"], o.reshape(B, S, cfg.n_heads * cfg.head_dim))


def _ffn(p: Dict[str, Any], x: jax.Array, cfg: LlamaConfig) -> jax.Array:
    if cfg.fuse_proj and "bias" not in p["w_gate"] and "bias" not in p["w_up"]:
        wgu = jnp.concatenate([p["w_gate"]["kernel"], p["w_up"]["kernel"]],
                              axis=1)
        gu = jnp.einsum("...i,io->...o", x, wgu)
        g, u = jnp.split(gu, 2, axis=-1)
        return L.dense(p["w_down"], jax.nn.silu(g) * u)
    return L.dense(p["w_down"],
                   jax.nn.silu(L.dense(p["w_gate"], x)) *
                   L.dense(p["w_up"], x))


def apply_layer(p: Dict[str, Any], x: jax.Array, cfg: LlamaConfig,
                cos: jax.Array, sin: jax.Array,
                attn_fn=None, pos_offset=0) -> jax.Array:
    # Named scopes (here and below) only label the ops they enclose in the
    # lowered program and the device trace (docs/profiling.md#scopes).
    with jax.named_scope("attn"):
        x = x + _attn(p, L.rmsnorm(p["attn_norm"], x), cfg, cos, sin,
                      attn_fn, pos_offset)
    with jax.named_scope("ffn"):
        x = x + _ffn(p, L.rmsnorm(p["ffn_norm"], x), cfg)
    return x


def apply(params: Dict[str, Any], ids: jax.Array, cfg: LlamaConfig,
          attn_fn=None, remat: bool = False,
          act_sharding=None, return_hidden: bool = False,
          pos_offset=0) -> jax.Array:
    """Forward: token ids [B, S] -> logits [B, S, vocab] (or the final-norm
    hidden states [B, S, dim] with ``return_hidden=True``, for chunked-loss
    callers that apply the lm_head themselves).

    ``remat=True`` wraps each layer in jax.checkpoint — rematerialization
    trades FLOPs for HBM, the standard TPU memory lever.

    ``pos_offset`` (int or traced scalar) shifts RoPE positions — under
    sequence parallelism each chip passes its global slice offset
    (``axis_index * S_shard``).

    ``act_sharding`` (a NamedSharding for the [B, S, D] residual stream)
    pins activations between layers, e.g. batch-sharded over (dp, fsdp) and
    replicated over tp.  Without it the GSPMD partitioner may pick a
    feature-sharded residual layout it can only reach by full
    rematerialization (the round-1 dryrun warning)."""
    cos, sin = L.rope_freqs(cfg.head_dim, cfg.max_seq, cfg.rope_theta)
    with jax.named_scope("embed"):
        x = L.embedding(params["embed"], ids).astype(cfg.dtype)

    def pin(x):
        if act_sharding is not None:
            return jax.lax.with_sharding_constraint(x, act_sharding)
        return x

    x = pin(x)
    layer = apply_layer
    if remat:
        layer = jax.checkpoint(apply_layer, static_argnums=(2, 5))

    for p in params["layers"]:
        x = pin(layer(p, x, cfg, cos, sin, attn_fn, pos_offset))
    x = L.rmsnorm(params["final_norm"], x)
    if return_hidden:
        return x
    with jax.named_scope("head"):
        return L.dense(params["lm_head"], x)


def loss_fn(params: Dict[str, Any], ids: jax.Array, cfg: LlamaConfig,
            attn_fn=None, remat: bool = False,
            act_sharding=None, ce_chunks: int = 0) -> jax.Array:
    """Next-token cross-entropy over shifted ids.

    ``ce_chunks > 0`` streams the lm_head matmul + loss over that many
    sequence chunks under ``jax.checkpoint``: only a [B, S/C, vocab] logits
    slab is ever live (vs the full [B, S, vocab] — ~1 GB bf16 at bench
    shapes), and the backward recomputes each slab instead of saving it.
    Costs one extra lm_head matmul per chunk in the backward (~6% of step
    FLOPs at bench shapes) for a large cut in peak HBM + traffic."""
    targets = ids[:, 1:]
    if ce_chunks:
        h = apply(params, ids[:, :-1], cfg, attn_fn=attn_fn, remat=remat,
                  act_sharding=act_sharding, return_hidden=True)
        B, S, D = h.shape
        if S % ce_chunks:
            raise ValueError(f"seq {S} not divisible by ce_chunks={ce_chunks}")
        hs = h.reshape(B, ce_chunks, S // ce_chunks, D).swapaxes(0, 1)
        ts = targets.reshape(B, ce_chunks, S // ce_chunks).swapaxes(0, 1)

        @jax.checkpoint
        def chunk_nll(hc, tc):
            return jnp.sum(
                L.softmax_cross_entropy(L.dense(params["lm_head"], hc), tc))

        with jax.named_scope("head"):
            total = jnp.sum(jax.lax.map(lambda x: chunk_nll(*x), (hs, ts)))
            return total / (B * S)
    logits = apply(params, ids[:, :-1], cfg, attn_fn=attn_fn, remat=remat,
                   act_sharding=act_sharding)
    with jax.named_scope("head"):
        return jnp.mean(L.softmax_cross_entropy(logits, targets))


# ----------------------------------------------------------- decode path
# Serving-plane KV cache (docs/serving.md) over models/paged.py's pool.
# Positions past a slot's live length are masked with the score dtype's
# minimum, which the fp32 softmax turns into an exact 0 — so prefill + N
# decode steps reproduce apply()'s logits bit-near (tests/test_serve.py).


def _pool(cfg) -> Tuple[paged.CacheKind, ...]:
    """The one kind of cache, without a name (the cache is the pool itself,
    its table an array): every layer's keys and values by kv head."""
    behind = (cfg.n_kv_heads, cfg.head_dim)
    return (paged.CacheKind(None, cfg.n_layers,
                            leaves={"k": behind, "v": behind}),)


def init_cache(cfg: LlamaConfig, num_blocks: int, block_size: int,
               dtype=None) -> Dict[str, jax.Array]:
    """Preallocate the paged KV pool: ``{"k","v"}`` of shape
    ``[n_layers, num_blocks, block_size, n_kv_heads, head_dim]``, one
    stacked buffer each that apply_cached indexes by layer and never
    unstacks (models/paged.py)."""
    return paged.init_pools(_pool(cfg), num_blocks, block_size,
                            dtype if dtype is not None else cfg.dtype)


def cache_shardings(mesh, cfg: LlamaConfig, num_blocks: int):
    """Blocks over the data axis, kv heads over a model axis."""
    return paged.pool_shardings(mesh, _pool(cfg), num_blocks)


copy_blocks = paged.copy_blocks
TICK_COUNTERS = ()      # the tick counts nothing beside its logits
#: the cached attention reads a slot's context as far as it reaches, no
#: farther (paged.attend_by_blocks with a Bound)
BOUNDED_READ = True
#: float32 scores one block of slots may hold (heads x columns x context x 4
#: B a slot): internlm2-1.8b's [16, 128] tick over 2,048 positions attends
#: two slots at a time, its [16, 5] tick all sixteen at once
SCORE_BYTES = 32 << 20


def attn_blocks(cfg: LlamaConfig, S: int, C: int, ctx: int
                ) -> Tuple[int, int]:
    """(slots a block, narrow columns) of the cached attention in a
    ``[S, C]`` tick over ``ctx`` gathered positions (paged.attend_by_blocks;
    the engine's ``wide_blocks_share`` reads a plan by the same two)."""
    return paged.attn_blocks(cfg.n_heads, S, C, ctx, SCORE_BYTES,
                             NARROW_COLS)


def _attend_tile(q, pos, ctx, start):
    """One tile of a block of slots' cached attention (paged.attend_by_blocks
    with a bound): the tile's keys and values ``ctx`` begin at position
    ``start``."""
    return L.attention_tile(
        q, ctx["k"], ctx["v"],
        paged.context_mask(pos - start, ctx["k"].shape[1]))


def _attn_cached(p: Dict[str, Any], x: jax.Array, cfg: LlamaConfig,
                 cos: jax.Array, sin: jax.Array, cache: Dict[str, jax.Array],
                 layer: int, block_tables: jax.Array, t: paged.Tick
                 ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Layer ``layer``'s attention over the paged cache, in place: ``cache``
    is the STACKED pools (init_cache's five axes) and comes back with this
    layer's new positions scattered in — no layer's pool is cut out of the
    stack or put back, so a donated cache stays one buffer through the tick.

    x: [1, R, dim] — the tick's rows (``t.take``; the slab [S, C, dim]
    itself where nothing is packed: S serving slots each contributing a
    chunk of C new token positions; prefill consumes whole chunks, decode
    uses C with one valid token).  The rows' k/v are
    scattered into the pool FIRST, then the queries go back to their slots
    and each attends over its slot's context with a per-position causal
    mask — so a single compiled step serves mixed prefill/decode ticks, a
    block of slots after another: only the blocks that hold a chunk at chunk
    width, and each a tile of context after another as far as its slots'
    contexts reach (paged.attend_by_blocks with a bound).
    Projections always take the unfused path (fuse_proj is a
    training-throughput lever; TP shards the separate kernels)."""
    rows = x.shape[:2]
    heads = lambda w, n: L.dense(p[w], x).reshape(rows + (n, cfg.head_dim))
    q = L.apply_rope_at(heads("wq", cfg.n_heads), cos, sin, t.pos)
    k = L.apply_rope_at(heads("wk", cfg.n_kv_heads), cos, sin, t.pos)
    cache = paged.write(cache, layer, *t.where[None],
                        {"k": k, "v": heads("wv", cfg.n_kv_heads)})
    o = paged.attend_by_blocks(
        _attend_tile, (q, t.positions, block_tables), t.n_new,
        *attn_blocks(cfg, *t.positions.shape,
                     block_tables.shape[1] * cache["k"].shape[2]),
        bound=paged.Bound(t.lengths, cache, layer, t.slab))
    # [S, Hkv, rep, C, head_dim] -> the rows
    o = t.take(jnp.moveaxis(o, 3, 1))
    return L.dense(p["wo"], o.reshape(rows + (-1,))), cache


def _logits(params: Dict[str, Any], x: jax.Array) -> jax.Array:
    """The final norm and the output head on hidden states ``[.., dim]``."""
    return L.dense(params["lm_head"], L.rmsnorm(params["final_norm"], x))


def _forward(params, tokens, cfg, cache, block_tables, lengths, n_new, head):
    """The tick's rows through the stack (decoder.forward): pre-norm
    attention over the paged cache, then the gated FFN."""
    cos, sin = L.rope_freqs(cfg.head_dim, cfg.max_seq, cfg.rope_theta)

    def layer(i, p, x, cache, t):
        with jax.named_scope("attn"):
            a, cache = _attn_cached(
                p, L.rmsnorm(p["attn_norm"], x), cfg, cos, sin,
                cache, i, block_tables, t)
            x = x + a
        with jax.named_scope("ffn"):
            x = x + _ffn(p, L.rmsnorm(p["ffn_norm"], x), cfg)
        return x, cache
    return decoder.forward(
        layer, functools.partial(_logits, params), _pool(cfg), params, tokens,
        cfg, cache, block_tables, lengths, n_new, head, max_seq=cfg.max_seq,
        reads=("pos",))


#: decoder.cached_pair has the contract; the greedy tokens are those of the
#: columns the tick reads (``greedy_cached(.., read)``).
apply_cached, greedy_cached = decoder.cached_pair(_forward, read=True)


def param_count(cfg: LlamaConfig) -> int:
    per_layer = (cfg.dim * cfg.n_heads * cfg.head_dim
                 + 2 * cfg.dim * cfg.n_kv_heads * cfg.head_dim
                 + cfg.n_heads * cfg.head_dim * cfg.dim
                 + 3 * cfg.dim * cfg.ffn_dim + 2 * cfg.dim)
    return (cfg.vocab * cfg.dim * 2 + cfg.dim
            + cfg.n_layers * per_layer)
