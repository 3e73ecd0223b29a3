"""Minimal functional layer library for the bundled model zoo.

The reference ships framework-native example models (reference:
examples/pytorch/pytorch_mnist.py, examples/keras/..., tf_cnn_benchmarks via
docs/benchmarks.rst).  Here the zoo is pure JAX: every layer is an
``init(key, ...) -> params`` / ``apply(params, x, ...) -> y`` pair with
params as plain dict pytrees, so models compose with pjit/shard_map sharding
and optax without a framework dependency.

TPU notes: matmul-heavy layers default to bfloat16-friendly shapes (multiples
of 128 where it matters); convs use NHWC which XLA maps best onto the MXU.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

Params = Dict[str, Any]


# ------------------------------------------------------------------ dense/emb
def dense_init(key, in_dim: int, out_dim: int, use_bias: bool = True,
               scale: Optional[float] = None, dtype=jnp.float32) -> Params:
    scale = scale if scale is not None else 1.0 / math.sqrt(in_dim)
    p = {"kernel": jax.random.normal(key, (in_dim, out_dim), dtype) * scale}
    if use_bias:
        p["bias"] = jnp.zeros((out_dim,), dtype)
    return p


def dense(p: Params, x: jax.Array,
          precision=None) -> jax.Array:
    y = jnp.einsum("...i,io->...o", x, p["kernel"], precision=precision)
    if "bias" in p:
        y = y + p["bias"]
    return y


def embedding_init(key, vocab: int, dim: int, dtype=jnp.float32) -> Params:
    return {"table": jax.random.normal(key, (vocab, dim), dtype) * 0.02}


def embedding(p: Params, ids: jax.Array) -> jax.Array:
    return jnp.take(p["table"], ids, axis=0)


# ----------------------------------------------------------------- norms/acts
def layernorm_init(dim: int, dtype=jnp.float32) -> Params:
    return {"scale": jnp.ones((dim,), dtype), "bias": jnp.zeros((dim,), dtype)}


def layernorm(p: Params, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mean) * lax.rsqrt(var + eps)
    return (y * p["scale"] + p["bias"]).astype(x.dtype)


def rmsnorm_init(dim: int, dtype=jnp.float32) -> Params:
    return {"scale": jnp.ones((dim,), dtype)}


def rmsnorm(p: Params, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    x32 = x.astype(jnp.float32)
    y = x32 * lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * p["scale"]).astype(x.dtype)


def norm(p: Params, x: jax.Array, cfg) -> jax.Array:
    """:func:`rmsnorm` with the config's own epsilon (``cfg.norm_eps``)."""
    return rmsnorm(p, x, eps=cfg.norm_eps)


def gelu(x: jax.Array) -> jax.Array:
    return jax.nn.gelu(x, approximate=True)


# ----------------------------------------------------------------------- conv
def conv_init(key, kh: int, kw: int, cin: int, cout: int,
              dtype=jnp.float32) -> Params:
    fan_in = kh * kw * cin
    scale = math.sqrt(2.0 / fan_in)  # He init for ReLU nets
    return {"kernel": jax.random.normal(key, (kh, kw, cin, cout),
                                        dtype) * scale}


def conv(p: Params, x: jax.Array, stride: int = 1,
         padding: str = "SAME") -> jax.Array:
    """NHWC conv — the layout XLA tiles onto the MXU."""
    return lax.conv_general_dilated(
        x, p["kernel"], window_strides=(stride, stride), padding=padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def batchnorm_init(dim: int, dtype=jnp.float32) -> Params:
    return {"scale": jnp.ones((dim,), dtype),
            "bias": jnp.zeros((dim,), dtype),
            "mean": jnp.zeros((dim,), dtype),
            "var": jnp.ones((dim,), dtype)}


def batchnorm(p: Params, x: jax.Array, training: bool = False,
              momentum: float = 0.9, eps: float = 1e-5,
              axis_name: Optional[str] = None
              ) -> Tuple[jax.Array, Params]:
    """BatchNorm over N,H,W.  With ``axis_name`` the batch statistics are
    allreduced across the mesh axis — SyncBatchNorm (reference:
    horovod/torch/sync_batch_norm.py, tensorflow sync_batch_norm.py:65
    allreduce of batch mean/var)."""
    if training:
        x32 = x.astype(jnp.float32)
        axes = tuple(range(x.ndim - 1))
        mean = jnp.mean(x32, axis=axes)
        var = jnp.mean(jnp.square(x32), axis=axes) - jnp.square(mean)
        if axis_name is not None:
            mean = lax.pmean(mean, axis_name)
            var = lax.pmean(var, axis_name)
        new_p = dict(p)
        new_p["mean"] = momentum * p["mean"] + (1 - momentum) * mean
        new_p["var"] = momentum * p["var"] + (1 - momentum) * var
    else:
        mean, var = p["mean"], p["var"]
        new_p = p
    y = (x.astype(jnp.float32) - mean) * lax.rsqrt(var + eps)
    return (y * p["scale"] + p["bias"]).astype(x.dtype), new_p


def conv_bn_init(key, kh: int, kw: int, cin: int, cout: int,
                 dtype=jnp.float32) -> Params:
    """conv (no bias) + BN parameter pair — the CNN zoo's basic unit."""
    return {"conv": conv_init(key, kh, kw, cin, cout, dtype),
            "bn": batchnorm_init(cout)}


def conv_bn_relu(p: Params, x: jax.Array, stride: int = 1,
                 padding: str = "SAME", training: bool = False,
                 axis_name: Optional[str] = None
                 ) -> Tuple[jax.Array, Params]:
    """conv -> BN -> relu with functional BN-state threading (shared by
    vgg.py / inception.py; resnet's bottleneck places its relus itself)."""
    out = dict(p)
    y = conv(p["conv"], x, stride=stride, padding=padding)
    y, out["bn"] = batchnorm(p["bn"], y, training, axis_name=axis_name)
    return jax.nn.relu(y), out


def maxpool(x: jax.Array, window: int = 3, stride: int = 2,
            padding: str = "SAME") -> jax.Array:
    return lax.reduce_window(x, -jnp.inf, lax.max,
                             (1, window, window, 1),
                             (1, stride, stride, 1), padding)


# --------------------------------------------------------------------- losses
def softmax_cross_entropy(logits: jax.Array, targets: jax.Array) -> jax.Array:
    """Per-position negative log-likelihood, ``logits[..., V]`` vs integer
    ``targets[...]``.

    Uses the identity ``nll = logsumexp(logits) - logits[target]`` instead of
    materializing ``log_softmax``: the logsumexp reduction fuses with the
    fp32 upcast, so the [..., V] tensor is never written to HBM in fp32 —
    at bench vocab sizes that full-softmax round trip is ~2 GB/step.
    Numerically identical to ``-log_softmax(logits)[target]`` in fp32."""
    lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    tgt = jnp.take_along_axis(logits, targets[..., None],
                              axis=-1)[..., 0].astype(jnp.float32)
    return lse - tgt


# ------------------------------------------------------------------ attention
def rope_freqs(head_dim: int, max_len: int, theta: float = 10000.0,
               dtype=jnp.float32) -> Tuple[jax.Array, jax.Array]:
    inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                           / head_dim))
    t = jnp.arange(max_len, dtype=jnp.float32)
    ang = jnp.outer(t, inv)  # [max_len, head_dim/2]
    return jnp.cos(ang).astype(dtype), jnp.sin(ang).astype(dtype)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array,
               offset: int = 0) -> jax.Array:
    """x: [..., seq, heads, head_dim]; rotary position embedding."""
    seq = x.shape[-3]
    c = lax.dynamic_slice_in_dim(cos, offset, seq, 0)[..., None, :]
    s = lax.dynamic_slice_in_dim(sin, offset, seq, 0)[..., None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s],
                           axis=-1).astype(x.dtype)


def apply_rope_at(x: jax.Array, cos: jax.Array, sin: jax.Array,
                  positions: jax.Array) -> jax.Array:
    """x: [B, S, heads, head_dim]; rotary embedding at EXPLICIT per-token
    positions [B, S] — the decode-path generalization of
    :func:`apply_rope`'s single scalar offset, where every batch row
    (serving slot) sits at its own sequence position.  Same rotation
    math on the same tables, so prefill+decode logits stay bit-near the
    full-sequence forward (docs/serving.md)."""
    c = jnp.take(cos, positions, axis=0)[..., None, :]
    s = jnp.take(sin, positions, axis=0)[..., None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s],
                           axis=-1).astype(x.dtype)


def qkv(p: Params, h: jax.Array, cfg, cos: jax.Array, sin: jax.Array,
        positions: jax.Array, qk_norm: bool = False, rotary: bool = True
        ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """A grouped-query attention's projections of h [B, S, D] by head
    (``wq``, ``wk``, ``wv`` of ``p``; ``cfg.n_heads`` query and
    ``cfg.n_kv_heads`` key and value heads of ``cfg.head_dim``).  With
    ``qk_norm`` queries and keys are normed over their head_dim
    (:func:`norm` with the gains ``q_norm``, ``k_norm``) and THEN, with
    ``rotary``, rotated at ``positions`` [B, S]; a layer without ``rotary``
    has no positional encoding at all."""
    def heads(w, n, gain=None):
        a = dense(p[w], h).reshape(h.shape[:2] + (n, cfg.head_dim))
        return norm(p[gain], a, cfg) if qk_norm and gain else a
    q = heads("wq", cfg.n_heads, "q_norm")
    k = heads("wk", cfg.n_kv_heads, "k_norm")
    if rotary:
        q = apply_rope_at(q, cos, sin, positions)
        k = apply_rope_at(k, cos, sin, positions)
    return q, k, heads("wv", cfg.n_kv_heads)


def causal_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     causal: bool = True,
                     mask: Optional[jax.Array] = None,
                     score_dtype: Optional[Any] = jnp.float32) -> jax.Array:
    """Multi-head attention core.  q: [B, S, H, D]; k/v: [B, S, Hkv, D]
    (grouped-query when Hkv < H).  Softmax in fp32 for stability; einsum
    contractions land on the MXU.

    Grouped-query heads are handled by folding the group into a batched
    einsum dimension rather than ``jnp.repeat``-ing k/v: no duplicated
    k/v buffers in the forward and no scatter-add un-repeat in their
    backward — the einsum's reduction over the group does it natively.

    ``score_dtype`` is the dtype the [.., S, S] score tensor MATERIALIZES
    in — the largest activation at long seq.  jnp.float32 (default)
    keeps every logit bit the MXU accumulated; ``None`` stores scores in
    the input dtype (half the score HBM traffic for bf16 models — the
    softmax still runs fp32 on the upcast inside one fused pass, so only
    one bf16 rounding of the logits is introduced)."""
    B, S, H, D = q.shape
    Sk = k.shape[1]
    Hkv = k.shape[2]
    rep = H // Hkv
    scale = 1.0 / math.sqrt(D)
    qg = q.reshape(B, S, Hkv, rep, D)
    sdt = q.dtype if score_dtype is None else score_dtype
    # Masked positions fill with the score dtype's own minimum: -1e30
    # overflows to -inf in float16 (5-bit exponent), and a fully-masked
    # row of -inf softmaxes to NaN where the fp32-score path stayed
    # finite.  finfo.min is representable by construction in every dtype.
    fill = jnp.asarray(jnp.finfo(sdt).min, sdt)
    logits = jnp.einsum("bqhrd,bkhd->bhrqk", qg, k,
                        preferred_element_type=sdt) * jnp.asarray(scale, sdt)
    if causal:
        causal_mask = jnp.tril(jnp.ones((S, Sk), jnp.bool_), k=Sk - S)
        logits = jnp.where(causal_mask[None, None, None], logits, fill)
    if mask is not None:
        # user masks address [B?, H, Sq, Sk]; expose the grouped logits in
        # that layout, mask, and re-group
        lg = logits.reshape(B, H, S, Sk)
        lg = jnp.where(mask, lg, fill)
        logits = lg.reshape(B, Hkv, rep, S, Sk)
    probs = jax.nn.softmax(logits.astype(jnp.float32),
                           axis=-1).astype(q.dtype)
    o = jnp.einsum("bhrqk,bkhd->bqhrd", probs, v)
    return o.reshape(B, S, H, v.shape[-1])  # v's own width (latent heads)


def attention_tile(q: jax.Array, k: jax.Array, v: jax.Array,
                   mask: jax.Array):
    """One TILE of keys of an attention whose softmax runs across tiles
    (models/paged.py ``attend_by_blocks``): q [B, S, H, D], k/v [B, K, Hkv,
    D] grouped as :func:`causal_attention` groups them, mask [B, 1, S, K].
    Returns ``(scores, weigh)``: float32 scores [B, Hkv, rep, S, K], scaled,
    masked with float32's minimum; ``weigh(p)`` the float32 value product
    [B, Hkv, rep, S, Dv] of probabilities shaped like the scores, which meet
    the values in the values' dtype."""
    B, S, H, D = q.shape
    Hkv = k.shape[2]
    qg = q.reshape(B, S, Hkv, H // Hkv, D)
    s = jnp.einsum("bqhrd,bkhd->bhrqk", qg, k,
                   preferred_element_type=jnp.float32) * (1.0 / math.sqrt(D))
    s = jnp.where(jnp.expand_dims(mask, 2), s, jnp.finfo(jnp.float32).min)
    return s, lambda p: jnp.einsum("bhrqk,bkhd->bhrqd", p.astype(v.dtype), v,
                                   preferred_element_type=jnp.float32)


def attention_tile_by_head(q: jax.Array, k: jax.Array, v: jax.Array,
                           mask: jax.Array):
    """:func:`attention_tile` of a tile BY HEAD, as a pool that keeps its
    keys and values by head inside a block is gathered (models/paged.py
    ``gather(.., by_head)``): k/v [B, T, Hkv, bs, D], T table entries of bs
    positions, key ``t * bs + s`` at ``[t, :, s]``.  The entries stay an
    axis of both products, so nothing of the tile's size is made between
    the gather and the scores; the scores come back ``[B, Hkv, rep, S, K]``
    with ``K = T * bs``, as ``mask`` [B, 1, S, K] and the softmax across
    tiles count the keys, and ``weigh(p)`` takes probabilities so shaped."""
    B, S, H, D = q.shape
    T, Hkv, bs = k.shape[1:4]
    qg = q.reshape(B, S, Hkv, H // Hkv, D)
    s = jnp.einsum("bqhrd,bthsd->bhrqts", qg, k,
                   preferred_element_type=jnp.float32) * (1.0 / math.sqrt(D))
    s = jnp.where(jnp.expand_dims(mask, 2), s.reshape(s.shape[:4] + (T * bs,)),
                  jnp.finfo(jnp.float32).min)
    return s, lambda p: jnp.einsum(
        "bhrqts,bthsd->bhrqd",
        p.astype(v.dtype).reshape(p.shape[:4] + (T, bs)), v,
        preferred_element_type=jnp.float32)
