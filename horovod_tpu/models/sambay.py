"""Decoder-hybrid-decoder ("SambaY", arXiv:2507.06607) — written by its
mechanisms, so that any model built from them is a config away:

  * **Five kinds of layer in one stack** (``cfg.kind(i)``; half = n_layers
    // 2).  The SELF-DECODER alternates a selective state-space layer
    (``mamba``, i even, i <= half) with sliding-window attention (``swa``,
    i odd, i < half); layer half + 1 is the one ``full`` attention layer;
    the CROSS-DECODER alternates a gated memory unit (``gmu``) with
    attention that projects queries only and reads the full layer's keys
    and values again (``cross``).  Every layer is ``x + mixer(LN(x))`` then
    a gated FFN on ``LN(x)``; LayerNorms have a bias; no positional
    encoding anywhere.  The head is the embedding.
  * **The selective scan** (Mamba-1): ``h_t = exp(D_t A) h_{t-1} + (D_t
    u_t) B_t``, ``y_t = h_t C_t + D u_t`` over a depthwise causal
    convolution's output ``u``, scan and carry in float32.  What a stream
    carries from one tick to the next FOLDS all its earlier tokens: the
    scan's carry ``h`` ``[d_state, d_inner]`` (held lanes-minor: d_inner is
    a multiple of 128, d_state is not) and the convolution's last ``d_conv
    - 1`` inputs.
  * **A gated memory unit** reads no cache: ``(silu(a W_1) * m) W_2`` with
    ``m`` the LAST state-space layer's scan output (before its gate) at the
    same position, an activation handed down the same tick.
  * **Differential attention** (arXiv:2410.05258): head n of ``n_heads /
    2`` has two queries and reads group ``n // (n_heads / n_kv_heads)``,
    whose two keys meet them one each and whose ONE value is twice as wide
    as a key; ``o = P1 v - lam P2 v``, normed over its width.  The two
    softmaxes are two rows of scores over one tile of keys
    (:func:`diff_tile`), so paged.attend_by_blocks folds them across tiles
    like any other head's; the subtraction and the norm come after.  A
    pair's keys are never cut apart: each query meets the pair, 2 head_dim
    wide as the pool holds it, with its other half zero.
  * **Four kinds of cache** (models/paged.py ``CacheKind``): the ONE full
    layer's keys and values in the paged pool, written by that layer and
    read by it and by every cross layer as far as a slot's context reaches
    (``attend_by_blocks`` with a ``Bound``); the window layers' rings, of
    which a tick gathers the blocks its queries' windows reach; the
    state-space layers' conv inputs, a fixed state a slot read by position;
    and their carries, a fixed state a slot of which a tick reads the ONE
    column its slot's last accepted row left (``paged.carry_read``) and
    writes one after each of its last rows, so that a verify row whose
    drafts are rejected leaves the carry where the next tick looks for it.

Serving contract as models/conv_moe.py: ``cache_kinds`` declares the kinds,
``init_cache`` sizes each, ``apply_cached`` takes the tables of the paged
and the ring kind (the state kinds have none), and the module samples on
the rows whose token the tick reads (``greedy_cached(.., read)``).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from . import decoder
from . import layers as L
from . import paged
from .paged import NARROW_COLS


@dataclasses.dataclass(frozen=True)
class SambaYConfig:
    vocab: int = 4096
    dim: int = 256
    n_layers: int = 8
    n_heads: int = 8
    n_kv_heads: int = 4
    ffn_dim: int = 512
    window: int = 64
    mb_per_layer: int = 2        # a state-space layer every so many layers
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    norm_eps: float = 1e-5
    max_seq: int = 512
    dtype: Any = jnp.float32
    # The most valid tokens one call of apply_cached holds (models/paged.py
    # pack); ServeEngine sets it to its own max_batch_tokens; 0 = every
    # position of the slab.
    max_tick_tokens: int = 0

    def __post_init__(self):
        if self.n_heads % self.n_kv_heads or self.n_kv_heads % 2:
            raise ValueError(
                f"{self.n_heads} query and {self.n_kv_heads} key heads: "
                "differential heads pair both, and a pair of keys serves a "
                "whole number of pairs of queries")

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    @property
    def d_inner(self) -> int:
        return self.expand * self.dim

    @property
    def dt_rank(self) -> int:
        return -(-self.dim // 16)

    @property
    def half(self) -> int:
        return self.n_layers // 2

    def kind(self, i: int) -> str:
        if i % self.mb_per_layer == 0:
            return MAMBA if i <= self.half else GMU
        return SWA if i < self.half else \
            FULL if i == self.half + 1 else CROSS

    def count(self, kind: str) -> int:
        return sum(self.kind(i) == kind for i in range(self.n_layers))

    def lambda_init(self, i: int) -> float:
        return 0.8 - 0.6 * math.exp(-0.3 * i)


#: the kinds of layer
MAMBA, SWA, FULL, CROSS, GMU = "mamba", "swa", "full", "cross", "gmu"

CONFIGS = {
    "tiny": SambaYConfig(vocab=256, dim=64, n_layers=8, n_heads=8,
                         n_kv_heads=4, ffn_dim=96, window=8, max_seq=128),
}

#: float32 scores one block of slots may hold (query heads x columns x keys
#: x 4 B a slot): a chunk-wide tick attends a slot at a time in either kind
SCORE_BYTES = 32 << 20
#: the names of the four cache kinds
KV, WINDOW, CONV, CARRY = "kv", "window", "conv", "carry"
#: the full layer's and the cross layers' reads go as far as a slot's context
BOUNDED_READ = True
TICK_COUNTERS = ()      # the tick counts nothing beside its tokens


# ----------------------------------------------------------------- weights
def _dt_bias(key, n: int, lo: float = 1e-3, hi: float = 1e-1) -> jax.Array:
    """Mamba's initial step bias: the inverse softplus of a step drawn
    log-uniformly over ``lo .. hi``."""
    dt = jnp.exp(jax.random.uniform(key, (n,)) * (math.log(hi) - math.log(lo))
                 + math.log(lo))
    return dt + jnp.log(-jnp.expm1(-dt))


def init_layer(key, cfg: SambaYConfig, i: int) -> Dict[str, Any]:
    k = jax.random.split(key, 12)
    d, di, hd, N = cfg.dim, cfg.d_inner, cfg.head_dim, cfg.d_state
    dense = lambda key, i, o: L.dense_init(key, i, o, use_bias=False,
                                           dtype=cfg.dtype)
    norm = lambda key: {
        "scale": jnp.ones((d,), cfg.dtype),
        "bias": (0.02 * jax.random.normal(key, (d,))).astype(cfg.dtype)}
    p = {"mix_norm": norm(k[0]), "ffn_norm": norm(k[1]),
         "ffn": {"fc1": dense(k[2], d, 2 * cfg.ffn_dim),
                 "fc2": dense(k[3], cfg.ffn_dim, d)}}
    kind = cfg.kind(i)
    if kind == MAMBA:
        p["mamba"] = {
            "in_proj": dense(k[4], d, 2 * di),
            "conv": {"taps": (jax.random.normal(k[5], (di, cfg.d_conv))
                              * cfg.d_conv ** -0.5).astype(cfg.dtype),
                     "bias": (0.02 * jax.random.normal(k[6], (di,))
                              ).astype(cfg.dtype)},
            "x_proj": dense(k[7], di, cfg.dt_rank + 2 * N),
            "dt_proj": dict(dense(k[8], cfg.dt_rank, di),
                            bias=_dt_bias(k[9], di).astype(cfg.dtype)),
            # held [d_state, d_inner] like the carry it multiplies: d_inner
            # is whole lanes, 16 is an eighth of one
            "A_log": jnp.broadcast_to(
                jnp.log(jnp.arange(1, N + 1, dtype=jnp.float32))[:, None],
                (N, di)).astype(cfg.dtype),
            "D": jnp.ones((di,), cfg.dtype),
            "out_proj": dense(k[10], di, d)}
    elif kind == GMU:
        p["gmu"] = {"w1": dense(k[4], d, di), "w2": dense(k[5], di, d)}
    else:
        width = cfg.n_heads * hd
        p["attn"] = {"wo": dense(k[5], width, d),
                     "subln": {"scale": jnp.ones((2 * hd,), cfg.dtype)}}
        if kind == CROSS:
            p["attn"]["wq"] = dense(k[4], d, width)
        else:
            p["attn"]["wqkv"] = dense(k[4], d,
                                      width + 2 * cfg.n_kv_heads * hd)
        for j, name in enumerate(("q1", "k1", "q2", "k2")):
            p["attn"]["lambda_" + name] = (
                0.1 * jax.random.normal(k[6 + j], (hd,))).astype(cfg.dtype)
    return p


def init(key, cfg: SambaYConfig) -> Dict[str, Any]:
    keys = jax.random.split(key, cfg.n_layers + 2)
    return {"embed": L.embedding_init(keys[0], cfg.vocab, cfg.dim, cfg.dtype),
            "final_norm": {
                "scale": jnp.ones((cfg.dim,), cfg.dtype),
                "bias": (0.02 * jax.random.normal(keys[1], (cfg.dim,))
                         ).astype(cfg.dtype)},
            "layers": [init_layer(keys[2 + i], cfg, i)
                       for i in range(cfg.n_layers)]}


# ------------------------------------------------------------------ pieces
def _norm(p, x, cfg):
    return L.layernorm(p, x, eps=cfg.norm_eps)


def _ffn(p, h):
    with jax.named_scope("ffn"):
        g, u = jnp.split(L.dense(p["fc1"], h), 2, axis=-1)
        return L.dense(p["fc2"], jax.nn.silu(g) * u)


def _logits(params, x, cfg):
    """Logits of hidden states x [.., D]: the final norm, then the embedding
    as the head."""
    return jnp.dot(_norm(params["final_norm"], x, cfg),
                   params["embed"]["table"].T)


def _ssm_in(p, a):
    """(u, z) of a state-space layer's input a [.., D]: what the convolution
    runs over and the output's gate."""
    with jax.named_scope("ssm/in"):
        return jnp.split(L.dense(p["in_proj"], a), 2, axis=-1)


def _ssm_conv(p, u, before, cfg):
    """``silu(sum_j w[:, j] u_{t-K+1+j} + b)``: ``before(back)`` is ``u``
    ``back`` positions before each row's own.  The taps' sum is taken in
    float32 and rounded once."""
    K, f32 = cfg.d_conv, jnp.float32
    with jax.named_scope("ssm/conv"):
        w = p["conv"]["taps"].astype(f32)
        v = w[:, K - 1] * u.astype(f32)
        for back in range(1, K):
            v = v + w[:, K - 1 - back] * before(back).astype(f32)
        return jax.nn.silu(v + p["conv"]["bias"].astype(f32)).astype(u.dtype)


class _Resets(NamedTuple):
    """Where, in rows that pack several slots' tokens side by side, a slot's
    rows begin (``first`` [G, T] bool) and what the scan carries into each:
    ``carry`` [S, d_state, d_inner] by ``slot`` [G, T]."""
    first: jax.Array
    slot: jax.Array
    carry: jax.Array


def _ssm_scan(p, u, cfg, h0, rows=None, resets: Optional[_Resets] = None,
              keep: bool = False):
    """The selective scan along axis 1 of u [G, T, d_inner] from the carry
    ``h0`` [G, d_state, d_inner]: (y [G, T, d_inner] BEFORE the gate, the
    carry after each row [T, G, d_state, d_inner] float32 if ``keep``).
    Only the first ``rows`` (a device value; None = all T) of the T rows
    are scanned — a verify tick without drafts scans one of its five
    columns —; what lies past them is zero and nobody's.  With ``resets``
    the carry is replaced where a slot's rows begin.  One code for a chunk's
    rows and a verify row's: a loop over the rows whose state is the carry,
    discretisation, recurrence and readout in float32."""
    f32, R, N = jnp.float32, cfg.dt_rank, cfg.d_state
    with jax.named_scope("ssm/scan"):
        dt, B, C = jnp.split(L.dense(p["x_proj"], u), [R, R + N], axis=-1)
        delta = jax.nn.softplus(
            jnp.einsum("...i,io->...o", dt, p["dt_proj"]["kernel"],
                       preferred_element_type=f32)
            + p["dt_proj"]["bias"].astype(f32))
        A = -jnp.exp(p["A_log"].astype(f32))                # [N, d_inner]
        t_major = lambda a: jnp.moveaxis(a.astype(f32), 1, 0)
        xs = tuple(map(t_major, (delta, delta * u.astype(f32), B, C)))
        if resets is not None:
            xs += (jnp.moveaxis(resets.first, 1, 0),
                   jnp.moveaxis(resets.slot, 1, 0))

        def step(t, state):
            h, ys, hs = state
            d_t, du_t, B_t, C_t, *begin = (     # [G, di] x 2, [G, N] x 2
                lax.dynamic_index_in_dim(x, t, keepdims=False) for x in xs)
            if begin:
                h = jnp.where(begin[0][:, None, None],
                              resets.carry[begin[1]], h)
            h = (jnp.exp(d_t[:, None, :] * A) * h
                 + B_t[:, :, None] * du_t[:, None, :])
            y = jnp.sum(h * C_t[:, :, None], axis=1)
            return (h, lax.dynamic_update_index_in_dim(ys, y, t, 0),
                    lax.dynamic_update_index_in_dim(hs, h, t, 0) if keep
                    else hs)
        G, T = u.shape[:2]
        _, ys, hs = lax.fori_loop(
            0, T if rows is None else jnp.minimum(rows, T), step,
            (h0, jnp.zeros((T, G, cfg.d_inner), f32),
             jnp.zeros((T, G) + h0.shape[1:] if keep else (), f32)))
        y = jnp.moveaxis(ys, 0, 1) + p["D"].astype(f32) * u.astype(f32)
        return y.astype(u.dtype), (hs if keep else None)


def _ssm_out(p, y, z):
    with jax.named_scope("ssm/out"):
        return L.dense(p["out_proj"], y * jax.nn.silu(z))


def _gmu(p, a, m):
    with jax.named_scope("gmu"):
        return L.dense(p["w2"], jax.nn.silu(L.dense(p["w1"], a)) * m)


def diff_tile(q, k, v, mask):
    """One tile of keys of a differential attention: q [B, C, H, hd] and the
    tile's k, v [B, K, KV hd], cut into heads here, mask [B, 1, C, K].
    Returns ``(scores, weigh)`` as layers.attention_tile does, for
    paged.attend_by_blocks or one softmax: float32 scores [B, G, rep, 2, C,
    K] — group, differential head of the group, which of its two queries —
    scaled and masked with float32's minimum, and ``weigh(p)`` the float32
    product [B, G, rep, 2, C, 2 hd] with the group's one value."""
    B, C, H, hd = q.shape
    K, G = k.shape[1], k.shape[2] // (2 * hd)
    rep = H // (2 * G)
    # a pair's two keys lie side by side in the pool, 2 hd = whole lanes:
    # each query meets the PAIR, its other half zero, so that no tile of
    # keys is cut into halves of a lane tile (a relayout of every tile a
    # layer: 6 ms of a 36 ms tick, PERF.md section 6, PR 44)
    qg = (q.reshape(B, C, G, rep, 2, 1, hd)
          * jnp.eye(2, dtype=q.dtype)[:, :, None]
          ).reshape(B, C, G, rep, 2, 2 * hd)
    kg, vg = k.reshape(B, K, G, 2 * hd), v.reshape(B, K, G, 2 * hd)
    s = jnp.einsum("bqgrjd,bkgd->bgrjqk", qg, kg,
                   preferred_element_type=jnp.float32) * hd ** -0.5
    s = jnp.where(mask[:, :, None, None], s, jnp.finfo(jnp.float32).min)
    return s, lambda p: jnp.einsum(
        "bgrjqk,bkgv->bgrjqv", p.astype(vg.dtype), vg,
        preferred_element_type=jnp.float32)


def _diff_out(p, o, cfg, i):
    """What differential attention does AFTER its two softmaxes, on o [B, C,
    G, rep, 2, 2 hd] (the two queries' value sums): ``o1 - lam o2``, an RMS
    norm with a gain over the value's width, ``1 - lam_init``, the output
    projection of the heads side by side -> [B, C, D]."""
    f32 = jnp.float32
    with jax.named_scope("attn/diff"):
        dot = lambda a, b: jnp.sum(p["lambda_" + a].astype(f32)
                                   * p["lambda_" + b].astype(f32))
        lam0 = cfg.lambda_init(i)
        lam = jnp.exp(dot("q1", "k1")) - jnp.exp(dot("q2", "k2")) + lam0
        o = o.astype(f32)
        o = o[..., 0, :] - lam * o[..., 1, :]           # [B, C, G, rep, 2hd]
        o = (o * lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + cfg.norm_eps)
             * p["subln"]["scale"].astype(f32) * (1.0 - lam0))
        o = o.astype(p["wo"]["kernel"].dtype)
    return L.dense(p["wo"], o.reshape(o.shape[:2] + (-1,)))


def _softmax_over(tile):
    """One softmax over a whole tile, the value sums of its probabilities
    with the columns second: [B, C, G, rep, 2, 2 hd]."""
    s, weigh = tile
    return jnp.moveaxis(weigh(jax.nn.softmax(s, axis=-1)), 4, 1)


def _heads(q, cfg):
    return q.reshape(q.shape[:-1] + (cfg.n_heads, cfg.head_dim))


def _qkv(p, a, cfg):
    """(q by head, k and v with a position's heads side by side)."""
    width = cfg.n_heads * cfg.head_dim
    kv = cfg.n_kv_heads * cfg.head_dim
    q, k, v = jnp.split(L.dense(p["wqkv"], a), [width, width + kv], axis=-1)
    return _heads(q, cfg), k, v


# ------------------------------------------------------- full-sequence path
def apply(params: Dict[str, Any], ids: jax.Array, cfg: SambaYConfig
          ) -> jax.Array:
    """Forward without a cache: ids [B, S] -> logits [B, S, vocab].  For
    tests and for checking the cached path against."""
    B, S = ids.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    causal = paged.context_mask(positions, S)
    window = paged.window_mask(positions, positions, cfg.window)
    x = L.embedding(params["embed"], ids).astype(cfg.dtype)
    m = shared = None
    for i, p in enumerate(params["layers"][:cfg.n_layers]):
        a, kind = _norm(p["mix_norm"], x, cfg), cfg.kind(i)
        if kind == MAMBA:
            u, z = _ssm_in(p["mamba"], a)
            before = lambda back, u=u: jnp.pad(
                u, ((0, 0), (back, 0), (0, 0)))[:, :S]
            u = _ssm_conv(p["mamba"], u, before, cfg)
            y, _ = _ssm_scan(p["mamba"], u, cfg, jnp.zeros(
                (B, cfg.d_state, cfg.d_inner), jnp.float32))
            if i == cfg.half:
                m = y
            x = x + _ssm_out(p["mamba"], y, z)
        elif kind == GMU:
            x = x + _gmu(p["gmu"], a, m)
        else:
            if kind == CROSS:
                q, (k, v) = _heads(L.dense(p["attn"]["wq"], a), cfg), shared
            else:
                q, k, v = _qkv(p["attn"], a, cfg)
                if kind == FULL:
                    shared = (k, v)
            o = _softmax_over(diff_tile(
                q, k, v, window if kind == SWA else causal))
            x = x + _diff_out(p["attn"], o, cfg, i)
        x = x + _ffn(p["ffn"], _norm(p["ffn_norm"], x, cfg))
    return _logits(params, x, cfg)


# ------------------------------------------------------------- decode path
def cache_kinds(cfg: SambaYConfig) -> Tuple[paged.CacheKind, ...]:
    """The four kinds of cache this stack keeps: the one full layer's whole
    contexts, the window layers' rings, and of the state-space layers the
    convolution's last ``d_conv - 1`` inputs and the scan's one carry."""
    n = cfg.count(MAMBA)
    # a position's heads side by side, lanes-minor; the carry ALWAYS float32
    heads = (cfg.n_kv_heads * cfg.head_dim,)
    return (paged.CacheKind(KV, cfg.count(FULL),
                            leaves={"k": heads, "v": heads}),
            paged.CacheKind(WINDOW, cfg.count(SWA), cfg.window,
                            leaves={"k": heads, "v": heads}),
            paged.CacheKind(CONV, n, state=cfg.d_conv - 1,
                            leaves={"u": (cfg.d_inner,)}),
            paged.CacheKind(CARRY, n, state=1, dtype=jnp.float32,
                            leaves={"h": (cfg.d_state, cfg.d_inner)}))


def _index_in_kind(cfg: SambaYConfig, i: int) -> int:
    """Layer i's index among the layers of its kind."""
    return paged.layer_of_kind(cfg.kind, i)[1]


def init_cache(cfg: SambaYConfig, num_blocks: Dict[str, Any],
               block_size: int, dtype=None) -> Dict[str, Dict[str, jax.Array]]:
    """One pool a kind.  ``{KV: {"k", "v"}}`` and ``{WINDOW: {"k", "v"}}`` of
    ``[kind's layers, num_blocks[kind], block_size, n_kv_heads * head_dim]``
    (a position's heads side by side, lanes-minor); ``{CONV: {"u"}}`` of
    ``[state-space layers, slots, columns, d_inner]`` and ``{CARRY: {"h"}}``
    of ``[.., slots, columns, d_state, d_inner]``, ALWAYS float32, the state
    kinds' ``num_blocks`` being ``(slots, columns)``."""
    return paged.init_pools(cache_kinds(cfg), num_blocks, block_size,
                            dtype if dtype is not None else cfg.dtype)


def cache_shardings(mesh, cfg: SambaYConfig, num_blocks: Dict[str, Any]):
    """{kind: sharding}: the paged pools' blocks and the states' slots over
    the data axis."""
    return paged.pool_shardings(mesh, cache_kinds(cfg), num_blocks)


#: Nothing to clone (paged.no_prefix_blocks): the engine refuses prefix
#: sharing over window and state kinds.
copy_blocks = paged.no_prefix_blocks


def attn_blocks(cfg: SambaYConfig, S: int, C: int, ctx: int
                ) -> Tuple[int, int]:
    """(slots a block, narrow columns) of the cached attention in a
    ``[S, C]`` tick over ``ctx`` gathered positions, either kind."""
    return paged.attn_blocks(cfg.n_heads, S, C, ctx, SCORE_BYTES,
                             NARROW_COLS)


def _mamba_cached(p, a, cfg, j, cache, t: paged.Tick):
    """State-space layer (the state kinds' j-th) on the tick's rows a: the
    convolution reads its earlier inputs as models/conv_moe.py's does
    (paged.state_read), the scan starts each slot's rows from the carry its
    last accepted row left (paged.carry_read: zero for a new tenant) and
    writes the carry after each of its rows that lands.  Returns (the
    mixer's output, y before the gate, cache)."""
    u, z = _ssm_in(p, a)
    with jax.named_scope("ssm/state"):
        earlier = paged.state_read(cache[CONV]["u"], j, u, t,
                                   cfg.d_conv - 1)
        conv = paged.write_slots(cache[CONV], j, *t.lands[CONV], {"u": u})
        carry = paged.carry_read(cache[CARRY]["h"], j, t.lengths)
    u = _ssm_conv(p, u, lambda back: earlier[back - 1], cfg)
    if t.slab.rows is None:     # the rows are the slab: a slot a row of it
        y, hs = _ssm_scan(p, u, cfg, carry, jnp.max(t.n_new), keep=True)
    else:                       # packed: slots side by side along the rows
        y, hs = _ssm_scan(
            p, u, cfg, jnp.zeros((1,) + carry.shape[1:], carry.dtype),
            jnp.sum(t.n_new), _Resets(t.first, t.slot, carry), keep=True)
    with jax.named_scope("ssm/state"):
        # the carries come out of the loop row-major [T, G, ..]: the small
        # index arrays turn, not the carries
        slot, col = (jnp.moveaxis(x, 1, 0) for x in paged.carry_index(
            t, cache[CARRY]["h"].shape[2]))
        cache = dict(cache, **{
            CONV: conv,
            CARRY: paged.write(cache[CARRY], j, slot, col, {"h": hs})})
    return _ssm_out(p, y, z), y, cache


def _shared_tile(q, pos, ctx, start):
    """One tile of a block of slots' read of the ONE full layer's pool
    (paged.attend_by_blocks with a bound: the same function for the full
    layer and every cross layer, so the read is traced once a tick); the
    tile's keys and values ``ctx`` begin at position ``start``."""
    return diff_tile(q, ctx["k"], ctx["v"],
                     paged.context_mask(pos - start, ctx["k"].shape[1]))


def _shared_read(q, cfg, cache, tables, t: paged.Tick):
    """Queries (rows, by head) against the ONE full layer's pool, as far as
    each slot's context reaches: [S, C, G, rep, 2, 2 hd]."""
    pool = cache[KV]
    o = paged.attend_by_blocks(
        _shared_tile, (q, t.positions, tables[KV]), t.n_new,
        *attn_blocks(cfg, *t.positions.shape,
                     tables[KV].shape[1] * pool["k"].shape[2]),
        bound=paged.Bound(t.lengths, pool, 0, t.slab))
    return jnp.moveaxis(o, 4, 1)


def _attn_cached(p, a, cfg, i, cache, tables, t: paged.Tick):
    """Attention layer i on the tick's rows a.  ``swa``: the rows' k/v go
    into the layer's ring (as models/swa_moe.py), then each slot attends
    the blocks of it that its queries' windows reach.  ``full``: they go into the
    one paged layer, which the layer then reads with a bound; ``cross``:
    queries alone, against that same layer as the full layer left it."""
    kind = cfg.kind(i)
    if kind == SWA:
        q, k, v = _qkv(p, a, cfg)
        j = _index_in_kind(cfg, i)
        with jax.named_scope("attn/window"):
            pool = paged.write(cache[WINDOW], j, *t.where[WINDOW],
                               {"k": k, "v": v})
            cache = dict(cache, **{WINDOW: pool})
            entries, bs = tables[WINDOW].shape[1], pool["k"].shape[2]

            def attend(q, pos, tab, top):
                # of the ring's entries only those of the blocks that the
                # windows of c queries from pos[:, 0] on can reach (a decode
                # row gathers 34 of its ring's 48; a chunk all of them);
                # which position an index holds is the ring's to say
                s, c = q.shape[:2]
                need = min(entries, (cfg.window + c - 2) // bs + 2)
                entry = (jnp.maximum(pos[:, :1] - cfg.window + 1, 0) // bs
                         + jnp.arange(need)) % entries
                ctx = paged.gather(pool, j,
                                   jnp.take_along_axis(tab, entry, axis=1))
                key_pos = jnp.take_along_axis(
                    paged.ring_positions(top, entries * bs
                                         ).reshape(s, entries, bs),
                    entry[:, :, None], axis=1).reshape(s, need * bs)
                mask = paged.window_mask(pos, key_pos, cfg.window)
                return _softmax_over(diff_tile(
                    q, ctx["k"], ctx["v"], mask)).astype(q.dtype)
            o = paged.attend_by_blocks(
                attend, (t.slab(q), t.positions, tables[WINDOW], t.top),
                t.n_new, *attn_blocks(cfg, *t.positions.shape,
                                      entries * bs))
    else:
        with jax.named_scope("attn/shared"):
            if kind == FULL:
                q, k, v = _qkv(p, a, cfg)
                cache = dict(cache, **{KV: paged.write(
                    cache[KV], 0, *t.where[KV], {"k": k, "v": v})})
            else:
                q = _heads(L.dense(p["wq"], a), cfg)
            o = _shared_read(q, cfg, cache, tables, t)
    return _diff_out(p, t.take(o), cfg, i), cache


def _forward(params, tokens, cfg, cache, tables, lengths, n_new, head):
    """The tick's rows through the stack (decoder.forward): the mixer of
    the layer's kind, then the gated FFN; what the LAST state-space layer
    scanned is handed down the tick to the gated memory units."""
    memory = {}

    def layer(i, p, x, cache, t):
        a, kind = _norm(p["mix_norm"], x, cfg), cfg.kind(i)
        if kind == MAMBA:
            y, scanned, cache = _mamba_cached(
                p["mamba"], a, cfg, _index_in_kind(cfg, i), cache, t)
            if i == cfg.half:
                memory["m"] = scanned
        elif kind == GMU:
            y = _gmu(p["gmu"], a, memory["m"])
        else:
            y, cache = _attn_cached(p["attn"], a, cfg, i, cache, tables, t)
        x = x + y
        return x + _ffn(p["ffn"], _norm(p["ffn_norm"], x, cfg)), cache
    return decoder.forward(
        layer, lambda x: _logits(params, x, cfg), cache_kinds(cfg), params,
        tokens, cfg, cache, tables, lengths, n_new, head,
        reads=("slot", "row", "top", "first"))


#: decoder.cached_pair has the contract: ``cache`` is a dict by kind,
#: ``block_tables`` ``{KV: table, WINDOW: ring table}``, the greedy tokens
#: those of the columns the tick reads.
apply_cached, greedy_cached = decoder.cached_pair(_forward, read=True)


def param_count(cfg: SambaYConfig) -> int:
    d, di, hd, N, K, R = (cfg.dim, cfg.d_inner, cfg.head_dim, cfg.d_state,
                          cfg.d_conv, cfg.dt_rank)
    q, kv = d * cfg.n_heads * hd, 2 * d * cfg.n_kv_heads * hd
    lam = 6 * hd
    mixer = {MAMBA: (2 * d * di + di * K + di + di * (R + 2 * N) + R * di
                     + di + di * N + di + di * d),
             SWA: 2 * q + kv + lam, FULL: 2 * q + kv + lam,
             CROSS: 2 * q + lam, GMU: 2 * d * di}
    return (cfg.vocab * d + 2 * d + sum(
        mixer[cfg.kind(i)] + 3 * d * cfg.ffn_dim + 4 * d
        for i in range(cfg.n_layers)))


__all__ = ["SambaYConfig", "CONFIGS", "TICK_COUNTERS", "KV", "WINDOW", "CONV",
           "CARRY", "BOUNDED_READ", "init", "apply", "cache_kinds",
           "init_cache", "cache_shardings", "copy_blocks", "apply_cached",
           "greedy_cached", "attn_blocks", "diff_tile", "param_count"]
