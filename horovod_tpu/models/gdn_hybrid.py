"""Gated delta-rule layers beside full attention (Gated DeltaNet,
arXiv:2412.06464; the delta rule chunked over the sequence, arXiv:2406.06484)
in the OLMo 2 / 3 block — written by its mechanisms, so that any model built
from them is a config away:

  * **Two kinds of layer in one stack** (``cfg.kind(i)``): ``full_every - 1``
    ``linear`` layers, then a ``full`` attention layer, and again.  Either
    is ``h = x + N1(mixer(x))``, ``y = h + N2(FFN(h))``: NO norm before a
    sublayer, an RMSNorm with a gain on its OUTPUT; the mixer reads ``x``
    itself.  The head is a final RMSNorm and an untied matrix.
  * **Full attention without positions**: ``q = Nq(W_q x)``, ``k = Nk(W_k
    x)`` normed over the whole projection BEFORE the split into heads, no
    rotary, one key head a query head.
  * **The gated delta rule**, position t, head n: ``[q; k; v] = silu(conv(
    W_qkv x))`` (a causal depthwise convolution, no bias), ``q = q / |q| /
    sqrt(dk)``, ``k = k / |k|``; ``beta = 2 sigmoid(W_b x)`` (an eigenvalue of
    the transition may be negative), ``g = -exp(A_log) softplus(W_a x +
    dt_bias)``, ``alpha = exp(g)``; the state ``S [dv, dk]`` float32:
    ``S' = alpha S``, ``S = S' + beta (v - S' k) k^T``, ``o = S q``; ``o
    = w * o / rms(o) * silu(W_z x)``, then ``W_o`` over the heads side by
    side.
  * **ONE matrix a slot a layer.**  A state ``[heads, dv, dk]`` float32 is
    too large to keep after every row of a verify row, as models/sambay.py
    keeps its scans' carries.  The ``delta`` cache kind (models/paged.py
    ``CacheKind.replay``) keeps the state COMMITTED after the last row that
    no later tick can take back, the position it stands after, and a ring of
    what the verify row's later rows fed the recurrence (k, v, g, beta: what
    the convolution and the norms left of them); the next tick replays the
    rows that were accepted before its own.
  * **One chunked form for every width** (:func:`delta_chunks`).  Within a
    chunk of T rows the states between rows are never made: with ``G`` the
    running sum of g, ``A[t, i] = beta_t exp(G_t - G_i) (k_t . k_i)`` below
    the diagonal, ``(I + A) [W_v | W_k] = [beta v | beta exp(G) k]`` (the
    inverse by forward substitution, :func:`unit_lower_inverse`: no power
    of A), ``U = W_v - W_k
    S_0^T``, ``o_t = exp(G_t) S_0 q_t + sum_{i <= t} exp(G_t - G_i) (q_t .
    k_i) u_i``, and the state after row r ``exp(G_r) S_0 + sum_{i <= r}
    exp(G_r - G_i) u_i k_i^T``.  A row that may be taken back (a verify
    row, at most ``2 rows + 1`` with the rows it replays) is ONE chunk a
    slot, all slots at once — one read and one write of the states a tick
    (:func:`_narrow`) —; a prompt's chunk is cut into chunks of
    ``cfg.chunk`` rows whose part that is free of the state runs for eight
    of them at once, the state passed from chunk to chunk of a slot
    (:func:`_wide`), over the tick's PACKED rows: a slot's rows start from
    that slot's state, rows of two slots never share a chunk, and no state
    a row exists anywhere.
  * **Three kinds of cache** (models/paged.py ``CacheKind``): the full
    layers' keys and values in the paged pool, BY HEAD inside a block
    (``by_head``: a gathered tile is scored as it lies), read as far as a
    slot's context reaches; the linear layers' last ``conv_kernel - 1`` conv
    inputs, a fixed state a slot read by position; and their matrix states,
    the ``delta`` kind above.

Serving contract as models/sambay.py: ``cache_kinds`` declares the kinds,
``init_cache`` sizes each, ``apply_cached`` takes the table of the paged
kind (the state kinds have none), and the module samples on the rows whose
token the tick reads (``greedy_cached(.., read)``).  ``TICK_COUNTERS``: the
rows the linear layers ran and the rows they replayed, what speculation
costs this state.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from . import decoder
from . import layers as L
from . import paged
from .paged import NARROW_COLS


@dataclasses.dataclass(frozen=True)
class GdnHybridConfig:
    vocab: int = 4096
    dim: int = 256
    n_layers: int = 8
    n_heads: int = 8             # the full layers': head_dim = dim / n_heads
    ffn_dim: int = 512
    full_every: int = 4          # layers i with (i + 1) % full_every == 0
    lin_heads: int = 8           # the linear layers' key AND value heads
    lin_key_dim: int = 24
    lin_value_dim: int = 48
    conv_kernel: int = 4
    chunk: int = 64              # rows a chunk of a prompt's rows
    norm_eps: float = 1e-6
    max_seq: int = 512
    dtype: Any = jnp.float32
    # The most valid tokens one call of apply_cached holds (models/paged.py
    # pack); ServeEngine sets it to its own max_batch_tokens; 0 = every
    # position of the slab.
    max_tick_tokens: int = 0

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    @property
    def conv_dim(self) -> int:
        return self.lin_heads * (2 * self.lin_key_dim + self.lin_value_dim)

    @property
    def row_dim(self) -> int:
        """What a row feeds the recurrence, side by side: k, v, g, beta."""
        return self.lin_heads * (self.lin_key_dim + self.lin_value_dim + 2)

    def kind(self, i: int) -> str:
        return FULL if (i + 1) % self.full_every == 0 else LINEAR

    def count(self, kind: str) -> int:
        return sum(self.kind(i) == kind for i in range(self.n_layers))


#: the kinds of layer
LINEAR, FULL = "linear", "full"

CONFIGS = {
    "tiny": GdnHybridConfig(vocab=256, dim=64, n_layers=8, n_heads=4,
                            ffn_dim=96, lin_heads=4, lin_key_dim=8,
                            lin_value_dim=16, chunk=8, max_seq=128),
}

#: float32 scores one block of slots may hold (heads x columns x keys x 4 B
#: a slot) in the full layers' cached attention
SCORE_BYTES = 32 << 20
#: the names of the three cache kinds
KV, CONV, DELTA = "kv", "conv", "delta"
#: the full layers read as far as a slot's context reaches
BOUNDED_READ = True
#: a first ``ticks``, then summed over the linear layers: the rows of the
#: tick that they ran, and the rows of the tick before that they replayed
TICK_COUNTERS = ("ticks", "gdn_rows", "gdn_replayed_rows")
#: the state's matmuls are float32's own on every device (a TPU's default
#: multiplies float32 in bfloat16)
EXACT = lax.Precision.HIGHEST


# ----------------------------------------------------------------- weights
def gate_init(key, n: int, a_max: float = 16.0, lo: float = 1e-3,
              hi: float = 1e-1) -> Tuple[jax.Array, jax.Array]:
    """(``A_log``, ``dt_bias``) [n] float32 as the layer's published code
    draws them: the log of a draw uniform over ``(0, a_max)``, and the
    inverse softplus of a step drawn log-uniformly over ``lo .. hi``."""
    ka, kd = jax.random.split(key)
    a = jax.random.uniform(ka, (n,), minval=1e-4, maxval=a_max)
    dt = jnp.exp(jax.random.uniform(kd, (n,))
                 * (math.log(hi) - math.log(lo)) + math.log(lo))
    return jnp.log(a), dt + jnp.log(-jnp.expm1(-dt))


def init_layer(key, cfg: GdnHybridConfig, i: int) -> Dict[str, Any]:
    k = jax.random.split(key, 10)
    d, H = cfg.dim, cfg.lin_heads
    dense = lambda key, i, o: L.dense_init(key, i, o, use_bias=False,
                                           dtype=cfg.dtype)
    ones = lambda n: {"scale": jnp.ones((n,), cfg.dtype)}
    p = {"mix_norm": ones(d), "ffn_norm": ones(d),
         "w_gate": dense(k[0], d, cfg.ffn_dim),
         "w_up": dense(k[1], d, cfg.ffn_dim),
         "w_down": dense(k[2], cfg.ffn_dim, d)}
    if cfg.kind(i) == FULL:
        p["attn"] = {"wq": dense(k[3], d, d), "wk": dense(k[4], d, d),
                     "wv": dense(k[5], d, d), "wo": dense(k[6], d, d),
                     "q_norm": ones(d), "k_norm": ones(d)}
    else:
        a_log, dt_bias = gate_init(k[9], H)
        p["gdn"] = {
            "qkv": dense(k[3], d, cfg.conv_dim),
            "z": dense(k[4], d, H * cfg.lin_value_dim),
            "a": dense(k[5], d, H), "b": dense(k[6], d, H),
            "conv": {"taps": (jax.random.normal(
                k[7], (cfg.conv_dim, cfg.conv_kernel))
                * cfg.conv_kernel ** -0.5).astype(cfg.dtype)},
            "A_log": a_log.astype(cfg.dtype),
            "dt_bias": dt_bias.astype(cfg.dtype),
            "o_norm": ones(cfg.lin_value_dim),
            "out": dense(k[8], H * cfg.lin_value_dim, d)}
    return p


def init(key, cfg: GdnHybridConfig) -> Dict[str, Any]:
    keys = jax.random.split(key, cfg.n_layers + 2)
    return {"embed": L.embedding_init(keys[0], cfg.vocab, cfg.dim, cfg.dtype),
            "final_norm": {"scale": jnp.ones((cfg.dim,), cfg.dtype)},
            "lm_head": L.dense_init(keys[1], cfg.dim, cfg.vocab,
                                    use_bias=False, dtype=cfg.dtype),
            "layers": [init_layer(keys[2 + i], cfg, i)
                       for i in range(cfg.n_layers)]}


# ------------------------------------------------------------------ pieces
def _norm(p, x, cfg):
    return L.rmsnorm(p, x, eps=cfg.norm_eps)


def _ffn(p, h):
    with jax.named_scope("ffn"):
        return L.dense(p["w_down"], jax.nn.silu(L.dense(p["w_gate"], h))
                       * L.dense(p["w_up"], h))


def _logits(params, x, cfg):
    """Logits of hidden states x [.., D]: the final norm, the untied head."""
    return L.dense(params["lm_head"], _norm(params["final_norm"], x, cfg))


def _block(p, x, y, cfg):
    """The OLMo block round a mixer's output y: norms on the OUTPUTS."""
    h = x + _norm(p["mix_norm"], y, cfg)
    return h + _norm(p["ffn_norm"], _ffn(p, h), cfg)


def _gdn_in(p, x):
    """(what the convolution runs over [.., conv_dim], the output's gate z,
    the decay's and beta's logits a, b [.., H] float32)."""
    with jax.named_scope("gdn/in"):
        f32 = lambda w: jnp.einsum("...i,io->...o", x, p[w]["kernel"],
                                   preferred_element_type=jnp.float32)
        return L.dense(p["qkv"], x), L.dense(p["z"], x), f32("a"), f32("b")


def _gdn_conv(p, u, before, cfg):
    """``silu(sum_j w[:, j] u_{t-K+1+j})``: ``before(back)`` is ``u``
    ``back`` positions before each row's own.  The taps' sum is taken in
    float32 and rounded once."""
    K, f32 = cfg.conv_kernel, jnp.float32
    with jax.named_scope("gdn/conv"):
        w = p["conv"]["taps"].astype(f32)
        v = w[:, K - 1] * u.astype(f32)
        for back in range(1, K):
            v = v + w[:, K - 1 - back] * before(back).astype(f32)
        return jax.nn.silu(v).astype(u.dtype)


def _gdn_gate(p, a, b):
    """(g = log alpha <= 0, beta in (0, 2)) float32 of the logits a, b."""
    f32 = jnp.float32
    with jax.named_scope("gdn/gate"):
        g = -jnp.exp(p["A_log"].astype(f32)) * jax.nn.softplus(
            a + p["dt_bias"].astype(f32))
        return g, 2.0 * jax.nn.sigmoid(b)


def _gdn_heads(u, cfg):
    """(q, k, v) float32 by head of the convolution's output u [..,
    conv_dim]: q and k of unit length, q over sqrt(dk) besides."""
    H, dk, dv, f32 = (cfg.lin_heads, cfg.lin_key_dim, cfg.lin_value_dim,
                      jnp.float32)
    with jax.named_scope("gdn/norm"):
        q, k, v = jnp.split(u.astype(f32), [H * dk, 2 * H * dk], axis=-1)
        heads = lambda a, w: a.reshape(a.shape[:-1] + (H, w))
        unit = lambda a: a * lax.rsqrt(
            jnp.sum(a * a, -1, keepdims=True) + cfg.norm_eps)
        return (unit(heads(q, dk)) * dk ** -0.5, unit(heads(k, dk)),
                heads(v, dv))


def _gdn_out(p, o, z, cfg):
    """``W_o [w * o / rms(o) * silu(z)]`` of the heads' outputs o [.., H,
    dv] float32 and the gate z [.., H dv]."""
    f32 = jnp.float32
    with jax.named_scope("gdn/out"):
        o = (o * lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + cfg.norm_eps)
             * p["o_norm"]["scale"].astype(f32))
        o = o.reshape(z.shape) * jax.nn.silu(z.astype(f32))
        return L.dense(p["out"], o.astype(z.dtype))


def unit_lower_inverse(A, block: int = 16):
    """``(I + A)^-1`` of strictly lower triangular A [.., T, T], float32, by
    forward substitution — no power of A, whatever the keys: row i of the
    inverse is ``e_i - A[i, :] X`` over the rows before it, a row after
    another inside diagonal blocks of ``block`` (or of T where that is
    smaller), all blocks of all matrices at once; then pairs of blocks are
    joined, ``[[P, 0], [-Q A_21 P, Q]]``, until one is left.  T is ``block``
    times a power of two where it is more than ``block``."""
    T = A.shape[-1]
    b = min(block, T)
    n = T // b
    if n * b != T or n & (n - 1):
        raise ValueError(f"{T} rows are no power of two of blocks of {b}")

    def blocks(size):
        """A's diagonal blocks of ``size`` [.., T / size, size, size]."""
        cut = A.reshape(A.shape[:-2] + (T // size, size, T // size, size))
        return jnp.moveaxis(jnp.diagonal(cut, axis1=-4, axis2=-2), -1, -3)
    D, eye = blocks(b), jnp.eye(b, dtype=A.dtype)
    X = jnp.zeros_like(D)
    for i in range(b):      # the rows from i on are still zero
        row = eye[i] - jnp.sum(D[..., i, :, None] * X, axis=-2)
        X = X.at[..., i, :].set(row)
    mm = lambda a, c: jnp.einsum("...ij,...jk->...ik", a, c, precision=EXACT)
    while n > 1:
        half = X.shape[-1]
        low = blocks(2 * half)[..., half:, :half]       # a pair's A_21
        P, Q = X[..., 0::2, :, :], X[..., 1::2, :, :]
        X = jnp.concatenate(
            [jnp.concatenate([P, jnp.zeros_like(P)], -1),
             jnp.concatenate([-mm(mm(Q, low), P), Q], -1)], axis=-2)
        n //= 2
    return X[..., 0, :, :]


def delta_chunks(q, k, v, g, beta):
    """The part of a chunk that is free of the state it starts from, for
    any number of chunks at once: q, k [.., T, H, dk], v [.., T, H, dv], g,
    beta [.., T, H], all float32 (a row that is nobody's has beta 0, g 0
    and k, v, q zero, and then changes nothing).  Returns a dict of float32,
    heads before rows:

      wv [.., H, T, dv], wk [.., H, T, dk]: ``(I + A)^-1 [beta v | beta
          exp(G) k]``, so that ``U = wv - wk S_0^T``;
      attn [.., H, T, T]: ``exp(G_t - G_i) (q_t . k_i)`` for ``i <= t``;
      qg [.., H, T, dk]: ``exp(G_t) q_t``;  k [.., H, T, dk];
      G [.., H, T]: the running sum of g over the chunk's rows."""
    T = q.shape[-3]
    turn = lambda a: jnp.moveaxis(a, -3, -2)        # [.., H, T, w]
    q, k, v = turn(q), turn(k), turn(v)
    g, beta = jnp.moveaxis(g, -2, -1), jnp.moveaxis(beta, -2, -1)
    G = jnp.cumsum(g, axis=-1)
    # exp(G_t - G_i) where i <= t and 0 above the diagonal, whose
    # differences are positive and may be large
    lower = jnp.tril(jnp.ones((T, T), bool))
    decay = jnp.exp(jnp.where(lower, G[..., :, None] - G[..., None, :],
                              -jnp.inf))
    dot = lambda a, b: jnp.einsum("...td,...id->...ti", a, b,
                                  precision=EXACT)
    A = beta[..., None] * decay * dot(k, k) * ~jnp.eye(T, dtype=bool)
    rhs = jnp.concatenate([beta[..., None] * v,
                           (beta * jnp.exp(G))[..., None] * k], axis=-1)
    w = jnp.einsum("...ti,...iw->...tw", unit_lower_inverse(A), rhs,
                   precision=EXACT)
    return {"wv": w[..., :v.shape[-1]], "wk": w[..., v.shape[-1]:],
            "attn": decay * dot(q, k), "qg": jnp.exp(G)[..., None] * q,
            "k": k, "G": G}


def delta_apply(c, S0, r=None):
    """A chunk (:func:`delta_chunks`) from the state ``S0`` [.., H, dv, dk]
    it starts from: (the rows' outputs o [.., H, T, dv], the state after
    row ``r`` [..] of the chunk, its last row where ``r`` is None)."""
    mm = lambda eq, a, b: jnp.einsum(eq, a, b, precision=EXACT)
    G = c["G"]
    T = G.shape[-1]
    # W_k S_0^T and (exp(G) q) S_0^T in ONE pass over the state
    read = mm("...tk,...vk->...tv",
              jnp.concatenate([c["wk"], c["qg"]], axis=-2), S0)
    U = c["wv"] - read[..., :T, :]
    o = read[..., T:, :] + mm("...ti,...iv->...tv", c["attn"], U)
    if r is None:
        r = jnp.full(G.shape[:-2], T - 1)
    r = r[..., None, None]                              # [.., 1, 1]
    Gr = jnp.take_along_axis(G, jnp.broadcast_to(r, G.shape[:-1] + (1,)),
                             axis=-1)
    # exp(G_r - G_i) for the rows up to r: none of them positive
    keep = jnp.arange(T) <= r
    w = jnp.where(keep, jnp.exp(jnp.where(keep, Gr - G, 0.0)), 0.0)
    S = (jnp.exp(Gr)[..., None] * S0
         + mm("...tv,...tk->...vk", U, w[..., None] * c["k"]))
    return o, S


# ------------------------------------------------------- full-sequence path
def _linear(p, x, cfg):
    """A linear layer's mixer on whole rows x [B, S, D] from nothing."""
    B, S = x.shape[:2]
    u, z, a, b = _gdn_in(p, x)
    before = lambda back: jnp.pad(u, ((0, 0), (back, 0), (0, 0)))[:, :S]
    q, k, v = _gdn_heads(_gdn_conv(p, u, before, cfg), cfg)
    g, beta = _gdn_gate(p, a, b)
    T = cfg.chunk
    pad = lambda a: jnp.pad(a, ((0, 0), (0, -S % T)) + ((0, 0),) * (a.ndim - 2)
                            ).reshape((B, -1, T) + a.shape[2:])
    with jax.named_scope("gdn/chunk"):
        c = delta_chunks(*map(pad, (q, k, v, g, beta)))
    with jax.named_scope("gdn/pass"):
        def step(S0, chunk):
            o, S1 = delta_apply(chunk, S0)
            return S1, o
        _, o = lax.scan(step, jnp.zeros(
            (B, cfg.lin_heads, cfg.lin_value_dim, cfg.lin_key_dim),
            jnp.float32), jax.tree_util.tree_map(
                lambda a: jnp.moveaxis(a, 1, 0), c))
    # [chunks, B, H, T, dv] -> [B, S, H, dv]
    o = jnp.moveaxis(o, (0, 2), (1, 3)).reshape(
        (B, -1) + o.shape[2:3] + o.shape[4:])[:, :S]
    return _gdn_out(p, o, z, cfg)


def _full(p, x, cfg):
    """A full layer's mixer on whole rows x [B, S, D]."""
    with jax.named_scope("attn/full"):
        heads = lambda a: a.reshape(a.shape[:2] + (cfg.n_heads, cfg.head_dim))
        o = L.causal_attention(*map(heads, _qkv(p, x, cfg)))
        return L.dense(p["wo"], o.reshape(x.shape))


def _qkv(p, x, cfg):
    """(q, k, v) [.., D] of a full layer, a position's heads side by side:
    queries and keys normed over the whole projection."""
    return (_norm(p["q_norm"], L.dense(p["wq"], x), cfg),
            _norm(p["k_norm"], L.dense(p["wk"], x), cfg),
            L.dense(p["wv"], x))


def apply(params: Dict[str, Any], ids: jax.Array, cfg: GdnHybridConfig
          ) -> jax.Array:
    """Forward without a cache: ids [B, S] -> logits [B, S, vocab].  For
    tests and for checking the cached path against."""
    x = L.embedding(params["embed"], ids).astype(cfg.dtype)
    for i, p in enumerate(params["layers"][:cfg.n_layers]):
        y = (_full(p["attn"], x, cfg) if cfg.kind(i) == FULL
             else _linear(p["gdn"], x, cfg))
        x = _block(p, x, y, cfg)
    return _logits(params, x, cfg)


# ------------------------------------------------------------- decode path
def cache_kinds(cfg: GdnHybridConfig) -> Tuple[paged.CacheKind, ...]:
    """The three kinds of cache this stack keeps: the full layers' whole
    contexts, and of the linear layers the convolution's last ``conv_kernel
    - 1`` inputs and the ONE matrix state a slot with the ring of rows a
    later tick may have to replay."""
    n, H = cfg.count(LINEAR), cfg.lin_heads
    # keys and values BY HEAD inside a block, ``[layers, blocks, n_heads,
    # block_size, head_dim]``: a gathered tile is scored as it lies.  What
    # made the compiler relay the whole pool into and out of every tick (two
    # copies of 1.5 GB a leaf in both programs compiled for a described v5e,
    # PR 48) was the WRITE by row, a scatter at ``[blk, :, off]`` whose
    # window spans the head axis; blocks rewritten whole (paged.write_blocks)
    # leave the pool in place (PR 50).  The state ALWAYS float32
    return (paged.CacheKind(KV, cfg.count(FULL), by_head=True,
                            leaves={"k": (cfg.n_heads, cfg.head_dim),
                                    "v": (cfg.n_heads, cfg.head_dim)}),
            paged.CacheKind(CONV, n, state=cfg.conv_kernel - 1,
                            leaves={"u": (cfg.conv_dim,)}),
            paged.CacheKind(
                DELTA, n, state=1, dtype=jnp.float32,
                leaves={"S": (H, cfg.lin_value_dim, cfg.lin_key_dim)},
                replay={"row": (cfg.row_dim,)}))


def _index_in_kind(cfg: GdnHybridConfig, i: int) -> int:
    """Layer i's index among the layers of its kind."""
    return paged.layer_of_kind(cfg.kind, i)[1]


def init_cache(cfg: GdnHybridConfig, num_blocks: Dict[str, Any],
               block_size: int, dtype=None) -> Dict[str, Dict[str, jax.Array]]:
    """One pool a kind.  ``{KV: {"k", "v"}}`` of ``[full layers,
    num_blocks[KV], n_heads, block_size, head_dim]``; ``{CONV: {"u"}}`` of
    ``[linear layers, slots, columns, conv_dim]``; ``{DELTA: {"S", "at", "row"}}``:
    ``S`` ``[linear layers, slots, 1, heads, dv, dk]`` and the ring ``row``
    ``[.., slots, rows, heads x (dk + dv + 2)]`` (a row's k, v, g and beta
    side by side, lanes-minor) ALWAYS float32, ``at`` int32, the state
    kinds' ``num_blocks`` being ``(slots, columns or rows)``."""
    return paged.init_pools(cache_kinds(cfg), num_blocks, block_size,
                            dtype if dtype is not None else cfg.dtype)


def cache_shardings(mesh, cfg: GdnHybridConfig, num_blocks: Dict[str, Any]):
    """{kind: sharding}: the paged pool's blocks and the states' slots over
    the data axis, the paged pool's heads over a model axis."""
    return paged.pool_shardings(mesh, cache_kinds(cfg), num_blocks)


#: Nothing to clone (paged.no_prefix_blocks): the engine refuses prefix
#: sharing over state kinds.
copy_blocks = paged.no_prefix_blocks


def attn_blocks(cfg: GdnHybridConfig, S: int, C: int, ctx: int
                ) -> Tuple[int, int]:
    """(slots a block, narrow columns) of the full layers' cached attention
    in a ``[S, C]`` tick over ``ctx`` gathered positions."""
    return paged.attn_blocks(cfg.n_heads, S, C, ctx, SCORE_BYTES,
                             NARROW_COLS)


def _pack(fed):
    """A row's k [.., H, dk], v [.., H, dv], g and beta [.., H] side by
    side, as the ring keeps them."""
    flat = lambda a: a.reshape(a.shape[:-2] + (-1,))
    return jnp.concatenate([flat(fed["k"]), flat(fed["v"]), fed["g"],
                            fed["beta"]], axis=-1)


def _unpack(row, cfg):
    """:func:`_pack` undone: {k, v, g, beta} of rows [.., row_dim]."""
    H, dk, dv = cfg.lin_heads, cfg.lin_key_dim, cfg.lin_value_dim
    k, v, g, beta = jnp.split(row, [H * dk, H * (dk + dv), H * (dk + dv + 1)],
                              axis=-1)
    heads = lambda a, w: a.reshape(a.shape[:-1] + (H, w))
    return {"k": heads(k, dk), "v": heads(v, dv), "g": g, "beta": beta}


def _grid(rows, ring, at_row, replayed, mine):
    """What the recurrence is fed, laid out by chunk: ``rows`` (a dict of
    the tick's own rows ``[N, ...]``) at ``at_row`` ``[.., T]`` where
    ``mine``, before them the ``ring``'s (``[.., rows, ...]`` in the order
    they are replayed, padded to T) where ``replayed``, and a row that
    changes nothing anywhere else."""
    T = at_row.shape[-1]
    out = {}
    for name, own in rows.items():
        wide = lambda m: m.reshape(m.shape + (1,) * (own.ndim - 1))
        a = jnp.where(wide(mine), own[jnp.clip(at_row, 0, own.shape[0] - 1)],
                      0.0)
        if name in ring:
            r = ring[name]
            r = jnp.pad(r, ((0, 0), (0, T - r.shape[1]))
                        + ((0, 0),) * (r.ndim - 2))
            a = jnp.where(wide(replayed), r, a)
        out[name] = a
    return out


def _narrow(rows, ring, S0, start, a, n, commit, T):
    """Every slot's rows that a later tick may take back, ONE chunk of T a
    slot and all slots at once: the ``a`` rows of the ring it replays, then
    its first ``n`` own rows (``start``: where they begin in ``rows``).
    Returns (the outputs [S, H, T, dv], the state after row ``a + commit``
    [S, H, dv, dk])."""
    e = jnp.arange(T)[None, :]
    with jax.named_scope("gdn/replay"):
        fed = _grid(rows, ring, start[:, None] + e - a[:, None],
                    e < a[:, None], (e >= a[:, None]) & (e < (a + n)[:, None]))
    with jax.named_scope("gdn/step"):
        c = delta_chunks(fed["q"], fed["k"], fed["v"], fed["g"], fed["beta"])
        return delta_apply(c, S0, a + commit)


#: chunks of a prompt's rows whose state-free part runs at once: a tick's
#: static bound on its chunks is three times what a plan with one prompt's
#: chunk holds (26 against 8 at the published sizes), and the part that is
#: free of the state cost that much for nothing (20 of a 101 ms tick on the
#: chip, PERF.md section 6, PR 48)
GROUP = 8


def _wide(rows, ring, S0, S1, start, a, n, wide, T, chunks):
    """The slots whose rows are a prompt's chunk (``wide`` [S]): each slot's
    ``a`` replayed rows and ``n`` own rows cut into chunks of T, ``chunks``
    (static) of them at most in the tick, ``GROUP`` at a time as far as the
    plan's chunks go: what is free of the state for a group's chunks at
    once, then the state passed along a slot's chunks, a slot after
    another.  Returns (the outputs [chunks, H, T, dv], where each slot's
    chunks begin [S], ``S1`` [S, H, dv, dk] with the state after the last
    row of each such slot in its place)."""
    S = start.shape[0]
    m = jnp.where(wide, a + n, 0)
    per = -(-m // T)                                    # chunks a slot
    end = jnp.cumsum(per)
    begin, total = end - per, end[-1]
    at = lambda a, i: lax.dynamic_index_in_dim(a, i, keepdims=False)

    def group(first, carry):
        c = first * GROUP + jnp.arange(GROUP)
        slot = jnp.minimum(jnp.sum(end[None, :] <= c[:, None], axis=1),
                           S - 1)
        nth = c - begin[slot]                           # of its slot's chunks
        e = nth[:, None] * T + jnp.arange(T)[None, :]   # of its slot's rows
        live = (c < total)[:, None]
        with jax.named_scope("gdn/replay"):
            # a slot's ring precedes its own rows in its FIRST chunk
            fed = _grid(rows, {k: r[slot] for k, r in ring.items()},
                        (start - a)[slot][:, None] + e,
                        live & (e < a[slot][:, None]),
                        live & (e >= a[slot][:, None])
                        & (e < m[slot][:, None]))
        with jax.named_scope("gdn/chunk"):
            free = delta_chunks(fed["q"], fed["k"], fed["v"], fed["g"],
                                fed["beta"])

        def step(i, carry):
            run, S1, out = carry
            s = at(slot, i)
            o, run = delta_apply(
                jax.tree_util.tree_map(lambda a: at(a, i), free),
                jnp.where(at(nth, i) == 0, at(S0, s), run))
            # a slot's later chunks overwrite its earlier ones' state
            return (run, lax.dynamic_update_index_in_dim(S1, run, s, 0),
                    lax.dynamic_update_index_in_dim(out, o, at(c, i), 0))
        with jax.named_scope("gdn/pass"):
            return lax.fori_loop(
                0, jnp.minimum(GROUP, total - first * GROUP), step, carry)
    H, dv = S0.shape[1:3]
    _, S1, out = lax.fori_loop(
        0, -(-total // GROUP), group,
        (jnp.zeros(S0.shape[1:], S0.dtype), S1,
         jnp.zeros((-(-chunks // GROUP) * GROUP, H, T, dv), jnp.float32)))
    return out, begin, S1


def _delta_cached(p, x, cfg, j, cache, t: paged.Tick):
    """Linear layer (the state kinds' j-th) on the tick's rows x: the
    convolution reads its earlier inputs as models/conv_moe.py's does
    (paged.state_read); the recurrence starts each slot's rows from its ONE
    committed state (paged.committed: zero for a new tenant), replays what
    the ring holds of the rows between that state and the slot's length,
    runs the tick's rows — :func:`_narrow` for the rows a later tick may
    take back, :func:`_wide` for a prompt's chunks — and commits
    (paged.commit_row).  Returns (the mixer's output, cache, [rows run, rows
    replayed])."""
    u, z, a_, b_ = _gdn_in(p, x)
    flat = lambda a: a.reshape((-1,) + a.shape[2:])
    with jax.named_scope("gdn/state"):
        earlier = paged.state_read(cache[CONV]["u"], j, u, t,
                                   cfg.conv_kernel - 1)
        conv = paged.write_slots(cache[CONV], j, *t.lands[CONV], {"u": u})
        delta = cache[DELTA]
        S0, at = paged.committed(delta, j, t.lengths, "S")
        ring = _unpack(paged.replay_read(delta["row"], j, at), cfg)
    q, k, v = _gdn_heads(
        _gdn_conv(p, u, lambda back: earlier[back - 1], cfg), cfg)
    g, beta = _gdn_gate(p, a_, b_)
    own = {"q": q, "k": k, "v": v, "g": g, "beta": beta}
    rows = {name: flat(a) for name, a in own.items()}
    S, C = t.positions.shape
    R = delta["row"].shape[2]                   # the ring's rows
    n = t.n_new
    a = jnp.where(n > 0, t.lengths - at, 0)     # rows to replay
    back = n <= R + 1                           # may be taken back
    o_n, S1 = _narrow(rows, ring, S0, t.start, a, jnp.where(back, n, 0),
                      paged.commit_row(n, R) * back, 2 * R + 1)
    slot, pos, length = t.row
    col = pos - length + a[slot]                # a row's place in its slot's
    o = o_n[slot, :, jnp.clip(col, 0, 2 * R)]
    if C > R + 1:
        N, T = rows["q"].shape[0], cfg.chunk
        most = min(S, N // (R + 2))             # slots that hold a chunk
        o_w, begin, S1 = _wide(
            rows, ring, S0, S1, t.start, a, n, ~back & (n > 0), T,
            most + -(-(N + most * R) // T))
        o = jnp.where(back[slot][:, None, None], o, o_w[
            jnp.clip(begin[slot] + col // T, 0, o_w.shape[0] - 1), :,
            col % T])
    with jax.named_scope("gdn/state"):
        kept = paged.write_slots({"row": delta["row"]}, j,
                                 *t.lands[DELTA], {"row": _pack(own)})
        cache = dict(cache, **{
            CONV: conv,
            DELTA: paged.commit(dict(delta, **kept), j, t.lengths, n, R,
                                S=S1)})
    counted = jnp.stack([jnp.sum(n), jnp.sum(a)]).astype(jnp.int32)
    return _gdn_out(p, o.reshape(x.shape[:2] + o.shape[1:]), z, cfg), \
        cache, counted


def _attend_tile(q, pos, ctx, start):
    """One tile of a block of slots' read of a full layer's pool
    (paged.attend_by_blocks with a bound); the tile's keys and values
    ``ctx``, by head as the pool keeps them ``[s, entries, heads, block,
    head_dim]``, begin at position ``start``."""
    k = ctx["k"]
    return L.attention_tile_by_head(
        q, k, ctx["v"],
        paged.context_mask(pos - start, k.shape[1] * k.shape[3]))


def _full_cached(p, x, cfg, j, cache, tables, t: paged.Tick):
    """Full layer (the paged kind's j-th) on the tick's rows x: their keys
    and values go into the pool first, then each slot attends as far as its
    context reaches."""
    with jax.named_scope("attn/full"):
        q, k, v = _qkv(p, x, cfg)
        pool = paged.write_blocks(cache[KV], j, *t.where[KV],
                                  {"k": k, "v": v})
        q = q.reshape(q.shape[:2] + (cfg.n_heads, cfg.head_dim))
        o = paged.attend_by_blocks(
            _attend_tile, (q, t.positions, tables[KV]), t.n_new,
            *attn_blocks(cfg, *t.positions.shape, tables[KV].shape[1]
                         * paged.block_size(pool, by_head=True)),
            bound=paged.Bound(t.lengths, pool, j, t.slab, by_head=True))
        # [S, H, 1, C, head_dim] -> the rows
        o = t.take(jnp.moveaxis(o, 3, 1))
        return (L.dense(p["wo"], o.reshape(x.shape)),
                dict(cache, **{KV: pool}))


def _forward(params, tokens, cfg, cache, tables, lengths, n_new, head):
    """The tick's rows through the stack (decoder.forward): the mixer of
    the layer's kind on the rows themselves, then the block's two norms and
    its FFN."""
    nothing = jnp.zeros(len(TICK_COUNTERS) - 1, jnp.int32)

    def layer(i, p, x, cache, t):
        j = _index_in_kind(cfg, i)
        if cfg.kind(i) == FULL:
            y, cache = _full_cached(p["attn"], x, cfg, j, cache, tables, t)
            counted = nothing
        else:
            y, cache, counted = _delta_cached(p["gdn"], x, cfg, j, cache, t)
        return _block(p, x, y, cfg), cache, counted
    return decoder.forward(
        layer, lambda x: _logits(params, x, cfg), cache_kinds(cfg), params,
        tokens, cfg, cache, tables, lengths, n_new, head,
        counters=TICK_COUNTERS, reads=("row",))


#: decoder.cached_pair has the contract: ``cache`` is a dict by kind,
#: ``block_tables`` ``{KV: table}``, the greedy tokens those of the columns
#: the tick reads.
apply_cached, greedy_cached = decoder.cached_pair(_forward, read=True)


def param_count(cfg: GdnHybridConfig) -> int:
    d, H, dk, dv = cfg.dim, cfg.lin_heads, cfg.lin_key_dim, cfg.lin_value_dim
    mixer = {FULL: 4 * d * d + 2 * d,
             LINEAR: (d * cfg.conv_dim + 2 * d * H * dv + 2 * d * H
                      + cfg.conv_dim * cfg.conv_kernel + 2 * H + dv)}
    return (2 * cfg.vocab * d + d + sum(
        mixer[cfg.kind(i)] + 3 * d * cfg.ffn_dim + 2 * d
        for i in range(cfg.n_layers)))


__all__ = ["GdnHybridConfig", "CONFIGS", "TICK_COUNTERS", "KV", "CONV",
           "DELTA", "BOUNDED_READ", "init", "apply", "cache_kinds",
           "init_cache", "cache_shardings", "copy_blocks", "apply_cached",
           "greedy_cached", "attn_blocks", "delta_chunks", "delta_apply",
           "param_count"]
