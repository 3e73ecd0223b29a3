"""Sequence / context parallelism: Ulysses all_to_all + ring attention.

The reference stops at the ``alltoall`` primitive users build SP from
(reference: operations.cc:1136-1198; SURVEY.md §5 — no built-in ring
attention).  Long-context is first-class here:

* **Ulysses** (all_to_all SP): inputs sharded over sequence; one all_to_all
  re-shards to head-parallel, full attention runs locally on H/n heads, a
  second all_to_all restores sequence sharding.  Cost: 2 all_to_alls per
  attention; works while n_sp <= n_kv_heads.

* **Ring attention**: k/v blocks rotate around the mesh axis ring via
  `lax.ppermute` (ICI neighbor exchanges) while each chip accumulates its
  queries' attention with an online-softmax (flash-style m/l/o running
  state).  Supports causal masking by block index; sequence length scales
  linearly with chips.

Both are SPMD functions used inside shard_map with the ``sp`` axis, and
slot into models via the ``attn_fn`` hook (models/llama.py, bert.py).
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax


# ------------------------------------------------------------------- ulysses
def ulysses_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                      axis_name: str = "sp",
                      causal: bool = True) -> jax.Array:
    """Attention over sequence-sharded q/k/v: [B, S/n, H, D] per chip.

    all_to_all trades the sequence shard for a head shard so every chip
    sees the full sequence for its H/n heads, then trades back."""
    from ..models.layers import causal_attention
    n = lax.psum(1, axis_name)
    H = q.shape[2]
    if H % n != 0:
        raise ValueError(f"heads {H} not divisible by sp axis size {n}")
    # [B, S/n, H, D] -> [B, S, H/n, D]: split heads (axis 2), concat seq (1)
    qh = lax.all_to_all(q, axis_name, split_axis=2, concat_axis=1,
                        tiled=True)
    kh = lax.all_to_all(k, axis_name, split_axis=2, concat_axis=1,
                        tiled=True)
    vh = lax.all_to_all(v, axis_name, split_axis=2, concat_axis=1,
                        tiled=True)
    o = causal_attention(qh, kh, vh, causal=causal)
    # back: [B, S, H/n, D] -> [B, S/n, H, D]
    return lax.all_to_all(o, axis_name, split_axis=1, concat_axis=2,
                          tiled=True)


# -------------------------------------------------------------- ring attention
def _block_attend(q, k, v, q_off, k_off, causal: bool,
                  m, l, o):
    """One flash-style accumulation step against a k/v block.

    q: [B, Sq, H, D]; k/v: [B, Sk, H, D]; m/l: [B, H, Sq]; o like q.
    Returns updated (m, l, o).  Softmax statistics kept in fp32."""
    B, Sq, H, D = q.shape
    Sk = k.shape[1]
    scale = 1.0 / math.sqrt(D)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        qi = q_off + jnp.arange(Sq)
        ki = k_off + jnp.arange(Sk)
        mask = qi[:, None] >= ki[None, :]
        logits = jnp.where(mask[None, None], logits, -1e30)
    m_new = jnp.maximum(m, jnp.max(logits, axis=-1))
    # guard fully-masked rows (m_new == -1e30): exp underflows to 0, fine.
    p = jnp.exp(logits - m_new[..., None])
    alpha = jnp.exp(m - m_new)
    l_new = l * alpha + jnp.sum(p, axis=-1)
    pv = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)
    o_new = o * alpha.transpose(0, 2, 1)[..., None].astype(o.dtype) + pv
    return m_new, l_new, o_new


def _pvary_missing(t, axis_name):
    """Mark ``t`` varying over ``axis_name`` so fori_loop carry types line
    up when the initial value is device-invariant (vma typing)."""
    axes = ((axis_name,) if isinstance(axis_name, str)
            else tuple(axis_name))
    missing = tuple(a for a in axes if a not in jax.typeof(t).vma)
    return lax.pcast(t, missing, to="varying") if missing else t


def _flash_ring_step(q, kk, vv, src, idx, block_q, block_k):
    """One ring step through the Pallas flash kernel.

    The k/v block now local started on chip ``src``; relative to this
    chip's q block it is either fully visible (src < idx — plain
    attention), diagonal (src == idx — standard causal), or fully masked
    (src > idx — zero contribution).  Offsets are whole-shard multiples,
    so the three cases are exact and pick the kernel's own causal flag —
    no offset masks needed.  Returns (out [B,S,H,D] in q.dtype,
    lse [B,H,S] fp32) for the logsumexp merge."""
    from ..ops.flash_attention import _flash_forward

    def full(_):
        return _flash_forward(q, kk, vv, False, block_q, block_k)

    def diag(_):
        return _flash_forward(q, kk, vv, True, block_q, block_k)

    def skip(_):
        B, S, H, _D = q.shape
        return (jnp.zeros_like(q),
                jnp.full((B, H, S), -jnp.inf, jnp.float32))

    case = jnp.where(src == idx, 1, jnp.where(src < idx, 0, 2))
    return lax.switch(case, [full, diag, skip], None)


def _lse_merge(o, lse, o2, lse2):
    """Combine two partial attentions over disjoint key sets from their
    (unnormalized-by-each-other) outputs and logsumexps."""
    lse_new = jnp.logaddexp(lse, lse2)
    # clamp the subtrahend so an all-masked (-inf) pair yields weight 0,
    # not exp(nan)
    safe = jnp.maximum(lse_new, -1e30)
    w1 = jnp.exp(lse - safe).transpose(0, 2, 1)[..., None]
    w2 = jnp.exp(lse2 - safe).transpose(0, 2, 1)[..., None]
    return o.astype(jnp.float32) * w1 + o2.astype(jnp.float32) * w2, lse_new


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _ring_attention_flash(q, k, v, axis_name: str,
                          block_q: int, block_k: int) -> jax.Array:
    """Causal ring attention with the Pallas flash kernel as the per-step
    block attention.  GQA k/v stay at their Hkv footprint: the kernel
    maps q-head groups onto shared kv heads itself, so the ring moves
    1/rep of the bytes the repeat-based path would.  Differentiable: the
    backward runs its own ring over the flash backward kernels (see
    ``_ring_flash_bwd``)."""
    return _ring_flash_fwd(q, k, v, axis_name, block_q, block_k)[0]


def _ring_flash_fwd(q, k, v, axis_name, block_q, block_k):
    n = int(lax.psum(1, axis_name))
    idx = lax.axis_index(axis_name)
    B, Sq, H, D = q.shape

    o0 = _pvary_missing(jnp.zeros_like(q, dtype=jnp.float32), axis_name)
    lse0 = _pvary_missing(jnp.full((B, H, Sq), -jnp.inf, jnp.float32),
                          axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def body(step, carry):
        o, lse, kk, vv = carry
        src = (idx - step) % n
        o2, lse2 = _flash_ring_step(q, kk, vv, src, idx, block_q, block_k)
        o, lse = _lse_merge(o, lse, o2, lse2)
        kk = lax.ppermute(kk, axis_name, perm)
        vv = lax.ppermute(vv, axis_name, perm)
        return o, lse, kk, vv

    o, lse, _, _ = lax.fori_loop(0, n, body, (o0, lse0, k, v))
    out = o.astype(q.dtype)
    return out, (q, k, v, out, lse)


def _ring_flash_bwd(axis_name, block_q, block_k, res, g):
    """Ring backward: one pass around the ring re-derives every block's
    gradient contribution from the saved GLOBAL lse (the flash backward
    kernels rebuild p = exp(s - lse) blockwise, so partial-key blocks
    yield exactly their share of dq/dk/dv).  dq accumulates locally; the
    dk/dv accumulators TRAVEL WITH their k/v block and arrive home after
    n hops having collected every chip's contribution."""
    from ..ops.flash_attention import _flash_backward

    q, k, v, out, lse, = res
    n = int(lax.psum(1, axis_name))
    idx = lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def block_grads(kk, vv, src):
        def full(_):
            return _flash_backward(q, kk, vv, out, lse, g, False,
                                   block_q, block_k)

        def diag(_):
            return _flash_backward(q, kk, vv, out, lse, g, True,
                                   block_q, block_k)

        def skip(_):
            return (jnp.zeros_like(q), jnp.zeros_like(kk),
                    jnp.zeros_like(vv))

        case = jnp.where(src == idx, 1, jnp.where(src < idx, 0, 2))
        return lax.switch(case, [full, diag, skip], None)

    dq0 = _pvary_missing(jnp.zeros(q.shape, jnp.float32), axis_name)
    dk0 = _pvary_missing(jnp.zeros(k.shape, jnp.float32), axis_name)
    dv0 = _pvary_missing(jnp.zeros(v.shape, jnp.float32), axis_name)

    def body(step, carry):
        dq, kk, vv, dkk, dvv = carry
        src = (idx - step) % n
        dq_b, dk_b, dv_b = block_grads(kk, vv, src)
        dq = dq + dq_b.astype(jnp.float32)
        dkk = dkk + dk_b.astype(jnp.float32)
        dvv = dvv + dv_b.astype(jnp.float32)
        kk = lax.ppermute(kk, axis_name, perm)
        vv = lax.ppermute(vv, axis_name, perm)
        dkk = lax.ppermute(dkk, axis_name, perm)
        dvv = lax.ppermute(dvv, axis_name, perm)
        return dq, kk, vv, dkk, dvv

    dq, _, _, dk, dv = lax.fori_loop(0, n, body, (dq0, k, v, dk0, dv0))
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


def _ring_flash_fwd_rule(q, k, v, axis_name, block_q, block_k):
    out, res = _ring_flash_fwd(q, k, v, axis_name, block_q, block_k)
    return out, res


_ring_attention_flash.defvjp(_ring_flash_fwd_rule, _ring_flash_bwd)


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                   axis_name: str = "sp",
                   causal: bool = True,
                   kernel: str = "xla",
                   block_q: int = 256, block_k: int = 256) -> jax.Array:
    """Ring attention over a sequence-sharded batch: [B, S/n, H, D] per chip.

    k/v blocks travel the ring (ppermute shift +1) for n steps; each chip
    accumulates online-softmax partial attention for its query block.
    ``kernel='flash'`` runs each step's block attention through the
    Pallas flash kernel (causal only; GQA k/v ride the ring unrepeated);
    the default ``'xla'`` path repeats GQA inputs up front."""
    if kernel == "flash":
        if not causal:
            raise NotImplementedError(
                "flash ring path is causal-only (the 3-way block split "
                "relies on it); use kernel='xla' for bidirectional")
        return _ring_attention_flash(q, k, v, axis_name, block_q, block_k)
    if kernel != "xla":
        raise ValueError(f"unknown ring attention kernel {kernel!r}")
    n = int(lax.psum(1, axis_name))
    idx = lax.axis_index(axis_name)
    B, Sq, H, D = q.shape
    Hkv = k.shape[2]
    if Hkv != H:
        rep = H // Hkv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    Sk = k.shape[1]

    m0 = jnp.full((B, H, Sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, H, Sq), jnp.float32)
    o0 = jnp.zeros_like(q, dtype=jnp.float32)
    # The carries become device-varying inside the loop (they mix with q);
    # mark the initial values varying so the fori_loop types line up.
    m0, l0, o0 = (_pvary_missing(t, axis_name) for t in (m0, l0, o0))
    q_off = idx * Sq
    perm = [(i, (i + 1) % n) for i in range(n)]

    def body(step, carry):
        m, l, o, kk, vv = carry
        # Block that started on chip (idx - step) mod n is now local.
        src = (idx - step) % n
        k_off = src * Sk
        m, l, o = _block_attend(q, kk, vv, q_off, k_off, causal, m, l, o)
        kk = lax.ppermute(kk, axis_name, perm)
        vv = lax.ppermute(vv, axis_name, perm)
        return m, l, o, kk, vv

    m, l, o, _, _ = lax.fori_loop(0, n, body, (m0, l0, o0, k, v))
    l = jnp.maximum(l, 1e-30)
    out = o / l.transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def make_ring_attn_fn(axis_name: str = "sp", causal: bool = True,
                      kernel: str = "xla",
                      block_q: int = 256, block_k: int = 256):
    """attn_fn hook for the model zoo (models/llama.py apply(attn_fn=...));
    ``kernel='flash'`` uses the Pallas kernel per ring step."""
    return functools.partial(ring_attention, axis_name=axis_name,
                             causal=causal, kernel=kernel,
                             block_q=block_q, block_k=block_k)


def make_ulysses_attn_fn(axis_name: str = "sp", causal: bool = True):
    return functools.partial(ulysses_attention, axis_name=axis_name,
                             causal=causal)
