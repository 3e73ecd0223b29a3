"""Pallas flash attention for TPU — the framework's hot-op kernel.

The reference ships CUDA kernels for its hot paths (reference:
horovod/common/ops/cuda/cuda_kernels.cu — batched memcpy + scale); this
framework's hot op is model attention, so the native kernel is a
blockwise online-softmax attention (flash attention) written in Pallas
for the MXU:

  * grid over (batch, q-head, q-block); K/V stream through VMEM in
    blocks with running (max, sum, accumulator) state — no [S, S] score
    matrix ever materializes in HBM;
  * fp32 accumulation regardless of input dtype (bf16 in, bf16 out);
  * causal masking skips fully-masked K blocks; GQA maps q-heads onto
    shared KV heads via the BlockSpec index map;
  * same signature as layers.causal_attention ([B, S, H, D], GQA by
    head-count ratio) so models swap it in via ``attn_fn``.

On the ``cpu`` backend (tests, CPU smoke) the kernel runs in Pallas
interpret mode — same code path, numerics checked against the XLA
reference implementation.  Ring attention (parallel/sequence.py) composes with it:
each ring step's local block attention can use this kernel.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

# Every grid axis (batch, head, q-or-k block) is independent — the
# sequential online-softmax walk over K/V lives in an in-kernel
# fori_loop, not on the grid — so Mosaic may pipeline/reorder grid
# iterations freely.  Ignored in interpret mode.
_GRID_SEMANTICS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "parallel"))


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, causal: bool,
                 block_k: int, seq_len: int, scale: float):
    # q_ref: [BQ, D]; k_ref/v_ref: [S, D]; o_ref: [BQ, D]; lse_ref: [BQ, 1]
    # (the trailing unit lane dim keeps the row-statistic blocks legal for
    # Mosaic's last-two-dims tiling rule; callers see lse as [B, H, S])
    #
    # MXU dtype discipline: matmul OPERANDS stay in the input dtype (the
    # MXU runs bf16 x bf16 -> fp32 at full rate; upcasting operands to
    # fp32 first would halve-or-worse its throughput), while every
    # softmax statistic and the output accumulator are fp32 via
    # preferred_element_type.  The scale folds into the fp32 accumulator
    # AFTER the q.k matmul, not into q.
    qi = pl.program_id(2)
    bq = q_ref.shape[0]
    d = q_ref.shape[1]
    q = q_ref[:]

    m = jnp.full((bq, 1), NEG_INF, jnp.float32)       # running max
    l = jnp.zeros((bq, 1), jnp.float32)               # running sum
    acc = jnp.zeros((bq, d), jnp.float32)

    q_start = qi * bq
    num_kb = pl.cdiv(seq_len, block_k)
    # causal split: K blocks strictly after this q block contribute
    # nothing; blocks entirely at-or-below the diagonal need no mask at
    # all (most blocks, for long sequences) — only the diagonal-crossing
    # tail pays the iota/compare/select VPU tax.
    kb_hi = jnp.minimum(num_kb,
                        pl.cdiv(q_start + bq, block_k)) if causal else num_kb
    kb_full = (q_start // block_k) if causal else num_kb

    def body(kb, carry, *, masked):
        m, l, acc = carry
        k_start = kb * block_k
        k = k_ref[pl.ds(k_start, block_k), :]
        v = v_ref[pl.ds(k_start, block_k), :]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if masked:
            q_pos = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (bq, block_k), 0)
            k_pos = k_start + jax.lax.broadcasted_iota(
                jnp.int32, (bq, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=1, keepdims=True)
        # p back to the input dtype for the second matmul (bf16 inputs ->
        # full-rate MXU; fp32 inputs keep fp32 precision)
        acc = acc * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l, acc

    m, l, acc = jax.lax.fori_loop(
        0, kb_full, functools.partial(body, masked=False), (m, l, acc))
    m, l, acc = jax.lax.fori_loop(
        kb_full, kb_hi, functools.partial(body, masked=causal), (m, l, acc))
    l = jnp.maximum(l, 1e-30)
    o_ref[:] = (acc / l).astype(o_ref.dtype)
    # logsumexp of the SCALED scores — the backward kernels rebuild
    # p = exp(s - lse) from it without re-running the online softmax.
    lse_ref[:] = m + jnp.log(l)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, *, causal: bool, block_k: int, seq_len: int,
                   scale: float):
    # q/do/dq: [BQ, D]; k/v: [S, D]; lse/delta: [BQ, 1]
    qi = pl.program_id(2)
    bq = q_ref.shape[0]
    d = q_ref.shape[1]
    # Same MXU dtype discipline as the forward: operands in input dtype,
    # fp32 accumulation, scale folded in fp32 (s after the matmul, dq at
    # the end).
    q = q_ref[:]
    do = do_ref[:]
    lse = lse_ref[:].astype(jnp.float32)
    delta = delta_ref[:].astype(jnp.float32)

    q_start = qi * bq
    num_kb = pl.cdiv(seq_len, block_k)
    kb_hi = jnp.minimum(num_kb,
                        pl.cdiv(q_start + bq, block_k)) if causal else num_kb
    # blocks entirely below the diagonal skip the mask (see _attn_kernel)
    kb_full = (q_start // block_k) if causal else num_kb

    def body(kb, dq, *, masked):
        k_start = kb * block_k
        k = k_ref[pl.ds(k_start, block_k), :]
        v = v_ref[pl.ds(k_start, block_k), :]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if masked:
            q_pos = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (bq, block_k), 0)
            k_pos = k_start + jax.lax.broadcasted_iota(
                jnp.int32, (bq, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta)).astype(q_ref.dtype)
        return dq + jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    dq = jax.lax.fori_loop(0, kb_full,
                           functools.partial(body, masked=False),
                           jnp.zeros((bq, d), jnp.float32))
    dq = jax.lax.fori_loop(kb_full, kb_hi,
                           functools.partial(body, masked=causal), dq)
    dq_ref[:] = (dq * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(k_ref, v_ref, q_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, *, causal: bool, block_q: int,
                    seq_len: int, scale: float):
    # k/v/dk/dv: [BK, D]; q/do: [S, D]; lse/delta: [S, 1]
    ki = pl.program_id(2)
    bk = k_ref.shape[0]
    d = k_ref.shape[1]
    # Input-dtype operands / fp32 accumulators, as in the other kernels.
    # dk absorbs the softmax scale once at the end (d/dk of s=(q.k)*scale)
    # instead of pre-scaling every q block.
    k = k_ref[:]
    v = v_ref[:]

    k_start = ki * bk
    num_qb = pl.cdiv(seq_len, block_q)
    # causal: q blocks strictly before this k block contribute nothing;
    # q blocks entirely past the diagonal need no mask (see _attn_kernel)
    qb_lo = (k_start // block_q) if causal else 0
    qb_full_lo = (pl.cdiv(k_start + bk, block_q) if causal else 0)

    def body(qb, carry, *, masked):
        dk, dv = carry
        q_start = qb * block_q
        q = q_ref[pl.ds(q_start, block_q), :]
        do = do_ref[pl.ds(q_start, block_q), :]
        lse = lse_ref[pl.ds(q_start, block_q), :].astype(jnp.float32)
        delta = delta_ref[pl.ds(q_start, block_q), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if masked:
            q_pos = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, bk), 0)
            k_pos = k_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, bk), 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        p = jnp.exp(s - lse)                              # [BQ2, BK]
        dv = dv + jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta)).astype(q.dtype)
        dk = dk + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return dk, dv

    zeros = (jnp.zeros((bk, d), jnp.float32),
             jnp.zeros((bk, d), jnp.float32))
    dk, dv = jax.lax.fori_loop(
        qb_lo, jnp.minimum(qb_full_lo, num_qb),
        functools.partial(body, masked=causal), zeros)
    dk, dv = jax.lax.fori_loop(
        jnp.minimum(qb_full_lo, num_qb), num_qb,
        functools.partial(body, masked=False), (dk, dv))
    dk_ref[:] = (dk * scale).astype(dk_ref.dtype)
    dv_ref[:] = dv.astype(dv_ref.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True,
                    block_q: int = 256, block_k: int = 256,
                    interpret: Optional[bool] = None) -> jax.Array:
    """Blockwise attention, model layout [B, S, H, D] with GQA.

    Training uses Pallas kernels on BOTH passes: the forward saves the
    per-row logsumexp, and the backward rebuilds the probabilities
    blockwise in two kernels (dq; dk+dv) — the flash-attention backward
    algorithm, no [S, S] score matrix in either direction.

    ``interpret=None`` interprets only on the ``cpu`` backend (tests,
    CPU smoke); every other backend compiles the kernel or raises."""
    return _flash_forward(q, k, v, causal, block_q, block_k, interpret)[0]


def _flash_fwd_rule(q, k, v, causal, block_q, block_k, interpret):
    out, lse = _flash_forward(q, k, v, causal, block_q, block_k, interpret)
    return out, (q, k, v, out, lse)


def _flash_bwd_rule(causal, block_q, block_k, interpret, res, g):
    q, k, v, out, lse = res
    return _flash_backward(q, k, v, out, lse, g, causal, block_q, block_k,
                           interpret)


def _resolve_blocks(S, block_q, block_k, interpret):
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    if S % block_q or S % block_k:
        raise ValueError(f"seq len {S} must divide block sizes "
                         f"({block_q}, {block_k})")
    return block_q, block_k, interpret


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "interpret"))
def _flash_forward(q: jax.Array, k: jax.Array, v: jax.Array,
                   causal: bool = True,
                   block_q: int = 256, block_k: int = 256,
                   interpret: Optional[bool] = None):
    B, S, H, D = q.shape
    HK = k.shape[2]
    if H % HK:
        raise ValueError(
            f"q heads ({H}) must be a multiple of kv heads ({HK}) for GQA")
    group = H // HK
    block_q, block_k, interpret = _resolve_blocks(S, block_q, block_k,
                                                  interpret)

    # kernel layout [B, H, S, D]
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    scale = 1.0 / (D ** 0.5)

    kernel = functools.partial(_attn_kernel, causal=causal,
                               block_k=block_k, seq_len=S, scale=scale)
    out, lse = pl.pallas_call(
        kernel,
        grid=(B, H, S // block_q),
        in_specs=[
            pl.BlockSpec((None, None, block_q, D),
                         lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((None, None, S, D),
                         lambda b, h, i, g=group: (b, h // g, 0, 0)),
            pl.BlockSpec((None, None, S, D),
                         lambda b, h, i, g=group: (b, h // g, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, None, block_q, D),
                         lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((None, None, block_q, 1),
                         lambda b, h, i: (b, h, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, D), q.dtype),
            jax.ShapeDtypeStruct((B, H, S, 1), jnp.float32),
        ],
        compiler_params=_GRID_SEMANTICS,
        interpret=interpret,
    )(qt, kt, vt)
    return jnp.swapaxes(out, 1, 2), lse[..., 0]


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "interpret"))
def _flash_backward(q, k, v, out, lse, g, causal: bool = True,
                    block_q: int = 256, block_k: int = 256,
                    interpret: Optional[bool] = None):
    B, S, H, D = q.shape
    HK = k.shape[2]
    group = H // HK
    block_q, block_k, interpret = _resolve_blocks(S, block_q, block_k,
                                                  interpret)
    scale = 1.0 / (D ** 0.5)

    qt = jnp.swapaxes(q, 1, 2)
    do = jnp.swapaxes(g, 1, 2)
    ot = jnp.swapaxes(out, 1, 2)
    # GQA: K/V stay at their real [B, HK, S, D] footprint; the h//group
    # index maps fan each q-head onto its shared kv head (same trick as
    # the forward), and only the per-q-head dk/dv OUTPUTS carry H extent
    # before the group summation below.
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    # delta_i = sum_d dO_i * O_i  (the softmax-jacobian row correction);
    # row statistics carry a trailing unit lane dim for Mosaic tiling
    delta = jnp.sum(do.astype(jnp.float32) * ot.astype(jnp.float32),
                    axis=-1, keepdims=True)
    lse = lse[..., None]

    qspec = pl.BlockSpec((None, None, block_q, D),
                         lambda b, h, i: (b, h, i, 0))
    kvfull = pl.BlockSpec((None, None, S, D),
                          lambda b, h, i, g=group: (b, h // g, 0, 0))
    qfull = pl.BlockSpec((None, None, S, D), lambda b, h, i: (b, h, 0, 0))
    rowq = pl.BlockSpec((None, None, block_q, 1),
                        lambda b, h, i: (b, h, i, 0))
    rowfull = pl.BlockSpec((None, None, S, 1), lambda b, h, i: (b, h, 0, 0))

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, causal=causal, block_k=block_k,
                          seq_len=S, scale=scale),
        grid=(B, H, S // block_q),
        in_specs=[qspec, kvfull, kvfull, qspec, rowq, rowq],
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct((B, H, S, D), q.dtype),
        compiler_params=_GRID_SEMANTICS,
        interpret=interpret,
    )(qt, kt, vt, do, lse, delta)

    kspec = pl.BlockSpec((None, None, block_k, D),
                         lambda b, h, i: (b, h, i, 0))
    kvblock = pl.BlockSpec((None, None, block_k, D),
                           lambda b, h, i, g=group: (b, h // g, i, 0))
    # Per-q-head dk/dv stay fp32 so the GQA group summation below does
    # not compound bf16 rounding; one cast to the input dtype at the end.
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, causal=causal, block_q=block_q,
                          seq_len=S, scale=scale),
        grid=(B, H, S // block_k),
        in_specs=[kvblock, kvblock, qfull, qfull, rowfull, rowfull],
        out_specs=[kspec, kspec],
        out_shape=[jax.ShapeDtypeStruct((B, H, S, D), jnp.float32),
                   jax.ShapeDtypeStruct((B, H, S, D), jnp.float32)],
        compiler_params=_GRID_SEMANTICS,
        interpret=interpret,
    )(kt, vt, qt, do, lse, delta)

    if group > 1:  # sum each kv head's group of q-head contributions
        dk = dk.reshape(B, HK, group, S, D).sum(axis=2)
        dv = dv.reshape(B, HK, group, S, D).sum(axis=2)

    return (jnp.swapaxes(dq, 1, 2),
            jnp.swapaxes(dk, 1, 2).astype(k.dtype),
            jnp.swapaxes(dv, 1, 2).astype(v.dtype))


flash_attention.defvjp(_flash_fwd_rule, _flash_bwd_rule)
