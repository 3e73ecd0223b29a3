"""Expert parallelism: switch-style MoE dispatch over an ``ep`` mesh axis.

Beyond-reference capability (SURVEY §2.3: EP is "NO built-in; same
alltoall primitive" — the reference only offers ``hvd.alltoall`` for
users to build this themselves).  Here it is first-class: a capacity-
bounded top-1 (switch) router builds a static-shape dispatch tensor, and
TWO ``lax.all_to_all`` hops over the ``ep`` axis move tokens to their
expert's chip and back — the canonical TPU MoE data path (einsum-based
dispatch/combine keeps everything on the MXU; static capacity keeps
shapes compile-time constant).

Layout: with E experts over an ep-way axis, each chip owns E/ep experts
and a token shard.  Per shard: route -> dispatch einsum [T,D]x[T,E,C] ->
[E,C,D] -> all_to_all -> expert FFN -> all_to_all back -> combine einsum
weighted by the router gate.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def init_moe_params(key, dim: int, hidden: int, n_experts: int,
                    dtype=jnp.float32) -> Dict[str, jnp.ndarray]:
    """Router + per-expert FFN weights, experts stacked on axis 0 (the
    axis sharded over ``ep``)."""
    k1, k2, k3 = jax.random.split(key, 3)
    scale_in = 1.0 / np.sqrt(dim)
    scale_out = 1.0 / np.sqrt(hidden)
    return {
        "router": (jax.random.normal(k1, (dim, n_experts)) *
                   scale_in).astype(dtype),
        "wi": (jax.random.normal(k2, (n_experts, dim, hidden)) *
               scale_in).astype(dtype),
        "wo": (jax.random.normal(k3, (n_experts, hidden, dim)) *
               scale_out).astype(dtype),
    }


def _route_topk(logits: jnp.ndarray, capacity: int, k: int = 1):
    """Top-k router, capacity-bounded (k=1: Switch; k=2: Mixtral/GShard).

    Returns the [T, E, C] dispatch tensor (0/1), the [T, E, C] COMBINE
    tensor (dispatch weighted by each choice's gate), and the
    load-balancing auxiliary loss (Switch eq. 4 generalized:
    E * sum_e f_e * P_e with f_e the raw pre-capacity fraction of
    routing assignments — 1.0 when balanced, up to E on collapse; the
    raw fraction is used because capacity-masking f_e would clamp the
    hot expert exactly when imbalance is worst).

    Gate convention follows the papers: k=1 uses the raw softmax prob
    (Switch); k>1 renormalizes the selected gates to sum to 1 per token
    (Mixtral).  Capacity slots are granted choice-major (every token's
    1st choice before any 2nd choice — GShard's priority order)."""
    T, E = logits.shape
    probs = jax.nn.softmax(logits, axis=-1)
    top_vals, top_idx = jax.lax.top_k(probs, k)  # [T, k]
    if k > 1:
        gates = top_vals / jnp.maximum(
            top_vals.sum(-1, keepdims=True), 1e-30)
    else:
        gates = top_vals

    # Slot bookkeeping runs in fp32 regardless of logits dtype: bf16
    # cumsum cannot represent integers above 256, so slot positions on
    # a hot expert would collide and sum multiple tokens into one
    # capacity slot.  Only disp/comb are cast back at the end.
    disp = jnp.zeros((T, E, capacity), jnp.float32)
    comb = jnp.zeros((T, E, capacity), jnp.float32)
    raw_total = jnp.zeros((E,), jnp.float32)
    slot_base = jnp.zeros((1, E), jnp.float32)
    gates32 = gates.astype(jnp.float32)
    for j in range(k):
        oh = jax.nn.one_hot(top_idx[:, j], E, dtype=jnp.float32)
        raw_total = raw_total + oh.sum(0)
        # 1-based slot per (token, expert), offset past prior choices'
        # claims so slots never collide across choice ranks.
        position = slot_base + jnp.cumsum(oh, axis=0) * oh
        within = jnp.logical_and(position >= 1, position <= capacity)
        ohk = oh * within
        disp_j = ohk[:, :, None] * jax.nn.one_hot(
            jnp.maximum(position - 1, 0).astype(jnp.int32), capacity,
            dtype=jnp.float32)
        disp = disp + disp_j
        comb = comb + disp_j * gates32[:, j][:, None, None]
        slot_base = slot_base + oh.sum(0, keepdims=True)
    aux = E * jnp.sum((raw_total / (T * k)) *
                      jnp.mean(probs.astype(jnp.float32), axis=0))
    return (disp.astype(logits.dtype), comb.astype(logits.dtype),
            aux.astype(logits.dtype))


def _expert_ffn(wi, wo, x):
    """Per-expert MLP batched over the local experts dim:
    x [El, S, D] -> [El, S, D]."""
    h = jax.nn.gelu(jnp.einsum("esd,edh->esh", x, wi))
    return jnp.einsum("esh,ehd->esd", h, wo)


def make_moe_fn(mesh: Mesh, n_experts: int,
                capacity_factor: float = 1.25,
                axis: str = "ep",
                experts_per_token: int = 1) -> Callable:
    """Build ``apply(params, x) -> (y, aux_loss)`` where ``x`` is
    [T, D] tokens (sharded over ``axis``) and ``params`` comes from
    :func:`init_moe_params` (experts sharded over ``axis``).

    ``experts_per_token``: 1 = Switch (raw-prob gate), 2 = Mixtral-style
    top-2 with renormalized gates.  Capacity scales with it:
    ``ceil(T * k * capacity_factor / E)`` slots per expert.

    Differentiable end-to-end; ``aux_loss`` is the Switch load-balancing
    term (mean over shards), to be added to the task loss scaled by the
    caller.
    """
    ep = mesh.shape[axis]
    if n_experts % ep:
        raise ValueError(f"n_experts={n_experts} not divisible by "
                         f"{axis}={ep}")
    e_local = n_experts // ep

    @partial(shard_map, mesh=mesh,
             in_specs=({"router": P(), "wi": P(axis), "wo": P(axis)},
                       P(axis)),
             out_specs=(P(axis), P()),
             check_vma=False)
    def _inner(params, x):
        T = x.shape[0]  # local token count
        capacity = int(np.ceil(T * experts_per_token * capacity_factor /
                               n_experts))
        logits = x @ params["router"]
        disp, comb, aux = _route_topk(logits, capacity,
                                      k=experts_per_token)

        # [T,D] x [T,E,C] -> [E,C,D]: tokens in their expert's slot.
        xd = jnp.einsum("td,tec->ecd", x, disp)
        # Ship slots to the owning chips: split E into [ep, e_local] and
        # trade the ep dim for the token-source dim.
        xd = xd.reshape(ep, e_local, capacity, xd.shape[-1])
        xd = lax.all_to_all(xd, axis, split_axis=0, concat_axis=0,
                            tiled=False)
        # Now [ep(source), e_local, C, D]: merge source chips into the
        # expert's working set (transpose first — a bare reshape would
        # interleave experts across source chunks).
        d = xd.shape[-1]
        xw = xd.transpose(1, 0, 2, 3).reshape(e_local, ep * capacity, d)
        yw = _expert_ffn(params["wi"], params["wo"], xw)
        # Send results home (inverse all_to_all).
        yd = yw.reshape(e_local, ep, capacity, d).transpose(1, 0, 2, 3)
        yd = lax.all_to_all(yd, axis, split_axis=0, concat_axis=0,
                            tiled=False)
        yd = yd.reshape(n_experts, capacity, yd.shape[-1])
        # Combine back to token order, weighted per choice by the gate.
        y = jnp.einsum("ecd,tec->td", yd, comb)
        return y, lax.pmean(aux, axis)

    def apply(params, x):
        if x.shape[0] % ep:
            raise ValueError(
                f"token count {x.shape[0]} not divisible by {axis}={ep}")
        return _inner(params, x)

    return apply


def moe_shardings(mesh: Mesh, params: Any, axis: str = "ep"):
    """NamedShardings for init_moe_params output: experts over ``ep``,
    router replicated."""
    return {
        "router": NamedSharding(mesh, P()),
        "wi": NamedSharding(mesh, P(axis)),
        "wo": NamedSharding(mesh, P(axis)),
    }


def moe_dense_reference(params, x, n_experts: int, capacity: int,
                        experts_per_token: int = 1):
    """Single-device reference with IDENTICAL routing math (for tests):
    every token goes through its routed expert(s) unless over capacity."""
    logits = x @ params["router"]
    disp, comb, aux = _route_topk(logits, capacity, k=experts_per_token)
    y_all = jnp.einsum("td,edh->teh", x, params["wi"])
    y_all = jax.nn.gelu(y_all)
    y_all = jnp.einsum("teh,ehd->ted", y_all, params["wo"])
    sel = comb.sum(-1)  # [T, E] per-(token,expert) combine weight
    y = jnp.einsum("ted,te->td", y_all, sel)
    return y, aux


__all__ = ["make_moe_fn", "init_moe_params", "moe_shardings",
           "moe_dense_reference"]
