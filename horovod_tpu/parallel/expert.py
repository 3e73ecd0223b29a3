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

import functools
from functools import partial
from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
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


# ------------------------------------------------------ a share of experts
# Serving-plane expert layer (docs/serving.md#held-experts): this chip holds
# ``held`` of ``total`` routed experts (numbers ``first .. first+held-1``),
# routes over all of them, and computes its own experts' part of the result.
# What the absent experts would add is the other chips' to compute; on one
# chip the layer runs without its exchange and nothing stands in for it.


def gated_ffn(w_gate, w_up, w_down, x, act=jax.nn.silu):
    """``(act(x W_gate) * x W_up) W_down``: one gated three-matrix expert
    (a shared expert, or one routed expert's tile of rows); ``act`` is the
    gate's activation (silu, or relu for a ReLU-gated expert)."""
    h = act(jnp.dot(x, w_gate)) * jnp.dot(x, w_up)
    return jnp.dot(h, w_down, preferred_element_type=jnp.float32)


#: bytes of one expert's three weight blocks a grid step may hold in VMEM,
#: both pipeline buffers counted (a v5e core has 128 MiB)
_TILE_FFN_VMEM = 48 << 20


def _tile_ffn_kernel(e_ref, x_ref, wg_ref, wu_ref, wd_ref, o_ref, *, act):
    """One block of the expert's hidden width: rounds where :func:`gated_ffn`
    rounds (the two products and their gated product in the rows' type),
    sums the blocks in float32."""
    @pl.when(pl.program_id(0) == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)
    x = x_ref[...]
    proj = lambda w_ref: jnp.dot(x, w_ref[...],
                                 preferred_element_type=jnp.float32
                                 ).astype(x.dtype).astype(jnp.float32)
    h = act(proj(wg_ref)).astype(x.dtype).astype(jnp.float32) * proj(wu_ref)
    o_ref[...] += jnp.dot(h.astype(x.dtype), wd_ref[...],
                          preferred_element_type=jnp.float32)


def tile_ffn(w_gate, w_up, w_down, x, e, act=jax.nn.silu):
    """:func:`gated_ffn` of rows ``x`` [tile, D] through expert ``e`` (a
    traced number) of the STACKED experts ``w_gate``, ``w_up`` [E, D, F] and
    ``w_down`` [E, F, D]: float32 [tile, D].  A kernel, because the expert's
    number is data: it reaches the blocks' index maps as a prefetched
    scalar, so the expert's matrices stream from where they lie, a block of
    the hidden width after another — indexed in XLA (``w[e]`` in a loop's
    body) each matrix is first copied out of the stack, three times the
    expert layer's bytes (PERF.md §6, PR 31).  Interpreted on the CPU."""
    tile, D = x.shape
    F = w_gate.shape[2]
    per_col = 2 * 3 * D * w_gate.dtype.itemsize     # both buffers, per column
    fb = max((b for b in range(128, F + 1, 128)
              if F % b == 0 and b * per_col <= _TILE_FFN_VMEM), default=F)
    return pl.pallas_call(
        functools.partial(_tile_ffn_kernel, act=act),
        out_shape=jax.ShapeDtypeStruct((tile, D), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(F // fb,),
            in_specs=[
                pl.BlockSpec((tile, D), lambda f, e: (0, 0)),
                pl.BlockSpec((None, D, fb), lambda f, e: (e[0], 0, f)),
                pl.BlockSpec((None, D, fb), lambda f, e: (e[0], 0, f)),
                pl.BlockSpec((None, fb, D), lambda f, e: (e[0], f, 0))],
            out_specs=pl.BlockSpec((tile, D), lambda f, e: (0, 0))),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=fb * per_col + 6 * tile * D * 4 + (16 << 20)),
        interpret=jax.default_backend() == "cpu",
        name="expert_tile_ffn",
    )(jnp.reshape(e, (1,)).astype(jnp.int32), x, w_gate, w_up, w_down)


def init_held_experts(key, dim: int, hidden: int, total: int, held: int,
                      dtype=jnp.float32) -> Dict[str, Any]:
    """Router over all ``total`` experts + the ``held`` gated experts this
    chip owns, stacked on axis 0."""
    k0, k1, k2, k3 = jax.random.split(key, 4)
    s_in, s_out = 1.0 / np.sqrt(dim), 1.0 / np.sqrt(hidden)

    def w(k, shape, s):
        return (jax.random.normal(k, shape) * s).astype(dtype)
    return {"router": {"kernel": w(k0, (dim, total), s_in)},
            "experts": {"w_gate": w(k1, (held, dim, hidden), s_in),
                        "w_up": w(k2, (held, dim, hidden), s_in),
                        "w_down": w(k3, (held, hidden, dim), s_out)}}


def route_sigmoid_topk(x, router_kernel, k: int, scale: float, bias=None,
                       eps: float = 1e-20):
    """Sigmoid scores over ALL experts in float32, the ``k`` largest with no
    groups, gates ``scale * s / (sum of the chosen s + eps)``.  A ``bias``
    [E] (a model's load-balancing ``expert_bias``) PICKS and does not weigh:
    the chosen are the ``k`` largest of ``s + bias``, their gates are made
    of the unbiased ``s``.  Returns (idx [T, k] int32, gates [T, k]
    float32)."""
    s = jax.nn.sigmoid(jnp.dot(x.astype(jnp.float32),
                               router_kernel.astype(jnp.float32),
                               precision=lax.Precision.HIGHEST))
    if bias is None:
        top, idx = lax.top_k(s, k)
    else:
        _, idx = lax.top_k(s + bias.astype(jnp.float32), k)
        top = jnp.take_along_axis(s, idx, axis=-1)
    return idx, scale * top / (top.sum(-1, keepdims=True) + eps)


def route_softmax_topk(x, router_kernel, k: int):
    """Router logits over ALL experts in float32, the ``k`` largest, gates
    the softmax over the CHOSEN logits (a softmax over all of them followed
    by a renormalised top-k is the same numbers).  Returns (idx [T, k]
    int32, gates [T, k] float32)."""
    top, idx = lax.top_k(jnp.dot(x.astype(jnp.float32),
                                 router_kernel.astype(jnp.float32),
                                 precision=lax.Precision.HIGHEST), k)
    return idx, jax.nn.softmax(top, axis=-1)


#: what ``held_experts`` counts, in the order of its counter vector
#: rows of one expert's tile (:func:`held_experts`): the number every model
#: that serves held experts declares as its ``EXPERT_TILE``
EXPERT_TILE = 64
HELD_COUNTERS = ("assignments", "assignments_held", "experts_touched",
                 "load_max")


def held_experts(p: Dict[str, Any], x, valid, *, first: int, k: int = 0,
                 scale: float = 1.0, tile: int = 64, routing=None,
                 act=jax.nn.silu):
    """This chip's part of a routed layer: ``sum_{e in top-k, e held}
    g_e E_e(x)`` for tokens ``x`` [T, D], rows with ``valid`` False routed
    nowhere.  ``p`` as :func:`init_held_experts` gives it.

    ``routing`` is the caller's decision ``(idx [T, k] int32 over ALL
    experts, gates [T, k] float32)`` — a model whose router reads another
    tensor than its experts do (models/swa_moe.py: the attention's input)
    routes there and hands it in; without it the layer routes inside
    itself from ``x`` with :func:`route_sigmoid_topk` (``k``, ``scale``).
    ``act`` is the experts' gate activation.

    Drop-free and batch-invariant: assignments are sorted by expert (a
    stable sort, so in token order within one) and cut into tiles of
    ``tile`` rows that never span two experts — as many tiles as an expert
    has rows, none when it has none.  ONE loop runs the tiles in that order,
    each through :func:`tile_ffn` with its expert's number, so no
    capacity bounds anything, the work grows with the assignments held here
    and not with experts x tokens, a token's sum is taken in expert order
    whoever shares its tick, and the program holds one loop body a layer
    however many experts are held (64 experts a layer in eight layers were
    512 unrolled loops and three minutes of compilation; PERF.md §6, PR 31).

    Returns (y [T, D] float32, counters int32[4] as HELD_COUNTERS)."""
    T, D = x.shape
    held = p["experts"]["w_gate"].shape[0]
    if routing is None:
        with jax.named_scope("moe/route"):
            routing = route_sigmoid_topk(x, p["router"]["kernel"], k, scale)
    idx, gates = routing
    k = idx.shape[1]
    with jax.named_scope("moe/dispatch"):
        local = idx - first
        mine = (local >= 0) & (local < held) & valid[:, None]
        key = jnp.where(mine, local, held).reshape(T * k)
        order = jnp.argsort(key, stable=True)
        tok = (order // k).astype(jnp.int32)        # token of a sorted row
        gate = gates.reshape(T * k)[order]
        counts = jnp.sum(key[:, None] == jnp.arange(held)[None, :], axis=0,
                         dtype=jnp.int32)
        starts = jnp.cumsum(counts) - counts
        n_valid = jnp.sum(valid, dtype=jnp.int32)
        counters = jnp.stack([n_valid * k, counts.sum(),
                              jnp.sum(counts > 0, dtype=jnp.int32),
                              counts.max()])
        # tiles, in expert order: tile t is expert ``e``'s
        # ``t - first_tile[e]``-th, e the experts whose tiles end by t
        n_tiles = (counts + tile - 1) // tile
        last_tile = jnp.cumsum(n_tiles)
        first_tile = last_tile - n_tiles
    w = p["experts"]
    rows = jnp.arange(tile, dtype=jnp.int32)

    def one_tile(t, y):
        with jax.named_scope("moe/dispatch"):
            e = jnp.sum(last_tile <= t, dtype=jnp.int32)
            at = (t - first_tile[e]) * tile + rows
            live = at < counts[e]
            src = jnp.minimum(starts[e] + at, T * k - 1)
            t_src = tok[src]
            xt = jnp.take(x, t_src, axis=0)
        with jax.named_scope("moe/experts"):
            out = tile_ffn(w["w_gate"], w["w_up"], w["w_down"], xt, e,
                           act) * gate[src][:, None]
        with jax.named_scope("moe/combine"):
            return y.at[jnp.where(live, t_src, T)].add(out, mode="drop")
    y = lax.fori_loop(0, last_tile[-1], one_tile,
                      jnp.zeros((T, D), jnp.float32))
    return y, counters


def held_ffn(p: Dict[str, Any], h, valid, *, route=None, **held):
    """:func:`held_experts` as a layer's feed-forward part on hidden states
    ``h`` [B, S, D] (``valid`` [B, S]): (y [B, S, D] in ``h``'s type,
    counters).  ``route(rows [T, D])`` -> ``(idx, gates)`` is the model's
    router, run here on what the experts read (under ``moe/route``); without
    one the decision is ``held``'s ``routing``, made elsewhere, or the
    layer's own.  ``held`` is :func:`held_experts`'s: ``first``, ``act``,
    ``tile`` ..."""
    B, S, D = h.shape
    rows = h.reshape(B * S, D)
    if route is not None:
        with jax.named_scope("moe/route"):
            held["routing"] = route(rows)
    y, counters = held_experts(p, rows, valid.reshape(B * S), **held)
    with jax.named_scope("moe/combine"):
        return y.reshape(B, S, D).astype(h.dtype), counters


__all__ = ["make_moe_fn", "init_moe_params", "moe_shardings",
           "moe_dense_reference", "gated_ffn", "tile_ffn", "init_held_experts",
           "route_sigmoid_topk", "route_softmax_topk", "held_experts",
           "held_ffn", "HELD_COUNTERS", "EXPERT_TILE"]
