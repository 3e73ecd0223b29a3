"""Pipeline parallelism: GPipe microbatch schedule over a mesh axis.

Beyond-reference capability (the reference is data-parallel only —
SURVEY §2.3 rows TP/PP/EP are "NO"): stages of a layer stack live on
different chips along a ``pp`` mesh axis, microbatches stream through a
``lax.scan`` of compute-then-``ppermute`` ticks, and XLA differentiates
THROUGH the schedule (ppermute's transpose is the reverse permute), so
the backward pass is pipelined automatically — no hand-written 1F1B
state machine, the idiomatic JAX formulation (scaling-book pipelining
chapter pattern).

Design constraints that make this MXU/ICI-friendly:
  * stage function input/output shapes match (transformer-block shape),
    so every tick is the same compiled program;
  * all cross-stage traffic is a single ``ppermute`` ring shift per tick
    riding ICI neighbors;
  * the schedule is static (``n_micro + n_stages - 1`` ticks), no
    data-dependent control flow.

Usage::

    params = stack_stage_params([stage0, stage1, ...])       # [S, ...]
    fn = make_pipeline_fn(stage_fn, mesh, n_micro=8)          # pp axis
    out = fn(params, x)            # x: [B, ...], out: [B, ...]
    loss_grads = jax.grad(lambda p, x, y: loss(fn(p, x), y))  # pipelined
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def stack_stage_params(stage_params: Sequence[Any]) -> Any:
    """Stack per-stage parameter pytrees along a new leading [S] axis —
    the layout the pipeline shards over the ``pp`` mesh axis (one stage
    slice per chip)."""
    return jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs, axis=0), *stage_params)


def _spmd_pipeline(stage_fn: Callable, params_local: Any, x: jnp.ndarray,
                   n_micro: int, axis: str) -> jnp.ndarray:
    """Body that runs INSIDE shard_map: this chip is stage ``idx`` of
    ``S``; microbatches enter at stage 0 and exit at stage S-1.

    ``x``: [M, mb, ...] microbatches (replicated across the pp axis —
    only stage 0 reads it); returns [M, mb, ...] outputs (replicated —
    only stage S-1's contribution is real, psum-broadcast at the end).
    """
    S = lax.psum(1, axis)
    idx = lax.axis_index(axis)
    params_stage = jax.tree_util.tree_map(lambda a: a[0], params_local)
    mb_shape = x.shape[1:]

    def tick(carry, t):
        recv, outputs = carry
        # What this stage works on at tick t is microbatch (t - idx).
        mb_idx = t - idx
        active = jnp.logical_and(mb_idx >= 0, mb_idx < n_micro)
        x_in = x[jnp.clip(t, 0, n_micro - 1)]
        inp = jnp.where(idx == 0, x_in, recv)
        out = stage_fn(params_stage, inp)
        out = jnp.where(active, out, jnp.zeros_like(out))
        # Last stage banks its finished microbatch (masked dynamic write;
        # other stages re-write the current value, a no-op).
        write = jnp.logical_and(idx == S - 1, active)
        slot = jnp.clip(mb_idx, 0, n_micro - 1)
        outputs = outputs.at[slot].set(
            jnp.where(write, out, outputs[slot]))
        # ...everyone shifts their activation to the next stage (one ICI
        # neighbor hop; the wrap-around link back to stage 0 carries
        # zeros, masked out by the idx == 0 branch above).
        nxt = lax.ppermute(out, axis,
                           [(i, (i + 1) % S) for i in range(S)])
        return (nxt, outputs), None

    recv0 = jnp.zeros(mb_shape, x.dtype)
    outputs0 = jnp.zeros((n_micro,) + mb_shape, x.dtype)
    (_, outputs), _ = lax.scan(tick, (recv0, outputs0),
                               jnp.arange(n_micro + S - 1))
    # Broadcast the last stage's banked outputs to every stage (sum of
    # zeros elsewhere).
    return lax.psum(jnp.where(idx == S - 1, outputs,
                              jnp.zeros_like(outputs)), axis)


def make_pipeline_fn(stage_fn: Callable, mesh: Mesh, n_micro: int,
                     axis: str = "pp",
                     batch_axis: str | None = None) -> Callable:
    """Build ``apply(params_stacked, x) -> out`` where ``params_stacked``
    has a leading [S] stage axis (see :func:`stack_stage_params`) and the
    batch is cut into ``n_micro`` microbatches.

    ``stage_fn(stage_params, x) -> y`` must preserve x's shape (the
    transformer-block contract).  The returned apply is differentiable;
    ``jax.grad`` through it yields a pipelined backward schedule.

    ``batch_axis`` composes pipeline with data parallelism on a 2-D mesh
    (e.g. ``pp x dp``): each microbatch's row dim is sharded over it, and
    because the stacked params enter replicated over that axis, autodiff
    through shard_map inserts the gradient psum automatically.
    """
    S = mesh.shape[axis]

    @partial(shard_map, mesh=mesh,
             in_specs=(P(axis), P(None, batch_axis)),
             out_specs=P(None, batch_axis),
             check_vma=False)
    def _inner(params_stacked, xm):
        return _spmd_pipeline(stage_fn, params_stacked, xm, n_micro, axis)

    def apply(params_stacked, x):
        B = x.shape[0]
        if B % n_micro:
            raise ValueError(f"batch {B} not divisible by "
                             f"n_micro={n_micro}")
        if batch_axis is not None and \
                (B // n_micro) % mesh.shape[batch_axis]:
            raise ValueError(
                f"microbatch rows {B // n_micro} not divisible by "
                f"{batch_axis}={mesh.shape[batch_axis]}")
        xm = x.reshape((n_micro, B // n_micro) + x.shape[1:])
        out = _inner(params_stacked, xm)
        return out.reshape((B,) + out.shape[2:])

    # surface for introspection/tests
    apply.n_stages = S
    apply.n_micro = n_micro
    return apply


def pipeline_shardings(mesh: Mesh, params_stacked: Any,
                       axis: str = "pp"):
    """NamedShardings placing each stage's slice of the stacked params on
    its pipeline chip (leading [S] axis over the ``pp`` mesh axis)."""
    sh = NamedSharding(mesh, P(axis))
    return jax.tree_util.tree_map(lambda _: sh, params_stacked)


def make_pipelined_llama(cfg, mesh: Mesh, n_micro: int,
                         axis: str = "pp",
                         batch_axis: str | None = None):
    """Pipeline the flagship llama over ``pp``: the shape-preserving layer
    stack runs through the GPipe schedule (layers grouped
    ``n_layers // n_stages`` per stage, scanned locally), while the
    embedding / final-norm / lm-head stay outside (they change shape).

    Returns ``(apply_fn, restack)`` where ``restack(params)`` converts a
    standard ``llama.init`` pytree into ``{"embed", "final_norm",
    "lm_head", "stages"}`` with stages stacked [S, L/S, ...], and
    ``apply_fn(pparams, ids) -> logits`` is differentiable end-to-end.
    """
    from ..models import llama as Ll
    from ..models import layers as L

    S = mesh.shape[axis]
    if cfg.n_layers % S:
        raise ValueError(f"n_layers={cfg.n_layers} not divisible by "
                         f"{axis}={S} stages")
    per_stage = cfg.n_layers // S
    cos, sin = L.rope_freqs(cfg.head_dim, cfg.max_seq, cfg.rope_theta)

    def restack(params):
        layers = params["layers"]
        groups = [stack_stage_params(layers[s * per_stage:
                                            (s + 1) * per_stage])
                  for s in range(S)]
        return {
            "embed": params["embed"],
            "final_norm": params["final_norm"],
            "lm_head": params["lm_head"],
            "stages": stack_stage_params(groups),  # [S, L/S, ...]
        }

    def stage_fn(stage_params, x):
        # stage_params: [L/S, ...]; scan this stage's layers locally.
        def body(h, lp):
            return Ll.apply_layer(lp, h, cfg, cos, sin), None
        out, _ = lax.scan(body, x, stage_params)
        return out

    pipe = make_pipeline_fn(stage_fn, mesh, n_micro, axis=axis,
                            batch_axis=batch_axis)

    def apply_fn(pparams, ids):
        x = L.embedding(pparams["embed"], ids).astype(cfg.dtype)
        x = pipe(pparams["stages"], x)
        x = L.rmsnorm(pparams["final_norm"], x)
        return L.dense(pparams["lm_head"], x)

    return apply_fn, restack


def pipeline_bubble_fraction(n_stages: int, n_micro: int) -> float:
    """The GPipe bubble overhead (S-1)/(M+S-1) — exposed so autotuning /
    benchmarks can pick ``n_micro`` (reference has no analog; standard
    pipelining arithmetic)."""
    return (n_stages - 1) / (n_micro + n_stages - 1)


__all__ = ["make_pipeline_fn", "stack_stage_params", "pipeline_shardings",
           "make_pipelined_llama", "pipeline_bubble_fraction"]
