"""DistributedOptimizer: gradient synchronization as an optax transform.

The reference wraps framework optimizers so that every ``step()`` allreduces
gradients first — via per-parameter hooks on torch (reference:
horovod/torch/optimizer.py:128-333) or gradient-tape interposition on TF
(reference: horovod/tensorflow/__init__.py:601-724), with local aggregation
over ``backward_passes_per_step`` (gradient_aggregation.py:16) and optional
grouped/fused buckets (optimizer.py ``num_groups``).

TPU-native shape: gradient sync belongs *inside* the jitted SPMD train step,
so ``DistributedOptimizer`` is an `optax.GradientTransformation` wrapper
whose ``update`` (a) optionally accumulates ``backward_passes_per_step``
micro-batches, (b) packs gradients into fusion buckets, (c) runs one fused
``psum``/Adasum per bucket over the mesh axis with optional fp16/bf16 wire
compression, then (d) delegates to the inner optimizer.  Used under
`shard_map`/`pmap` binding ``axis_name`` — or with ``axis_name=None`` it
degrades to the inner optimizer (single-chip).

``sync_gradients`` is exposed standalone as the `DistributedGradientTape`
analog (reference: tensorflow/__init__.py:726-816).
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional, Sequence, Tuple, \
    Union

import jax
import jax.numpy as jnp
import numpy as np
import optax

from .common.reduce_op import ReduceOp, Average, Sum
from .ops import spmd, wire as _wire
from .ops.compression import Compression, Compressor
from .ops.fusion import make_plan, fused_apply

AxisName = Union[str, Sequence[str]]
WirePolicy = Union[str, Callable[[int, Any, AxisName], str]]

DEFAULT_FUSION_BYTES = 128 * 1024 * 1024


def _resolve_wire_policy(wire_policy: Optional[WirePolicy],
                         quantized_wire: bool,
                         compression: type[Compressor],
                         op: ReduceOp
                         ) -> Tuple[Optional[Any],
                                    Optional[type[Compressor]]]:
    """The policy plane's resolution order (docs/tensor-fusion.md):

        wire_policy > quantized_wire > compression > HOROVOD_WIRE_POLICY

    The pre-policy kwargs keep working as deprecated aliases —
    ``quantized_wire=True`` maps to the 'int8_ring' policy and
    ``Compression.bf16/fp16`` to their cast policies — and combining them
    is no longer an error: the stronger format simply wins.  Returns
    ``(policy_fn, legacy_compressor)``; a custom Compressor subclass
    (no policy equivalent) returns as the legacy compressor instead."""
    if wire_policy is not None:
        return _wire.get_policy(wire_policy), None
    if quantized_wire:
        if op not in (Average, Sum):
            raise ValueError(
                "quantized_wire supports Average/Sum reductions only "
                f"(got {op}); Adasum/Min/Max/Product have no quantized "
                "ring")
        return _wire.get_policy("int8_ring"), None
    if compression is Compression.bf16:
        return _wire.get_policy("bf16"), None
    if compression is Compression.fp16:
        return _wire.get_policy("fp16"), None
    if compression is not Compression.none:
        return None, compression  # custom compressor: legacy fused path
    from . import runtime as _rt
    if _rt.is_initialized():
        name = _rt.get().wire_policy()
    else:
        from .common.knobs import current
        name = _wire.validate_policy_name(current("HOROVOD_WIRE_POLICY"))
    return _wire.get_policy(name), None


def _plan_for(leaves, threshold: int):
    """Bucket plan for a flat leaf list — through the runtime's
    ``BucketPlanCache`` when initialized, so repeat traces of the SPMD
    path hit the cache (and move the ``hvd_fusion_plan_cache_*``
    metrics) exactly like the eager path (ops/collectives.py)."""
    from . import runtime as _rt
    shapes = [l.shape for l in leaves]
    dtypes = [l.dtype for l in leaves]
    if _rt.is_initialized():
        return _rt.get().plan_cache.get(shapes, dtypes, threshold)
    return make_plan(shapes, dtypes, threshold)


def _sync_impl(grads: Any,
               residuals: Optional[Any],
               axis_name: Optional[AxisName],
               op: ReduceOp,
               compression: type[Compressor],
               prescale_factor: float,
               postscale_factor: float,
               fusion_threshold_bytes: Optional[int],
               quantized_wire: bool,
               wire_policy: Optional[WirePolicy]) -> Tuple[Any, Any]:
    """Shared engine behind sync_gradients / sync_gradients_ef; returns
    ``(synced, new_residuals)`` (residuals pass through untouched when
    error feedback is off or nothing lossy ran)."""
    if axis_name is None:
        return grads, residuals
    # Resolve a logical axis against the global mesh so standalone callers
    # (the DistributedGradientTape analog) get two-level dcn/ici routing on
    # multi-slice meshes.  An axis already bound at the call site (the
    # caller's own mesh) is left untouched — the binding context, not the
    # global mesh, owns its meaning.
    from . import runtime as _rt
    if isinstance(axis_name, str) and _rt.is_initialized():
        try:
            jax.lax.axis_size(axis_name)  # bound in this trace?
        except NameError:
            from .parallel.hierarchical import resolve_axis
            try:
                axis_name = resolve_axis(axis_name, _rt.get().mesh)
            except ValueError:
                pass
    leaves, treedef = jax.tree_util.tree_flatten(grads)
    if not leaves:
        return grads, residuals
    threshold = fusion_threshold_bytes
    if threshold is None:
        # fusion_threshold() tracks the autotuner when HOROVOD_AUTOTUNE is
        # on; a threshold change re-traces with the new bucket plan.
        threshold = (_rt.get().fusion_threshold()
                     if _rt.is_initialized() else DEFAULT_FUSION_BYTES)
    plan = _plan_for(leaves, threshold)

    policy, legacy_comp = _resolve_wire_policy(
        wire_policy, quantized_wire, compression, op)
    if legacy_comp is not None:
        # Custom Compressor subclass: the pre-policy fused path (no error
        # feedback — custom codecs predate the plane and own their loss).
        def reduce_bucket(buf: jax.Array) -> jax.Array:
            buf, ctx = legacy_comp.compress(buf)
            buf = spmd.allreduce(buf, axis_name, op=op,
                                 prescale_factor=prescale_factor,
                                 postscale_factor=postscale_factor)
            return legacy_comp.decompress(buf, ctx)

        with jax.named_scope("grad_sync"):
            synced = fused_apply(leaves, plan, reduce_bucket)
        return jax.tree_util.tree_unflatten(treedef, synced), residuals

    formats = _wire.plan_formats(plan, policy, axis_name, op)
    res_leaves = (jax.tree_util.tree_leaves(residuals)
                  if residuals is not None else None)
    # grad_sync/bucket<i> in the lowered program and the device trace
    # (docs/profiling.md#scopes); the bucket loops open the inner scope
    with jax.named_scope("grad_sync"):
        synced, new_res = _wire.wire_sync(
            leaves, plan, formats, axis_name, op,
            prescale_factor=prescale_factor,
            postscale_factor=postscale_factor, residuals=res_leaves)
    out = jax.tree_util.tree_unflatten(treedef, synced)
    if new_res is None:
        return out, residuals
    return out, jax.tree_util.tree_unflatten(treedef, new_res)


def sync_gradients(grads: Any,
                   axis_name: Optional[AxisName],
                   op: ReduceOp = Average,
                   compression: type[Compressor] = Compression.none,
                   prescale_factor: float = 1.0,
                   postscale_factor: float = 1.0,
                   fusion_threshold_bytes: Optional[int] = None,
                   quantized_wire: bool = False,
                   wire_policy: Optional[WirePolicy] = None) -> Any:
    """Allreduce a gradient pytree over ``axis_name`` with bucket fusion.

    The fusion plan is computed at trace time (static shapes), so the
    compiled step contains a handful of large collectives — the XLA-era
    equivalent of the reference's 128 MiB fusion buffer
    (reference: controller.cc:778-915, fusion_buffer_manager.cc) — and
    cached in the runtime's BucketPlanCache across traces.

    ``wire_policy`` picks a wire format PER BUCKET (ops/wire.py): a
    format name ('none'/'bf16'/'fp16'/'int8_ring'/'dcn_int8'), 'auto'
    (per-bucket heuristic, autotuned when HOROVOD_AUTOTUNE is on), or a
    callable ``(nbytes, dtype, axis_name) -> name``.  The older
    ``quantized_wire``/``compression`` kwargs keep working as deprecated
    aliases; resolution order is wire_policy > quantized_wire >
    compression > the HOROVOD_WIRE_POLICY knob.  For error-feedback
    residuals (stateful), use :func:`sync_gradients_ef` or
    :func:`distributed_optimizer`."""
    out, _ = _sync_impl(grads, None, axis_name, op, compression,
                        prescale_factor, postscale_factor,
                        fusion_threshold_bytes, quantized_wire, wire_policy)
    return out


def sync_gradients_ef(grads: Any,
                      residuals: Any,
                      axis_name: Optional[AxisName],
                      op: ReduceOp = Average,
                      compression: type[Compressor] = Compression.none,
                      prescale_factor: float = 1.0,
                      postscale_factor: float = 1.0,
                      fusion_threshold_bytes: Optional[int] = None,
                      quantized_wire: bool = False,
                      wire_policy: Optional[WirePolicy] = None
                      ) -> Tuple[Any, Any]:
    """:func:`sync_gradients` with EF-SGD error feedback: ``residuals``
    (a pytree shaped like ``grads``; zeros initially) is added into the
    gradients before compression, and each lossy bucket's rank-local
    encode error comes back as the new residual.  Returns
    ``(synced, new_residuals)``.  ``distributed_optimizer`` carries this
    state automatically; this entry point exists for custom loops and
    tests."""
    return _sync_impl(grads, residuals, axis_name, op, compression,
                      prescale_factor, postscale_factor,
                      fusion_threshold_bytes, quantized_wire, wire_policy)


class _AccState(NamedTuple):
    inner: Any
    counter: jax.Array          # micro-batch counter
    acc: Any                    # accumulated (unsynced) gradients


class _WireState(NamedTuple):
    """Optimizer state of the error-feedback wire path: the inner
    optimizer's state plus the per-leaf EF residuals (rank-local; the
    quantization/cast error not yet transmitted, added back into the next
    step's gradient before compression)."""
    inner: Any
    residual: Any


def _ef_enabled(error_feedback: Optional[bool],
                wire_policy: Optional[WirePolicy],
                quantized_wire: bool,
                compression: type[Compressor]) -> bool:
    """Error feedback defaults to the HOROVOD_WIRE_EF knob whenever a
    wire policy is requested BY KWARG (wire_policy, or the deprecated
    quantized_wire / Compression.bf16|fp16 aliases).  Activation purely
    via the HOROVOD_WIRE_POLICY env knob does NOT add EF state: residuals
    change the optax state structure, and the env knob's contract is
    zero user-code changes — code that inits state from the *inner*
    optimizer (the long-standing make_train_step pattern) must keep
    working.  Pass ``error_feedback=True`` (or any wire kwarg) to opt
    residuals in; ``error_feedback=False`` always wins the other way."""
    if error_feedback is not None:
        return bool(error_feedback)
    active = (wire_policy not in (None, "none") or quantized_wire
              or compression in (Compression.bf16, Compression.fp16))
    if not active:
        return False
    from .common.knobs import current
    return bool(current("HOROVOD_WIRE_EF"))


def distributed_optimizer(optimizer: optax.GradientTransformation,
                          axis_name: Optional[AxisName] = "hvd",
                          op: ReduceOp = Average,
                          compression: type[Compressor] = Compression.none,
                          prescale_factor: float = 1.0,
                          postscale_factor: float = 1.0,
                          backward_passes_per_step: int = 1,
                          fusion_threshold_bytes: Optional[int] = None,
                          quantized_wire: bool = False,
                          wire_policy: Optional[WirePolicy] = None,
                          error_feedback: Optional[bool] = None,
                          overlap: Optional[bool] = None,
                          overlap_depth: Optional[int] = None,
                          ) -> optax.GradientTransformation:
    """Wrap ``optimizer`` so updates see globally-synced gradients.

    Parity map (reference: torch/optimizer.py:506 DistributedOptimizer):
      * ``op=Average|Sum|Adasum``  — reduction op, incl. hvd.Adasum
      * ``compression``            — wire compression of fused buckets
        (deprecated alias for ``wire_policy='bf16'/'fp16'``)
      * ``backward_passes_per_step`` — local aggregation before sync
        (reference: gradient_aggregation.py)
      * bucket fusion replaces ``num_groups`` — automatic by byte threshold.
      * ``quantized_wire``         — deprecated alias for
        ``wire_policy='int8_ring'`` (ops/quantized.py; EQuARX, PAPERS.md).
      * ``wire_policy``            — per-bucket wire format (ops/wire.py):
        a format name, 'auto', or a callable; no reference equivalent.
      * ``error_feedback``         — EF-SGD residuals as optimizer state
        for the lossy wire formats; default: the HOROVOD_WIRE_EF knob
        when a wire policy is active.
      * ``overlap`` / ``overlap_depth`` — the overlap plane
        (ops/overlap.py; docs/overlap.md): with
        ``backward_passes_per_step = k > 1``, pipeline the per-microbatch
        fused syncs against the next microbatch's compute instead of one
        sync after microbatch k (default: the HOROVOD_OVERLAP /
        HOROVOD_OVERLAP_DEPTH knobs — the reference's background-thread
        overlap, restructured into the traced program).
    """
    if backward_passes_per_step < 1:
        raise ValueError("backward_passes_per_step must be >= 1")

    sync_kw = dict(op=op, compression=compression,
                   prescale_factor=prescale_factor,
                   postscale_factor=postscale_factor,
                   fusion_threshold_bytes=fusion_threshold_bytes,
                   quantized_wire=quantized_wire, wire_policy=wire_policy)

    # The synced core, split into its two halves — sync (collective, EF
    # residuals threaded through the core state) and apply (inner
    # optimizer only) — so the microbatch pipeline below can issue syncs
    # independently of the update.  core_update is their composition: the
    # path every non-pipelined call takes.
    if _ef_enabled(error_feedback, wire_policy, quantized_wire, compression):
        def core_init(params):
            return _WireState(
                inner=optimizer.init(params),
                residual=jax.tree_util.tree_map(jnp.zeros_like, params))

        def core_sync(grads, state: _WireState):
            synced, res = sync_gradients_ef(grads, state.residual,
                                            axis_name, **sync_kw)
            return synced, _WireState(state.inner, res)

        def core_apply(synced, state: _WireState, params=None, **extra):
            updates, inner = optimizer.update(synced, state.inner, params,
                                              **extra)
            return updates, _WireState(inner, state.residual)
    else:
        def core_init(params):
            return optimizer.init(params)

        def core_sync(grads, state):
            return sync_gradients(grads, axis_name, **sync_kw), state

        def core_apply(synced, state, params=None, **extra):
            return optimizer.update(synced, state, params, **extra)

    def core_update(grads, state, params=None, **extra):
        synced, state = core_sync(grads, state)
        return core_apply(synced, state, params, **extra)

    if backward_passes_per_step == 1:
        return optax.GradientTransformation(core_init, core_update)

    n = backward_passes_per_step

    from .ops import overlap as _overlap
    if _overlap.overlap_enabled(overlap):
        depth = _overlap.resolve_depth(overlap_depth)

        def on_trace(grads, k, d):
            leaves = jax.tree_util.tree_leaves(grads)
            if leaves:
                _overlap.microbatch_overlap_model(leaves, axis_name, k, d)

        return _overlap.make_pipelined_transform(
            core_init, core_sync, core_apply, n, depth, on_trace=on_trace)

    def init_fn(params):
        zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
        return _AccState(inner=core_init(params),
                         counter=jnp.zeros((), jnp.int32),
                         acc=zeros)

    def update_fn(grads, state: _AccState, params=None, **extra):
        acc = jax.tree_util.tree_map(lambda a, g: a + g, state.acc, grads)
        is_sync_step = (state.counter + 1) % n == 0

        def do_sync(_):
            mean = jax.tree_util.tree_map(lambda a: a / n, acc)
            updates, inner = core_update(mean, state.inner, params, **extra)
            zeros = jax.tree_util.tree_map(jnp.zeros_like, acc)
            return updates, _AccState(inner, state.counter + 1, zeros)

        def skip(_):
            updates = jax.tree_util.tree_map(jnp.zeros_like, grads)
            return updates, _AccState(state.inner, state.counter + 1, acc)

        return jax.lax.cond(is_sync_step, do_sync, skip, operand=None)

    return optax.GradientTransformation(init_fn, update_fn)


def wire_residual_report(residuals: Any, plan=None) -> dict:
    """Host-side EF residual norms, published to the
    ``hvd_wire_residual_norm`` gauges (per bucket when a plan is given,
    per leaf index otherwise).  ``residuals`` is the residual pytree out
    of a ``_WireState`` (or :func:`sync_gradients_ef`); returns the
    ``{label: l2_norm}`` dict it recorded."""
    from .utils import metrics as _metrics
    leaves = jax.tree_util.tree_leaves(residuals)
    report = {}
    if plan is not None:
        for i, bucket in enumerate(plan.buckets):
            sq = 0.0
            for idx in bucket.indices:
                arr = np.asarray(leaves[idx], dtype=np.float64)
                sq += float(np.sum(arr * arr))
            report[f"bucket{i}"] = float(np.sqrt(sq))
    else:
        for i, leaf in enumerate(leaves):
            arr = np.asarray(leaf, dtype=np.float64)
            report[f"leaf{i}"] = float(np.sqrt(np.sum(arr * arr)))
    for label, norm in report.items():
        _metrics.WIRE_RESIDUAL_NORM.set(norm, bucket=label)
    return report


# CamelCase alias matching the reference's public name.
DistributedOptimizer = distributed_optimizer


def distributed_grad(loss_fn, axis_name: Optional[AxisName] = "hvd",
                     op: ReduceOp = Average,
                     compression: type[Compressor] = Compression.none,
                     has_aux: bool = False,
                     fusion_threshold_bytes: Optional[int] = None,
                     wire_policy: Optional[WirePolicy] = None):
    """`DistributedGradientTape` analog (reference:
    tensorflow/__init__.py:726-816): returns a grad function whose gradients
    are already allreduced over ``axis_name``.  ``wire_policy`` as in
    :func:`sync_gradients` (stateless, so no error feedback — use
    :func:`distributed_optimizer` for EF)."""
    gfn = jax.grad(loss_fn, has_aux=has_aux)

    def wrapped(*args, **kwargs):
        if has_aux:
            g, aux = gfn(*args, **kwargs)
            return sync_gradients(
                g, axis_name, op=op, compression=compression,
                fusion_threshold_bytes=fusion_threshold_bytes,
                wire_policy=wire_policy), aux
        g = gfn(*args, **kwargs)
        return sync_gradients(g, axis_name, op=op, compression=compression,
                              fusion_threshold_bytes=fusion_threshold_bytes,
                              wire_policy=wire_policy)

    return wrapped
