"""Data-parallel training step builder — the core Horovod use-case.

The reference's product is: wrap your optimizer, gradients get allreduced
(reference: torch/optimizer.py:506, tensorflow/__init__.py:601).  The
TPU-native equivalent packages the whole train step: a jitted `shard_map`
over the mesh where the batch is split along the data axis, gradients are
bucket-fused and psum'd (via DistributedOptimizer), and params/optimizer
state stay replicated.

This is the explicit, Horovod-style mode — collectives are visible and
controllable (fusion threshold, compression, Adasum, hierarchical two-level
reduction).  The implicit GSPMD mode (sharding-annotation driven) lives in
parallel/fsdp.py.  When per-rank memory — not compute — caps model scale,
the ZeRO chain (parallel/zero.py, docs/zero.md) is this module's
memory-bound sibling: the same shard_map discipline with optimizer state
(level 1), gradients (level 2) and parameters (level 3) sharded 1/n along
the fusion-bucket plan, numerics unchanged.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import optax
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..common.reduce_op import ReduceOp, Average
from ..ops.compression import Compression, Compressor
from ..optimizer import distributed_optimizer
from .hierarchical import resolve_axis

AxisName = Union[str, Sequence[str]]


def cast_params(tree: Any, dtype) -> Any:
    """Cast floating leaves of a param pytree (ints/bools untouched)."""
    def one(x):
        if hasattr(x, "dtype") and jnp.issubdtype(x.dtype, jnp.floating):
            return x.astype(dtype)
        return x
    return jax.tree_util.tree_map(one, tree)


def _compute_cast(loss_fn: Callable, compute_dtype) -> Callable:
    """Mixed precision the TPU way: params (and optimizer state) stay in
    their storage dtype — typically fp32 "master" weights — and are cast
    to ``compute_dtype`` (bf16) just for the forward.  jax differentiates
    through the cast, so gradients and the optimizer update arrive back in
    the storage dtype; no dual copy of the weights is kept."""
    if compute_dtype is None:
        return loss_fn

    def fn(params, *batch):
        return loss_fn(cast_params(params, compute_dtype), *batch)
    return fn


def _resolve_donate(donate: Optional[bool]) -> bool:
    """HOROVOD_TPU_DONATE_BUFFERS is the default when the caller doesn't
    say — the TPU analog of the reference's persistent fusion-buffer
    residency (knob registered in common/knobs.py)."""
    if donate is not None:
        return donate
    from ..common.knobs import current
    return bool(current("HOROVOD_TPU_DONATE_BUFFERS"))


def _step_body(loss_fn: Callable, optimizer: optax.GradientTransformation,
               mesh: Mesh, axis_name: AxisName, donate: Optional[bool], *,
               has_aux: bool = False, remat: bool = False,
               compute_dtype=None, **sync) -> Tuple:
    """What every step builder shares: ``(body, axes, donate)`` where
    ``body(params, opt_state, *batch) -> (params, opt_state, loss[, aux])``
    is ONE optimizer step on this chip's batch shard (forward/backward, the
    fused gradient sync inside ``dist_opt.update`` — ``sync`` is
    distributed_optimizer's keywords —, the update, the loss's mean over the
    data axis), ``axes`` the mesh axes of the batch, ``donate`` resolved."""
    axis_name = resolve_axis(axis_name, mesh)
    dist_opt = distributed_optimizer(optimizer, axis_name=axis_name, **sync)
    fn = _compute_cast(loss_fn, compute_dtype)
    fn = jax.checkpoint(fn) if remat else fn

    def body(params, opt_state, *batch):
        out, grads = jax.value_and_grad(fn, has_aux=has_aux)(params, *batch)
        loss, aux = (out[0], (out[1],)) if has_aux else (out, ())
        with jax.named_scope("optimizer"):
            updates, opt_state = dist_opt.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
        return (params, opt_state, jax.lax.pmean(loss, axis_name)) + aux

    axes = axis_name if isinstance(axis_name, tuple) else (axis_name,)
    return body, axes, _resolve_donate(donate)


def _spmd_jit(body: Callable, mesh: Mesh, batch_specs: Tuple, n_out: int,
              donate: bool) -> Callable:
    """``body`` as one jitted ``shard_map``: params, optimizer state and
    outputs replicated, the batch split as ``batch_specs`` says."""
    f = shard_map(body, mesh=mesh, in_specs=(P(), P()) + batch_specs,
                  out_specs=(P(),) * n_out, check_vma=False)
    return jax.jit(f, donate_argnums=(0, 1) if donate else ())


def make_train_step(loss_fn: Callable,
                    optimizer: optax.GradientTransformation,
                    mesh: Mesh,
                    axis_name: AxisName = "hvd",
                    op: ReduceOp = Average,
                    compression: type[Compressor] = Compression.none,
                    backward_passes_per_step: int = 1,
                    fusion_threshold_bytes: Optional[int] = None,
                    donate: Optional[bool] = None,
                    has_aux: bool = False,
                    compute_dtype=None,
                    wire_policy=None,
                    error_feedback: Optional[bool] = None,
                    overlap: Optional[bool] = None,
                    overlap_depth: Optional[int] = None) -> Callable:
    """Build ``step(params, opt_state, *batch) -> (params, opt_state, loss)``.

    ``loss_fn(params, *batch_shard)`` is evaluated per chip on the local
    batch shard; gradients are fused+allreduced; the update is applied
    identically everywhere (params replicated).

    ``compute_dtype=jnp.bfloat16`` with fp32 params is the standard TPU
    mixed-precision recipe: fp32 master weights + optimizer state, bf16
    forward/backward (params are cast inside the step; the gradient of the
    cast lands back in fp32).

    ``donate=True`` donates params/opt_state so XLA updates them in place in
    HBM — the analog of the reference's persistent fusion buffer residency
    (default: the HOROVOD_TPU_DONATE_BUFFERS knob).  ``axis_name`` may be a
    logical name that resolves to a two-level dcn/ici axis pair on
    multi-slice meshes (parallel/hierarchical.py).  ``wire_policy`` /
    ``error_feedback`` select per-bucket wire formats with EF residuals
    for the gradient sync (ops/wire.py; docs/tensor-fusion.md).
    ``overlap`` / ``overlap_depth`` pipeline the per-microbatch syncs
    when ``backward_passes_per_step > 1`` (ops/overlap.py;
    docs/overlap.md — for the syncs to actually interleave with the
    next microbatch's compute, drive the k calls inside ONE program:
    :func:`make_microbatched_train_step`).
    """
    body, axes, donate = _step_body(
        loss_fn, optimizer, mesh, axis_name, donate, has_aux=has_aux,
        compute_dtype=compute_dtype, op=op, compression=compression,
        backward_passes_per_step=backward_passes_per_step,
        fusion_threshold_bytes=fusion_threshold_bytes,
        wire_policy=wire_policy, error_feedback=error_feedback,
        overlap=overlap, overlap_depth=overlap_depth)

    @functools.lru_cache(maxsize=None)
    def build(nbatch: int):
        return _spmd_jit(body, mesh, (P(axes),) * nbatch, 3 + has_aux, donate)

    def step(params, opt_state, *batch):
        out = build(len(batch))(params, opt_state, *batch)
        # Framework-level timeline mark for the compiled step (the in-jit
        # collectives are XLA-fused; per-op detail lives in xprof).
        from .. import runtime as _rt
        if _rt.is_initialized() and _rt.get().timeline is not None:
            nbytes = sum(int(getattr(b, "nbytes", 0))
                         for b in jax.tree_util.tree_leaves(batch))
            _rt.get().timeline.record_op("spmd/train_step", "STEP", nbytes)
        return out

    return step


def make_microbatched_train_step(loss_fn: Callable,
                                 optimizer: optax.GradientTransformation,
                                 mesh: Mesh,
                                 backward_passes_per_step: int,
                                 axis_name: AxisName = "hvd",
                                 op: ReduceOp = Average,
                                 fusion_threshold_bytes: Optional[int] = None,
                                 donate: Optional[bool] = None,
                                 remat: bool = False,
                                 compute_dtype=None,
                                 wire_policy=None,
                                 error_feedback: Optional[bool] = None,
                                 overlap: Optional[bool] = None,
                                 overlap_depth: Optional[int] = None
                                 ) -> Callable:
    """Build ``step(params, opt_state, batch) -> (params, opt_state,
    loss)`` running ONE optimizer step over ``k =
    backward_passes_per_step`` microbatches inside a single compiled
    program via ``lax.scan`` — the overlap plane's lax.scan software
    pipeline (ops/overlap.py; docs/overlap.md).

    ``batch`` leaves are shaped ``(k, global_batch, ...)``; each scan
    iteration runs one microbatch's forward/backward and one pipelined
    ``dist_opt.update`` call, so with overlap on the fused sync of
    microbatch *i* is issued in iteration *i + depth* — inside the same
    program region as that microbatch's compute, where XLA can run them
    concurrently.  The final iteration drains the buffer and applies the
    inner optimizer.  With overlap off this is exactly the classic
    accumulate-k-then-sync step, scanned.  ``opt_state`` comes from this
    wrapper's own ``init`` (the k > 1 contract of distributed_optimizer).
    """
    k = backward_passes_per_step
    if k < 1:
        raise ValueError("backward_passes_per_step must be >= 1")
    one, axes, donate = _step_body(
        loss_fn, optimizer, mesh, axis_name, donate, remat=remat,
        compute_dtype=compute_dtype, op=op, backward_passes_per_step=k,
        fusion_threshold_bytes=fusion_threshold_bytes,
        wire_policy=wire_policy, error_feedback=error_feedback,
        overlap=overlap, overlap_depth=overlap_depth)

    def body(params, opt_state, batch):
        # non-final microbatches return zero updates: applying them keeps
        # the carry structure uniform and costs one no-op add
        def scanned(carry, mb):
            params, opt_state, loss = one(*carry, mb)
            return (params, opt_state), loss

        (params, opt_state), losses = jax.lax.scan(
            scanned, (params, opt_state), batch)
        return params, opt_state, jnp.mean(losses)

    # batch: (k, global_batch, ...) — shard the batch dim (axis 1).
    return _spmd_jit(body, mesh, (P(None, axes),), 3, donate)


def make_scanned_train_step(loss_fn: Callable,
                            optimizer: optax.GradientTransformation,
                            mesh: Mesh,
                            axis_name: AxisName = "hvd",
                            op: ReduceOp = Average,
                            compression: type[Compressor] = Compression.none,
                            fusion_threshold_bytes: Optional[int] = None,
                            donate: Optional[bool] = None,
                            remat: bool = False,
                            compute_dtype=None,
                            unroll: int = 1,
                            wire_policy=None,
                            error_feedback: Optional[bool] = None
                            ) -> Callable:
    """Build ``run(params, opt_state, batches) -> (params, opt_state, losses)``
    executing ``batches.shape[0]`` optimizer steps inside ONE compiled program
    via ``lax.scan``.

    This is the honest-benchmark (and low-dispatch-overhead) variant of
    :func:`make_train_step`: a single device dispatch covers K steps, so
    host→device dispatch latency is amortized K-fold and a device-to-host
    fetch of ``losses`` fences ALL K steps — timing cannot silently measure
    an empty async queue.  The reference's analog is the timed-iteration
    loop of examples/pytorch/pytorch_synthetic_benchmark.py:104-109; on TPU
    the idiomatic form is scan-inside-jit, not a Python loop.

    ``batches`` is a pytree whose leaves are stacked per-step inputs of
    shape ``(K, global_batch, ...)``; each step's slice is sharded over the
    data axis.  ``losses`` comes back with shape ``(K,)``.
    ``compute_dtype`` as in :func:`make_train_step` (fp32 master weights,
    bf16 compute).  ``unroll`` passes through to ``lax.scan`` — unrolled
    iterations remove per-step loop overhead and let XLA overlap across
    step boundaries, at the cost of a proportionally bigger program.
    """
    one, axes, donate = _step_body(
        loss_fn, optimizer, mesh, axis_name, donate, remat=remat,
        compute_dtype=compute_dtype, op=op, compression=compression,
        fusion_threshold_bytes=fusion_threshold_bytes,
        wire_policy=wire_policy, error_feedback=error_feedback)

    def body(params, opt_state, batches):
        def scanned(carry, batch):
            params, opt_state, loss = one(*carry, batch)
            return (params, opt_state), loss

        (params, opt_state), losses = jax.lax.scan(
            scanned, (params, opt_state), batches, unroll=unroll)
        return params, opt_state, losses

    # batches: (K, batch, ...) — shard the *batch* dim (axis 1) per chip.
    return _spmd_jit(body, mesh, (P(None, axes),), 3, donate)


def shard_batch(batch: Any, mesh: Mesh,
                axis_name: AxisName = "hvd", axis: int = 0) -> Any:
    """Device-put a host batch sharded along ``axis`` over the mesh axis."""
    axis_name = resolve_axis(axis_name, mesh)
    axes = axis_name if isinstance(axis_name, tuple) else (axis_name,)
    sharding = NamedSharding(mesh, P(*((None,) * axis), axes))
    return jax.tree_util.tree_map(
        lambda x: jax.device_put(x, sharding), batch)


def shard_local_batch(batch: Any, mesh: Mesh,
                      axis_name: AxisName = "hvd", axis: int = 0) -> Any:
    """Assemble a GLOBAL batch-sharded array from each process's LOCAL
    slice — the multi-host input-pipeline entry point: every process
    loads ONLY the rows its own chips consume (1/P of the global batch),
    unlike :func:`shard_batch`, which expects the full global batch on
    every host.  Per-process loader shard -> global jax.Array, no
    cross-host data movement."""
    axis_name = resolve_axis(axis_name, mesh)
    axes = axis_name if isinstance(axis_name, tuple) else (axis_name,)
    sharding = NamedSharding(mesh, P(*((None,) * axis), axes))
    return jax.tree_util.tree_map(
        lambda x: jax.make_array_from_process_local_data(sharding, x),
        batch)


def replicate(tree: Any, mesh: Mesh) -> Any:
    """Device-put a pytree fully replicated over the mesh.

    Leaves are copied (not aliased): train steps donate their params, and a
    donated buffer that aliased the caller's original array would delete it
    out from under a later ``replicate`` of the same tree."""
    sharding = NamedSharding(mesh, P())
    return jax.tree_util.tree_map(
        lambda x: jax.device_put(jnp.array(x, copy=True), sharding), tree)
