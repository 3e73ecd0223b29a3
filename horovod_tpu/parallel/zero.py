"""ZeRO weight-update sharding for the data-parallel path, levels 1-3.

The technique of "Automatic Cross-Replica Sharding of Weight Update in
Data-Parallel Training" (arXiv:2004.13336, retrieved in PAPERS.md) plus
the ZeRO line of work: in plain data parallelism every chip redundantly
holds the FULL parameters, gradients and optimizer state and applies the
SAME update.  Sharding along the existing fusion-bucket plan removes the
redundancy one entity at a time (``zero_level``, docs/zero.md):

  level 1   optimizer state sharded 1/n: per bucket the chain is
            grads --reduce_scatter--> 1/n, sharded elementwise update,
            updates --all_gather--> applied to replicated params.
            RS + AG == one allreduce in wire bytes, state HBM / n.
  level 2   + gradient shards: each bucket's gradient shard stays
            resident after its reduce_scatter, and with
            ``backward_passes_per_step = k > 1`` accumulation happens ON
            the 1/n shard — no full gradient accumulator is ever
            materialized, and the per-microbatch grad all_gather that
            level 1 needs to keep its full accumulator disappears
            (strictly FEWER wire bytes than level 1 at k > 1).
  level 3   + parameter shards: params live between steps as per-bucket
            fp32 shards (1/n per chip, ``shard_zero3_params``) and the
            step all-gathers each bucket's params just-in-time at step
            start — plan order (first-needed buckets first), an
            ``ag_prefetch``-deep issue window (HOROVOD_ZERO_AG_PREFETCH;
            the overlap plane's latency-hiding discipline) — then frees
            the gathered full bucket after its leaves are consumed.  The
            update applies to the local shard; no update all_gather.

Wire-policy composition (ops/wire.py): the reduce_scatter leg carries
the per-bucket wire format under the ONE-SHOT codec model — each rank's
contribution is encoded once before the scatter (``wire.wire_roundtrip``)
so the EF-SGD residual ``x - C(x)`` is exactly compensable — with EF
residuals stored per bucket INSIDE the sharded state (rank-local rows of
a ``[n, bucket]`` array, so elastic resharding re-derives them with their
buckets).  The all-gather legs (updates at level <= 2, params at level 3)
stay exact: their payload is master state with no error-feedback channel,
and an exact AG is what makes the levels bit-near comparable.

Schedule contract (what the equivalence matrix proves,
tests/test_zero.py): the bucket-interleaved chain syncs EVERY microbatch
at every level — the uniform schedule under which levels 1/2/3 compute
identical per-element values for any wire format x EF x k, because
all_gather-then-slice is the identity.  The legacy monolithic level-1
chain (``interleaved=False``: one flat vector, accumulate-then-sync,
no wire formats) remains as the anchor the bucketed chain is proven
against.  Reverse-priority issue order for the gradient legs
(overlap.priority_order: backprop produces the tail buckets' gradients
first), plan order for the level-3 param gathers (the forward consumes
the head buckets first — last-needed buckets gathered last).

Relationship to :mod:`.fsdp` (ONE ZeRO-3 story, two schedulers): this
module is the EXPLICITLY-scheduled ZeRO-3 — shard_map collectives the
chain places itself, composing with wire formats, the overlap pipeline
and the per-bucket trace markers; ``fsdp.py`` is the COMPILER-scheduled
realization — sharding annotations from which GSPMD materializes the
same allgather-on-use / reduce-scatter-on-gradient pattern.  Same
memory math (``perf/costmodel.zero_memory_bytes`` prices both), pick by
control: explicit knobs here, compiler freedom there (docs/zero.md).

Cost-model closure (docs/profiling.md): the trace-time byte/memory
gauges this module sets (``hvd_zero_*``, ``hvd_overlap_*[plane=zeroN]``)
are computed FROM ``perf/costmodel.zero_comm_bytes`` — the same function
``hvd.perf_report()``'s per-level what-if table and the ledger's
predicted step use — so prediction and trace agree by construction and
the ledger measures their drift against the wall clock.
"""

from __future__ import annotations

from typing import Any, Callable, List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax, shard_map
from jax.flatten_util import ravel_pytree
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..common.reduce_op import ReduceOp, Average
from .hierarchical import resolve_axis

ZERO_LEVELS = (1, 2, 3)


class _ZeroEFBlock(NamedTuple):
    """One bucket's sharded state when error feedback is on: the vmapped
    inner optimizer state (``[n, bucket/n, ...]``, dim 0 over the axis)
    plus the EF residual as rank-local rows of a ``[n, bucket]`` array —
    each rank's row is ITS one-shot encode error for this bucket, riding
    the same sharded out_specs as the state so reshard/elastic handle it
    with the bucket."""
    inner: Any
    residual: jnp.ndarray


# ------------------------------------------------------------ knob surface
def validate_zero_knobs(knobs) -> None:
    """Fail loudly AT INIT on invalid ZeRO knob values (consumed by
    hvd.init, the overlap/wire validation pattern — docs/zero.md)."""
    from ..ops.overlap import MAX_OVERLAP_DEPTH
    lvl = int(knobs["HOROVOD_ZERO_LEVEL"])
    if lvl not in (0,) + ZERO_LEVELS:
        raise ValueError(
            f"HOROVOD_ZERO_LEVEL={lvl} invalid; the weight-update "
            "sharding level must be 0 (off), 1, 2 or 3 (docs/zero.md)")
    pre = int(knobs["HOROVOD_ZERO_AG_PREFETCH"])
    if not 1 <= pre <= MAX_OVERLAP_DEPTH:
        raise ValueError(
            f"HOROVOD_ZERO_AG_PREFETCH={pre} invalid; the ZeRO-3 param "
            f"all-gather prefetch depth must be in [1, "
            f"{MAX_OVERLAP_DEPTH}] (docs/zero.md)")


def resolve_zero_level(level: Optional[int] = None) -> int:
    """Live ZeRO level: kwarg > HOROVOD_ZERO_LEVEL knob (env-live via
    ``current``).  0 = off (plain data parallel)."""
    if level is None:
        from ..common.knobs import current
        level = int(current("HOROVOD_ZERO_LEVEL"))
    level = int(level)
    if level not in (0,) + ZERO_LEVELS:
        raise ValueError(
            f"zero level {level} invalid; must be 0, 1, 2 or 3 "
            "(HOROVOD_ZERO_LEVEL, docs/zero.md)")
    return level


def resolve_ag_prefetch(depth: Optional[int] = None) -> int:
    """Live ZeRO-3 param all-gather prefetch depth: kwarg > tuned bandit
    arm (Runtime.zero_ag_prefetch — the overlap-depth arm covers it) >
    HOROVOD_ZERO_AG_PREFETCH knob."""
    from ..ops.overlap import MAX_OVERLAP_DEPTH
    if depth is None:
        from .. import runtime as _rt
        if _rt.is_initialized():
            depth = _rt.get().zero_ag_prefetch()
        else:
            from ..common.knobs import current
            depth = int(current("HOROVOD_ZERO_AG_PREFETCH"))
    depth = int(depth)
    if not 1 <= depth <= MAX_OVERLAP_DEPTH:
        raise ValueError(
            f"zero AG prefetch depth {depth} out of range "
            f"[1, {MAX_OVERLAP_DEPTH}] (docs/zero.md)")
    return depth


def _resolve_wire_policy(wire_policy):
    """Kwarg > runtime's live policy (bandit-refined) > knob — the
    data_parallel resolution order, so the zero chain composes with the
    global wire plane without new knobs."""
    if wire_policy is not None:
        if callable(wire_policy):
            return wire_policy
        from ..ops.wire import validate_policy_name
        return validate_policy_name(wire_policy)
    from .. import runtime as _rt
    if _rt.is_initialized():
        return _rt.get().wire_policy()
    from ..common.knobs import current
    from ..ops.wire import validate_policy_name
    return validate_policy_name(current("HOROVOD_WIRE_POLICY"))


def _resolve_ef(error_feedback: Optional[bool]) -> bool:
    """EF request: kwarg > HOROVOD_WIRE_EF knob.  Env-default activation
    is safe HERE (unlike distributed_optimizer) because zero state always
    comes from this module's own init — init and step resolve the same
    way and the step validates the layout structurally regardless."""
    if error_feedback is not None:
        return bool(error_feedback)
    from ..common.knobs import current
    return bool(current("HOROVOD_WIRE_EF"))


# --------------------------------------------------------------- internals
def _single_axis(axis_name, mesh: Mesh) -> str:
    axis = resolve_axis(axis_name, mesh)
    if isinstance(axis, tuple):
        if len(axis) != 1:
            raise ValueError(
                "zero update sharding shards over ONE mesh axis; got "
                f"{axis} (flatten the mesh or pick a single axis)")
        axis = axis[0]
    return axis


def _flat_size(params: Any) -> int:
    return sum(int(np.prod(l.shape))
               for l in jax.tree_util.tree_leaves(params))


def _flatten(tree: Any) -> jnp.ndarray:
    """One fp32 vector for the whole pytree (stock ravel; the fp32 cast
    first keeps the update math full-precision for bf16 params)."""
    flat, _ = ravel_pytree(jax.tree_util.tree_map(
        lambda l: l.astype(jnp.float32), tree))
    return flat


def _unflatten_like(flat: jnp.ndarray, tree: Any) -> Any:
    """Inverse of :func:`_flatten` against ``tree``'s structure, casting
    each leaf back to ITS dtype (ravel_pytree's unravel wants the ravel
    dtype back, so the cast stays explicit here)."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    out = []
    off = 0
    for l in leaves:
        n = int(np.prod(l.shape))
        out.append(flat[off:off + n].reshape(l.shape).astype(l.dtype))
        off += n
    return jax.tree_util.tree_unflatten(treedef, out)


def _bucket_plan(params: Any, threshold_bytes: Any):
    """Fusion-bucket plan over the fp32-flattened parameter leaves,
    through the runtime's BucketPlanCache when initialized — the
    interleaved chain's bucket split, its issue orders and the level-3
    shard geometry are pure functions of this plan, so identical
    (shapes, threshold) signatures reuse all of them, and an
    elastic/chaos reset re-derives the geometry for the new world size
    simply by rebuilding the step against the new mesh."""
    leaves = jax.tree_util.tree_leaves(params)
    shapes = [tuple(l.shape) for l in leaves]
    # update math is fp32 regardless of storage dtype (see _flatten)
    dtypes = [jnp.float32] * len(leaves)
    from .. import runtime as _rt
    if threshold_bytes is None:
        from ..optimizer import DEFAULT_FUSION_BYTES
        threshold_bytes = (_rt.get().fusion_threshold()
                           if _rt.is_initialized() else DEFAULT_FUSION_BYTES)
    if _rt.is_initialized():
        return _rt.get().plan_cache.get(shapes, dtypes, threshold_bytes)
    from ..ops.fusion import make_plan
    return make_plan(shapes, dtypes, threshold_bytes)


def _f32_leaves(tree: Any):
    return [l.astype(jnp.float32)
            for l in jax.tree_util.tree_leaves(tree)]


def _pack_padded(leaves, bucket, n: int) -> jnp.ndarray:
    """One bucket's leaves as a flat fp32 vector padded to a multiple of
    the axis size (static shapes; the pad is the per-bucket analog of the
    monolithic path's tail pad)."""
    from ..ops.fusion import pack_bucket_padded
    return pack_bucket_padded(leaves, bucket, n)


def _padded_len(nelems: int, n: int) -> int:
    return -(-nelems // n) * n


def _zero_formats(plan, policy, axis: str, n: int) -> List[str]:
    """Per-bucket RS-leg wire formats, via the wire plane's plan_formats
    with EXPLICIT axis sizes — so the state init (outside shard_map) and
    the traced step resolve identical formats and agree on the EF
    layout."""
    from ..ops import wire as _wire
    return _wire.plan_formats(plan, _wire.get_policy(policy), axis,
                              ReduceOp.AVERAGE, axis_sizes={"flat": n})


def _expected_state(optimizer, plan, n: int, ef: bool):
    """Abstract (shape/dtype) pytree of the bucket-interleaved state —
    what init produces and what the step validates against."""
    blocks = []
    for b in plan.buckets:
        L = _padded_len(sum(b.sizes), n)
        inner = jax.eval_shape(
            jax.vmap(optimizer.init),
            jax.ShapeDtypeStruct((n, L // n), jnp.float32))
        if ef:
            blocks.append(_ZeroEFBlock(
                inner=inner,
                residual=jax.ShapeDtypeStruct((n, L), jnp.float32)))
        else:
            blocks.append(inner)
    return tuple(blocks)


def _check_state_layout(opt_state, expected, what: str) -> None:
    """Structural validation of the passed opt_state against the layout
    this step builder compiles for — structure AND leaf shapes, so a
    state inited ``interleaved=True`` consumed by a monolithic step (or
    vice versa, or EF-on state meeting an EF-off step, or a stale world
    size after an elastic reset) raises here instead of mis-slicing."""
    exp_def = jax.tree_util.tree_structure(expected)
    got_def = jax.tree_util.tree_structure(opt_state)
    ok = exp_def == got_def
    if ok:
        for e, g in zip(jax.tree_util.tree_leaves(expected),
                        jax.tree_util.tree_leaves(opt_state)):
            if tuple(e.shape) != tuple(jnp.shape(g)):
                ok = False
                break
    if not ok:
        raise ValueError(
            f"zero opt_state layout mismatch for the {what} step: the "
            "`interleaved`, `zero_level`, wire/EF settings and world "
            "size of init_sharded_opt_state/init_zero_state and the "
            "step builder must match — e.g. state inited with "
            "interleaved=True must not be consumed by a monolithic "
            f"(interleaved=False) step builder (docs/zero.md).  "
            f"Expected {exp_def} with shapes "
            f"{[tuple(l.shape) for l in jax.tree_util.tree_leaves(expected)]}; "
            f"got {got_def} with shapes "
            f"{[tuple(jnp.shape(l)) for l in jax.tree_util.tree_leaves(opt_state)]}")


# ----------------------------------------------------- trace-time recording
def _record_zero_trace(plan, order, formats, level: int, n: int, k: int,
                       depth: int, ef: bool, opt_state,
                       param_bytes_full: int) -> None:
    """Trace-time observability for one compiled zero chain: the
    hvd_zero_* gauges (analytical per-rank residency), the
    hvd_overlap_*[plane=zeroN] exposed/overlapped byte split computed
    FROM perf/costmodel.zero_comm_bytes (prediction == trace model by
    construction), and the zero.bucket.{ag,rs,free} schedule markers in
    the merged timeline (docs/zero.md, docs/timeline.md)."""
    from ..ops.overlap import record_overlap
    from ..perf import costmodel as _cm
    from ..utils import metrics as M
    from ..utils.timeline import trace_instant

    padded = [_padded_len(sum(b.sizes), n) for b in plan.buckets]
    per_bucket = [
        _cm.zero_comm_bytes(L, n, level, k=k,
                            wire_format=formats[bi])["total_bytes"]
        for bi, L in enumerate(padded)]
    total = float(sum(per_bucket))
    # Pipeline split convention of the interleaved chain (the zero1 model
    # since PR 4): the first-issued and last-issued buckets' traffic
    # halves sit exposed at the pipeline ends; everything between runs
    # under an in-flight neighbor.
    exposed = (total if plan.num_buckets <= 1 else
               0.5 * (per_bucket[order[0]] + per_bucket[order[-1]]))
    record_overlap(total, exposed, plane=f"zero{level}")

    elems = sum(padded)
    state_bytes = sum(
        int(np.prod(l.shape)) * jnp.dtype(l.dtype).itemsize
        for l in jax.tree_util.tree_leaves(opt_state))
    M.ZERO_LEVEL.set(level)
    M.ZERO_AG_PREFETCH.set(depth if level == 3 else 0)
    M.ZERO_SHARDED_BYTES.set(
        param_bytes_full // n if level == 3 else param_bytes_full,
        kind="params")
    M.ZERO_SHARDED_BYTES.set(
        elems * 4 // n if level >= 2 else elems * 4, kind="grads")
    # called from inside shard_map: the body's opt_state view is the
    # LOCAL [1, ...] block, so its bytes are already per-rank.
    M.ZERO_SHARDED_BYTES.set(state_bytes, kind="opt_state")
    M.ZERO_SHARDED_BYTES.set(elems * 4 if ef else 0, kind="ef_residual")

    if level == 3:
        for j, bi in enumerate(range(plan.num_buckets)):  # plan order
            trace_instant("zero", "zero.bucket.ag",
                          args={"bucket": int(bi), "position": j,
                                "level": level, "prefetch": depth,
                                "nbytes": int(padded[bi]) * 4})
            trace_instant("zero", "zero.bucket.free",
                          args={"bucket": int(bi), "level": level,
                                "nbytes": int(padded[bi]) * 4})
    for j, bi in enumerate(order):
        trace_instant("zero", "zero.bucket.rs",
                      args={"bucket": int(bi), "position": j,
                            "level": level, "format": formats[bi],
                            "k": k, "nbytes": int(padded[bi]) * 4})


# ----------------------------------------------------------------- init API
def init_sharded_opt_state(optimizer: optax.GradientTransformation,
                           params: Any, mesh: Mesh,
                           axis_name="hvd",
                           interleaved: bool = False,
                           fusion_threshold_bytes: Any = None,
                           zero_level: int = 1,
                           wire_policy=None,
                           error_feedback: Optional[bool] = None) -> Any:
    """Optimizer state over the flat parameter shards: leaf layout is
    ``[n, padded/n, ...]`` with dim 0 sharded over the axis, so each chip
    materializes state for exactly 1/n of the parameters.

    ``interleaved=True`` returns the bucket-interleaved layout instead —
    a tuple with one such sharded block PER FUSION BUCKET (plan order) —
    and must pair with a step built ``interleaved=True``: the layouts
    differ structurally, which is why the flag is a kwarg and never an
    env knob, and why the step builders validate the layout they are
    handed (a mismatch raises, never mis-slices).  Per parameter the
    stored VALUES are identical in both layouts — only the element ->
    chip mapping moves.  Levels 2 and 3 share level 1's state layout
    (the gradient shard is intra-step, the param shards live separately
    via :func:`shard_zero3_params`); when a lossy wire format is active
    with EF, each bucket's block gains its sharded residual
    (:class:`_ZeroEFBlock`).
    """
    level = resolve_zero_level(zero_level)
    if level == 0:
        raise ValueError(
            "zero_level=0 is plain data parallelism — init the inner "
            "optimizer directly (docs/zero.md)")
    if level >= 2 and not interleaved:
        raise ValueError(
            f"zero_level={level} is bucket-interleaved by construction; "
            "pass interleaved=True (docs/zero.md)")
    axis = _single_axis(axis_name, mesh)
    n = int(mesh.shape[axis])

    if interleaved:
        plan = _bucket_plan(params, fusion_threshold_bytes)
        formats = _zero_formats(plan, _resolve_wire_policy(wire_policy),
                                axis, n)
        from ..ops.wire import is_lossy
        ef = _resolve_ef(error_feedback) and any(
            is_lossy(f) for f in formats)

        def init(params):
            leaves = _f32_leaves(params)
            out = []
            for b in plan.buckets:
                flat = _pack_padded(leaves, b, n)
                inner = jax.vmap(optimizer.init)(
                    flat.reshape(n, flat.shape[0] // n))
                if ef:
                    out.append(_ZeroEFBlock(
                        inner=inner,
                        residual=jnp.zeros((n, flat.shape[0]),
                                           jnp.float32)))
                else:
                    out.append(inner)
            return tuple(out)
    else:
        if wire_policy is not None and wire_policy != "none":
            raise ValueError(
                "the monolithic zero chain carries no wire formats; use "
                "interleaved=True for per-bucket wire policies "
                "(docs/zero.md)")
        total = _flat_size(params)
        padded = -(-total // n) * n

        def init(params):
            flat = jnp.pad(_flatten(params), (0, padded - total))
            shards = flat.reshape(n, padded // n)
            return jax.vmap(optimizer.init)(shards)

    # out_shardings: each chip WRITES only its 1/n block — materializing
    # the full state replicated first would OOM exactly the large-model
    # regime this module exists for.
    shapes = jax.eval_shape(init, params)
    out_shardings = jax.tree_util.tree_map(
        lambda _: NamedSharding(mesh, P(axis)), shapes)
    return jax.jit(init, out_shardings=out_shardings)(params)


def init_zero_state(optimizer: optax.GradientTransformation,
                    params: Any, mesh: Mesh, axis_name="hvd",
                    zero_level: Optional[int] = None,
                    wire_policy=None,
                    error_feedback: Optional[bool] = None,
                    fusion_threshold_bytes: Any = None) -> Any:
    """The level-aware spelling of :func:`init_sharded_opt_state`:
    ``zero_level`` defaults to the HOROVOD_ZERO_LEVEL knob and the
    layout is bucket-interleaved (the chain's construction).  Level 3
    params are sharded separately via :func:`shard_zero3_params`."""
    return init_sharded_opt_state(
        optimizer, params, mesh, axis_name=axis_name, interleaved=True,
        fusion_threshold_bytes=fusion_threshold_bytes,
        zero_level=resolve_zero_level(zero_level),
        wire_policy=wire_policy, error_feedback=error_feedback)


# ------------------------------------------------------- level-3 param API
def shard_zero3_params(params: Any, mesh: Mesh, axis_name="hvd",
                       fusion_threshold_bytes: Any = None) -> Any:
    """Shard a replicated param tree into the level-3 resident layout:
    one ``[n, padded/n]`` fp32 array per fusion bucket, dim 0 over the
    axis — each chip keeps 1/n of every bucket (the update master copy;
    fp32 regardless of storage dtype, like the monolithic chain's update
    math).  Geometry is a pure function of (plan, n), so an elastic
    reset re-derives it for the new world size by re-running
    gather -> shard."""
    axis = _single_axis(axis_name, mesh)
    n = int(mesh.shape[axis])
    plan = _bucket_plan(params, fusion_threshold_bytes)

    def shard(params):
        leaves = _f32_leaves(params)
        return tuple(_pack_padded(leaves, b, n).reshape(n, -1)
                     for b in plan.buckets)

    shapes = jax.eval_shape(shard, params)
    out_shardings = jax.tree_util.tree_map(
        lambda _: NamedSharding(mesh, P(axis)), shapes)
    return jax.jit(shard, out_shardings=out_shardings)(params)


def gather_zero3_params(pshards: Any, params_template: Any, mesh: Mesh,
                        axis_name="hvd",
                        fusion_threshold_bytes: Any = None) -> Any:
    """Reassemble the full (replicated) param tree from the level-3
    bucket shards — for eval, checkpointing and elastic resharding
    (gather at the old world size, :func:`shard_zero3_params` at the
    new).  ``params_template`` supplies shapes/dtypes (arrays or
    ShapeDtypeStructs)."""
    from ..ops.fusion import unpack_bucket
    plan = _bucket_plan(params_template, fusion_threshold_bytes)
    tleaves, treedef = jax.tree_util.tree_flatten(params_template)

    def gather(pshards):
        out: List[Optional[jnp.ndarray]] = [None] * plan.num_leaves
        for bi, b in enumerate(plan.buckets):
            unpack_bucket(pshards[bi].reshape(-1)[:sum(b.sizes)], b, out)
        return jax.tree_util.tree_unflatten(
            treedef, [l.astype(t.dtype) for l, t in zip(out, tleaves)])

    repl = jax.tree_util.tree_map(
        lambda _: NamedSharding(mesh, P()),
        jax.eval_shape(gather, pshards))
    return jax.jit(gather, out_shardings=repl)(pshards)


# ------------------------------------------------------------- step builders
def make_zero_train_step(loss_fn: Callable,
                         optimizer: optax.GradientTransformation,
                         mesh: Mesh,
                         axis_name="hvd",
                         op: ReduceOp = Average,
                         donate=None,
                         remat: bool = False,
                         zero_level: Optional[int] = None,
                         interleaved: Optional[bool] = None,
                         wire_policy=None,
                         error_feedback: Optional[bool] = None,
                         backward_passes_per_step: int = 1,
                         ag_prefetch: Optional[int] = None,
                         fusion_threshold_bytes: Any = None,
                         params_template: Any = None) -> Callable:
    """Build the ZeRO train step for ``zero_level`` (module docstring).

    Levels 1/2: ``step(params, opt_state, batch) -> (params, opt_state,
    loss)`` with params replicated.  Level 3: ``step(param_shards,
    opt_state, batch) -> (param_shards, opt_state, loss)`` where
    ``param_shards`` comes from :func:`shard_zero3_params` and
    ``params_template`` (shapes/dtypes) is required to derive the bucket
    plan.  ``opt_state`` comes from :func:`init_zero_state` /
    :func:`init_sharded_opt_state` built under the SAME level/wire/EF
    settings — the step validates the layout structurally and raises on
    mismatch.  With ``backward_passes_per_step = k > 1`` the batch
    leaves carry a leading ``k`` axis and the chain syncs every
    microbatch (levels 2/3 accumulate on the 1/n shard).  Numerics are
    level-invariant: the equivalence matrix (tests/test_zero.py) pins
    params AND per-element optax state equal across level x wire format
    x EF x k.
    """
    level = resolve_zero_level(zero_level)
    if level == 0:
        raise ValueError(
            "zero_level=0 is plain data parallelism — use "
            "parallel.data_parallel.make_train_step (docs/zero.md)")
    if op != Average:
        raise ValueError("zero update sharding reduces with Average "
                         "(gradient mean); prescale for other semantics")
    if level >= 2 and interleaved is False:
        raise ValueError(
            f"zero_level={level} is bucket-interleaved by construction "
            "(the gradient/param shards ARE per-fusion-bucket slices); "
            "interleaved=False exists only for the legacy level-1 "
            "monolithic chain (docs/zero.md)")
    axis = _single_axis(axis_name, mesh)
    n = int(mesh.shape[axis])
    fn = jax.checkpoint(loss_fn) if remat else loss_fn
    from .data_parallel import _resolve_donate
    donate = _resolve_donate(donate)
    k = int(backward_passes_per_step)
    if k < 1:
        raise ValueError("backward_passes_per_step must be >= 1")

    if not (interleaved if interleaved is not None else True):
        return _make_monolithic_step(fn, optimizer, mesh, axis, n, donate,
                                     k, wire_policy, error_feedback)
    return _make_bucketed_step(fn, optimizer, mesh, axis, n, donate,
                               level, k, wire_policy, error_feedback,
                               ag_prefetch, fusion_threshold_bytes,
                               params_template)


def make_zero1_train_step(loss_fn: Callable,
                          optimizer: optax.GradientTransformation,
                          mesh: Mesh,
                          axis_name="hvd",
                          op: ReduceOp = Average,
                          donate=None,
                          remat: bool = False,
                          interleaved: bool = False,
                          fusion_threshold_bytes: Any = None) -> Callable:
    """Level-1 compat spelling (pre-level API): monolithic by default,
    bucket-interleaved with ``interleaved=True``.  New code uses
    :func:`make_zero_train_step`."""
    return make_zero_train_step(
        loss_fn, optimizer, mesh, axis_name=axis_name, op=op,
        donate=donate, remat=remat, zero_level=1,
        interleaved=bool(interleaved),
        fusion_threshold_bytes=fusion_threshold_bytes)


def _make_monolithic_step(fn: Callable,
                          optimizer: optax.GradientTransformation,
                          mesh: Mesh, axis: str, n: int, donate: bool,
                          k: int, wire_policy,
                          error_feedback: Optional[bool]) -> Callable:
    """The legacy level-1 chain: ONE flat fp32 vector, one RS, one
    sharded update, one AG — the anchor the bucketed chain's equivalence
    matrix is pinned against.  Carries no wire formats (nothing is
    bucketed to decide per) and takes one batch per step."""
    if k != 1:
        raise ValueError(
            "the monolithic zero chain takes one batch per step "
            "(backward_passes_per_step=1); microbatched steps ride the "
            "bucket-interleaved chain (interleaved=True, docs/zero.md)")
    if wire_policy is not None and wire_policy != "none":
        raise ValueError(
            "the monolithic zero chain carries no wire formats; use "
            "interleaved=True for per-bucket wire policies "
            "(docs/zero.md)")
    if error_feedback:
        raise ValueError(
            "error feedback needs a lossy wire format, which the "
            "monolithic zero chain does not carry (docs/zero.md)")

    def body(params, opt_state, batch):
        loss, grads = jax.value_and_grad(fn)(params, batch)
        total = _flat_size(params)
        padded = -(-total // n) * n
        shard_len = padded // n
        gflat = jnp.pad(_flatten(grads), (0, padded - total))
        # sum-reduce + scatter my shard: [n, L/n] -> [1, L/n] per chip
        gshard = lax.psum_scatter(gflat.reshape(n, shard_len), axis,
                                  scatter_dimension=0, tiled=True)
        gshard = gshard.reshape(shard_len) / n
        # my slice of the flattened params (adamw's decoupled weight
        # decay needs them); params are replicated so this is a local
        # static-size slice
        pflat = jnp.pad(_flatten(params), (0, padded - total))
        pshard = lax.dynamic_slice_in_dim(
            pflat, lax.axis_index(axis) * shard_len, shard_len)
        # the local state block carries the [1, ...] sharded leading dim
        state_local = jax.tree_util.tree_map(lambda x: x[0], opt_state)
        updates, state_local = optimizer.update(gshard, state_local,
                                                pshard)
        opt_state = jax.tree_util.tree_map(lambda x: x[None], state_local)
        # rebuild the full update: [L/n] -> [L]
        ufull = lax.all_gather(updates, axis, axis=0, tiled=True)
        params = optax.apply_updates(
            params, _unflatten_like(ufull[:total], params))
        return params, opt_state, lax.pmean(loss, axis)

    expected_cache: dict = {}

    def step(params, opt_state, batch):
        exp = expected_cache.get("state")
        if exp is None:
            padded = _padded_len(_flat_size(params), n)
            exp = expected_cache["state"] = jax.eval_shape(
                jax.vmap(optimizer.init),
                jax.ShapeDtypeStruct((n, padded // n), jnp.float32))
        _check_state_layout(opt_state, exp, "monolithic")
        return shard_map(
            body, mesh=mesh,
            in_specs=(P(), P(axis), P(axis)),
            out_specs=(P(), P(axis), P()),
            check_vma=False)(params, opt_state, batch)

    # donate the old params/opt_state buffers so XLA updates in place
    # (the same knob-driven default as data_parallel.make_train_step)
    return jax.jit(step, donate_argnums=(0, 1) if donate else ())


def _make_bucketed_step(fn: Callable,
                        optimizer: optax.GradientTransformation,
                        mesh: Mesh, axis: str, n: int, donate: bool,
                        level: int, k: int, wire_policy,
                        error_feedback: Optional[bool],
                        ag_prefetch: Optional[int],
                        fusion_threshold_bytes: Any,
                        params_template: Any) -> Callable:
    """The bucket-interleaved ZeRO chain, levels 1-3 (module docstring).

    Per fusion bucket and microbatch the gradient leg is: pack padded ->
    (+ EF residual) -> one-shot wire encode -> psum_scatter -> /n, in
    reverse-priority issue order.  Level 1 all-gathers each microbatch's
    shard back to keep the FULL synced-gradient accumulator resident
    (its defining redundancy — and exactly the wire bytes level 2
    deletes); levels 2/3 accumulate the 1/n shard.  The epilogue runs
    the sharded elementwise update per bucket and either all-gathers the
    updates onto replicated params (levels 1/2) or applies them to the
    resident param shard (level 3, whose step START gathered the full
    params bucket-by-bucket in plan order under the ag_prefetch
    window)."""
    from ..ops import wire as _wire
    from ..ops.fusion import unpack_bucket
    from ..ops.overlap import priority_order

    if level == 3 and params_template is None:
        raise ValueError(
            "zero_level=3 keeps params sharded between steps, so the "
            "step builder needs params_template (a pytree of arrays or "
            "ShapeDtypeStructs matching the model) to derive the bucket "
            "plan and leaf layout (docs/zero.md)")

    policy = _resolve_wire_policy(wire_policy)
    ef_requested = _resolve_ef(error_feedback)

    if level == 3:
        tleaves, treedef = jax.tree_util.tree_flatten(params_template)
        param_bytes_full = sum(
            int(np.prod(l.shape)) * jnp.dtype(l.dtype).itemsize
            for l in tleaves)

    def body(params_in, opt_state, batch):
        if level == 3:
            plan = _bucket_plan(params_template, fusion_threshold_bytes)
        else:
            plan = _bucket_plan(params_in, fusion_threshold_bytes)
        order = priority_order(plan)
        nb = plan.num_buckets
        formats = _zero_formats(plan, policy, axis, n)
        ef = ef_requested and any(_wire.is_lossy(f) for f in formats)
        depth = resolve_ag_prefetch(ag_prefetch) if level == 3 else 0
        pbytes = (param_bytes_full if level == 3 else sum(
            int(np.prod(l.shape)) * jnp.dtype(l.dtype).itemsize
            for l in jax.tree_util.tree_leaves(params_in)))
        _record_zero_trace(plan, order, formats, level, n, k, depth, ef,
                           opt_state, pbytes)
        my = lax.axis_index(axis)

        # ---- level 3: materialize full params from the resident bucket
        # shards, plan order (the forward consumes bucket 0's leaves
        # first), ag_prefetch-deep issue window: AG(bucket j+depth) is
        # issued before bucket j's unpack so a latency-hiding scheduler
        # overlays the gathers with the unpack/forward consumption; the
        # gathered flat bucket has no uses after its leaves unpack, so
        # XLA frees it behind the step (zero.bucket.free).
        if level == 3:
            def ag(bi):
                return lax.all_gather(params_in[bi][0], axis, axis=0,
                                      tiled=True)
            gathered = {j: ag(j) for j in range(min(depth, nb))}
            full: List[Optional[jnp.ndarray]] = [None] * plan.num_leaves
            for j in range(nb):
                if j + depth < nb:
                    gathered[j + depth] = ag(j + depth)
                b = plan.buckets[j]
                unpack_bucket(gathered.pop(j)[:sum(b.sizes)], b, full)
            params = jax.tree_util.tree_unflatten(
                treedef, [l.astype(t.dtype)
                          for l, t in zip(full, tleaves)])
            pleaves_raw = None
        else:
            params = params_in
            pleaves_raw, ptreedef = jax.tree_util.tree_flatten(params)
            pleaves_f32 = [l.astype(jnp.float32) for l in pleaves_raw]

        inner_states = [opt_state[bi].inner if ef else opt_state[bi]
                        for bi in range(nb)]
        res = ([opt_state[bi].residual[0] for bi in range(nb)]
               if ef else None)

        # ---- per-microbatch gradient legs (reverse-priority order:
        # backprop produces the tail buckets' gradients first)
        mbs = ([batch] if k == 1 else
               [jax.tree_util.tree_map(lambda x, _i=i: x[_i], batch)
                for i in range(k)])
        acc: List[Optional[jnp.ndarray]] = [None] * nb
        losses = []
        for mb in mbs:
            loss, grads = jax.value_and_grad(fn)(params, mb)
            losses.append(lax.pmean(loss, axis))
            gleaves = [l.astype(jnp.float32)
                       for l in jax.tree_util.tree_leaves(grads)]
            for bi in order:
                b = plan.buckets[bi]
                flat = _pack_padded(gleaves, b, n)
                if ef:
                    flat = flat + res[bi]
                enc = _wire.wire_roundtrip(flat, formats[bi])
                if ef and _wire.is_lossy(formats[bi]):
                    res[bi] = flat - enc
                shard_len = flat.shape[0] // n
                gshard = lax.psum_scatter(
                    enc.reshape(n, shard_len), axis,
                    scatter_dimension=0, tiled=True)
                gshard = gshard.reshape(shard_len) / n
                if level == 1 and k > 1:
                    # full synced-gradient accumulator (the level-1
                    # redundancy): gather the shard back every microbatch
                    contrib = lax.all_gather(gshard, axis, axis=0,
                                             tiled=True)
                else:
                    contrib = gshard
                acc[bi] = contrib if acc[bi] is None else acc[bi] + contrib

        # ---- epilogue: sharded update per bucket (priority order),
        # then AG(updates) onto replicated params (levels 1/2) or a
        # local shard apply (level 3).
        new_blocks: List[Any] = [None] * nb
        ufulls: List[Optional[jnp.ndarray]] = [None] * nb
        new_pshards: List[Optional[jnp.ndarray]] = [None] * nb
        for bi in order:
            b = plan.buckets[bi]
            if level == 1 and k > 1:
                shard_len = acc[bi].shape[0] // n
                gshard = lax.dynamic_slice_in_dim(
                    acc[bi], my * shard_len, shard_len) / k
            else:
                shard_len = acc[bi].shape[0]
                gshard = acc[bi] / k
            if level == 3:
                pshard = params_in[bi][0]
            else:
                pflat = _pack_padded(pleaves_f32, b, n)
                pshard = lax.dynamic_slice_in_dim(
                    pflat, my * shard_len, shard_len)
            state_local = jax.tree_util.tree_map(lambda x: x[0],
                                                 inner_states[bi])
            updates, state_local = optimizer.update(gshard, state_local,
                                                    pshard)
            inner_new = jax.tree_util.tree_map(lambda x: x[None],
                                               state_local)
            new_blocks[bi] = (_ZeroEFBlock(inner=inner_new,
                                           residual=res[bi][None])
                              if ef else inner_new)
            if level == 3:
                new_pshards[bi] = (pshard + updates)[None]
            else:
                ufulls[bi] = lax.all_gather(updates, axis, axis=0,
                                            tiled=True)

        loss = jnp.mean(jnp.stack(losses))
        if level == 3:
            return tuple(new_pshards), tuple(new_blocks), loss
        out: List[Optional[jnp.ndarray]] = [None] * plan.num_leaves
        for bi, b in enumerate(plan.buckets):
            unpack_bucket(ufulls[bi][:sum(b.sizes)], b, out)
        updates_tree = jax.tree_util.tree_unflatten(
            ptreedef, [u.astype(l.dtype)
                       for u, l in zip(out, pleaves_raw)])
        params = optax.apply_updates(params_in, updates_tree)
        return params, tuple(new_blocks), loss

    batch_spec = P(axis) if k == 1 else P(None, axis)
    param_spec = P(axis) if level == 3 else P()
    jitted = jax.jit(
        shard_map(body, mesh=mesh,
                  in_specs=(param_spec, P(axis), batch_spec),
                  out_specs=(param_spec, P(axis), P()),
                  check_vma=False),
        donate_argnums=(0, 1) if donate else ())

    expected_cache: dict = {}

    def step(params, opt_state, batch):
        exp = expected_cache.get("state")
        if exp is None:
            plan = _bucket_plan(params_template if level == 3 else params,
                                fusion_threshold_bytes)
            formats = _zero_formats(plan, policy, axis, n)
            ef = ef_requested and any(_wire.is_lossy(f) for f in formats)
            exp = expected_cache["state"] = _expected_state(
                optimizer, plan, n, ef)
        _check_state_layout(opt_state, exp,
                            f"bucket-interleaved level-{level}")
        return jitted(params, opt_state, batch)

    return step
