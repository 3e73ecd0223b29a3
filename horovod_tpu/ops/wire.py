"""Wire-policy plane: per-bucket wire formats for the fused gradient sync.

The ingredients existed in isolation — bf16/fp16 cast compression
(ops/compression.py), the int8 quantized ring (ops/quantized.py, EQuARX
arxiv 2506.17615), two-level ici/dcn routing (parallel/hierarchical.py) —
but as mutually-exclusive global flags: one wire format for every bucket,
no error compensation, so the aggressive formats were unsafe to enable.
This module composes them into a *policy*: a function

    policy(bucket_nbytes, dtype, axis_name) -> wire format name

evaluated per fusion bucket at trace time, so a compiled step can send its
handful of huge fp32 buckets as int8 ring hops while the small latency-bound
tail rides uncompressed.  The reference's analog is a single global
``Compression.fp16`` switch (horovod/torch/compression.py); per-bucket
selection has no reference equivalent.

Formats
-------
  none       exact allreduce in the bucket dtype
  bf16/fp16  cast compression around the allreduce (ops/compression.py)
  int8_ring  int8 quantized ring allreduce, fp32 accumulation
             (ops/quantized.py) — 1/4 the wire bytes of fp32
  dcn_int8   EQuARX-selective composition for two-level (dcn.X, ici.X)
             meshes: reduce_scatter(ici) -> int8 ring over dcn ->
             all_gather(ici) — only the slow DCN leg is quantized
             (parallel/hierarchical.py dcn_selective_int8_allreduce)

Policies are named by the same strings plus ``auto`` (per-bucket heuristic,
bandit-tuned online when HOROVOD_AUTOTUNE is on — utils/autotune.py).
Convergence safety for the lossy formats comes from error-feedback
residuals kept as optimizer state (optimizer.py): each rank's one-shot
encode error ``x - C(x)`` is added back into the next step's gradient
before compression (EF-SGD), which rescues the small-magnitude coordinates
an int8 dead zone would otherwise silently drop forever.

Determinism: every format decodes to bit-identical values on all ranks
(the int8 ring's allgather phase circulates the *quantized* chunks, and
the cast formats decompress a replicated psum result), so replicated
params cannot drift — asserted per format by tests/test_wire.py.
"""

from __future__ import annotations

import math
from typing import Any, Callable, Dict, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
from jax import lax

from ..common.reduce_op import ReduceOp
from ..utils import metrics as _metrics
from .compression import Compression

AxisName = Union[str, Sequence[str]]
# policy(bucket_nbytes, dtype, axis_name) -> format name
Policy = Callable[[int, Any, AxisName], str]

FORMAT_NAMES = ("none", "bf16", "fp16", "int8_ring", "dcn_int8")
POLICY_NAMES = FORMAT_NAMES + ("auto",)
LOSSY_FORMATS = ("bf16", "fp16", "int8_ring", "dcn_int8")

# auto-policy thresholds: below SMALL the collective is latency-bound and
# compression overhead (quantize/cast + scale exchange) buys nothing;
# above INT8_MIN the 4x byte saving dominates the bounded ring noise.
SMALL_BUCKET_BYTES = 64 * 1024
INT8_MIN_BYTES = 4 * 1024 * 1024

# The int8 wire also carries one fp32 scale per chunk per hop
# (ops/quantized.py).  The byte MODEL below excludes it: for the buckets
# the int8 formats ever apply to (>= INT8_MIN_BYTES) the scale words are
# < 0.01% of the payload, and excluding them keeps the per-element
# ratios exact (int8 = 1/2 bf16 = 1/4 fp32).


def validate_policy_name(name: str) -> str:
    """Fail loudly on unknown policy names (consumed by hvd.init for the
    HOROVOD_WIRE_POLICY knob)."""
    if name not in POLICY_NAMES:
        raise ValueError(
            f"unknown wire policy {name!r}; valid policies: "
            f"{', '.join(POLICY_NAMES)} (HOROVOD_WIRE_POLICY, "
            "docs/tensor-fusion.md)")
    return name


def _is_hierarchical(axis_name: AxisName) -> bool:
    from ..parallel.hierarchical import split_hierarchy
    return split_hierarchy(axis_name) is not None


def auto_policy(nbytes: int, dtype: Any, axis_name: AxisName) -> str:
    """The per-bucket heuristic behind ``HOROVOD_WIRE_POLICY=auto``:
    big floating buckets take the int8 wire (DCN-selective on a two-level
    mesh), mid-size fp32 buckets cast to bf16, and the small latency-bound
    tail stays exact."""
    dt = jnp.dtype(dtype)
    if not jnp.issubdtype(dt, jnp.floating):
        return "none"
    if nbytes < SMALL_BUCKET_BYTES:
        return "none"
    if nbytes >= INT8_MIN_BYTES:
        return "dcn_int8" if _is_hierarchical(axis_name) else "int8_ring"
    # mid-size: halve the wire if the dtype has the headroom
    return "bf16" if dt.itemsize >= 4 else "none"


def get_policy(policy: Union[str, Policy]) -> Policy:
    """Resolve a policy name (or pass a callable through) to the
    per-bucket decision function."""
    if callable(policy):
        return policy
    validate_policy_name(policy)
    if policy == "auto":
        return auto_policy
    return lambda nbytes, dtype, axis_name: policy


def is_lossy(fmt: str) -> bool:
    return fmt in LOSSY_FORMATS


def resolve_format(fmt: str, dtype: Any, axis_name: AxisName,
                   op: ReduceOp) -> str:
    """Degrade a requested format to what the bucket can actually carry:
    non-float buckets and non-linear reductions stay exact, no-op casts
    collapse to none, and ``dcn_int8`` on a flat axis falls back to the
    flat int8 ring (there is no separate slow leg to select)."""
    if fmt not in FORMAT_NAMES:
        raise ValueError(f"unknown wire format {fmt!r}; valid formats: "
                         f"{', '.join(FORMAT_NAMES)}")
    dt = jnp.dtype(dtype)
    if fmt == "none" or not jnp.issubdtype(dt, jnp.floating):
        return "none"
    if fmt in ("int8_ring", "dcn_int8"):
        # Quantized rings exist for Average/Sum only (scales don't commute
        # with min/max/product and Adasum re-reduces pairwise).
        if op not in (ReduceOp.AVERAGE, ReduceOp.SUM):
            return "none"
        if fmt == "dcn_int8" and not _is_hierarchical(axis_name):
            return "int8_ring"
        return fmt
    wire_dt = jnp.dtype({"bf16": jnp.bfloat16, "fp16": jnp.float16}[fmt])
    if wire_dt == dt:
        return "none"  # casting to the bucket's own dtype moves nothing
    return fmt


def reduce_bucket(buf: jax.Array, fmt: str, axis_name: AxisName,
                  op: ReduceOp, prescale_factor: float = 1.0,
                  postscale_factor: float = 1.0) -> jax.Array:
    """Allreduce one flat bucket buffer in its wire format."""
    from . import spmd
    if fmt in ("none", "bf16", "fp16"):
        comp = Compression.by_name(fmt) if fmt != "none" else None
        if comp is not None:
            buf, ctx = comp.compress(buf)
        buf = spmd.allreduce(buf, axis_name, op=op,
                             prescale_factor=prescale_factor,
                             postscale_factor=postscale_factor)
        return comp.decompress(buf, ctx) if comp is not None else buf

    average = op == ReduceOp.AVERAGE
    if prescale_factor != 1.0:
        buf = buf * prescale_factor
    if fmt == "int8_ring":
        from .quantized import quantized_ring_allreduce
        out = quantized_ring_allreduce(buf, axis_name, average=average)
    elif fmt == "dcn_int8":
        from ..parallel.hierarchical import (dcn_selective_int8_allreduce,
                                             split_hierarchy)
        pair = split_hierarchy(axis_name)
        if pair is None:
            raise ValueError(
                "dcn_int8 needs a canonical (dcn.X, ici.X) axis pair; "
                f"got {axis_name!r} (resolve_format degrades this case)")
        out = dcn_selective_int8_allreduce(buf, ici_axis=pair[1],
                                           dcn_axis=pair[0],
                                           average=average)
    else:
        raise ValueError(f"unknown wire format {fmt!r}")
    if postscale_factor != 1.0:
        out = out * postscale_factor
    return out


def wire_roundtrip(buf: jax.Array, fmt: str) -> jax.Array:
    """``C(buf)`` — the decoded value of putting ``buf`` on the wire in
    ``fmt``, under the one-shot codec model (encode once, decode once).
    This is what the ZeRO chain's reduce_scatter leg feeds the collective
    (parallel/zero.py): each rank's contribution is encoded exactly once
    before the scatter, so the compensable error is ``buf - C(buf)`` —
    the same residual :func:`local_error` reports."""
    if fmt in ("bf16", "fp16"):
        comp = Compression.by_name(fmt)
        c, ctx = comp.compress(buf)
        return comp.decompress(c, ctx)
    if fmt in ("int8_ring", "dcn_int8"):
        from .quantized import int8_roundtrip
        return int8_roundtrip(buf)
    return buf


def local_error(buf: jax.Array, fmt: str) -> jax.Array:
    """The rank-local compensable encode error ``x - C(x)`` of putting
    ``buf`` on the wire in ``fmt`` — the EF-SGD residual.  One-shot codec
    model: for the multi-hop rings this is the error of this rank's own
    contribution (the only part a rank *can* compensate)."""
    if is_lossy(fmt):
        return buf - wire_roundtrip(buf, fmt)
    return jnp.zeros_like(buf)


# ------------------------------------------------------------ wire model
def _axis_sizes(axis_name: AxisName) -> Dict[str, int]:
    """Trace-time ring sizes by fabric: ``{"flat": n}`` for a plain axis,
    ``{"ici": i, "dcn": d}`` for the canonical two-level pair.  Unbound
    axes (host-side calls outside shard_map) report size 1."""
    from ..parallel.hierarchical import split_hierarchy

    def size(ax) -> int:
        try:
            return int(lax.psum(1, ax))
        except NameError:
            return 1
    pair = split_hierarchy(axis_name)
    if pair is not None:
        return {"dcn": size(pair[0]), "ici": size(pair[1])}
    if isinstance(axis_name, (tuple, list)):
        n = 1
        for ax in axis_name:
            n *= size(ax)
        return {"flat": n}
    return {"flat": size(axis_name)}


def modeled_wire_bytes(nelems: int, itemsize: int, fmt: str,
                      axis_sizes: Dict[str, int]) -> Dict[str, Any]:
    """Per-chip wire bytes for ONE allreduce of an ``nelems``-element
    bucket, by fabric, under the standard ring model (each chip sends
    2(n-1) chunks of nelems/n elements; int8 hops add one fp32 scale per
    chunk).  ``bottleneck`` is the slow-fabric total — DCN on a two-level
    mesh, the single fabric otherwise.  A *model*, not a measurement: it
    exists so policies are comparable analytically (bench.py --wire) and
    the savings counters move without device introspection."""
    def ring(n: int, elems: int, wire_itemsize: float) -> float:
        if n <= 1:
            return 0.0
        return 2.0 * (n - 1) * math.ceil(elems / n) * wire_itemsize

    two_level = "dcn" in axis_sizes
    if fmt == "dcn_int8" and two_level:
        ici, dcn = axis_sizes["ici"], axis_sizes["dcn"]
        shard = math.ceil(nelems / max(ici, 1))
        per_fabric = {
            # exact fp32 reduce_scatter + all_gather legs on ICI
            "ici": 2.0 * (ici - 1) * shard * 4.0,
            "dcn": ring(dcn, shard, 1.0),
        }
        return {"per_fabric": per_fabric,
                "bottleneck": per_fabric["dcn"]}

    wire_itemsize = {"none": float(itemsize), "bf16": 2.0, "fp16": 2.0,
                     "int8_ring": 1.0, "dcn_int8": 1.0}[fmt]
    if two_level:
        # flat formats on a hierarchical axis: the combined ring's hops all
        # potentially cross DCN (exactly why dcn_int8/hierarchical exist) —
        # charge the full ring to the slow fabric.
        n = axis_sizes["ici"] * axis_sizes["dcn"]
        total = ring(n, nelems, wire_itemsize)
        return {"per_fabric": {"dcn": total}, "bottleneck": total}
    n = axis_sizes.get("flat", 1)
    total = ring(n, nelems, wire_itemsize)
    return {"per_fabric": {"flat": total}, "bottleneck": total}


def plan_formats(plan, policy: Policy, axis_name: AxisName,
                 op: ReduceOp,
                 axis_sizes: Optional[Dict[str, int]] = None) -> List[str]:
    """Decide (and record) the wire format of every bucket in a fusion
    plan.  Runs at trace time, once per compiled program — the metric
    families therefore count decisions per trace (see utils/metrics.py).

    ``axis_sizes`` overrides the bound-axis probe: callers that decide
    formats OUTSIDE shard_map (the ZeRO chain's state init, which must
    agree structurally with the traced step — parallel/zero.py) pass the
    mesh sizes explicitly so both sides resolve identical formats."""
    sizes = _axis_sizes(axis_name) if axis_sizes is None else axis_sizes
    total_ranks = 1
    for v in sizes.values():
        total_ranks *= v
    fmts: List[str] = []
    for bucket in plan.buckets:
        fmt = resolve_format(policy(bucket.nbytes, bucket.dtype, axis_name),
                             bucket.dtype, axis_name, op)
        if total_ranks <= 1:
            # a single-member axis moves no bytes: compressing would only
            # add noise — and EF would "compensate" an error the wire
            # never incurred.
            fmt = "none"
        fmts.append(fmt)
        _metrics.WIRE_BUCKETS.inc(format=fmt)
        # Tracing plane: one instant per bucket decision (trace time, once
        # per compiled program) so the merged timeline shows WHICH wire
        # format each bucket encodes/decodes with (docs/timeline.md).
        from ..utils.timeline import trace_instant
        trace_instant("wire", f"wire.encode.{fmt}",
                      args={"bucket": len(fmts) - 1,
                            "nbytes": int(bucket.nbytes)})
        if fmt != "none":
            nelems = sum(bucket.sizes)
            itemsize = jnp.dtype(bucket.dtype).itemsize
            base = modeled_wire_bytes(nelems, itemsize, "none", sizes)
            this = modeled_wire_bytes(nelems, itemsize, fmt, sizes)
            saved = base["bottleneck"] - this["bottleneck"]
            if saved > 0:
                _metrics.WIRE_BYTES_SAVED.inc(saved, format=fmt)
    return fmts


# ------------------------------------------------------------- sync engine
def wire_sync(leaves: Sequence[jax.Array], plan, formats: Sequence[str],
              axis_name: AxisName, op: ReduceOp,
              prescale_factor: float = 1.0, postscale_factor: float = 1.0,
              residuals: Optional[Sequence[jax.Array]] = None):
    """Reduce every bucket in its wire format.

    With ``residuals`` (error feedback): the residual is added into the
    gradient per leaf BEFORE packing, each lossy bucket's one-shot encode
    error is captured as the new residual, and the function returns
    ``(synced_leaves, new_residuals)``.  Without residuals the second
    element is None.  Residuals are rank-local state; synced outputs are
    bit-identical on every rank regardless.
    """
    from .fusion import pack_bucket, unpack_bucket
    ef = residuals is not None
    if ef:
        leaves = [l + r.astype(l.dtype) for l, r in zip(leaves, residuals)]
        new_res: List[jax.Array] = [jnp.zeros_like(l) for l in leaves]
    out: List[Optional[jax.Array]] = [None] * plan.num_leaves
    for i, (bucket, fmt) in enumerate(zip(plan.buckets, formats)):
        with jax.named_scope(f"bucket{i}"):
            buf = pack_bucket(leaves, bucket)
            if ef and is_lossy(fmt):
                unpack_bucket(local_error(buf, fmt), bucket, new_res)
            buf = reduce_bucket(buf, fmt, axis_name, op,
                                prescale_factor=prescale_factor,
                                postscale_factor=postscale_factor)
            unpack_bucket(buf, bucket, out)
    return out, (new_res if ef else None)
