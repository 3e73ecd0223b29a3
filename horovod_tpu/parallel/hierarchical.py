"""Hierarchical (two-level ICI/DCN) collectives for multi-slice meshes.

The reference's hierarchical allreduce splits the ring into an intra-node
stage and a cross-node stage: NCCL ReduceScatter inside the node, one
MPI_Allreduce per local rank across nodes, then NCCL Allgather back
(reference: nccl_operations.cc:188-319, toggled by
HOROVOD_HIERARCHICAL_ALLREDUCE, common.h:81-82; MPIHierarchicalAllgather in
mpi_operations.cc).  The payoff: the slow inter-node link carries 1/local_size
of the data.

On TPU the same shape maps to a two-axis mesh: an ``ici.X`` axis (chips
within a slice, fast ICI links) and a ``dcn.X`` axis (across slices, slow
DCN).  The mesh spec ``'dcn.data=2,ici.data=8'`` (parsed by
runtime.Runtime._build_mesh) builds that topology with dcn as the OUTER mesh
axis, so global worker order is dcn-major.  The two-level algorithm:

    reduce_scatter over ici  →  allreduce over dcn  →  all_gather over ici

sends exactly ``bytes/ici_size`` over DCN per chip — the same 1/local_size
saving as the reference.  Padding to a multiple of ici_size mirrors the
reference's FUSION_BUFFER_ATOMIC_UNIT padding (nccl_operations.cc:230-260).
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..common.reduce_op import ReduceOp

AxisName = Union[str, Sequence[str]]


def resolve_axis(axis_name: AxisName, mesh) -> AxisName:
    """Resolve a logical axis name against a (possibly two-level) mesh.

    On a mesh built from ``'dcn.data=2,ici.data=8'`` the logical axis
    ``'data'`` resolves to the tuple ``('dcn.data', 'ici.data')`` — dcn
    first, matching the mesh's outer-to-inner order — so user code written
    for a flat mesh runs unchanged on a multi-slice one.  Plain axis names
    pass through; tuples are returned as-is."""
    if isinstance(axis_name, str):
        names = mesh.axis_names
        if axis_name in names:
            return axis_name
        pair = ("dcn." + axis_name, "ici." + axis_name)
        if all(p in names for p in pair):
            return pair
        raise ValueError(
            f"axis {axis_name!r} not in mesh axes {tuple(names)} (nor as a "
            f"dcn.{axis_name}/ici.{axis_name} two-level pair)")
    return tuple(axis_name)


def split_hierarchy(axis_name: AxisName) -> Optional[Tuple[str, str]]:
    """Return ``(dcn_axis, ici_axis)`` when ``axis_name`` is the canonical
    dcn-major 2-tuple of mesh axes named by the ``dcn.X``/``ici.X``
    convention, else None.

    Only the canonical order is recognized: for order-sensitive collectives
    (allgather) the hierarchical algorithm produces dcn-major concatenation,
    which matches the flat path only when the tuple is dcn-major too —
    normalizing a reversed tuple would let the knob silently permute
    results."""
    if (isinstance(axis_name, (tuple, list)) and len(axis_name) == 2):
        a, b = axis_name
        if str(a).startswith("dcn.") and str(b).startswith("ici."):
            return (str(a), str(b))
    return None


def hierarchical_allreduce(x: jax.Array,
                           ici_axis: str,
                           dcn_axis: str,
                           op: ReduceOp = ReduceOp.SUM,
                           prescale_factor: float = 1.0,
                           postscale_factor: float = 1.0) -> jax.Array:
    """Two-level allreduce over (ici_axis, dcn_axis).

    SUM/AVERAGE ride the reduce_scatter→dcn-allreduce→all_gather pipeline;
    MIN/MAX/PRODUCT have no scatter-reduce primitive and fall back to the
    flat combined-axis reduction (they never carry gradient volume).  Must
    run inside shard_map/pjit binding both axes.
    """
    if op not in (ReduceOp.SUM, ReduceOp.AVERAGE):
        # Flat fallback via the lax primitives directly — routing back
        # through spmd.allreduce would re-enter this function while the
        # hierarchical knob is on.
        if prescale_factor != 1.0:
            x = x * prescale_factor
        axes = (dcn_axis, ici_axis)
        if op == ReduceOp.MIN:
            out = lax.pmin(x, axes)
        elif op == ReduceOp.MAX:
            out = lax.pmax(x, axes)
        elif op == ReduceOp.PRODUCT:
            out = jnp.prod(lax.all_gather(x, axes), axis=0)
        elif op == ReduceOp.ADASUM:
            from .adasum import adasum_allreduce
            out = adasum_allreduce(x, axes)
        else:
            raise ValueError(f"unknown ReduceOp {op!r}")
        if postscale_factor != 1.0:
            out = out * postscale_factor
        return out

    if prescale_factor != 1.0:
        x = x * prescale_factor

    shape = x.shape
    flat = jnp.ravel(x)
    n = flat.shape[0]
    # Axis sizes are static at trace time inside shard_map/pjit.
    ici = int(lax.axis_size(ici_axis))
    dcn = int(lax.axis_size(dcn_axis))
    pad = (-n) % ici
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])

    # Stage 1: ICI reduce-scatter — each chip owns 1/ici of the reduced sum.
    shard = lax.psum_scatter(flat, ici_axis, scatter_dimension=0, tiled=True)
    # Stage 2: DCN allreduce on the shard — DCN traffic = bytes/ici.
    shard = lax.psum(shard, dcn_axis)
    # Stage 3: ICI all-gather back to the full buffer.
    full = lax.all_gather(shard, ici_axis, axis=0, tiled=True)
    if pad:
        full = full[:n]
    out = jnp.reshape(full, shape)

    if op == ReduceOp.AVERAGE:
        out = out / jnp.asarray(ici * dcn, out.dtype)
    if postscale_factor != 1.0:
        out = out * postscale_factor
    return out


def dcn_selective_int8_allreduce(x: jax.Array,
                                 ici_axis: str,
                                 dcn_axis: str,
                                 average: bool = True) -> jax.Array:
    """Two-level allreduce that quantizes ONLY the slow leg (EQuARX-style
    selective composition, arxiv 2506.17615; the ``dcn_int8`` wire
    format of ops/wire.py):

        reduce_scatter over ICI (full precision)
        -> int8 ring allreduce over DCN (ops/quantized.py)
        -> all_gather over ICI (full precision)

    ICI has ~10x DCN's bandwidth, so spending quantization noise where
    the bytes are cheap buys nothing; this keeps the intra-slice legs
    exact and sends 1/ici of the payload at 1 byte/element across DCN —
    4x less DCN traffic than the plain hierarchical fp32 pipeline at a
    single slow-leg quantization's noise (2(dcn-1) int8 hops on 1/ici of
    the data, vs 2(n-1) hops on all of it for the flat int8 ring).
    Must run inside shard_map/pjit binding both axes."""
    shape, dtype = x.shape, x.dtype
    flat = jnp.ravel(x).astype(jnp.float32)
    n = flat.shape[0]
    ici = int(lax.axis_size(ici_axis))
    dcn = int(lax.axis_size(dcn_axis))
    pad = (-n) % ici
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    shard = lax.psum_scatter(flat, ici_axis, scatter_dimension=0, tiled=True)
    from ..ops.quantized import quantized_ring_allreduce
    shard = quantized_ring_allreduce(shard, dcn_axis, average=False)
    full = lax.all_gather(shard, ici_axis, axis=0, tiled=True)
    if pad:
        full = full[:n]
    out = jnp.reshape(full, shape)
    if average:
        out = out / jnp.asarray(ici * dcn, out.dtype)
    return out.astype(dtype)


def hierarchical_allgather(x: jax.Array,
                           ici_axis: str,
                           dcn_axis: str,
                           axis: int = 0) -> jax.Array:
    """Two-level allgather: gather over ICI, then over DCN.

    Global concatenation order is dcn-major — identical to a flat
    ``all_gather`` over ``(dcn_axis, ici_axis)`` on a mesh whose outer axis
    is dcn (reference: MPIHierarchicalAllgather's shared-memory + cross
    allgather, mpi_operations.cc)."""
    inner = lax.all_gather(x, ici_axis, axis=axis, tiled=True)
    return lax.all_gather(inner, dcn_axis, axis=axis, tiled=True)
