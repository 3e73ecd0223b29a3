"""Eager (host-level) collectives: the classic ``hvd.allreduce`` surface.

The reference's public ops take one tensor per rank-process and return the
reduced tensor, executing asynchronously on a background thread (reference:
operations.cc:919-1198 Enqueue*, torch/mpi_ops.py:95-841).  On TPU the worker
unit is the *chip* and a single Python process drives ``local_size()`` chips,
so the eager API here takes a **leading per-chip axis**:

    x.shape == (local_size, *tensor_shape)   # one slice per local chip

and returns the same layout.  A tensor *without* that leading axis is treated
as identical on every local chip (every chip-rank holds the same value —
exactly the reference's semantics when all ranks pass the same tensor).

Execution: each op is a jitted ``shard_map`` over the flattened mesh, cached
by (shape, dtype, op) — the compiled-program cache plays the role of the
reference's response cache for eager mode.  Multi-host processes contribute
their local shard via ``jax.make_array_from_process_local_data``; XLA runs
the collective over ICI/DCN.

Async API: ``allreduce_async`` & friends return a ``Handle``; ``synchronize``
/ ``poll`` mirror the reference's handle manager (reference:
torch/mpi_ops.py:843-881, torch/handle_manager.{h,cc}).  JAX dispatch is
already async — the handle wraps the in-flight on-device value.
"""

from __future__ import annotations

import functools
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import runtime as _rt
from ..common.reduce_op import ReduceOp, Average
from ..utils import metrics as _metrics
from . import spmd
from .fusion import fused_apply

Array = jax.Array
TensorLike = Union[jax.Array, np.ndarray, float, int]


# --------------------------------------------------------------- input marking
class ProcessLocalArray(np.ndarray):
    """Marks an array as *one value per process*: the eager layer replicates
    it across local chips instead of interpreting a leading dim that happens
    to equal local_size() as a per-chip axis (see :func:`_per_chip`)."""
    _hvd_per_chip = False


def process_local(x: TensorLike) -> np.ndarray:
    """View ``x`` as a process-level tensor with no per-chip leading axis."""
    arr = np.asarray(x)
    if not arr.flags["C_CONTIGUOUS"]:
        arr = np.ascontiguousarray(arr)
    return arr.reshape(arr.shape).view(ProcessLocalArray)


# --------------------------------------------------------------------- mesh IO
def _mesh_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(mesh.axis_names)


def _flat_spec(mesh: Mesh) -> P:
    """PartitionSpec sharding axis 0 over *all* mesh axes (chips flattened)."""
    return P(_mesh_axes(mesh))


def _per_chip(rt: "_rt.Runtime", x: TensorLike) -> Tuple[jnp.ndarray, bool]:
    """Normalize input to a host array of shape [local_size, ...].

    Returns (array, had_chip_axis)."""
    arr = jnp.asarray(x)
    ls = rt.local_size()
    if arr.ndim >= 1 and arr.shape[0] == ls and getattr(
            x, "_hvd_per_chip", True) is not False:
        return arr, True
    # Replicate this process's single value across its chips.
    return jnp.broadcast_to(arr[None], (ls,) + arr.shape), False


def _make_global(rt: "_rt.Runtime", local: jnp.ndarray) -> Array:
    """Assemble the global [size, ...] array sharded over the mesh chips."""
    mesh = rt.mesh
    sharding = NamedSharding(mesh, _flat_spec(mesh))
    if rt.process_size() == 1:
        return jax.device_put(local, sharding)
    return jax.make_array_from_process_local_data(sharding, np.asarray(local))


def _to_local(rt: "_rt.Runtime", global_arr: Array) -> Array:
    """Extract this process's [local_size, ...] slice of the result."""
    if rt.process_size() == 1:
        return global_arr
    shards = sorted(global_arr.addressable_shards,
                    key=lambda s: (s.index[0].start or 0) if s.index else 0)
    if len(shards) == 1:
        return shards[0].data
    # Shards live on different local devices; assemble on host (jnp.stack
    # across device-committed arrays is rejected by jax).
    return jnp.asarray(np.concatenate([np.asarray(s.data) for s in shards],
                                      axis=0))


# ----------------------------------------------------------------- jit caching
@functools.lru_cache(maxsize=4096)
def _compiled(mesh_id: int, kind: str, **static) -> Any:
    """Build + cache the jitted shard_map program for an eager op.

    Keyed by mesh identity and op signature — the compiled-program cache is
    the eager path's response cache (reference: response_cache.h:44-100)."""
    rt = _rt.get()
    mesh = rt.mesh
    axes = _mesh_axes(mesh)
    spec = _flat_spec(mesh)

    from jax import shard_map

    def annotate(jitted):
        # NVTX-range analog (reference: nvtx_op_range.h wraps every
        # user-facing op): xprof correlates this host range with the
        # device activity it launches; no-op outside a trace session.
        range_name = f"HOROVOD_{kind.upper()}"

        def dispatch(*args):
            with jax.profiler.TraceAnnotation(range_name):
                return jitted(*args)
        return dispatch

    def wrap(body, out_specs=None):
        return annotate(jax.jit(shard_map(body, mesh=mesh,
                                          in_specs=(spec,),
                                          out_specs=out_specs or spec)))

    if kind == "allreduce":
        op = ReduceOp(static["op"])
        pre, post = static["pre"], static["post"]

        def body(x):  # x: [1, ...] per chip
            return spmd.allreduce(x, axes, op=op, prescale_factor=pre,
                                  postscale_factor=post)
        return wrap(body)
    if kind == "grouped_allreduce":
        op = ReduceOp(static["op"])
        pre, post = static["pre"], static["post"]
        plan = static["plan"]

        def gbody(*leaves):
            # Leaves arrive as [1, ...] per-chip shards; ravel each so the
            # fusion plan (computed over raveled sizes) lines up.
            flat = [jnp.ravel(l) for l in leaves]
            outs = fused_apply(
                flat, plan,
                lambda buf: spmd.allreduce(buf, axes, op=op,
                                           prescale_factor=pre,
                                           postscale_factor=post))
            return tuple(jnp.reshape(o, l.shape)
                         for o, l in zip(outs, leaves))
        n = static["n_leaves"]
        return annotate(jax.jit(shard_map(
            gbody, mesh=mesh, in_specs=(spec,) * n,
            out_specs=(spec,) * n)))
    if kind == "allgather":
        def agbody(x):  # [1, rows, ...] -> full concat, replicated out
            g = spmd.allgather(x, axes, axis=0)
            return g
        # The gathered result is identical on every chip (out_specs=P());
        # jax's varying-mesh-axes check can't prove that, so disable it.
        return annotate(jax.jit(shard_map(agbody, mesh=mesh,
                                          in_specs=(spec,),
                                          out_specs=P(),
                                          check_vma=False)))
    if kind == "broadcast":
        root = static["root"]

        def bbody(x):
            return spmd.broadcast(x, axes, root=root)
        return wrap(bbody)
    if kind == "alltoall":
        def a2abody(x):  # [1, size*block, ...] equal splits
            y = jnp.squeeze(x, axis=0)
            out = spmd.alltoall(y, axes, split_axis=0, concat_axis=0)
            return out[None]
        return wrap(a2abody)
    if kind == "reducescatter":
        op = ReduceOp(static["op"])

        def rsbody(x):
            y = jnp.squeeze(x, axis=0)
            out = spmd.reducescatter(y, axes, op=op, scatter_axis=0)
            return out[None]
        return wrap(rsbody)
    if kind == "barrier":
        def barbody(x):
            # Fold the collective's result into the output so jit cannot
            # dead-code-eliminate the psum.
            z = spmd.barrier(axes)
            return x + z.astype(x.dtype)
        return wrap(barbody)
    raise ValueError(kind)


def _mesh_key(rt) -> int:
    return id(rt.mesh)


def _tl(rt, name: Optional[str], kind: str, nbytes: int,
        t0: Optional[float] = None) -> None:
    """Timeline emit for one eager collective (reference: per-op activities
    from every backend, e.g. nccl_operations.cc:144-181).  X events carry
    the real host-side latency measured from ``t0`` (the same window _rec
    feeds the metrics histogram) and are anchored at span START, so they
    render where the op ran, at their true width — not as 1 µs slivers at
    completion time.  The negotiated torch path adds NEGOTIATE/QUEUE
    phases around these.

    Auto-generated names ('x.noname.N') collapse to their prefix (the
    timeline's collapse_name): each unique name allocates a chrome pid +
    metadata entry forever, so per-call unique names would leak memory
    and bloat the trace."""
    if rt.timeline is not None:
        if not name:
            name = kind.lower()
        dur_us = None
        if t0 is not None:
            dur_us = (time.perf_counter() - t0) * 1e6
        rt.timeline.record_op(name, kind, nbytes, duration_us=dur_us)


def _rec(kind: str, nbytes: int, t0: float) -> None:
    """Metrics emit for one eager collective: count, payload bytes, and
    host-side latency (assembly + dispatch, plus completion wherever the
    op blocks — the sync allreduce under the stall inspector does)."""
    op = kind.lower()
    _metrics.COLLECTIVE_OPS.inc(op=op)
    _metrics.COLLECTIVE_BYTES.inc(nbytes, op=op)
    _metrics.COLLECTIVE_LATENCY.observe(time.perf_counter() - t0, op=op)


# ------------------------------------------------------------------ public API
def allreduce(tensor: TensorLike,
              average: Optional[bool] = None,
              name: Optional[str] = None,
              op: ReduceOp = Average,
              prescale_factor: float = 1.0,
              postscale_factor: float = 1.0) -> Array:
    """Allreduce across all chips; returns per-chip results [local_size, ...].

    Mirrors ``hvd.allreduce`` incl. the deprecated ``average`` flag
    (reference: tensorflow/__init__.py:54-155, torch/mpi_ops.py:95-139)."""
    rt = _rt.get()
    t0 = time.perf_counter()
    if average is not None:
        op = ReduceOp.AVERAGE if average else ReduceOp.SUM
    if rt.stall_inspector is not None and name:
        rt.stall_inspector.record_submit(name)
    local, had_axis = _per_chip(rt, tensor)
    g = _make_global(rt, local)
    fn = _compiled(_mesh_key(rt), "allreduce", op=int(op),
                   pre=float(prescale_factor), post=float(postscale_factor))
    out = fn(g)
    if rt.stall_inspector is not None and name:
        # The watchdog must observe actual completion, not async dispatch:
        # block before clearing the pending entry (the sync allreduce API is
        # blocking in the reference too; use allreduce_async to overlap).
        jax.block_until_ready(out)
        rt.stall_inspector.record_complete(name)
    res = _to_local(rt, out)
    _rec("ALLREDUCE", int(local.nbytes), t0)
    _tl(rt, name, "ALLREDUCE", int(local.nbytes), t0)
    return res if had_axis else res[0]


def grouped_allreduce(tensors: Sequence[TensorLike],
                      average: Optional[bool] = None,
                      name: Optional[str] = None,
                      op: ReduceOp = Average,
                      prescale_factor: float = 1.0,
                      postscale_factor: float = 1.0) -> List[Array]:
    """Fused multi-tensor allreduce (reference: operations.cc:919-1056
    EnqueueTensorAllreduces; torch ``grouped_allreduce``).  Tensors are
    bucketed by the fusion threshold and reduced in few large collectives."""
    rt = _rt.get()
    t0 = time.perf_counter()
    if average is not None:
        op = ReduceOp.AVERAGE if average else ReduceOp.SUM
    pairs = [_per_chip(rt, t) for t in tensors]
    locals_ = [p[0] for p in pairs]
    had = [p[1] for p in pairs]
    # Plan over *per-chip raveled* sizes: inside shard_map each leaf is a
    # [1, ...] shard that gets raveled before bucketing.
    shapes = [(int(np.prod(l.shape[1:])) if l.ndim > 1 else 1,)
              for l in locals_]
    dtypes = [l.dtype for l in locals_]
    plan = rt.plan_cache.get(shapes, dtypes, rt.fusion_threshold())
    gs = [_make_global(rt, l) for l in locals_]
    fn = _compiled(_mesh_key(rt), "grouped_allreduce", op=int(op),
                   pre=float(prescale_factor), post=float(postscale_factor),
                   plan=plan, n_leaves=len(gs))
    outs = fn(*gs)
    res = [_to_local(rt, o) for o in outs]
    _rec("GROUPED_ALLREDUCE", int(sum(l.nbytes for l in locals_)), t0)
    _tl(rt, name, "GROUPED_ALLREDUCE", int(sum(l.nbytes for l in locals_)),
        t0)
    return [r if h else r[0] for r, h in zip(res, had)]


def allgather(tensor: TensorLike, name: Optional[str] = None) -> Array:
    """Concatenate every chip's tensor along axis 0 (reference:
    collective_operations.h:133-204).  Input is per-chip
    ``[local_size, rows, ...]``; output is ``[size*rows, ...]``.  For ragged
    first dims use :func:`allgather_ragged`."""
    rt = _rt.get()
    t0 = time.perf_counter()
    local, had = _per_chip(rt, tensor)
    g = _make_global(rt, local)
    fn = _compiled(_mesh_key(rt), "allgather")
    out = fn(g)  # replicated full concat [size, rows, ...]
    _rec("ALLGATHER", int(local.nbytes), t0)
    _tl(rt, name, "ALLGATHER", int(local.nbytes), t0)
    out = jnp.reshape(out, (-1,) + out.shape[2:])
    return out


def allgather_ragged(tensors: Sequence[TensorLike],
                     name: Optional[str] = None) -> Array:
    """Allgather with per-chip different first dims — the reference supports
    ragged allgather natively via per-rank size negotiation (reference:
    controller.cc:580-650 tensor sizes in Response).  Implemented by padding
    to the max first-dim, gathering, then slicing on the host."""
    rt = _rt.get()
    ls = rt.local_size()
    if len(tensors) != ls:
        raise ValueError(f"expected {ls} per-chip tensors, got {len(tensors)}")
    arrs = [jnp.asarray(t) for t in tensors]
    rows = [int(a.shape[0]) for a in arrs]
    # Host-side size exchange across processes (the negotiation analog).
    # process_allgather is process-major; collectives number chips by mesh
    # position, so re-index via the process->chip-position map.
    if rt.process_size() > 1:
        per_proc = np.asarray(process_allgather(
            np.array(rows, np.int64))).reshape(rt.process_size(), ls)
        all_rows = [0] * rt.size()
        for p, positions in enumerate(rt.chip_positions_by_process()):
            for j, pos in enumerate(positions):
                all_rows[pos] = int(per_proc[p, j])
    else:
        all_rows = rows
    max_rows = int(max(all_rows))
    padded = jnp.stack([
        jnp.pad(a, [(0, max_rows - a.shape[0])] + [(0, 0)] * (a.ndim - 1))
        for a in arrs])
    g = allgather(padded)  # [size*max_rows, ...] after reshape inside
    g = jnp.reshape(g, (len(all_rows), max_rows) + g.shape[1:])
    pieces = [g[i, :r] for i, r in enumerate(all_rows)]
    return jnp.concatenate(pieces, axis=0)


def broadcast(tensor: TensorLike, root_rank: int = 0,
              name: Optional[str] = None) -> Array:
    """Broadcast the value held by chip ``root_rank`` to all chips
    (reference: operations.cc:1096-1134)."""
    rt = _rt.get()
    t0 = time.perf_counter()
    local, had = _per_chip(rt, tensor)
    g = _make_global(rt, local)
    fn = _compiled(_mesh_key(rt), "broadcast", root=int(root_rank))
    out = fn(g)
    _rec("BROADCAST", int(local.nbytes), t0)
    _tl(rt, name, "BROADCAST", int(local.nbytes), t0)
    res = _to_local(rt, out)
    return res if had else res[0]


def alltoall(tensor: TensorLike,
             splits: Optional[TensorLike] = None,
             name: Optional[str] = None) -> Tuple[Array, Array]:
    """All-to-all with optional uneven splits; returns (output, recv_splits)
    like the reference (reference: operations.cc:1136-1198, torch/mpi_ops.py:
    759-841).  Per-chip input ``[local_size, rows, ...]``; ``splits`` is
    ``[local_size, size]`` (rows sent to each destination chip)."""
    rt = _rt.get()
    t0 = time.perf_counter()
    n = rt.size()
    local, had = _per_chip(rt, tensor)
    if splits is None:
        rows = local.shape[1]
        if rows % n != 0:
            raise ValueError(
                f"alltoall without splits requires rows ({rows}) divisible "
                f"by size ({n})")
        g = _make_global(rt, local)
        fn = _compiled(_mesh_key(rt), "alltoall")
        out = _to_local(rt, fn(g))
        _rec("ALLTOALL", int(local.nbytes), t0)
        _tl(rt, name, "ALLTOALL", int(local.nbytes), t0)
        recv = jnp.full((rt.local_size(), n), rows // n, jnp.int32)
        if not had:
            return out[0], recv[0]
        return out, recv

    # Uneven splits: pad each destination block to the global max block,
    # run the dense equal-split all_to_all, reassemble with recv splits.
    sp = np.asarray(splits, np.int64)
    if sp.ndim == 1:
        sp = np.broadcast_to(sp[None], (rt.local_size(), n)).copy()
    if rt.process_size() > 1:
        per_proc = np.asarray(process_allgather(sp)).reshape(
            rt.process_size(), rt.local_size(), n)
        all_sp = np.zeros((n, n), np.int64)  # [src_chip_pos, dst_chip_pos]
        for p, positions in enumerate(rt.chip_positions_by_process()):
            for j, pos in enumerate(positions):
                all_sp[pos] = per_proc[p, j]
    else:
        all_sp = sp  # [size, size]: all_sp[src, dst]
    max_blk = int(all_sp.max())
    ls = rt.local_size()
    pads = []
    for i in range(ls):
        off = 0
        blocks = []
        for d in range(n):
            c = int(sp[i, d])
            blk = local[i, off:off + c]
            blk = jnp.pad(blk, [(0, max_blk - c)] + [(0, 0)] * (blk.ndim - 1))
            blocks.append(blk)
            off += c
        pads.append(jnp.concatenate(blocks, axis=0))
    padded = jnp.stack(pads)  # [ls, n*max_blk, ...]
    g = _make_global(rt, padded)
    fn = _compiled(_mesh_key(rt), "alltoall")
    out = _to_local(rt, fn(g))  # [ls, n*max_blk, ...]
    _rec("ALLTOALL", int(local.nbytes), t0)
    _tl(rt, name, "ALLTOALL", int(local.nbytes), t0)
    # recv_splits[i, src] = all_sp[src, mesh position of local chip i]
    local_pos = rt.local_chip_positions()
    recv_np = np.stack([all_sp[:, local_pos[i]] for i in range(ls)])
    outs = []
    for i in range(ls):
        blocks = [out[i, s * max_blk: s * max_blk + int(recv_np[i, s])]
                  for s in range(n)]
        outs.append(jnp.concatenate(blocks, axis=0))
    if not had:
        return outs[0], jnp.asarray(recv_np[0], jnp.int32)
    # Ragged per-chip outputs can differ in rows; return list if ragged.
    rows_per = {int(r.sum()) for r in recv_np}
    if len(rows_per) == 1:
        return jnp.stack(outs), jnp.asarray(recv_np, jnp.int32)
    return outs, jnp.asarray(recv_np, jnp.int32)  # type: ignore


def reducescatter(tensor: TensorLike, op: ReduceOp = Average,
                  name: Optional[str] = None) -> Array:
    """Reduce across chips and scatter shards: chip i gets rows
    ``[i*rows/n : (i+1)*rows/n]`` of the reduction."""
    rt = _rt.get()
    t0 = time.perf_counter()
    local, had = _per_chip(rt, tensor)
    g = _make_global(rt, local)
    fn = _compiled(_mesh_key(rt), "reducescatter", op=int(op))
    out = _to_local(rt, fn(g))
    _rec("REDUCESCATTER", int(local.nbytes), t0)
    _tl(rt, name, "REDUCESCATTER", int(local.nbytes), t0)
    return out


def barrier() -> None:
    """Block until all processes/chips reach the barrier (reference:
    MPIController::Barrier, mpi_controller.cc:227)."""
    rt = _rt.get()
    t0 = time.perf_counter()
    g = _make_global(rt, jnp.zeros((rt.local_size(), 1), jnp.int32))
    fn = _compiled(_mesh_key(rt), "barrier")
    jax.block_until_ready(fn(g))
    _rec("BARRIER", 0, t0)
    _tl(rt, None, "BARRIER", 0, t0)


def process_allgather(x: np.ndarray) -> np.ndarray:
    """Host-side gather of a small numpy array from every process — used for
    size negotiation of ragged collectives (the reference exchanges sizes in
    the controller: mpi_controller.cc per-rank split exchange)."""
    rt = _rt.get()
    if rt.process_size() == 1:
        return np.asarray(x)[None]
    from jax.experimental import multihost_utils
    return np.asarray(multihost_utils.process_allgather(np.asarray(x)))


# ------------------------------------------------------------------ async API
class Handle:
    """An in-flight collective (reference: handle_manager.{h,cc}).  JAX
    dispatch is asynchronous, so the value is already on its way; the handle
    exposes poll/synchronize semantics."""

    __slots__ = ("_value",)

    def __init__(self, value):
        self._value = value

    def poll(self) -> bool:
        try:
            ready = jax.tree_util.tree_leaves(jax.tree_util.tree_map(
                lambda a: a.is_ready() if hasattr(a, "is_ready") else True,
                self._value))
            return all(ready)
        except Exception:
            return True

    def wait(self):
        return jax.block_until_ready(self._value)


def allreduce_async(tensor: TensorLike, average: Optional[bool] = None,
                    name: Optional[str] = None,
                    op: ReduceOp = Average) -> Handle:
    return Handle(allreduce(tensor, average=average, name=name, op=op))


def allgather_async(tensor: TensorLike, name: Optional[str] = None) -> Handle:
    return Handle(allgather(tensor, name=name))


def broadcast_async(tensor: TensorLike, root_rank: int = 0,
                    name: Optional[str] = None) -> Handle:
    return Handle(broadcast(tensor, root_rank=root_rank, name=name))


def synchronize(handle: Handle):
    """Wait for an async op (reference: torch/mpi_ops.py:843-881)."""
    return handle.wait()


def poll(handle: Handle) -> bool:
    return handle.poll()
