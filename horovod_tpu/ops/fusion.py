"""Tensor fusion: bucket plans + the bucket-plan cache.

The reference packs many small tensors into one persistent 128 MiB fusion
buffer per (device, framework, stream) and runs a single collective over it
(reference: fusion_buffer_manager.{h,cc}, controller.cc:778-915 FuseResponses,
knob HOROVOD_FUSION_THRESHOLD set at operations.cc:448).  On TPU the buffer
itself is unnecessary — XLA keeps the concatenated bucket in HBM and
`donate_argnums` aliases it in place — but the *planning* survives: grouping
gradients into few large same-dtype buckets turns hundreds of tiny `psum`s
into a handful of big ones that saturate ICI.

The reference's response cache memoizes negotiated responses so repeat
iterations skip coordination (reference: response_cache.h:44-100).  Its TPU
analog is the `BucketPlanCache` below: plans are keyed by the exact
(shapes, dtypes, threshold) signature of the step, so steady-state training
hits the cache every step.

All packing/unpacking code is jit-traceable (static shapes only).
"""

from __future__ import annotations

import collections
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..utils import metrics as _metrics


class Bucket:
    """One fused collective: a list of leaf indices sharing a dtype."""

    __slots__ = ("dtype", "indices", "sizes", "shapes", "nbytes")

    def __init__(self, dtype):
        self.dtype = dtype
        self.indices: List[int] = []
        self.sizes: List[int] = []
        self.shapes: List[Tuple[int, ...]] = []
        self.nbytes = 0

    def add(self, idx: int, shape: Tuple[int, ...], nbytes: int) -> None:
        self.indices.append(idx)
        self.shapes.append(tuple(shape))
        self.sizes.append(int(np.prod(shape)) if shape else 1)
        self.nbytes += nbytes


class BucketPlan:
    """A fusion plan for a flat list of tensors.

    Hashable *by value* so jit caches keyed on a plan don't recompile when
    an identical plan object is rebuilt (e.g. with the plan cache disabled).
    """

    def __init__(self, buckets: List[Bucket], num_leaves: int):
        self.buckets = buckets
        self.num_leaves = num_leaves
        self._sig = (num_leaves, tuple(
            (str(b.dtype), tuple(b.indices), tuple(b.shapes))
            for b in buckets))

    @property
    def num_buckets(self) -> int:
        return len(self.buckets)

    def __hash__(self) -> int:
        return hash(self._sig)

    def __eq__(self, other: object) -> bool:
        return isinstance(other, BucketPlan) and self._sig == other._sig


def make_plan(shapes: Sequence[Tuple[int, ...]],
              dtypes: Sequence[Any],
              threshold_bytes: int) -> BucketPlan:
    """Greedy same-dtype bucketing up to ``threshold_bytes`` per bucket.

    Mirrors FuseResponses' greedy fill with the dtype look-ahead (the
    reference skips mixed-dtype fusion; reference: controller.cc:778-915):
    tensors are taken in submission order, opened buckets are per-dtype, and
    a bucket closes when adding the next same-dtype tensor would exceed the
    threshold.  A tensor larger than the threshold gets its own bucket.
    """
    open_buckets: Dict[Any, Bucket] = {}
    done: List[Bucket] = []

    def close(b: Bucket, reason: str) -> None:
        done.append(b)
        _metrics.FUSION_FLUSHES.inc(reason=reason)
        _metrics.FUSION_BUCKET_BYTES.observe(b.nbytes)

    for i, (shape, dtype) in enumerate(zip(shapes, dtypes)):
        dt = jnp.dtype(dtype)
        nbytes = int(np.prod(shape)) * dt.itemsize if shape else dt.itemsize
        b = open_buckets.get(dt)
        if b is not None and b.nbytes + nbytes > threshold_bytes and b.indices:
            close(b, "threshold")  # next tensor would overflow the bucket
            b = None
        if b is None:
            b = Bucket(dt)
            open_buckets[dt] = b
        b.add(i, shape, nbytes)
        if b.nbytes >= threshold_bytes:
            close(b, "filled")
            del open_buckets[dt]
    for b in open_buckets.values():
        if b.indices:
            close(b, "tail")  # end-of-step leftover
    return BucketPlan(done, len(shapes))


class BucketPlanCache:
    """LRU cache of bucket plans (the response-cache analog).

    Capacity semantics follow HOROVOD_CACHE_CAPACITY (reference:
    global_state.h:89, default 1024); capacity 0 disables caching.
    """

    def __init__(self, capacity: int = 1024):
        self.capacity = capacity
        self._cache: "collections.OrderedDict[Any, BucketPlan]" = \
            collections.OrderedDict()
        self.hits = 0
        self.misses = 0

    def get(self,
            shapes: Sequence[Tuple[int, ...]],
            dtypes: Sequence[Any],
            threshold_bytes: int) -> BucketPlan:
        key = (tuple(map(tuple, shapes)),
               tuple(str(jnp.dtype(d)) for d in dtypes),
               int(threshold_bytes))
        if self.capacity > 0 and key in self._cache:
            self._cache.move_to_end(key)
            self.hits += 1
            return self._cache[key]
        self.misses += 1
        plan = make_plan(shapes, dtypes, threshold_bytes)
        if self.capacity > 0:
            self._cache[key] = plan
            while len(self._cache) > self.capacity:
                self._cache.popitem(last=False)
        return plan


# -------------------------------------------------------------- pack / unpack
def pack_bucket(leaves: Sequence[jax.Array], bucket: Bucket) -> jax.Array:
    """Concatenate the bucket's leaves into one flat 1-D buffer (jit-safe)."""
    parts = [jnp.ravel(leaves[i]) for i in bucket.indices]
    if len(parts) == 1:
        return parts[0]
    return jnp.concatenate(parts)


def pack_bucket_padded(leaves: Sequence[jax.Array], bucket: Bucket,
                       multiple: int) -> jax.Array:
    """:func:`pack_bucket` padded to a multiple of ``multiple`` — the
    shard-geometry form the ZeRO weight-update chain reduces/scatters
    (parallel/zero.py): a bucket split 1/n per chip needs a length
    divisible by the axis size, and the pad is static so XLA sees
    fixed-shape collectives."""
    flat = pack_bucket(leaves, bucket)
    total = flat.shape[0]
    padded = -(-total // max(multiple, 1)) * max(multiple, 1)
    if padded == total:
        return flat
    return jnp.pad(flat, (0, padded - total))


def unpack_bucket(buffer: jax.Array, bucket: Bucket,
                  out: List[Optional[jax.Array]]) -> None:
    """Split a fused buffer back into its leaves, writing into ``out``."""
    offset = 0
    for idx, size, shape in zip(bucket.indices, bucket.sizes, bucket.shapes):
        piece = buffer[offset:offset + size] if len(bucket.indices) > 1 \
            else buffer
        out[idx] = jnp.reshape(piece, shape)
        offset += size


def fused_apply(leaves: Sequence[jax.Array],
                plan: BucketPlan,
                fn) -> List[jax.Array]:
    """Apply ``fn`` (a collective) to each fused bucket and un-fuse.

    ``fn`` receives the flat 1-D bucket buffer and must return a same-shaped
    buffer (e.g. ``lambda b: lax.psum(b, axis)``).
    """
    return fused_apply_per_bucket(leaves, plan,
                                  [fn] * plan.num_buckets)


def fused_apply_per_bucket(leaves: Sequence[jax.Array],
                           plan: BucketPlan,
                           fns: Sequence) -> List[jax.Array]:
    """Like :func:`fused_apply` with one ``fn`` PER BUCKET — the
    wire-policy plane (ops/wire.py) reduces each bucket in its own wire
    format, so the collective differs bucket to bucket."""
    if len(fns) != plan.num_buckets:
        raise ValueError(f"{len(fns)} fns for {plan.num_buckets} buckets")
    out: List[Optional[jax.Array]] = [None] * plan.num_leaves
    for i, (bucket, fn) in enumerate(zip(plan.buckets, fns)):
        with jax.named_scope(f"bucket{i}"):
            buf = pack_bucket(leaves, bucket)
            buf = fn(buf)
            unpack_bucket(buf, bucket, out)
    return out  # type: ignore[return-value]
