"""Adasum numerical tests.

The reference checks the Adasum combine formula against a Python model
(reference: test/parallel/test_adasum_pytorch.py, test_adasum_tensorflow.py).
We replicate: a numpy recursive-halving model vs the on-mesh ppermute
implementation."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from horovod_tpu.parallel.adasum import adasum_allreduce


def _data_mesh():
    """The legacy single-axis data mesh these tests' shard_maps hardcode
    ("hvd") — built directly from the devices, independent of the
    runtime's resolved training mesh, so the CI layout knob dimension
    (HOROVOD_LAYOUT=auto; docs/parallelism.md) keeps this suite green."""
    import jax
    import numpy as _np
    from jax.sharding import Mesh as _Mesh
    return _Mesh(_np.array(jax.devices()), ("hvd",))


def _adasum_pair_np(a, b):
    dot = float(np.sum(a * b))
    na = float(np.sum(a * a))
    nb = float(np.sum(b * b))
    ca = 1.0 - dot / (2 * na) if na > 0 else 1.0
    cb = 1.0 - dot / (2 * nb) if nb > 0 else 1.0
    return ca * a + cb * b


def _adasum_np(vectors):
    vs = [v.astype(np.float64) for v in vectors]
    n = len(vs)
    k = 1
    while k < n:
        out = list(vs)
        for i in range(n):
            out[i] = _adasum_pair_np(vs[i], vs[i ^ k])
        vs = out
        k *= 2
    return vs[0]


def test_adasum_matches_numpy_model(hvd):
    mesh = _data_mesh()
    n = hvd.size()
    rng = np.random.RandomState(0)
    xs = rng.randn(n, 16).astype(np.float32)

    f = jax.jit(shard_map(lambda x: adasum_allreduce(x, "hvd"), mesh=mesh,
                          in_specs=(P("hvd"),), out_specs=P("hvd")))
    out = np.asarray(f(jnp.asarray(xs)))
    expected = _adasum_np([xs[i] for i in range(n)])
    for i in range(n):
        np.testing.assert_allclose(out[i], expected, rtol=1e-4)


def test_adasum_identical_vectors_sum_like_average(hvd):
    """Adasum of n identical vectors v yields v (scale-invariance property:
    parallel gradients are averaged; reference adasum.h docstring)."""
    mesh = _data_mesh()
    n = hvd.size()
    v = np.random.RandomState(1).randn(8).astype(np.float32)
    xs = np.broadcast_to(v, (n, 8)).copy()
    f = jax.jit(shard_map(lambda x: adasum_allreduce(x, "hvd"), mesh=mesh,
                          in_specs=(P("hvd"),), out_specs=P("hvd")))
    out = np.asarray(f(jnp.asarray(xs)))
    np.testing.assert_allclose(out[0], v, rtol=1e-4)


def test_adasum_orthogonal_vectors_sum(hvd):
    """Orthogonal gradients add (the other end of the Adasum interpolation)."""
    mesh = _data_mesh()
    n = hvd.size()
    xs = np.zeros((n, n), np.float32)
    for i in range(n):
        xs[i, i] = 1.0
    f = jax.jit(shard_map(lambda x: adasum_allreduce(x, "hvd"), mesh=mesh,
                          in_specs=(P("hvd"),), out_specs=P("hvd")))
    out = np.asarray(f(jnp.asarray(xs)))
    np.testing.assert_allclose(out[0], np.ones(n), rtol=1e-4)


def test_eager_adasum_reduce_op(hvd):
    """ReduceOp.ADASUM through the eager allreduce API
    (reference: hvd.Adasum, operations.cc:911-913)."""
    n = hvd.local_size()
    xs = np.random.RandomState(2).randn(n, 8).astype(np.float32)
    out = np.asarray(hvd.allreduce(xs, op=hvd.Adasum))
    expected = _adasum_np([xs[i] for i in range(n)])
    np.testing.assert_allclose(out[0], expected, rtol=1e-4)
