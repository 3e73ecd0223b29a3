"""DistributedOptimizer / gradient-sync tests (reference analog:
test/parallel/test_torch.py optimizer coverage, gradient_aggregation tests)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd_mod
from horovod_tpu.optimizer import sync_gradients, distributed_optimizer
from horovod_tpu.ops.compression import Compression



def _data_mesh():
    """The legacy single-axis data mesh these tests' shard_maps hardcode
    ("hvd") — built directly from the devices, independent of the
    runtime's resolved training mesh, so the CI layout knob dimension
    (HOROVOD_LAYOUT=auto; docs/parallelism.md) keeps this suite green."""
    import jax
    import numpy as _np
    from jax.sharding import Mesh as _Mesh
    return _Mesh(_np.array(jax.devices()), ("hvd",))


def _shmap(fn, mesh, n_in, n_out=1):
    return shard_map(fn, mesh=mesh, in_specs=(P("hvd"),) * n_in,
                     out_specs=(P("hvd"),) * n_out if n_out > 1 else P("hvd"))


def test_sync_gradients_mean(hvd):
    mesh = _data_mesh()
    n = hvd.size()
    grads = {"w": np.random.RandomState(0).randn(n, 4).astype(np.float32),
             "b": np.random.RandomState(1).randn(n, 2).astype(np.float32)}

    def body(w, b):
        g = sync_gradients({"w": w, "b": b}, "hvd")
        return g["w"], g["b"]

    f = jax.jit(_shmap(body, mesh, 2, 2))
    w, b = f(grads["w"], grads["b"])
    np.testing.assert_allclose(np.asarray(w)[0], grads["w"].mean(axis=0),
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(b)[3], grads["b"].mean(axis=0),
                               rtol=1e-5)


def test_sync_gradients_fusion_matches_unfused(hvd):
    """Bucketed (fused) sync must be numerically identical to per-tensor."""
    mesh = _data_mesh()
    n = hvd.size()
    rng = np.random.RandomState(42)
    gs = [rng.randn(n, k + 1).astype(np.float32) for k in range(6)]

    def body_fused(*leaves):
        return tuple(sync_gradients(list(leaves), "hvd",
                                    fusion_threshold_bytes=64))

    def body_unfused(*leaves):
        return tuple(sync_gradients(list(leaves), "hvd",
                                    fusion_threshold_bytes=1))

    f1 = jax.jit(_shmap(body_fused, mesh, 6, 6))
    f2 = jax.jit(_shmap(body_unfused, mesh, 6, 6))
    for a, b in zip(f1(*gs), f2(*gs)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)


def test_sync_gradients_compression_fp16(hvd):
    mesh = _data_mesh()
    n = hvd.size()
    g = np.random.RandomState(3).randn(n, 32).astype(np.float32)

    def body(x):
        return sync_gradients(x, "hvd", compression=Compression.fp16)

    out = jax.jit(_shmap(body, mesh, 1))(g)
    assert out.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(out)[0], g.mean(axis=0), atol=2e-3)


def test_distributed_optimizer_end_to_end(hvd):
    """Data-parallel SGD: one step with per-chip different grads must equal
    single-worker SGD on the mean gradient."""
    mesh = _data_mesh()
    n = hvd.size()
    w0 = np.ones(4, np.float32)
    lr = 0.1
    opt = distributed_optimizer(optax.sgd(lr), axis_name="hvd")
    batches = np.random.RandomState(7).randn(n, 4).astype(np.float32)

    def loss(w, x):
        return jnp.sum((w - x) ** 2)

    def step(w, x):
        # w arrives replicated per chip ([1? no...]) — pass with P() spec
        g = jax.grad(loss)(w, x[0])
        state = opt.init(w)
        updates, _ = opt.update(g, state, w)
        return optax.apply_updates(w, updates)

    f = jax.jit(shard_map(step, mesh=mesh,
                          in_specs=(P(), P("hvd")), out_specs=P(),
                          check_vma=False))
    w1 = np.asarray(f(jnp.asarray(w0), jnp.asarray(batches)))
    mean_grad = np.mean([2 * (w0 - b) for b in batches], axis=0)
    np.testing.assert_allclose(w1, w0 - lr * mean_grad, rtol=1e-5)


def test_backward_passes_per_step(hvd):
    """Local aggregation (reference: gradient_aggregation.py): updates apply
    only every Nth micro-batch, using the averaged accumulated gradient."""
    mesh = _data_mesh()
    n = hvd.size()
    lr = 1.0
    opt = distributed_optimizer(optax.sgd(lr), axis_name="hvd",
                                backward_passes_per_step=2)
    w0 = jnp.zeros(3)
    g1 = np.random.RandomState(0).randn(n, 3).astype(np.float32)
    g2 = np.random.RandomState(1).randn(n, 3).astype(np.float32)

    def two_steps(w, a, b):
        state = opt.init(w)
        u1, state = opt.update(a[0], state, w)
        w = optax.apply_updates(w, u1)
        u2, state = opt.update(b[0], state, w)
        w = optax.apply_updates(w, u2)
        return w

    f = jax.jit(shard_map(two_steps, mesh=mesh,
                          in_specs=(P(), P("hvd"), P("hvd")),
                          out_specs=P(), check_vma=False))
    w = np.asarray(f(w0, jnp.asarray(g1), jnp.asarray(g2)))
    expected = -lr * (g1.mean(axis=0) + g2.mean(axis=0)) / 2.0
    np.testing.assert_allclose(w, expected, rtol=1e-5)


def test_distributed_grad(hvd):
    """DistributedGradientTape analog."""
    mesh = _data_mesh()
    n = hvd.size()
    xs = np.random.RandomState(5).randn(n, 4).astype(np.float32)

    def loss(w, x):
        return jnp.sum(w * x)

    def body(w, x):
        g = hvd_mod.distributed_grad(loss, axis_name="hvd")(w, x[0])
        return g

    f = jax.jit(shard_map(body, mesh=mesh, in_specs=(P(), P("hvd")),
                          out_specs=P(), check_vma=False))
    g = np.asarray(f(jnp.ones(4), jnp.asarray(xs)))
    np.testing.assert_allclose(g, xs.mean(axis=0), rtol=1e-5)
