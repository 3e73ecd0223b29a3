"""Quantized ring allreduce (ops/quantized.py; technique: EQuARX,
PAPERS.md): int8 wire, fp32 accumulation, ring hop structure."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from horovod_tpu.ops.quantized import quantized_ring_allreduce


def _data_mesh():
    """The legacy single-axis data mesh these tests' shard_maps hardcode
    ("hvd") — built directly from the devices, independent of the
    runtime's resolved training mesh, so the CI layout knob dimension
    (HOROVOD_LAYOUT=auto; docs/parallelism.md) keeps this suite green."""
    import jax
    import numpy as _np
    from jax.sharding import Mesh as _Mesh
    return _Mesh(_np.array(jax.devices()), ("hvd",))


def _run(x_per_rank, mesh, average=True):
    f = shard_map(
        functools.partial(quantized_ring_allreduce, axis_name="hvd",
                          average=average),
        mesh=mesh, in_specs=P("hvd"), out_specs=P("hvd"),
        check_vma=False)
    return np.asarray(jax.jit(f)(x_per_rank))


def test_quantized_allreduce_matches_mean(hvd):
    mesh = _data_mesh()
    n = hvd.size()
    rng = np.random.RandomState(0)
    # per-rank values; stacked on axis 0 -> one row per chip
    x = jnp.asarray(rng.randn(n, 5, 37).astype(np.float32))
    out = _run(x, mesh)
    exact = np.asarray(x).mean(axis=0)
    got = out.reshape(n, 5, 37)
    # every rank holds the same (approximate) mean
    for r in range(1, n):
        np.testing.assert_allclose(got[r], got[0], rtol=0, atol=1e-6)
    # quantization error: bounded, small relative to the signal
    err = np.abs(got[0] - exact).max()
    assert err < 0.05, err  # ~2(N-1) int8 hops of unit-scale data
    assert np.corrcoef(got[0].ravel(), exact.ravel())[0, 1] > 0.999


def test_quantized_allreduce_sum_and_dtype(hvd):
    mesh = _data_mesh()
    n = hvd.size()
    x = jnp.ones((n, 16), jnp.bfloat16)
    out = _run(x, mesh, average=False)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.full((n, 16), n, np.float32))


def test_quantized_allreduce_sum_error_bound(hvd):
    """Requantization noise grows linearly in N (module docstring): the
    summed result must stay within a few percent of the exact sum's
    scale — the EQuARX operating regime for gradient reduction."""
    mesh = _data_mesh()
    n = hvd.size()
    rng = np.random.RandomState(3)
    x = jnp.asarray(
        rng.randint(-127, 128, (n, 64)).astype(np.float32))
    out = _run(x, mesh, average=False)
    exact = np.asarray(x).sum(axis=0)
    got = out.reshape(n, 64)
    scale = np.abs(exact).max()
    assert np.abs(got[0] - exact).max() < 0.05 * scale
    assert np.corrcoef(got[0], exact)[0, 1] > 0.999


def test_quantized_allreduce_ragged_sizes(hvd):
    """Payload not divisible by the ring size exercises the padding."""
    mesh = _data_mesh()
    n = hvd.size()
    x = jnp.asarray(np.random.RandomState(5).randn(n, 13), np.float32)
    out = _run(x, mesh)
    exact = np.asarray(x).mean(axis=0)
    assert np.abs(out.reshape(n, 13)[0] - exact).max() < 0.05


def test_distributed_optimizer_quantized_wire_trains(hvd):
    """End-to-end: a DP step whose gradient sync rides the int8 ring
    converges like the exact-psum step (loss drop + near-identical
    weights after a few steps)."""
    import optax

    import horovod_tpu as h

    mesh = _data_mesh()
    rng = np.random.RandomState(0)
    W = jnp.asarray(rng.randn(12, 3), jnp.float32)
    X = jnp.asarray(rng.randn(64, 12), jnp.float32)
    Y = jnp.asarray(rng.randn(64, 3), jnp.float32)

    def loss_fn(w, x, y):
        return jnp.mean((x @ w - y) ** 2)

    def make_step(quantized):
        opt = h.DistributedOptimizer(optax.sgd(0.05), axis_name="hvd",
                                     quantized_wire=quantized)

        def body(w, s, x, y):
            g = jax.grad(loss_fn)(w, x, y)
            u, s = opt.update(g, s, w)
            return optax.apply_updates(w, u), s
        f = shard_map(body, mesh=mesh,
                      in_specs=(P(), P(), P("hvd"), P("hvd")),
                      out_specs=(P(), P()), check_vma=False)
        return jax.jit(f), opt

    outs = {}
    for quantized in (False, True):
        step, opt = make_step(quantized)
        w, s = W, opt.init(W)
        for _ in range(5):
            w, s = step(w, s, X, Y)
        outs[quantized] = np.asarray(w)
    l0 = float(loss_fn(W, X, Y))
    lq = float(loss_fn(jnp.asarray(outs[True]), X, Y))
    assert lq < l0  # trains
    # int8 noise keeps it near the exact trajectory
    np.testing.assert_allclose(outs[True], outs[False], atol=5e-3)


def test_quantized_wire_rejects_min_max(hvd):
    import optax

    import horovod_tpu as h
    with pytest.raises(ValueError, match="Average/Sum"):
        opt = h.DistributedOptimizer(optax.sgd(0.1), axis_name="hvd",
                                     op=h.Min, quantized_wire=True)
        mesh = _data_mesh()
        f = shard_map(
            lambda w: opt.update({"w": w}, opt.init({"w": w}))[0]["w"],
            mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False)
        jax.jit(f)(jnp.ones((8,)))


def test_quantized_allreduce_two_level_axes(hvd, monkeypatch):
    """Tuple axes ring PER AXIS (big ring on ICI, small on DCN) and the
    result equals the global mean within quantization noise."""
    import horovod_tpu as h
    # Claims the mesh with an explicit spec — incompatible with the CI
    # layout knob dim (docs/parallelism.md#knobs); clear for the duration.
    for k in ("HOROVOD_LAYOUT", "HOROVOD_TP", "HOROVOD_PP"):
        monkeypatch.delenv(k, raising=False)
    h.shutdown()
    h.init(mesh_spec="dcn.d=2,ici.d=4")
    try:
        mesh = h.mesh()
        rng = np.random.RandomState(1)
        x = jnp.asarray(rng.randn(8, 21), jnp.float32)
        f = shard_map(
            functools.partial(quantized_ring_allreduce,
                              axis_name=("dcn.d", "ici.d")),
            mesh=mesh, in_specs=P(("dcn.d", "ici.d")),
            out_specs=P(("dcn.d", "ici.d")), check_vma=False)
        out = np.asarray(jax.jit(f)(x)).reshape(8, 21)
        exact = np.asarray(x).mean(axis=0)
        assert np.abs(out[0] - exact).max() < 0.05
        for r in range(1, 8):
            np.testing.assert_allclose(out[r], out[0], atol=1e-6)
    finally:
        h.shutdown()
        monkeypatch.undo()
        h.init()


def test_quantized_wire_with_compression_resolves_to_int8(hvd):
    """quantized_wire + compression used to be a hard ValueError; the
    wire-policy plane replaced that with a resolution order (wire_policy >
    quantized_wire > compression, ops/wire.py) — the combo now runs and
    the int8 ring wins, matching a pure quantized_wire sync exactly."""
    from horovod_tpu.ops.compression import Compression
    from horovod_tpu.optimizer import sync_gradients
    mesh = _data_mesh()
    n = hvd.size()
    g = jnp.asarray(np.random.RandomState(11).randn(n, 48), jnp.float32)

    def run(**kw):
        f = shard_map(lambda x: sync_gradients(x, "hvd", **kw),
                      mesh=mesh, in_specs=P("hvd"), out_specs=P("hvd"),
                      check_vma=False)
        return np.asarray(jax.jit(f)(g))

    combo = run(compression=Compression.bf16, quantized_wire=True)
    pure = run(quantized_wire=True)
    np.testing.assert_array_equal(combo, pure)
    # and an explicit wire_policy beats both deprecated aliases
    explicit = run(compression=Compression.bf16, quantized_wire=True,
                   wire_policy="none")
    np.testing.assert_allclose(explicit[0], np.asarray(g).mean(axis=0),
                               rtol=1e-5)
