"""Per-rank worker for the overlap-plane integration test.

Launched by hvdrun with -np 2 on localhost (4 virtual CPU chips each, the
8-chip cross-process mesh): the microbatch-pipelined gradient sync
(ops/overlap.py) must CONVERGE on the quadratic toy with the overlapped
schedule — its per-microbatch syncs ride real cross-process XLA
collectives here, not the single-process loopback of the unit tier — and
land bit-identical parameters on every chip of every process.
"""

import sys

import _env_setup  # noqa: F401  (must run before other jax imports)

import numpy as np  # noqa: E402

import horovod_tpu as hvd  # noqa: E402


def main() -> int:
    hvd.init()
    assert hvd.process_size() == 2, hvd.process_size()
    n = hvd.size()
    assert n == 8, n

    import jax  # noqa: E402
    import jax.numpy as jnp  # noqa: E402
    import optax  # noqa: E402
    from jax.sharding import NamedSharding, PartitionSpec as P

    from jax import shard_map
    from horovod_tpu.ops.overlap import _OverlapState
    from horovod_tpu.optimizer import distributed_optimizer

    mesh = hvd.mesh()
    d, k, lr, cycles = 16, 2, 0.1, 120
    rng = np.random.RandomState(0)
    target = rng.randn(d).astype(np.float32)
    # per-chip zero-mean noise: the mean gradient is exact, each rank's
    # is not — the regime where a sync that dropped a microbatch would
    # visibly stall convergence.
    noise = rng.randn(n, k, d).astype(np.float32) * 5.0
    noise -= noise.mean(axis=0, keepdims=True)

    opt = distributed_optimizer(optax.sgd(lr), axis_name="hvd",
                                backward_passes_per_step=k,
                                overlap=True, overlap_depth=1)

    def body(w, z):
        state = opt.init(w)
        assert isinstance(state, _OverlapState)

        def cycle(carry, _):
            w, state = carry
            for mb in range(k):
                g = (w - jnp.asarray(target)) + z[0, mb]
                u, state = opt.update(g, state, w)
                w = optax.apply_updates(w, u)
            return (w, state), jnp.float32(0)

        (w, _), _ = jax.lax.scan(cycle, (w, state), None, length=cycles)
        return w[None]  # (1, d) per chip -> (n, d) global

    f = jax.jit(shard_map(body, mesh=mesh, in_specs=(P(), P("hvd")),
                          out_specs=P("hvd"), check_vma=False))
    z_global = jax.make_array_from_process_local_data(
        NamedSharding(mesh, P("hvd")),
        noise[[p for p in range(n)
               if hvd.mesh().devices.flatten()[p].process_index
               == hvd.process_rank()]])
    out = jax.block_until_ready(f(jnp.zeros(d), z_global))

    # every local chip converged to the target, identically
    rows = [np.asarray(s.data)[0] for s in out.addressable_shards]
    for r in rows:
        assert np.abs(r - target).max() < 1e-3, np.abs(r - target).max()
        np.testing.assert_array_equal(r, rows[0])

    # the overlap gauges moved on this process
    fams = hvd.metrics_snapshot()["families"]
    fracs = {s["labels"].get("plane"): s["value"]
             for s in fams["hvd_overlap_overlapped_fraction"]["samples"]}
    assert 0.0 < fracs.get("microbatch", 0.0) <= 1.0, fracs

    print(f"OVERLAP-OK process {hvd.process_rank()}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
