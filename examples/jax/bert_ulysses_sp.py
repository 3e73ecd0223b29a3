"""BERT masked-LM pretraining with Ulysses sequence parallelism
(BASELINE.json config 4: "BERT-Large TF2 with tensor-fusion autotune +
hvd.alltoall for seq-parallel", rebuilt TPU-native).

The sequence axis is sharded across the mesh: every chip holds an
``S/n`` slice of each example, embeds its GLOBAL positions (offset by
``axis_index``), and attention trades sequence shards for head shards
through ``all_to_all`` (parallel/sequence.py ulysses_attention — the
reference's hvd.alltoall seq-parallel recipe).  Gradients allreduce over
the same axis.  This is how 8k+ token documents train on chips whose HBM
cannot hold full-sequence activations.

    python examples/jax/bert_ulysses_sp.py --cpu
"""

import argparse
import os


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=80)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=512,
                    help="GLOBAL sequence length (sharded n ways)")
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--mask-rate", type=float, default=0.15)
    ap.add_argument("--cpu", action="store_true",
                    help="8 virtual CPU chips (smoke mode)")
    args = ap.parse_args()
    if args.cpu:
        from horovod_tpu.utils.platform import force_cpu
        force_cpu(virtual_chips=8)  # binds jax config; env var alone loses

    import numpy as np
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import PartitionSpec as P

    import horovod_tpu as hvd
    from horovod_tpu.models import bert
    from jax import shard_map
    from horovod_tpu.parallel.sequence import ulysses_attention

    hvd.init()
    mesh = hvd.mesh()
    axis = mesh.axis_names[0]
    n = hvd.size()

    if args.cpu:
        import dataclasses
        # 8 heads so the head axis divides the 8-chip smoke mesh
        cfg = dataclasses.replace(bert.CONFIGS["tiny"], n_heads=8)
    else:
        cfg = bert.CONFIGS["base"]
    seq = min(args.seq, cfg.max_seq)
    assert seq % n == 0 and cfg.n_heads % n == 0, (seq, cfg.n_heads, n)
    shard = seq // n

    params = jax.device_get(bert.init(jax.random.PRNGKey(0), cfg))
    opt = optax.adam(args.lr)

    # Synthetic MLM stream with learnable structure: token at i+1 repeats
    # token at i, so masked positions are predictable from neighbors —
    # which requires attention ACROSS sequence shards to learn.
    rng = np.random.RandomState(0)
    MASK_ID = 0

    def make_batch():
        base = rng.randint(1, cfg.vocab, (args.batch, seq // 2))
        ids = np.repeat(base, 2, axis=1)[:, :seq]
        labels = ids.copy()
        mask = rng.rand(args.batch, seq) < args.mask_rate
        ids = np.where(mask, MASK_ID, ids)
        return (jnp.asarray(ids, jnp.int32),
                jnp.asarray(labels, jnp.int32),
                jnp.asarray(mask, jnp.float32))

    attn = lambda q, k, v: ulysses_attention(q, k, v, axis_name=axis,
                                             causal=False)

    def shard_loss(p, ids, labels, mask):
        # GLOBAL positions for this chip's sequence slice
        idx = jax.lax.axis_index(axis)
        positions = idx * shard + jnp.arange(shard)
        logits = bert.apply(p, ids, cfg, attn_fn=attn, positions=positions)
        from horovod_tpu.models import layers as L
        nll = L.softmax_cross_entropy(logits, labels)
        # masked-position mean over the GLOBAL sequence: psum num and den
        num = jax.lax.psum(jnp.sum(nll * mask), axis)
        den = jax.lax.psum(jnp.sum(mask), axis)
        return num / jnp.maximum(den, 1.0)

    @jax.jit
    def step(p, s, ids, labels, mask):
        def body(p, s, ids, labels, mask):
            loss, g = jax.value_and_grad(shard_loss)(p, ids, labels, mask)
            # the allreduce of the reference, over the same axis the
            # alltoall rides.  PSUM, not pmean: shard_loss is already the
            # global masked mean, so each chip's grad holds only its own
            # sequence-shard's contribution — summing completes it.
            g = jax.lax.psum(g, axis)
            up, s = opt.update(g, s, p)
            return optax.apply_updates(p, up), s, loss[None]
        return shard_map(
            body, mesh=mesh,
            in_specs=(P(), P(), P(None, axis), P(None, axis),
                      P(None, axis)),
            out_specs=(P(), P(), P(axis)), check_vma=False,
        )(p, s, ids, labels, mask)

    state = opt.init(params)
    first = last = None
    for i in range(args.steps):
        ids, labels, mask = make_batch()
        params, state, loss = step(params, state, ids, labels, mask)
        last = float(np.asarray(loss)[0])
        if first is None:
            first = last
        if hvd.rank() == 0 and i % 10 == 0:
            print(f"step {i:3d}  mlm loss {last:.4f}")

    if hvd.rank() == 0:
        print(f"seq {seq} over {n} chips ({shard}/chip); "
              f"loss {first:.4f} -> {last:.4f}")
        assert last < first * 0.95, "MLM loss did not drop"
        print("OK")


if __name__ == "__main__":
    main()
