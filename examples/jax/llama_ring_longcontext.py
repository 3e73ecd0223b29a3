"""Long-context llama training with flash-kernel ring attention.

The context is sharded across the mesh (SURVEY.md §5: long-context
first-class): each chip holds S/n tokens, RoPE gets the chip's global
position offset, and attention runs the ring — k/v blocks hop neighbor
to neighbor (`ppermute` over ICI) while every chip accumulates its
queries' attention blockwise.  ``kernel='flash'`` runs each hop through
the Pallas kernel with the ring-level custom VJP, so the full training
step (forward AND backward) never materializes an [S, S] score matrix
or an unsharded sequence.  Activation memory per chip stays flat as
context length scales with the mesh.

    python examples/jax/llama_ring_longcontext.py --cpu
    python examples/jax/llama_ring_longcontext.py --cpu --kernel xla
"""

import argparse
import os


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=40)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=512,
                    help="GLOBAL context length (sharded n ways)")
    ap.add_argument("--lr", type=float, default=1e-2)
    ap.add_argument("--kernel", default="flash", choices=["flash", "xla"],
                    help="per-ring-step block attention implementation")
    ap.add_argument("--cpu", action="store_true",
                    help="8 virtual CPU chips (smoke mode)")
    args = ap.parse_args()
    if args.cpu:
        from horovod_tpu.utils.platform import force_cpu
        force_cpu(virtual_chips=8)  # binds jax config; env var alone loses

    import dataclasses

    import numpy as np
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import PartitionSpec as P

    import horovod_tpu as hvd
    from horovod_tpu.models import llama
    from horovod_tpu.models import layers as L
    from jax import shard_map
    from horovod_tpu.parallel.sequence import make_ring_attn_fn

    hvd.init()
    mesh = hvd.mesh()
    axis = mesh.axis_names[0]
    n = hvd.size()

    if args.cpu:
        cfg = dataclasses.replace(llama.CONFIGS["tiny"], max_seq=512)
        args.seq = min(args.seq, 256)
    else:
        cfg = dataclasses.replace(llama.CONFIGS["mini"], max_seq=8192)
    seq = args.seq
    assert seq % n == 0, (seq, n)
    # apply_rope's dynamic_slice CLAMPS out-of-range offsets instead of
    # erroring — past max_seq, high-rank chips would silently reuse tail
    # positions
    assert seq <= cfg.max_seq, (seq, cfg.max_seq)
    shard = seq // n

    params = llama.init(jax.random.PRNGKey(0), cfg)
    opt = optax.adam(args.lr)
    attn = make_ring_attn_fn(axis_name=axis, causal=True,
                             kernel=args.kernel)

    # Synthetic LM stream with long-range structure: the second half of
    # every document REPEATS its first half, so predicting the echo
    # requires attending seq/2 tokens back — across shard boundaries.
    rng = np.random.RandomState(0)

    def make_batch():
        half = rng.randint(1, cfg.vocab, (args.batch, seq // 2 + 1))
        ids = np.concatenate([half, half], axis=1)[:, :seq + 1]
        inp, tgt = ids[:, :-1], ids[:, 1:]
        return jnp.asarray(inp, jnp.int32), jnp.asarray(tgt, jnp.int32)

    def shard_loss(p, inp, tgt):
        # per-chip forward on its slice, RoPE at the slice's global offset
        off = jax.lax.axis_index(axis) * shard
        h = llama.apply(p, inp, cfg, attn_fn=attn, return_hidden=True,
                        pos_offset=off)
        nll = L.softmax_cross_entropy(L.dense(p["lm_head"], h), tgt)
        # equal shard sizes: global token mean = psum(sum)/global count
        return jax.lax.psum(jnp.sum(nll), axis) / (args.batch * seq)

    @jax.jit
    def step(p, s, inp, tgt):
        def body(p, s, inp, tgt):
            loss, g = jax.value_and_grad(shard_loss)(p, inp, tgt)
            # psum: each chip's grad carries only its shard's terms
            g = jax.lax.psum(g, axis)
            up, s = opt.update(g, s, p)
            return optax.apply_updates(p, up), s, loss[None]
        return shard_map(
            body, mesh=mesh,
            in_specs=(P(), P(), P(None, axis), P(None, axis)),
            out_specs=(P(), P(), P(axis)), check_vma=False,
        )(p, s, inp, tgt)

    state = opt.init(params)
    first = last = None
    for i in range(args.steps):
        inp, tgt = make_batch()
        params, state, loss = step(params, state, inp, tgt)
        last = float(np.asarray(loss)[0])
        if first is None:
            first = last
        if hvd.rank() == 0 and i % 10 == 0:
            print(f"step {i:3d}  lm loss {last:.4f}")

    if hvd.rank() == 0:
        print(f"context {seq} over {n} chips ({shard}/chip, "
              f"{args.kernel} ring); loss {first:.4f} -> {last:.4f}")
        assert last < first * 0.95, "LM loss did not drop"
        print("OK")


if __name__ == "__main__":
    main()
