"""The third rehearsal (on-chip-measurement guide, section 2): compile the
cells' programs at their real sizes for a described v5e:2x2, with no chip.
Prints compile-time figures (memory_analysis), which are no measurements.

  JAX_PLATFORMS=cpu python3 perfbench/tools/rehearse_compile.py [tick|step1|step4] [cache_blocks]
"""
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402

from perfbench.lib import spec, weights  # noqa: E402


def report(name, compiled, t):
    m = compiled.memory_analysis()
    print(name, f"compile {time.time() - t:.0f}s",
          {k: round(getattr(m, k) / 1e9, 3) for k in (
              "argument_size_in_bytes", "output_size_in_bytes",
              "alias_size_in_bytes", "temp_size_in_bytes")}, flush=True)


def tick(topo, blocks):
    _, config, _ = spec.cell("serve-decode")
    e = dict(config["engine"], cache_blocks=blocks)
    llama, cfg = spec.family(config).program(config)
    mesh = Mesh(np.array(topo.devices[:1]), ("hvd",))
    rep = NamedSharding(mesh, P())
    sds = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=rep)
    params = jax.tree_util.tree_map(
        lambda x: sds(x.shape, x.dtype),
        jax.eval_shape(lambda: weights.make(config, weights.seed_key(0),
                                            cfg.dtype)))
    cache = jax.tree_util.tree_map(
        lambda x: sds(x.shape, x.dtype),
        jax.eval_shape(lambda: llama.init_cache(cfg, blocks, e["block_size"])))
    S, C = e["max_slots"], e["prefill_chunk"]
    mb = -(-e["max_seq_len"] // e["block_size"])

    def step_fn(params, cache, bt, lengths, n_new, tokens, src, dst):
        cache = llama.copy_blocks(cache, src, dst)
        logits, cache = llama.apply_cached(params, tokens, cfg, cache, bt,
                                           lengths, n_new)
        return cache, jnp.argmax(logits.astype(jnp.float32), -1).astype(jnp.int32)
    t = time.time()
    c = jax.jit(step_fn, donate_argnums=(1,)).lower(
        params, cache, sds((S, mb), jnp.int32), sds((S,), jnp.int32),
        sds((S,), jnp.int32), sds((S, C), jnp.int32), sds((S,), jnp.int32),
        sds((S,), jnp.int32)).compile()
    report(f"internlm2-1.8b tick [{S},{C}] cache_blocks={blocks}", c, t)


def step(topo, n):
    import optax
    from horovod_tpu.parallel.data_parallel import make_scanned_train_step
    name = "train-dp1" if n == 1 else "train-dp4"
    _, config, tr = spec.cell(name)
    mesh = Mesh(np.array(topo.devices[:n]), ("hvd",))
    rep = NamedSharding(mesh, P())
    o = tr["optimizer"]
    opt = optax.adamw(o["lr"], weight_decay=o["weight_decay"])
    run = make_scanned_train_step(spec.family(config).loss(config, tr), opt,
                                  mesh, donate=True)
    params = jax.eval_shape(lambda: weights.make(config, weights.seed_key(0),
                                                 weights.dtype_of(config)))
    state = jax.eval_shape(opt.init, params)
    put = lambda tree: jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=rep), tree)
    batch = jax.ShapeDtypeStruct(
        (1, tr["global_batch"], tr["seq"] + 1), jnp.int32,
        sharding=NamedSharding(mesh, P(None, "hvd")))
    t = time.time()
    c = run.lower(put(params), put(state), batch).compile()
    report(f"mistral-7b-v0.3 x4 layers step on {n} chip(s)", c, t)
    print("  all-reduce ops in HLO:", c.as_text().count(" all-reduce("),
          c.as_text().count("all-reduce-start("))


if __name__ == "__main__":
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    what = sys.argv[1] if len(sys.argv) > 1 else "tick"
    if what == "tick":
        tick(topo, int(sys.argv[2]) if len(sys.argv) > 2 else 2048)
    else:
        step(topo, 1 if what == "step1" else 4)
