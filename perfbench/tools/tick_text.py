"""The lowered (StableHLO) text of a serving cell's tick programs at both
widths, for a described v5e chip and with no chip: its length and sha256 a
program, and the text itself under ``--out DIR``.  What a PR shows with it:
that the cells whose modules it did not touch run the programs they ran.

  JAX_PLATFORMS=cpu python3 perfbench/tools/tick_text.py [--out DIR] CELL ...

Run it in the parent's checkout and in the change's and compare.  Two things
that are no part of the program enter the text through the source locations
that a Pallas kernel's serialized body carries (``parallel/expert.py``
``tile_ffn``): the checkout's path, and the line number of every frame of the
stack above the kernel — ``serve/engine.py``'s ``step_fn`` among them, so any
edit higher up in that file moves four bytes of each expert kernel.  Run both
sides by one path (``python3 /some/link/perfbench/tools/tick_text.py``, the
link pointing at one checkout, then at the other: the modules are imported
by the path this file was called by) and, to leave the frames out,
``JAX_TRACEBACK_IN_LOCATIONS_LIMIT=0 JAX_INCLUDE_FULL_TRACEBACKS_IN_LOCATIONS=0``:
what then differs is the program's.  The builder's tool, never the driver's;
nothing runs on a device and nothing here is a measurement.
"""

import argparse
import dataclasses
import hashlib
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
# (not resolved: a symlinked checkout keeps the path it was called by)
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def lowered(cell, one_chip):
    """({width: jax ``Lowered``}, the pools as shapes) of ``cell``'s tick
    program at its decode width and its chunk width, as ServeEngine jits it
    (``tick_program`` on ``max_batch_tokens`` rows, each kind's pool sized
    by the scheduler, the pools and the chain donated), from shapes alone
    (tests/test_tpu_compile.py compiles them)."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.serve import engine as E
    from perfbench.lib import spec, weights
    _, config, _ = spec.cell(cell)
    scfg = E.ServeConfig(**config["engine"])
    model, cfg = spec.family(config).program(config)
    cfg = dataclasses.replace(cfg, max_tick_tokens=scfg.max_batch_tokens)
    sds = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=one_chip)
    tree = lambda t: jax.tree_util.tree_map(lambda x: sds(x.shape, x.dtype), t)
    params = tree(jax.eval_shape(lambda: weights.make(
        config, weights.seed_key(0), weights.dtype_of(config))))
    S, i32 = scfg.max_slots, jnp.int32
    sched = E.Scheduler(scfg, kinds=model.cache_kinds(cfg)
                        if hasattr(model, "cache_kinds") else ())
    tables = jax.tree_util.tree_map(lambda t: sds(t.shape, i32),
                                    sched.device_tables())
    cache = tree(jax.eval_shape(lambda: model.init_cache(
        cfg, sched.pool_blocks(), scfg.block_size)))
    # a model that denoises blocks hands on two more states a slot
    B = E.block_length(cfg)
    chain = (sds((S, scfg.max_seq_len), i32), sds((S,), i32), sds((S,), i32)
             ) + ((sds((S, B), i32), sds((S,), i32)) if B else ())
    orig = jax.default_backend
    jax.default_backend = lambda: "tpu"     # the expert tile is Mosaic's
    try:
        return {C: jax.jit(E.tick_program(model, cfg, scfg),
                           donate_argnums=tuple(range(1, 2 + len(chain)))
                           ).lower(
            params, cache, *chain, tables,
            sds((len(E.BLOCK_ROW if B else E.ROW), S), i32),
            sds((S, C), i32))
            for C in (E.decode_width(scfg, B), scfg.prefill_chunk)}, cache
    finally:
        jax.default_backend = orig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("cells", nargs="+")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    for cell in args.cells:
        for C, low in lowered(cell, one_chip)[0].items():
            text = low.as_text()
            if args.out:
                os.makedirs(args.out, exist_ok=True)
                with open(os.path.join(args.out, f"{cell}-{C}.mlir"),
                          "w") as f:
                    f.write(text)
            print(f"tick_text: {cell} [slots, {C}] {len(text)} bytes sha256 "
                  f"{hashlib.sha256(text.encode()).hexdigest()}", flush=True)


if __name__ == "__main__":
    main()
