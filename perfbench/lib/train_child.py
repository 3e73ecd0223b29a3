"""The chip-holding child of a training cell: the program's data-parallel
step (hvd.init, hvd.mesh, optax.adamw inside make_scanned_train_step's
DistributedOptimizer) on seeded weights and a seeded token feed, checked
against the plain reference on its first three steps and then timed."""

from __future__ import annotations

import argparse
import collections
import shutil
import tempfile
import threading
import time

import numpy as np

from . import checks, child, reference, spec, tracered, weights

CHECK_STEPS = 3


class Feed:
    """Fresh seeded token batches, one call's worth at a time, made on the
    host one call ahead.  Every row differs."""

    def __init__(self, seed, vocab, shape):
        self.seed, self.vocab, self.shape = int(seed), vocab, shape
        self._next, self._ready, self._thread = 0, None, None

    def host(self, i):
        rng = np.random.default_rng([self.seed, i])
        return rng.integers(0, self.vocab, self.shape, dtype=np.int32)

    def _make(self, i, put):
        self._ready = put(self.host(i))

    def take(self, put):
        """The next call's batches on the device; starts making the one
        after on a thread of its own."""
        if self._thread is None:
            self._make(self._next, put)
        else:
            self._thread.join()
        out = self._ready
        self._next += 1
        self._thread = threading.Thread(target=self._make,
                                        args=(self._next, put))
        self._thread.start()
        return out

    def close(self):
        if self._thread is not None:
            self._thread.join()
        self._ready = self._thread = None


class Program:
    """What `python bench.py` builds, on seeded weights and a seeded feed:
    hvd.init, hvd.mesh, adamw inside make_scanned_train_step's
    DistributedOptimizer, the state made on the device under the mesh's
    sharding.  ``call()`` is the window's own call."""

    def __init__(self, config, traffic, seed, broken=""):
        import jax
        import jax.numpy as jnp
        import optax
        from jax.sharding import NamedSharding, PartitionSpec as P

        import horovod_tpu as hvd
        from horovod_tpu.parallel.data_parallel import (
            make_scanned_train_step, shard_batch)

        self.config, self.seed, self.broken = config, seed, broken
        K, B, S = (traffic["steps_per_call"], traffic["global_batch"],
                   traffic["seq"])
        if CHECK_STEPS % K:
            raise SystemExit(f"steps_per_call {K} must divide {CHECK_STEPS}")
        self.K = K
        o = traffic["optimizer"]
        hvd.init()
        mesh = hvd.mesh()
        child.say(phase="hvd.init done", at_s=child.since_start())
        loss_fn = spec.family(config).loss(config, traffic)
        self.opt = optax.adamw(o["lr"], b1=o["b1"], b2=o["b2"], eps=o["eps"],
                               weight_decay=o["weight_decay"])
        self.run = make_scanned_train_step(loss_fn, self.opt, mesh)
        self.rep = NamedSharding(mesh, P())
        self.dtype = weights.dtype_of(config)
        self.put = lambda host: shard_batch(jnp.asarray(host), mesh, axis=1)
        self.shape = (K, B, S + 1)
        self._make = jax.jit(
            lambda key: weights.make(config, key, self.dtype),
            out_shardings=self.rep)
        self.reseed(seed)

    def reseed(self, seed):
        """Fresh state and feed from ``seed``; the compiled step stays."""
        import jax
        self.seed = seed
        self.feed = Feed(seed, self.config["vocab_size"], self.shape)
        self.params = self._make(weights.seed_key(seed))
        jax.block_until_ready(self.params)
        child.say(phase="weights made", at_s=child.since_start())
        self.opt_state = jax.jit(self.opt.init,
                                 out_shardings=self.rep)(self.params)
        jax.block_until_ready(self.opt_state)
        child.say(phase="state made", at_s=child.since_start())

    def call(self):
        """One call of K steps on the next batches; returns its losses (on
        the device: fetching them is the fence)."""
        import jax.numpy as jnp
        batches = self.feed.take(self.put)
        if self.broken == "frozen-step":   # tests: a step that does nothing
            return jnp.full((self.K,), 10.0)
        self.params, self.opt_state, losses = self.run(
            self.params, self.opt_state, batches)
        return losses

    def first_steps(self):
        """The first CHECK_STEPS steps through ``call``: their losses, the
        norms of Adam's first moment after the first call (for K = 1 the
        first gradient as the optimizer got it, times 1 - b1), and the norm
        of the parameters' change, leaf by leaf."""
        import jax
        import jax.numpy as jnp
        norms = jax.jit(lambda tree: jax.tree_util.tree_map(
            lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))),
            tree))
        change = jax.jit(lambda p, p0: jnp.sqrt(jnp.sum(jnp.square(
            p.astype(jnp.float32) - p0))))
        w = reference.Weights(self.config, self.seed)
        prog = {"loss": []}
        for i in range(CHECK_STEPS // self.K):
            prog["loss"] += np.asarray(self.call(), np.float64).tolist()
            if i == 0:
                adam = next(s for s in jax.tree_util.tree_leaves(
                    self.opt_state, is_leaf=lambda s: hasattr(s, "mu"))
                    if hasattr(s, "mu"))
                prog["mnorm"] = {k: float(v) for k, v in
                                 weights.flat(norms(adam.mu)).items()}
                child.say(phase="first call done", at_s=child.since_start())
        prog["dnorm"] = {k: float(change(x, w(k)))
                         for k, x in weights.flat(self.params).items()}
        return prog

    def free(self):
        import jax
        leaves = jax.tree_util.tree_leaves(self.params)
        plan = [[x.shape for x in leaves], [x.dtype for x in leaves]]
        self.feed.close()
        self.params = self.opt_state = None
        return plan


def reference_numbers(config, traffic, seed, quant=None):
    """The reference's (or, with ``quant``, the control's) numbers on the
    first CHECK_STEPS steps of the seed's feed."""
    import jax
    K, B, S = traffic["steps_per_call"], traffic["global_batch"], traffic["seq"]
    feed = Feed(seed, config["vocab_size"], (K, B, S + 1))
    first = np.concatenate([feed.host(i) for i in range(CHECK_STEPS // K)])
    ref = reference.train_steps(config, seed, list(first),
                                traffic["optimizer"], devices=jax.devices(),
                                quant=quant)
    ref["mnorm"] = ref["mnorm"][K - 1]
    return ref


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--dry", type=int, default=0)
    ap.add_argument("--break", dest="broken", default="")
    args = ap.parse_args(argv)
    entry, config, traffic = spec.cell(args.workload)
    if args.dry:
        config, traffic = spec.tiny(config), spec.tiny_train(traffic)
    device, counter = child.bring_up(entry["chips"], args.dry)

    import jax

    K, B, S = traffic["steps_per_call"], traffic["global_batch"], traffic["seq"]
    child.say(phase="devices up", at_s=child.since_start())
    prog_obj = Program(config, traffic, args.seed, broken=args.broken)
    child.say(hbm_in_use_after_init=child.memory("bytes_in_use"),
              params=sum(x.size for x in
                         jax.tree_util.tree_leaves(prog_obj.params)))
    prog = prog_obj.first_steps()
    child.say(phase="first steps read", at_s=child.since_start())
    feed, call = prog_obj.feed, prog_obj.call
    warm_lowerings = counter.lowerings

    # --- the window
    tokens_per_call = K * B * S
    inflight = collections.deque()
    done_calls, losses_seen = 0, []
    trace_dir, traced = None, None
    setup_s = child.since_start()
    t0 = time.perf_counter()

    def harvest():
        nonlocal done_calls
        losses_seen.extend(np.asarray(inflight.popleft()).tolist())  # fence
        done_calls += 1

    while time.perf_counter() - t0 < args.seconds:
        if args.trace and trace_dir is None and \
                done_calls >= traffic["trace"]["skip_calls"]:
            t_excl = time.perf_counter()
            calls_before = done_calls + len(inflight)
            while inflight:
                harvest()
            trace_dir = tempfile.mkdtemp(prefix="pb-trace-")
            jax.profiler.start_trace(trace_dir,
                                     profiler_options=child.trace_options())
            t_tr = time.perf_counter()
            for _ in range(traffic["trace"]["calls"]):
                with jax.profiler.TraceAnnotation("pb:dispatch"):
                    inflight.append(call())
            with jax.profiler.TraceAnnotation("pb:fetch-losses"):
                while inflight:
                    harvest()
            traced = {"host_s": time.perf_counter() - t_tr,
                      "calls": traffic["trace"]["calls"]}
            jax.profiler.stop_trace()
            traced["excluded_s"] = time.perf_counter() - t_excl
            traced["excluded_calls"] = done_calls - calls_before
            continue
        inflight.append(call())
        if len(inflight) >= traffic["dispatch_ahead"]:
            harvest()
    while inflight:
        harvest()
    window_s = time.perf_counter() - t0
    window_lowerings = counter.lowerings - warm_lowerings
    peak = child.memory("peak_bytes_in_use")   # before the reference comes

    # --- the program's state goes, the reference follows the first steps
    plan = prog_obj.free()
    del inflight
    t_ref = time.perf_counter()
    ref = reference_numbers(config, traffic, args.seed)
    ref_s = time.perf_counter() - t_ref
    numbers, where = checks.train_numbers(prog, ref)
    numbers["window_nonfinite_losses"] = float(
        sum(1 for x in losses_seen if not np.isfinite(x)))
    numbers["window_compilations"] = float(window_lowerings)
    rows, correct = checks.judge(numbers, traffic["check"])
    for name, value, limit, ok in rows:
        child.say(check=name, value=value, limit=limit, ok=ok,
                  leaf=where.get(name))
    child.say(reference_s=ref_s, program_loss=prog["loss"],
              reference_loss=ref["loss"],
              window_calls=done_calls, window_s=window_s,
              cache_hits=counter.cache_hits, lowerings=counter.lowerings,
              hbm_peak=peak, last_loss=losses_seen[-1])

    out = {"correct": correct, "attempted": done_calls * K, "failed": 0,
           "checks": [row[:3] for row in rows],
           "device": dict(device, memory_peak_bytes=peak),
           "setup_s": setup_s, "reference_s": ref_s, "window_s": window_s,
           "steps": done_calls * K, "tokens": done_calls * tokens_per_call,
           "chips": entry["chips"]}
    out["untraced_s"] = window_s - (traced or {}).get("excluded_s", 0.0)
    out["untraced_tokens"] = tokens_per_call * (
        done_calls - (traced or {}).get("excluded_calls", 0))
    if traced:
        red = tracered.reduce_trace(trace_dir, dry=args.dry)
        shutil.rmtree(trace_dir, ignore_errors=True)
        from horovod_tpu import runtime
        rt = runtime.get()
        out["trace"] = dict(red, steps=traced["calls"] * K,
                            host_s=traced["host_s"])
        out["bucket_plan_buckets"] = rt.plan_cache.get(
            plan[0], plan[1], rt.fusion_threshold()).num_buckets
    child.emit("train", **out)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
