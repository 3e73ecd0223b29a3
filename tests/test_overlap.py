"""Overlap-plane tests (ops/overlap.py; docs/overlap.md).

Covers the plane's one hard guarantee — overlap is a SCHEDULING change,
never a semantics change — per wire format and EF mode for the
microbatch pipeline, the bucket-interleaved ZeRO-1 path against the
monolithic chain (params AND per-element optimizer-state values), the
deterministic plan-cache-keyed reverse-priority bucket order, the
overlap-depth bandit arm (csrc ProductBandit) determinism, init-time
knob validation, the double-buffered input prefetch, and the
hvd_overlap_* metric families."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from horovod_tpu.ops import overlap
from horovod_tpu.ops.overlap import _OverlapState, priority_order
from horovod_tpu.optimizer import _AccState, distributed_optimizer


# ------------------------------------------------- scheduling equivalence
def _run_cycle(hvd, opt, grads_per_mb, w0):
    """One full optimizer cycle: k update calls in one trace."""
    mesh = _data_mesh()
    k = len(grads_per_mb)

    def body(w, *gr):
        s = opt.init(w)
        for g in gr:
            u, s = opt.update(g[0], s, w)
            w = optax.apply_updates(w, u)
        return w

    f = jax.jit(shard_map(body, mesh=mesh,
                          in_specs=(P(),) + (P("hvd"),) * k,
                          out_specs=P(), check_vma=False))
    return np.asarray(f(w0, *[jnp.asarray(g) for g in grads_per_mb]))


def _data_mesh():
    """The legacy single-axis data mesh these tests' shard_maps hardcode
    ("hvd") — built directly from the devices, independent of the
    runtime's resolved training mesh, so the CI layout knob dimension
    (HOROVOD_LAYOUT=auto; docs/parallelism.md) keeps this suite green."""
    import jax
    import numpy as _np
    from jax.sharding import Mesh as _Mesh
    return _Mesh(_np.array(jax.devices()), ("hvd",))


@pytest.mark.parametrize("policy", ["none", "bf16", "int8_ring"])
@pytest.mark.parametrize("ef", [False, True])
def test_pipelined_step_matches_sequential(hvd, policy, ef):
    """The acceptance guarantee: for k in {2, 4}, every pipeline depth
    lands the same final params as the sequential issue order of the
    same per-microbatch syncs (depth 0), per wire format, EF on/off —
    and for the lossless format the pipeline also matches the legacy
    accumulate-k-then-sync path (linearity of psum)."""
    n = hvd.size()
    rng = np.random.RandomState(3)
    w0 = jnp.ones(24)

    def make(k, **kw):
        return distributed_optimizer(optax.sgd(0.1), axis_name="hvd",
                                     backward_passes_per_step=k,
                                     wire_policy=policy,
                                     error_feedback=ef, **kw)

    for k in (2, 4):
        gs = [rng.randn(n, 24).astype(np.float32) for _ in range(k)]
        seq = _run_cycle(hvd, make(k, overlap=True, overlap_depth=0),
                         gs, w0)
        for depth in sorted({1, k - 1}):
            pip = _run_cycle(hvd, make(k, overlap=True,
                                       overlap_depth=depth), gs, w0)
            np.testing.assert_allclose(pip, seq, rtol=2e-6, atol=2e-7)
        if policy == "none":
            legacy = _run_cycle(hvd, make(k, overlap=False), gs, w0)
            np.testing.assert_allclose(seq, legacy, rtol=1e-5, atol=1e-6)


def test_k1_overlap_is_identity(hvd):
    """backward_passes_per_step=1 has nothing to pipeline: overlap on
    and off build the same core transformation."""
    n = hvd.size()
    g = [np.random.RandomState(0).randn(n, 8).astype(np.float32)]
    w0 = jnp.ones(8)
    on = _run_cycle(hvd, distributed_optimizer(
        optax.sgd(0.1), axis_name="hvd", overlap=True), g, w0)
    off = _run_cycle(hvd, distributed_optimizer(
        optax.sgd(0.1), axis_name="hvd", overlap=False), g, w0)
    np.testing.assert_array_equal(on, off)


def test_env_knob_alone_activates_pipeline(monkeypatch):
    """HOROVOD_OVERLAP=1 with no code changes flips k>1 users onto the
    pipelined state (safe: k>1 state always comes from the wrapper's own
    init, so init and update agree on the structure)."""
    opt = distributed_optimizer(optax.sgd(0.1), axis_name=None,
                                backward_passes_per_step=2)
    assert isinstance(opt.init(jnp.ones(4)), _AccState)
    monkeypatch.setenv("HOROVOD_OVERLAP", "1")
    opt = distributed_optimizer(optax.sgd(0.1), axis_name=None,
                                backward_passes_per_step=2)
    assert isinstance(opt.init(jnp.ones(4)), _OverlapState)
    # explicit kwarg opt-out always wins the other way
    opt = distributed_optimizer(optax.sgd(0.1), axis_name=None,
                                backward_passes_per_step=2, overlap=False)
    assert isinstance(opt.init(jnp.ones(4)), _AccState)


def test_resolve_depth_bounds():
    assert overlap.resolve_depth(0) == 0
    assert overlap.resolve_depth(overlap.MAX_OVERLAP_DEPTH) == \
        overlap.MAX_OVERLAP_DEPTH
    with pytest.raises(ValueError, match="out of range"):
        overlap.resolve_depth(-1)
    with pytest.raises(ValueError, match="out of range"):
        overlap.resolve_depth(overlap.MAX_OVERLAP_DEPTH + 1)


# --------------------------------------------- bucket-interleaved ZeRO-1
def _toy_model():
    rng = np.random.RandomState(0)
    params = {"w1": jnp.asarray(rng.randn(7, 5), jnp.float32),
              "b1": jnp.asarray(rng.randn(5), jnp.float32),
              "w2": jnp.asarray(rng.randn(5, 1), jnp.float32)}

    def loss_fn(p, batch):
        x, y = batch
        h = jnp.tanh(x @ p["w1"] + p["b1"])
        return jnp.mean((h @ p["w2"] - y) ** 2)
    return params, loss_fn


def test_zero1_interleaved_matches_monolithic(hvd):
    """(b) the interleaved pipeline is bit-near the monolithic chain:
    same params after several adamw steps AND the same per-element
    optimizer-state values (only the element -> chip mapping moves)."""
    from horovod_tpu.parallel.data_parallel import replicate, shard_batch
    from horovod_tpu.parallel.zero import (init_sharded_opt_state,
                                           make_zero1_train_step,
                                           _bucket_plan)

    mesh = _data_mesh()
    n = hvd.size()
    params, loss_fn = _toy_model()
    opt = optax.adam(1e-2)
    thresh = 64  # tiny threshold -> several buckets on the toy

    m_step = make_zero1_train_step(loss_fn, opt, mesh)
    i_step = make_zero1_train_step(loss_fn, opt, mesh, interleaved=True,
                                   fusion_threshold_bytes=thresh)
    m_p = replicate(params, mesh)
    m_s = init_sharded_opt_state(opt, m_p, mesh)
    i_p = replicate(params, mesh)
    i_s = init_sharded_opt_state(opt, i_p, mesh, interleaved=True,
                                 fusion_threshold_bytes=thresh)
    plan = _bucket_plan(params, thresh)
    assert plan.num_buckets >= 2  # the pipeline has something to overlap
    assert len(i_s) == plan.num_buckets

    rng = np.random.RandomState(1)
    for _ in range(3):
        xs = rng.randn(8 * n, 7).astype(np.float32)
        ys = rng.randn(8 * n, 1).astype(np.float32)
        batch = (shard_batch(jnp.asarray(xs), mesh),
                 shard_batch(jnp.asarray(ys), mesh))
        m_p, m_s, m_l = m_step(m_p, m_s, batch)
        i_p, i_s, i_l = i_step(i_p, i_s, batch)
        np.testing.assert_allclose(float(m_l), float(i_l), rtol=1e-6)
    for key in params:
        np.testing.assert_allclose(np.asarray(i_p[key]),
                                   np.asarray(m_p[key]),
                                   rtol=1e-6, atol=1e-7)

    # identical optax state per ELEMENT: reassemble the interleaved
    # layout (per-bucket shards) into flat leaf order and compare against
    # the monolithic flat vector, for both adam moments.
    leaves = jax.tree_util.tree_leaves(params)
    sizes = [int(np.prod(l.shape)) for l in leaves]
    total = sum(sizes)
    offs = np.cumsum([0] + sizes)
    for moment in ("mu", "nu"):
        mono = np.asarray(getattr(m_s[0], moment)).reshape(-1)[:total]
        flat = np.zeros(total, np.float64)
        for bi, b in enumerate(plan.buckets):
            vec = np.asarray(getattr(i_s[bi][0], moment)).reshape(-1)
            vec = vec[:sum(b.sizes)]
            off = 0
            for idx, sz in zip(b.indices, b.sizes):
                flat[offs[idx]:offs[idx] + sz] = vec[off:off + sz]
                off += sz
        np.testing.assert_allclose(flat, mono, rtol=1e-6, atol=1e-8)


def test_priority_order_deterministic_and_plan_cached(hvd):
    """(c) the reverse-priority issue order is a pure function of the
    plan, and the plan comes from the runtime's BucketPlanCache — so an
    identical (shapes, threshold) signature reuses both."""
    import horovod_tpu.runtime as hrt
    from horovod_tpu.parallel.zero import _bucket_plan

    params, _ = _toy_model()
    rt = hrt.get()
    h0 = rt.plan_cache.hits
    p1 = _bucket_plan(params, 64)
    p2 = _bucket_plan(params, 64)
    assert rt.plan_cache.hits > h0      # second lookup hit the cache
    assert p1 is p2                      # same cached object
    order = priority_order(p1)
    assert order == tuple(reversed(range(p1.num_buckets)))
    assert order == priority_order(p2)  # deterministic


# ------------------------------------------------------- autotune arm dim
def test_product_bandit_determinism():
    """(d) the overlap-depth arm dimension (csrc ProductBandit): two
    identical replays pull identical (policy, depth) sequences and
    finalize on the same pair — the broadcast-safety property."""
    from horovod_tpu.common.basics import NativeProductBandit

    score = {(0, 0): 1.0, (0, 1): 2.0, (0, 2): 1.5,
             (1, 0): 3.0, (1, 1): 5.0, (1, 2): 4.0}

    def play():
        b = NativeProductBandit(2, 3, steps_per_sample=1, max_pulls=24)
        seq = []
        while not b.done:
            seq.append((b.arm_a, b.arm_b))
            b.update(score[(b.arm_a, b.arm_b)])
        return seq, (b.arm_a, b.arm_b)

    s1, f1 = play()
    s2, f2 = play()
    assert s1 == s2 and f1 == f2 == (1, 1)
    assert NativeProductBandit(1, 1).done  # nothing to choose


def test_autotuner_tunes_depth_arm():
    """The joint (policy, depth) search converges to the best-scoring
    pair and exposes both through wire_policy / overlap_depth (broadcast
    with the threshold in multi-process runs)."""
    from horovod_tpu.common.knobs import Knobs
    from horovod_tpu.utils.autotune import Autotuner

    knobs = Knobs({"HOROVOD_AUTOTUNE": True,
                   "HOROVOD_AUTOTUNE_WARMUP_SAMPLES": 0,
                   "HOROVOD_AUTOTUNE_STEPS_PER_SAMPLE": 1,
                   "HOROVOD_AUTOTUNE_BAYES_OPT_MAX_SAMPLES": 4})
    tuner = Autotuner(knobs, policy_arms=["none", "int8_ring"],
                      depth_arms=[1, 2, 4])
    score = {("none", 1): 1.0, ("none", 2): 1.2, ("none", 4): 1.1,
             ("int8_ring", 1): 2.0, ("int8_ring", 2): 4.0,
             ("int8_ring", 4): 3.0}
    for _ in range(400):
        if tuner.done:
            break
        tuner.record(int(1e9 * score[(tuner.wire_policy,
                                      tuner.overlap_depth)]), 1.0)
    assert tuner.done
    assert (tuner.wire_policy, tuner.overlap_depth) == ("int8_ring", 2)
    tuner.close()

    # depth-only tuning rides the plain ArmBandit
    solo = Autotuner(knobs, depth_arms=[1, 2])
    assert solo.wire_policy is None and solo.overlap_depth == 1
    solo.close()


def test_runtime_overlap_depth_resolves_tuned_arm(hvd, monkeypatch):
    """Runtime.overlap_depth(): knob-driven (env-live), refined to the
    bandit's depth arm when tuning is on — the wire_policy() pattern."""
    import horovod_tpu.runtime as hrt
    from horovod_tpu.common.knobs import Knobs
    from horovod_tpu.utils.autotune import Autotuner

    rt = hrt.get()
    assert rt.overlap_depth() == 1  # the default
    monkeypatch.setenv("HOROVOD_OVERLAP_DEPTH", "3")
    assert rt.overlap_depth() == 3
    monkeypatch.setenv("HOROVOD_OVERLAP_DEPTH", "0")
    with pytest.raises(ValueError, match="HOROVOD_OVERLAP_DEPTH"):
        rt.overlap_depth()
    monkeypatch.delenv("HOROVOD_OVERLAP_DEPTH")
    tuner = Autotuner(Knobs({"HOROVOD_AUTOTUNE": True}),
                      depth_arms=[1, 2, 4])
    tuner._depth_arm = 2
    monkeypatch.setattr(rt, "autotuner", tuner)
    assert rt.overlap_depth() == 4
    tuner.close()


# -------------------------------------------------- init knob validation
@pytest.mark.parametrize("knob,bad", [
    ("HOROVOD_OVERLAP_DEPTH", "0"),
    ("HOROVOD_OVERLAP_DEPTH", "-2"),
    ("HOROVOD_OVERLAP_DEPTH", "99"),
    ("HOROVOD_PREFETCH_DEPTH", "0"),
    ("HOROVOD_PREFETCH_DEPTH", "-1"),
    ("HOROVOD_FUSION_THRESHOLD", "-4096"),
    ("HOROVOD_CACHE_CAPACITY", "-1"),
])
def test_invalid_knobs_fail_loudly_at_init(hvd, monkeypatch, knob, bad):
    """The knob-validation satellite: overlap/prefetch depths AND the
    negative-value cases the wire-era validation missed must all fail AT
    hvd.init with the knob named, not as a trace error later."""
    import horovod_tpu as h
    monkeypatch.setenv(knob, bad)
    h.shutdown()
    try:
        with pytest.raises(ValueError, match=knob):
            h.init()
    finally:
        monkeypatch.delenv(knob)
        h.init()


# ----------------------------------------------------------- prefetch
def test_prefetch_double_buffers_to_device(hvd, monkeypatch):
    """The input-leg satellite: prefetch() yields every batch, in order,
    already transferred (device arrays), with the depth defaulting to
    the HOROVOD_PREFETCH_DEPTH knob."""
    from horovod_tpu.data.loader import prefetch

    batches = [{"x": np.full((2,), i, np.float32)} for i in range(5)]
    out = list(prefetch(iter(batches), depth=2))
    assert len(out) == 5
    assert all(isinstance(o["x"], jax.Array) for o in out)
    assert [int(o["x"][0]) for o in out] == [0, 1, 2, 3, 4]

    # knob-driven depth (env-live via `current`)
    monkeypatch.setenv("HOROVOD_PREFETCH_DEPTH", "3")
    seen = []
    gen = prefetch((seen.append(i) or {"x": np.zeros(1)}
                    for i in range(6)))
    first = next(gen)
    assert isinstance(first["x"], jax.Array)
    assert len(seen) == 3  # the knob's depth was eagerly transferred

    with pytest.raises(ValueError, match="prefetch depth"):
        list(prefetch(iter(batches), depth=0))

    # custom transfer fn (e.g. a sharded put)
    calls = []
    out = list(prefetch(iter(batches[:2]), depth=1,
                        transfer=lambda b: calls.append(1) or b))
    assert len(out) == 2 and len(calls) == 2


# ------------------------------------------------------------- metrics
def test_overlap_metrics_families(hvd):
    """hvd.metrics_snapshot() exposes the hvd_overlap_* families with
    per-plane labels after a pipelined trace, fraction in [0, 1]."""
    import horovod_tpu as h
    from horovod_tpu.utils import metrics as M

    n = hvd.size()
    opt = distributed_optimizer(optax.sgd(0.1), axis_name="hvd",
                                backward_passes_per_step=2, overlap=True,
                                overlap_depth=1)
    g = np.random.RandomState(0).randn(2, n, 12).astype(np.float32)
    _run_cycle(hvd, opt, [g[0], g[1]], jnp.ones(12))

    frac = M.OVERLAP_FRACTION.value(plane="microbatch")
    assert 0.0 < frac <= 1.0
    assert M.OVERLAP_EXPOSED_BYTES.value(plane="microbatch") >= 0.0
    fams = h.metrics_snapshot()["families"]
    assert "hvd_overlap_exposed_bytes" in fams
    assert "hvd_overlap_overlapped_fraction" in fams
    planes = {s["labels"].get("plane")
              for s in fams["hvd_overlap_overlapped_fraction"]["samples"]}
    assert "microbatch" in planes


def test_microbatched_scan_step_matches_unpipelined(hvd):
    """make_microbatched_train_step (the lax.scan software pipeline):
    overlap on ≡ overlap off for the lossless default — one optimizer
    step over k scanned microbatches either way."""
    from horovod_tpu.parallel.data_parallel import (
        make_microbatched_train_step, replicate, shard_batch)

    mesh = _data_mesh()
    n = hvd.size()
    params, loss_fn = _toy_model()
    k = 3
    rng = np.random.RandomState(2)
    batch = (shard_batch(jnp.asarray(
                 rng.randn(k, 8 * n, 7).astype(np.float32)), mesh, axis=1),
             shard_batch(jnp.asarray(
                 rng.randn(k, 8 * n, 1).astype(np.float32)), mesh, axis=1))

    finals = {}
    for label, on in (("pipelined", True), ("legacy", False)):
        opt = optax.sgd(0.05)
        step = make_microbatched_train_step(
            loss_fn, opt, mesh, backward_passes_per_step=k,
            overlap=on, overlap_depth=1, donate=False)
        dopt = distributed_optimizer(opt, axis_name="hvd",
                                     backward_passes_per_step=k,
                                     overlap=on, overlap_depth=1)
        p = replicate(params, mesh)
        s = replicate(dopt.init(params), mesh)
        p, s, loss = step(p, s, batch)
        assert np.isfinite(float(loss))
        finals[label] = p
    for key in params:
        np.testing.assert_allclose(np.asarray(finals["pipelined"][key]),
                                   np.asarray(finals["legacy"][key]),
                                   rtol=1e-5, atol=1e-6)
