"""Wire-policy plane tests (ops/wire.py; docs/tensor-fusion.md).

Covers: per-bucket policy decisions and resolution order, error-feedback
residuals (EF-SGD) on a quadratic toy where int8-without-EF shows
measurable bias, the bit-identical-across-ranks decode invariant for
every wire path, the analytical wire-byte model's ratios, the plan-cache
routing of the SPMD sync path, and the policy-arm bandit (csrc ArmBandit
+ its Autotuner layer)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from horovod_tpu.ops import wire
from horovod_tpu.optimizer import (sync_gradients, sync_gradients_ef,
                                   distributed_optimizer,
                                   wire_residual_report, _WireState)


# --------------------------------------------------------- policy functions
def test_policy_name_validation():
    for name in wire.POLICY_NAMES:
        assert wire.validate_policy_name(name) == name
    with pytest.raises(ValueError, match="unknown wire policy"):
        wire.validate_policy_name("int9")
    with pytest.raises(ValueError, match="HOROVOD_WIRE_POLICY"):
        wire.validate_policy_name("gzip")


def _data_mesh():
    """The legacy single-axis data mesh these tests' shard_maps hardcode
    ("hvd") — built directly from the devices, independent of the
    runtime's resolved training mesh, so the CI layout knob dimension
    (HOROVOD_LAYOUT=auto; docs/parallelism.md) keeps this suite green."""
    import jax
    import numpy as _np
    from jax.sharding import Mesh as _Mesh
    return _Mesh(_np.array(jax.devices()), ("hvd",))


def test_unknown_policy_fails_loudly_at_init(hvd, monkeypatch):
    import horovod_tpu as h
    monkeypatch.setenv("HOROVOD_WIRE_POLICY", "int9")
    h.shutdown()
    try:
        with pytest.raises(ValueError, match="unknown wire policy"):
            h.init()
    finally:
        monkeypatch.delenv("HOROVOD_WIRE_POLICY")
        h.init()


def test_auto_policy_is_per_bucket():
    flat, hier = "hvd", ("dcn.data", "ici.data")
    f32 = jnp.float32
    # the small latency-bound tail stays exact
    assert wire.auto_policy(1024, f32, flat) == "none"
    # mid-size fp32 halves the wire
    assert wire.auto_policy(1 << 20, f32, flat) == "bf16"
    # big buckets take the int8 ring; DCN-selective on a two-level mesh
    assert wire.auto_policy(64 << 20, f32, flat) == "int8_ring"
    assert wire.auto_policy(64 << 20, f32, hier) == "dcn_int8"
    # integer buckets never compress
    assert wire.auto_policy(64 << 20, jnp.int32, flat) == "none"


def test_resolve_format_degradations():
    from horovod_tpu.common.reduce_op import Average, Min
    f32 = jnp.float32
    assert wire.resolve_format("int8_ring", f32, "hvd", Average) == \
        "int8_ring"
    # non-linear reductions stay exact
    assert wire.resolve_format("int8_ring", f32, "hvd", Min) == "none"
    # dcn_int8 on a flat axis has no slow leg to select
    assert wire.resolve_format("dcn_int8", f32, "hvd", Average) == \
        "int8_ring"
    assert wire.resolve_format(
        "dcn_int8", f32, ("dcn.d", "ici.d"), Average) == "dcn_int8"
    # no-op casts collapse
    assert wire.resolve_format("bf16", jnp.bfloat16, "hvd", Average) == \
        "none"
    # integers never compress
    assert wire.resolve_format("int8_ring", jnp.int32, "hvd", Average) == \
        "none"
    with pytest.raises(ValueError, match="unknown wire format"):
        wire.resolve_format("auto", f32, "hvd", Average)


# ------------------------------------------------------- decode determinism
def _sync_rows(hvd, g, **kw):
    mesh = _data_mesh()
    f = shard_map(lambda x: sync_gradients(x, "hvd", **kw), mesh=mesh,
                  in_specs=P("hvd"), out_specs=P("hvd"), check_vma=False)
    return np.asarray(jax.jit(f)(g))


@pytest.mark.parametrize("policy", ["none", "bf16", "fp16", "int8_ring"])
def test_wire_paths_decode_bit_identical_across_ranks(hvd, policy):
    """Every wire format must decode to the SAME post-allreduce values on
    every rank — replicated params drift apart otherwise."""
    n = hvd.size()
    g = jnp.asarray(np.random.RandomState(7).randn(n, 41), jnp.float32)
    rows = _sync_rows(hvd, g, wire_policy=policy)
    for r in range(1, n):
        np.testing.assert_array_equal(rows[r], rows[0])
    exact = np.asarray(g).mean(axis=0)
    tol = {"none": 1e-6, "bf16": 2e-2, "fp16": 5e-3}.get(policy, 5e-2)
    assert np.abs(rows[0] - exact).max() < tol


def test_dcn_int8_two_level_mesh(hvd, monkeypatch):
    """dcn_int8 on a real (dcn, ici) mesh: quantizes only the DCN leg,
    matches the global mean within ring noise, decodes bit-identically."""
    import horovod_tpu as h
    # This test claims the mesh with an explicit spec, which validation
    # rejects alongside the CI layout knob dim (docs/parallelism.md#knobs)
    # — clear the knobs for the duration, restore before the re-init.
    for k in ("HOROVOD_LAYOUT", "HOROVOD_TP", "HOROVOD_PP"):
        monkeypatch.delenv(k, raising=False)
    h.shutdown()
    h.init(mesh_spec="dcn.wd=2,ici.wd=4")
    try:
        mesh = h.mesh()
        axis = ("dcn.wd", "ici.wd")
        x = jnp.asarray(np.random.RandomState(2).randn(8, 29), jnp.float32)
        f = shard_map(
            lambda g: sync_gradients(g, axis, wire_policy="dcn_int8"),
            mesh=mesh, in_specs=P(axis), out_specs=P(axis), check_vma=False)
        out = np.asarray(jax.jit(f)(x))
        exact = np.asarray(x).mean(axis=0)
        assert np.abs(out[0] - exact).max() < 0.05
        for r in range(1, 8):
            np.testing.assert_array_equal(out[r], out[0])
    finally:
        h.shutdown()
        monkeypatch.undo()
        h.init()


# ----------------------------------------------------------- error feedback
def test_error_feedback_rescues_biased_int8_descent(hvd):
    """EF-SGD on a quadratic toy: per-rank gradients carry large zero-mean
    noise (the minibatch regime), so the int8 wire's per-chunk scale dwarfs
    the true descent signal and deterministic rounding noise stalls
    convergence.  With EF the untransmitted error re-enters the next step,
    making the time-averaged wire unbiased: the EF run tracks the fp32
    optimum several times closer than int8-without-EF."""
    mesh = _data_mesh()
    n = hvd.size()
    d, lr, steps = 32, 0.05, 400
    rng = np.random.RandomState(0)
    t = rng.randn(d).astype(np.float32)
    z = rng.randn(n, d).astype(np.float32) * 100.0
    z -= z.mean(axis=0, keepdims=True)  # exact mean gradient = w - t

    def make_run(mode):
        def body(w0, zr):
            def one(carry, _):
                w, res = carry
                g = (w - jnp.asarray(t)) + zr[0]
                if mode == "exact":
                    s = sync_gradients(g, "hvd")
                elif mode == "int8":
                    s = sync_gradients(g, "hvd", wire_policy="int8_ring")
                else:
                    s, res = sync_gradients_ef(g, res, "hvd",
                                               wire_policy="int8_ring")
                return (w - lr * s, res), jnp.float32(0)
            (w, res), _ = jax.lax.scan(one, (w0, jnp.zeros(d)), None,
                                       length=steps)
            return w, res
        return jax.jit(shard_map(body, mesh=mesh,
                                 in_specs=(P(), P("hvd")),
                                 out_specs=(P(), P()), check_vma=False))

    errs, residuals = {}, {}
    for mode in ("exact", "int8", "ef"):
        w, res = make_run(mode)(jnp.zeros(d), jnp.asarray(z))
        errs[mode] = float(np.abs(np.asarray(w) - t).max())
        residuals[mode] = res
    assert errs["exact"] < 1e-3
    assert errs["ef"] < 0.2          # EF tracks the fp32 optimum
    assert errs["int8"] > 2 * errs["ef"]  # no-EF shows measurable bias
    # the residual carries real untransmitted mass, and the report helper
    # publishes it to the gauges
    report = wire_residual_report(residuals["ef"])
    assert sum(report.values()) > 0
    from horovod_tpu.utils import metrics as M
    assert M.WIRE_RESIDUAL_NORM.value(bucket="leaf0") == report["leaf0"]


def test_distributed_optimizer_carries_ef_state(hvd):
    """wire_policy on the optimizer wrapper keeps EF residuals as optax
    state (_WireState beside the inner state) and they become nonzero
    once a lossy bucket runs."""
    import optax

    mesh = _data_mesh()
    n = hvd.size()
    opt = distributed_optimizer(optax.sgd(0.1), axis_name="hvd",
                                wire_policy="int8_ring")
    g = jnp.asarray(np.random.RandomState(3).randn(n, 24), jnp.float32)

    def body(w, gr):
        s = opt.init(w)
        assert isinstance(s, _WireState)
        u, s = opt.update(gr[0], s, w)
        return optax.apply_updates(w, u), s.residual

    f = jax.jit(shard_map(body, mesh=mesh, in_specs=(P(), P("hvd")),
                          out_specs=(P(), P()), check_vma=False))
    w, res = f(jnp.ones(24), g)
    assert np.all(np.isfinite(np.asarray(w)))
    assert float(np.abs(np.asarray(res)).sum()) > 0

    # explicit opt-out keeps the plain inner state
    opt2 = distributed_optimizer(optax.sgd(0.1), axis_name="hvd",
                                 wire_policy="int8_ring",
                                 error_feedback=False)
    assert not isinstance(opt2.init(jnp.ones(4)), _WireState)


# ------------------------------------------------ knob-driven auto policy
def test_env_auto_policy_zero_user_code_changes(hvd, monkeypatch):
    """HOROVOD_WIRE_POLICY=auto routes a plain sync_gradients call (no
    new kwargs anywhere) through per-bucket formats: a >=4 MiB fp32
    bucket takes the int8 ring, and the wire metrics record it."""
    from horovod_tpu.utils import metrics as M

    monkeypatch.setenv("HOROVOD_WIRE_POLICY", "auto")
    n = hvd.size()
    before = M.WIRE_BUCKETS.value(format="int8_ring")
    g = jnp.asarray(
        np.random.RandomState(5).randn(n, 1 << 20).astype(np.float32))
    rows = _sync_rows(hvd, g)   # zero user-code changes
    assert M.WIRE_BUCKETS.value(format="int8_ring") > before
    assert M.WIRE_BYTES_SAVED.value(format="int8_ring") > 0
    exact = np.asarray(g).mean(axis=0)
    assert np.abs(rows[0] - exact).max() < 0.05
    for r in range(1, n):
        np.testing.assert_array_equal(rows[r], rows[0])


def test_spmd_sync_routes_through_plan_cache(hvd):
    """The satellite fix: sync_gradients plans through rt.plan_cache (not
    a direct make_plan), so repeat traces of the same gradient signature
    hit the cache and the hvd_fusion_plan_cache_* metrics move."""
    import horovod_tpu.runtime as hrt

    rt = hrt.get()
    mesh = _data_mesh()
    n = hvd.size()
    gs = jnp.asarray(np.random.RandomState(9).randn(n, 17), jnp.float32)
    h0, m0 = rt.plan_cache.hits, rt.plan_cache.misses

    def trace_once():
        f = shard_map(lambda x: sync_gradients(x, "hvd"), mesh=mesh,
                      in_specs=P("hvd"), out_specs=P("hvd"),
                      check_vma=False)
        return jax.jit(f)(gs)

    trace_once()
    assert rt.plan_cache.misses >= m0  # first trace may miss or hit
    h1 = rt.plan_cache.hits
    trace_once()  # fresh jit closure -> fresh trace, same signature
    assert rt.plan_cache.hits > h1
    snap = __import__("horovod_tpu").metrics_snapshot()["families"]
    hits = snap["hvd_fusion_plan_cache_hits_total"]["samples"][0]["value"]
    assert hits == rt.plan_cache.hits


# -------------------------------------------------------------- wire model
def test_wire_byte_model_ratios():
    """The acceptance ratios, analytically: int8 <= 1/2 of bf16 <= 1/2 of
    fp32 per bucket, and dcn_int8's bottleneck (DCN) bytes beat the flat
    int8 ring's on a two-level mesh."""
    flat = {"flat": 8}
    nelems = 1 << 20
    f32 = wire.modeled_wire_bytes(nelems, 4, "none", flat)["bottleneck"]
    b16 = wire.modeled_wire_bytes(nelems, 4, "bf16", flat)["bottleneck"]
    i8 = wire.modeled_wire_bytes(nelems, 4, "int8_ring", flat)["bottleneck"]
    assert i8 <= b16 / 2 <= f32 / 4
    hier = {"ici": 4, "dcn": 2}
    d8 = wire.modeled_wire_bytes(nelems, 4, "dcn_int8", hier)
    i8h = wire.modeled_wire_bytes(nelems, 4, "int8_ring", hier)
    assert d8["bottleneck"] < i8h["bottleneck"]
    assert set(d8["per_fabric"]) == {"ici", "dcn"}
    # single-member axis moves nothing
    assert wire.modeled_wire_bytes(64, 4, "none",
                                   {"flat": 1})["bottleneck"] == 0


# ------------------------------------------------------------------ bandit
def test_native_arm_bandit_converges_and_is_deterministic():
    from horovod_tpu.common.basics import NativeArmBandit

    scores = {0: 1.0, 1: 3.0, 2: 2.0}

    def play():
        b = NativeArmBandit(3, steps_per_sample=1, max_pulls=12)
        seq = []
        while not b.done:
            seq.append(b.arm)
            b.update(scores[b.arm])
        return seq, b.arm
    seq1, final1 = play()
    seq2, final2 = play()
    assert seq1 == seq2 and final1 == final2 == 1
    # single arm: nothing to choose
    assert NativeArmBandit(1).done


def test_autotuner_tunes_policy_arm(hvd):
    """The policy dimension layered on the GP: the bandit converges to the
    best-scoring arm and wire_policy exposes it (broadcast alongside the
    threshold in multi-process runs, so every process compiles the same
    program)."""
    from horovod_tpu.common.knobs import Knobs
    from horovod_tpu.utils.autotune import Autotuner

    knobs = Knobs({"HOROVOD_AUTOTUNE": True,
                   "HOROVOD_AUTOTUNE_WARMUP_SAMPLES": 0,
                   "HOROVOD_AUTOTUNE_STEPS_PER_SAMPLE": 1,
                   "HOROVOD_AUTOTUNE_BAYES_OPT_MAX_SAMPLES": 4})
    arms = ["auto", "none", "bf16", "int8_ring"]
    tuner = Autotuner(knobs, policy_arms=arms)
    score = {"auto": 2.0, "none": 1.0, "bf16": 2.5, "int8_ring": 4.0}
    for _ in range(200):
        if tuner.done:
            break
        tuner.record(int(1e9 * score[tuner.wire_policy]), 1.0)
    assert tuner.done
    assert tuner.wire_policy == "int8_ring"
    tuner.close()


def test_runtime_wire_policy_resolves_auto_to_tuned_arm(hvd, monkeypatch):
    """Runtime.wire_policy(): the knob's 'auto' refines to the live
    bandit arm, the default stays 'none', and env changes are honored
    post-init (the `current` contract)."""
    import horovod_tpu.runtime as hrt
    from horovod_tpu.common.knobs import Knobs
    from horovod_tpu.utils.autotune import Autotuner

    rt = hrt.get()
    # pin the baseline: CI's wire-auto knob dimension sets the env var
    monkeypatch.setenv("HOROVOD_WIRE_POLICY", "none")
    assert rt.wire_policy() == "none"
    monkeypatch.setenv("HOROVOD_WIRE_POLICY", "bf16")
    assert rt.wire_policy() == "bf16"
    monkeypatch.setenv("HOROVOD_WIRE_POLICY", "auto")
    assert rt.wire_policy() == "auto"  # no tuner: the heuristic policy
    tuner = Autotuner(Knobs({"HOROVOD_AUTOTUNE": True}),
                      policy_arms=["none", "int8_ring"])
    tuner._policy_arm = 1
    monkeypatch.setattr(rt, "autotuner", tuner)
    assert rt.wire_policy() == "int8_ring"
    tuner.close()
