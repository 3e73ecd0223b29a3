"""Autotune subsystem tests: native GP regression, Bayesian optimization,
parameter manager convergence, and runtime wiring.

The reference tunes (fusion threshold, cycle time) by expected-improvement
Bayesian optimization over a Gaussian process scored in bytes/sec
(reference: horovod/common/parameter_manager.{h,cc},
optim/bayesian_optimization.{h,cc}, optim/gaussian_process.{h,cc}).
"""

import math

import numpy as np
import pytest

from horovod_tpu.common.basics import (BayesianOptimizer, GaussianProcess,
                                       NativeParameterManager)


# ------------------------------------------------------------------------- GP
def test_gp_interpolates_training_points():
    X = [[0.0], [0.5], [1.0]]
    y = [0.0, 1.0, 0.0]
    gp = GaussianProcess(length=0.3, sigma_f=1.0, noise=1e-6)
    gp.fit(X, y)
    for xi, yi in zip(X, y):
        mean, var = gp.predict(xi)
        assert abs(mean - yi) < 1e-2
        assert var < 1e-2


def test_gp_uncertainty_grows_away_from_data():
    gp = GaussianProcess(length=0.1, sigma_f=1.0, noise=1e-6)
    gp.fit([[0.0]], [1.0])
    _, var_near = gp.predict([0.01])
    _, var_far = gp.predict([0.9])
    assert var_far > var_near * 10


def test_gp_smooth_interpolation():
    xs = np.linspace(0, 1, 9)
    gp = GaussianProcess(length=0.3, sigma_f=1.0, noise=1e-6)
    gp.fit(xs[:, None].tolist(), np.sin(2 * np.pi * xs).tolist())
    for q in np.linspace(0.1, 0.9, 7):
        mean, _ = gp.predict([q])
        assert abs(mean - math.sin(2 * math.pi * q)) < 0.15


# ------------------------------------------------------------------------- BO
def test_bo_finds_max_of_smooth_function():
    # f peaks at x = 0.3; BO should localize it within a few dozen samples.
    def f(x):
        return -((x - 0.3) ** 2)

    bo = BayesianOptimizer(dims=1, seed=7)
    x = [0.9]
    for _ in range(25):
        bo.add_sample(x, f(x[0]))
        x = bo.next_sample()
    assert abs(bo.best_x[0] - 0.3) < 0.1
    assert bo.best_y > -0.01


def test_bo_explores_before_exploiting():
    bo = BayesianOptimizer(dims=2, seed=3)
    pts = [bo.next_sample() for _ in range(3)]
    # Pure exploration with no samples: points differ and live in [0,1]^2.
    assert all(0.0 <= v <= 1.0 for p in pts for v in p)


# --------------------------------------------------------------- param manager
def _simulate(pm, optimum_threshold, steps=4000):
    """Feed the PM a synthetic throughput model peaked at optimum_threshold:
    score falls off with log-distance from the optimum and with cycle time."""
    for _ in range(steps):
        if pm.done:
            break
        t = pm.threshold
        c = pm.cycle_ms
        log_dist = abs(math.log2(max(t, 1)) -
                       math.log2(optimum_threshold))
        score = 1e9 * math.exp(-0.5 * log_dist) / (1.0 + 0.05 * c)
        # Update takes (bytes, seconds): synthesize bytes for 1 second.
        pm.update(int(score), 1.0)
    return pm


def test_param_manager_converges_to_good_threshold():
    pm = NativeParameterManager(initial_threshold=128 << 20,
                                initial_cycle_ms=10.0,
                                warmup_samples=1, steps_per_sample=2,
                                max_samples=16)
    _simulate(pm, optimum_threshold=8 << 20)
    assert pm.done
    # Within 2 octaves of the optimum (the synthetic surface is broad).
    assert abs(math.log2(pm.threshold) - math.log2(8 << 20)) < 3.0


def test_param_manager_reports_scores():
    pm = NativeParameterManager(initial_threshold=64 << 20,
                                initial_cycle_ms=5.0,
                                warmup_samples=0, steps_per_sample=1,
                                max_samples=5)
    _simulate(pm, optimum_threshold=64 << 20, steps=100)
    assert pm.best_score > 0


# ------------------------------------------------------------- runtime wiring
def test_autotuner_runtime_wiring(tmp_path):
    from horovod_tpu.common.knobs import Knobs
    from horovod_tpu.utils.autotune import Autotuner

    log_file = tmp_path / "autotune.csv"
    knobs = Knobs({"HOROVOD_AUTOTUNE": True,
                   "HOROVOD_AUTOTUNE_LOG": str(log_file),
                   "HOROVOD_AUTOTUNE_WARMUP_SAMPLES": 0,
                   "HOROVOD_AUTOTUNE_STEPS_PER_SAMPLE": 1,
                   "HOROVOD_AUTOTUNE_BAYES_OPT_MAX_SAMPLES": 4})
    tuner = Autotuner(knobs)
    t0 = tuner.fusion_threshold
    assert t0 == knobs["HOROVOD_FUSION_THRESHOLD"]
    for i in range(10):
        with tuner.measure(nbytes=100 << 20):
            pass
        if tuner.done:
            break
    assert tuner.done
    tuner.close()
    text = log_file.read_text()
    assert "threshold_bytes" in text
    assert len(text.strip().splitlines()) >= 2


def test_fusion_threshold_follows_autotuner(hvd):
    rt = __import__("horovod_tpu.runtime", fromlist=["get"]).get()
    assert rt.fusion_threshold() == rt.knobs["HOROVOD_FUSION_THRESHOLD"]


def test_core_autotune_loopback():
    """Native core cycle-loop autotune: enable on a 1-rank loopback core,
    submit traffic, check the autotune state advances."""
    from horovod_tpu.common.basics import CoordinationCore, LoopbackHub

    hub = LoopbackHub(1)
    core = CoordinationCore.loopback(hub, rank=0, cycle_ms=1.0)
    try:
        core.enable_autotune(warmup_samples=0, steps_per_sample=1,
                             max_samples=3)
        state0 = core.autotune_state()
        assert state0 is not None
        for i in range(40):
            core.submit(f"t{i}", "f32:4:allreduce:1", nbytes=1 << 20)
            r = core.wait(timeout_s=5.0)
            assert r is not None
            state = core.autotune_state()
            if state["done"]:
                break
        assert core.autotune_state()["done"]
    finally:
        core.shutdown()
        core.close()
        hub.close()


def test_tuned_threshold_propagates_to_bucket_planner(hvd, monkeypatch):
    """The autotuner's LIVE threshold must drive the fusion plan the
    optimizer path builds when no explicit threshold is passed
    (VERDICT-r2 #9; reference: ParameterManager -> fusion buffer size)."""
    import jax
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from horovod_tpu.common.knobs import Knobs
    from jax import shard_map
    from horovod_tpu.ops.fusion import make_plan
    from horovod_tpu.optimizer import sync_gradients
    from horovod_tpu.utils.autotune import Autotuner
    import horovod_tpu.runtime as hrt

    rt = hrt.get()
    tuner = Autotuner(Knobs({"HOROVOD_AUTOTUNE": True,
                             "HOROVOD_AUTOTUNE_WARMUP_SAMPLES": 0,
                             "HOROVOD_AUTOTUNE_STEPS_PER_SAMPLE": 1}))
    monkeypatch.setattr(rt, "autotuner", tuner)

    n = hvd.size()
    gs = [np.random.RandomState(k).randn(n, 64).astype(np.float32)
          for k in range(6)]
    shapes = [(64,)] * 6
    dtypes = [np.dtype(np.float32)] * 6

    recorded = {}
    # The SPMD sync path routes through the runtime's BucketPlanCache
    # (same cache the eager path uses — the hvd_fusion_plan_cache_*
    # metrics move for both); spy there to see the threshold it plans at.
    real_get = rt.plan_cache.get

    def spy(shapes_, dtypes_, threshold):
        recorded["threshold"] = threshold
        return real_get(shapes_, dtypes_, threshold)

    monkeypatch.setattr(rt.plan_cache, "get", spy)

    def run():
        def body(*leaves):
            return tuple(sync_gradients(list(leaves), "hvd"))
        return jax.jit(shard_map(
            body, mesh=rt.mesh, in_specs=(P("hvd"),) * 6,
            out_specs=(P("hvd"),) * 6, check_vma=False))(*gs)

    run()
    assert recorded["threshold"] == tuner.fusion_threshold

    # simulate a tuned value: the next plan must use it (one bucket of
    # <=300B holds exactly one 256B tensor)
    tuner._threshold = 300
    run()
    assert recorded["threshold"] == 300
    plan = make_plan(shapes, dtypes, 300)
    assert all(len(b.indices) == 1 for b in plan.buckets)
    tuner.close()
