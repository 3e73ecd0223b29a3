"""3D layout plane (parallel/layout.py + perf/costmodel solver;
docs/parallelism.md).

Composition proofs: the (dp, tp, pp) composed chain — Megatron TP over
tp, GPipe over pp, the ZeRO bucket chain over dp — is bit-near the
pure-dp reference at every (mesh, zero_level) combination under the
exact wire, and level-equivalent within a layout under lossy wires
(bucket geometry differs between layouts, so lossy cross-layout
comparisons are loose by design — docs/parallelism.md#cpu-virtual).

Solver proofs: enumeration respects the divisibility constraints,
ranking is fits-first by predicted step time, the memory cap filters,
and the chain's trace-time gauges pin the cost model's byte formulas.
"""

import functools

import numpy as np
import jax
import jax.numpy as jnp
import optax
import pytest
from jax.sharding import Mesh

from horovod_tpu.models import llama as Ll
from horovod_tpu.parallel import layout as lay
from horovod_tpu.parallel import zero as zero_mod
from horovod_tpu.perf import costmodel as cm

CFG = Ll.CONFIGS["tiny"]
B, S = 8, 16


def _mesh(dp, tp, pp):
    return Mesh(np.array(jax.devices()).reshape(dp, tp, pp),
                ("dp", "tp", "pp"))


def _ids(seed=1):
    return jax.random.randint(jax.random.PRNGKey(seed), (B, S + 1), 0,
                              CFG.vocab)


def _flat_leaves(p):
    """Stage leaves [pp, L/pp, ...] -> [L, ...] so different-pp layouts
    compare leaf-for-leaf."""
    stages = jax.tree_util.tree_map(
        lambda a: a.reshape((-1,) + a.shape[2:]), p["stages"])
    return jax.tree_util.tree_leaves(
        {"embed": p["embed"], "final_norm": p["final_norm"],
         "lm_head": p["lm_head"], "stages": stages})


@functools.lru_cache(maxsize=None)
def _train_llama(dp, tp, pp, level, wire="none", ef=None, steps=3,
                 thresh=None):
    """`steps` composed-chain steps on a fresh (dp, tp, pp) mesh from
    the seed-0 init; returns (losses, final params in stacked form).
    Cached — the dp-only reference run is shared across the matrix."""
    mesh = _mesh(dp, tp, pp)
    params = Ll.init(jax.random.PRNGKey(0), CFG)
    stacked = lay.llama_layout_params(params, pp)
    specs = lay.llama_layout_specs(stacked)
    opt = optax.adam(1e-2)
    st = lay.init_layout_state(opt, stacked, specs, mesh,
                               zero_level=level, wire_policy=wire,
                               error_feedback=ef,
                               fusion_threshold_bytes=thresh)
    step = lay.make_llama_layout_train_step(
        CFG, opt, mesh, n_micro=2, zero_level=level, wire_policy=wire,
        error_feedback=ef, fusion_threshold_bytes=thresh, donate=False)
    p = (lay.shard_layout_params(stacked, specs, mesh,
                                 fusion_threshold_bytes=thresh)
         if level == 3 else stacked)
    losses = []
    for i in range(steps):
        p, st, loss = step(p, st, _ids(seed=1))
        losses.append(float(loss))
    if level == 3:
        p = lay.gather_layout_params(p, stacked, specs, mesh,
                                     fusion_threshold_bytes=thresh)
    return losses, p


def _model8():
    """llama-tiny layout model descriptor at world=8."""
    return cm.llama_layout_model(
        vocab=CFG.vocab, dim=CFG.dim, n_layers=CFG.n_layers,
        n_heads=CFG.n_heads, n_kv_heads=CFG.n_kv_heads,
        ffn_dim=CFG.ffn_dim, batch=B, seq=S)


# ------------------------------------------------------------------ solver
def test_layout_solver_enumerates_and_ranks():
    sol = cm.solve_layout(_model8(), 8)
    assert sol["n_candidates"] == len(sol["candidates"]) > 0
    meshes = {tuple(r["layout"][a] for a in ("dp", "tp", "pp"))
              for r in sol["candidates"]}
    # tp | n_kv_heads (= 2) and pp | n_layers (= 2) bound the space.
    assert meshes == {(8, 1, 1), (4, 2, 1), (4, 1, 2), (2, 2, 2)}
    for r in sol["candidates"]:
        l = r["layout"]
        assert l["dp"] * l["tp"] * l["pp"] == 8
        assert CFG.n_kv_heads % l["tp"] == 0
        assert CFG.n_layers % l["pp"] == 0
    # Ranking: 1..N, fits-first, then predicted step ascending.
    ranks = [r["rank"] for r in sol["candidates"]]
    assert ranks == list(range(1, len(ranks) + 1))
    fitting = [r["step_s"] for r in sol["candidates"] if r["fits"]]
    assert fitting == sorted(fitting)
    assert sol["chosen"] == sol["candidates"][0]
    assert sol["chosen"]["fits"]


def test_layout_solver_memory_cap_filters():
    free = cm.solve_layout(_model8(), 8)
    totals = sorted(r["memory"]["total_bytes"]
                    for r in free["candidates"])
    # A cap between the smallest and largest rows must mark some rows
    # non-fitting and push them below every fitting row.
    cap = (totals[0] + totals[-1]) / 2.0
    sol = cm.solve_layout(_model8(), 8, mem_cap_bytes=cap)
    fits = [r["fits"] for r in sol["candidates"]]
    assert True in fits and False in fits
    assert fits == sorted(fits, reverse=True)  # fitting rows first
    assert sol["chosen"]["fits"]
    assert sol["chosen"]["memory"]["total_bytes"] <= cap
    assert sol["mem_cap_bytes"] == cap


def test_layout_solver_no_valid_factorization_raises():
    model = dict(_model8(), n_heads=3, n_kv_heads=3, n_layers=3, batch=3)
    with pytest.raises(ValueError):
        cm.solve_layout(model, 8)  # nothing divides; even dp=8 ∤ batch=3


def test_layout_cost_model_terms():
    # TP comm: 2 fwd + 2 bwd ring all_reduces per resident layer block.
    assert cm.tp_comm_bytes(1, 128, 64, 2) == 0.0
    per = cm.ring_wire_bytes(128 * 64, 4.0, 2)
    assert cm.tp_comm_bytes(2, 128, 64, 2) == pytest.approx(4.0 * 2 * per)
    # PP comm: one send per tick boundary, forward + backward.
    assert cm.pp_comm_bytes(1, 4, 32, 64) == 0.0
    assert cm.pp_comm_bytes(2, 4, 32, 64) == pytest.approx(
        2.0 * (4 + 1) * 32 * 64 * 4.0)
    # Bubble: (S-1)/(M+S-1), the pipeline.py formula.
    t = cm.layout_step_time(_model8(), 2, 2, 2, n_micro=2)
    assert t["bubble_fraction"] == pytest.approx(
        (2 - 1) / (2 + 2 - 1))
    assert t["step_s"] > 0
    # Memory: ZeRO terms divide by tp*pp (sharded weights), activations
    # divide by dp*pp only (the residual stream is tp-replicated).
    m1 = cm.layout_memory_bytes(_model8(), 8, 1, 1, zero_level=1)
    m2 = cm.layout_memory_bytes(_model8(), 2, 2, 2, zero_level=1)
    z1 = cm.zero_memory_bytes(1, _model8()["n_params"], 8)
    assert m1["params_bytes"] == pytest.approx(z1["params_bytes"])
    z2 = cm.zero_memory_bytes(1, _model8()["n_params"] / 4, 2)
    assert m2["params_bytes"] == pytest.approx(z2["params_bytes"])
    assert m2["activation_bytes"] == pytest.approx(
        (B / 2) * S * (CFG.n_layers / 2) * CFG.dim
        * cm.ACTIVATION_MULT * 4.0)


def test_layout_model_descriptor_matches_param_count():
    model = _model8()
    assert model["n_params"] == Ll.param_count(CFG)
    assert model["flops_per_step"] == pytest.approx(
        cm.train_flops_per_token(model["n_params"]) * B * S)


# ------------------------------------------------------------- knob surface
def _knobs(layout="", tp=0, pp=0, level=1):
    return {"HOROVOD_LAYOUT": layout, "HOROVOD_TP": tp,
            "HOROVOD_PP": pp, "HOROVOD_ZERO_LEVEL": level}


def test_layout_knob_validation():
    lay.validate_layout_knobs(_knobs(), world=8)
    lay.validate_layout_knobs(_knobs("auto", tp=2), world=8)
    lay.validate_layout_knobs(_knobs("2,2,2"), world=8)
    cases = [
        (_knobs("bogus"), 8, ""),          # unknown policy word
        (_knobs("2,2"), 8, ""),            # malformed triple
        (_knobs("2,2,2"), 16, ""),         # product != world
        (_knobs("0,4,2"), 8, ""),          # zero factor
        (_knobs("auto", tp=3), 8, ""),     # tp does not divide world
        (_knobs("auto", pp=3), 8, ""),     # pp does not divide world
        (_knobs("auto", tp=4, pp=4), 8, ""),  # tp*pp exceeds world
        (_knobs("dp-only", tp=2), 8, ""),  # dp-only vs tp conflict
        (_knobs("2,2,2", tp=4), 8, ""),    # triple vs HOROVOD_TP
        (_knobs("", tp=2), 8, ""),         # TP without HOROVOD_LAYOUT
        (_knobs("auto"), 8, "data=8"),     # layout vs explicit mesh
        ({"HOROVOD_LAYOUT": "", "HOROVOD_TP": -1, "HOROVOD_PP": 0,
          "HOROVOD_ZERO_LEVEL": 1}, 8, ""),  # negative degree
    ]
    for knobs, world, mesh_spec in cases:
        with pytest.raises(ValueError):
            lay.validate_layout_knobs(knobs, world=world,
                                      mesh_spec=mesh_spec)


def test_resolve_layout_modes():
    from horovod_tpu.utils import metrics as M
    assert lay.resolve_layout(8, _knobs()) is None
    assert lay.resolve_layout(8, _knobs("dp-only")) == (8, 1, 1)
    assert lay.resolve_layout(8, _knobs("4,1,2")) == (4, 1, 2)
    with pytest.raises(ValueError):
        lay.resolve_layout(16, _knobs("2,2,2"))
    # auto, topology-only: zero-FLOP model ties break toward pure dp.
    assert lay.resolve_layout(8, _knobs("auto")) == (8, 1, 1)
    # auto under constraints: the solver honors HOROVOD_TP/HOROVOD_PP
    # and the decision gauges carry the solve.
    assert lay.resolve_layout(8, _knobs("auto", tp=2, pp=2)) == (2, 2, 2)
    assert M.LAYOUT_CANDIDATES.value() > 0
    assert M.LAYOUT_CHOSEN_RANK.value() >= 1
    # auto with a model: the choice is a valid llama-tiny factorization.
    got = lay.resolve_layout(8, _knobs("auto"), model=_model8())
    assert got[0] * got[1] * got[2] == 8
    assert CFG.n_kv_heads % got[1] == 0 and CFG.n_layers % got[2] == 0
    assert lay.layout_mesh_spec(*got).startswith(f"dp={got[0]},tp=")


def test_layout_of_mesh_rejects_legacy():
    # An explicit legacy mesh, not the session fixture: under the CI
    # layout knob dim the session mesh IS a 3-axis layout mesh.
    legacy = Mesh(np.array(jax.devices()), ("hvd",))
    with pytest.raises(ValueError):
        lay.layout_of_mesh(legacy)
    assert lay.layout_of_mesh(_mesh(4, 2, 1)) == (4, 2, 1)


# ------------------------------------------------------------- restacking
def test_llama_layout_restack_and_specs():
    params = Ll.init(jax.random.PRNGKey(0), CFG)
    for pp in (1, 2):
        stacked = lay.llama_layout_params(params, pp)
        lead = next(iter(stacked["stages"].values()))
        first = jax.tree_util.tree_leaves(lead)[0]
        assert first.shape[:2] == (pp, CFG.n_layers // pp)
        # Flattened back out, every layer leaf is bit-identical.
        ref = _flat_leaves(lay.llama_layout_params(params, 1))
        got = _flat_leaves(stacked)
        for a, b in zip(got, ref):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        specs = lay.llama_layout_specs(stacked)
        assert specs["stages"]["wq"]["kernel"] == \
            jax.sharding.PartitionSpec("pp", None, None, "tp")
        assert specs["stages"]["w_down"]["kernel"] == \
            jax.sharding.PartitionSpec("pp", None, "tp", None)
        assert specs["stages"]["attn_norm"]["scale"] == \
            jax.sharding.PartitionSpec("pp")
        assert specs["lm_head"]["kernel"] == jax.sharding.PartitionSpec()
    with pytest.raises(ValueError):
        lay.llama_layout_params(params, 3)  # 3 does not divide n_layers


# ------------------------------------------------------- composed training
def test_generic_layout_step_trains_toy_on_3d_mesh():
    """The generic (replicated-params) composed path: the quadratic toy
    trains on the full 3D mesh with the chain over dp, and matches a
    single-device optax loop exactly (docs/parallelism.md#generic)."""
    mesh = _mesh(2, 2, 2)
    params = {"w": jnp.linspace(-1.0, 1.0, 5), "b": jnp.float32(0.1)}
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(16, 5).astype(np.float32))
    y = jnp.asarray(rng.randn(16).astype(np.float32))

    def loss_fn(p, batch):
        xb, yb = batch
        return jnp.mean((xb @ p["w"] + p["b"] - yb) ** 2)

    opt = optax.adam(0.1)
    st = lay.init_layout_state(opt, params, jax.sharding.PartitionSpec(),
                               mesh, zero_level=2)
    step = lay.make_layout_train_step(loss_fn, opt, mesh, zero_level=2,
                                      donate=False)
    p = params
    for _ in range(4):
        p, st, loss = step(p, st, (x, y))

    ref_p, ref_st = params, opt.init(params)
    for _ in range(4):
        g = jax.grad(loss_fn)(ref_p, (x, y))
        updates, ref_st = opt.update(g, ref_st, ref_p)
        ref_p = optax.apply_updates(ref_p, updates)
    np.testing.assert_allclose(np.asarray(p["w"]),
                               np.asarray(ref_p["w"]), atol=1e-5)
    np.testing.assert_allclose(float(p["b"]), float(ref_p["b"]),
                               atol=1e-5)


def test_composed_core_bit_near():
    """Fast-tier slice of the composition matrix: the full (2, 2, 2)
    mesh at level 2 against the dp-only composed reference at level 1 —
    losses track the pure reference and final params agree to float32
    accumulation-order noise.

    The params' tolerance is 5e-4, a twentieth of ONE of the three Adam
    steps (lr 1e-2), and not the 1e-4 the losses' noise would suggest: Adam
    divides a gradient by the root of its second moment, so where a
    gradient element is near zero the 1e-8 by which two orders of a float32
    sum differ is a few percent of the normalised step.  The two meshes
    reduce in different orders, and one element of 16,384 then lands 1.6e-4
    apart after three steps (every run since PR 44); a wrong gradient moves
    an element by whole steps of 1e-2."""
    ref_loss = float(Ll.loss_fn(Ll.init(jax.random.PRNGKey(0), CFG),
                                _ids(seed=1), CFG))
    base_losses, base_p = _train_llama(8, 1, 1, level=1)
    losses, p = _train_llama(2, 2, 2, level=2)
    assert base_losses[0] == pytest.approx(ref_loss, abs=1e-4)
    for a, b in zip(losses, base_losses):
        assert a == pytest.approx(b, abs=2e-5)
    for a, b in zip(_flat_leaves(p), _flat_leaves(base_p)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=5e-4)


def test_chain_trace_gauges_pin_cost_model():
    """Satellite (b) closure: the composed chain's trace-time gauges —
    the zero plane bytes recorded by _record_zero_trace with n = dp —
    equal the cost model's zero_comm_bytes at the tp/pp-divided local
    parameter count (single forced bucket, exact wire)."""
    from horovod_tpu.utils import metrics as M
    dp, tp, pp = 4, 2, 1
    losses, _ = _train_llama(dp, tp, pp, level=1, steps=1,
                             thresh=1 << 30)
    assert np.isfinite(losses[0])
    assert M.ZERO_LEVEL.value() == 1
    mesh = _mesh(dp, tp, pp)
    stacked = lay.llama_layout_params(
        Ll.init(jax.random.PRNGKey(0), CFG), pp)
    local = lay._local_template(stacked,
                                lay.llama_layout_specs(stacked), mesh)
    nelems = sum(int(np.prod(l.shape))
                 for l in jax.tree_util.tree_leaves(local))
    padded = zero_mod._padded_len(nelems, dp)
    expect = cm.zero_comm_bytes(padded, dp, 1)["total_bytes"]
    got = M.OVERLAP_EXPOSED_BYTES.value(plane="zero1")
    assert got == pytest.approx(expect)


@pytest.mark.parametrize("mesh_dims", [(8, 1, 1), (4, 2, 1), (4, 1, 2),
                                       (2, 2, 2)])
def test_composed_matrix_all_meshes_levels(mesh_dims):
    """The full composition matrix (slow tier): every valid llama-tiny
    factorization of world=8 at every zero level, exact wire, against
    the dp-only level-1 composed reference AND the single-device
    llama.loss_fn forward."""
    ref_loss = float(Ll.loss_fn(Ll.init(jax.random.PRNGKey(0), CFG),
                                _ids(seed=1), CFG))
    base_losses, base_p = _train_llama(8, 1, 1, level=1)
    base_flat = _flat_leaves(base_p)
    dp, tp, pp = mesh_dims
    for level in (1, 2, 3):
        losses, p = _train_llama(dp, tp, pp, level=level)
        assert losses[0] == pytest.approx(ref_loss, abs=1e-4)
        for a, b in zip(losses, base_losses):
            assert a == pytest.approx(b, abs=2e-5)
        for a, b in zip(_flat_leaves(p), base_flat):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32),
                                       atol=1e-4)


def test_composed_lossy_wire_levels_agree():
    """Lossy wires thread through the composed chain unchanged: within
    one layout the three levels remain exactly equivalent under
    int8_ring + EF (the zero chain's invariant), the pre-update forward
    still matches the reference bitwise, and training stays sane.
    Cross-layout comparisons are loose — bucket geometry differs, so
    quantization chunks differ (docs/parallelism.md#cpu-virtual)."""
    ref_loss = float(Ll.loss_fn(Ll.init(jax.random.PRNGKey(0), CFG),
                                _ids(seed=1), CFG))
    base_losses, _ = _train_llama(8, 1, 1, level=1)
    runs = {level: _train_llama(2, 2, 2, level=level, wire="int8_ring",
                                ef=True)
            for level in (1, 2, 3)}
    l1, p1 = runs[1]
    assert l1[0] == pytest.approx(ref_loss, abs=1e-4)
    assert l1[-1] < l1[0]  # int8 grads still train
    for level in (2, 3):
        ll, pl = runs[level]
        for a, b in zip(ll, l1):
            assert a == pytest.approx(b, abs=2e-5)
        # Param tolerance is looser than the exact-wire matrix: a
        # 1-ulp difference in a pre-quantization gradient can flip an
        # int8 bucket boundary, and the flip's size is the QUANTIZATION
        # STEP (bucket scale / 127) regardless of the element's own
        # magnitude (observed: 1-4/16k elements, <= ~2e-3 absolute).
        for a, b in zip(_flat_leaves(pl), _flat_leaves(p1)):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32),
                                       atol=5e-3)
    # Loose envelope vs the exact-wire reference trajectory.
    for a, b in zip(l1, base_losses):
        assert abs(a - b) < 0.3


# ------------------------------------------------------- report + doctor
def test_perf_report_layout_section_and_doctor():
    from horovod_tpu.perf import ledger
    from horovod_tpu.runner.doctor import render_perf
    led = ledger.PerfLedger()
    with pytest.raises(ValueError):
        led.configure(layout_model={"n_params": 1})  # world missing
    led.configure(chip="cpu", link="loopback",
                  layout_model=dict(_model8(), world=8,
                                    active={"dp": 4, "tp": 2, "pp": 1,
                                            "zero_level": 1}))
    led.record_step(0.05)
    rep = led.report()
    sec = rep["layout"]
    assert sec["world"] == 8 and sec["n_candidates"] > 0
    assert sec["chosen"]["rank"] == 1
    assert sec["active"]["layout"] == {"dp": 4, "tp": 2, "pp": 1}
    assert sec["active"]["zero_level"] == 1
    assert sec["predicted_vs_measured"]["step_ratio"] > 0
    # mem_cap defaults to the memory plane's measured headroom when the
    # sampler has run in this process; otherwise it stays None and
    # every candidate fits.
    if sec["mem_cap_bytes"] is None:
        assert all(r["fits"] for r in sec["candidates"])
    view = {"fleet": {"verdict": "compute-bound",
                      "decomposition": rep["decomposition"]},
            "ranks": {"0": dict(rep, rank=0)}}
    text = render_perf(view)
    assert "layout solver" in text
    assert "dp x tp x pp" in text
    assert "predicted/measured" in text


def test_layout_section_respects_explicit_mem_cap():
    from horovod_tpu.perf import ledger
    led = ledger.PerfLedger()
    free = cm.solve_layout(_model8(), 8)
    totals = sorted(r["memory"]["total_bytes"]
                    for r in free["candidates"])
    cap = (totals[0] + totals[-1]) / 2.0
    led.configure(layout_model=dict(_model8(), world=8,
                                    mem_cap_bytes=cap))
    led.record_step(0.05)
    sec = led.report()["layout"]
    assert sec["mem_cap_bytes"] == cap
    assert sec["chosen"]["memory"]["total_bytes"] <= cap
    assert not all(r["fits"] for r in sec["candidates"])
