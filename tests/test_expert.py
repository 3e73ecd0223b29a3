"""Expert parallelism (parallel/expert.py): the all_to_all MoE data path
must equal the single-device reference with identical routing math,
gradients must flow, and capacity dropping must behave."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from horovod_tpu.parallel.expert import (init_moe_params, make_moe_fn,
                                         moe_dense_reference,
                                         moe_shardings)

EP = 4


def _mesh(hvd):
    return Mesh(np.array(jax.devices()[:EP]).reshape(EP), ("ep",))


def _sharded_reference(params, x, n_experts, capacity_factor, ep):
    """Per-shard dense reference: routing (incl. cumsum positions and
    capacity drops) happens within each chip's token shard, exactly as
    the distributed path does."""
    T = x.shape[0]
    t_local = T // ep
    capacity = int(np.ceil(t_local * capacity_factor / n_experts))
    ys, auxs = [], []
    for s in range(ep):
        y, aux = moe_dense_reference(params,
                                     x[s * t_local:(s + 1) * t_local],
                                     n_experts, capacity)
        ys.append(y)
        auxs.append(aux)
    return jnp.concatenate(ys), jnp.mean(jnp.stack(auxs))


def test_moe_matches_dense_reference(hvd):
    mesh = _mesh(hvd)
    E, D, H, T = 8, 16, 32, 64
    params = init_moe_params(jax.random.PRNGKey(0), D, H, E)
    x = jax.random.normal(jax.random.PRNGKey(1), (T, D))

    fn = make_moe_fn(mesh, n_experts=E, capacity_factor=2.0)
    y, aux = fn(params, x)
    y_ref, aux_ref = _sharded_reference(params, x, E, 2.0, EP)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(float(aux), float(aux_ref), rtol=1e-5)


def test_moe_capacity_drops_tokens(hvd):
    """With a tiny capacity factor some tokens must be dropped (output
    exactly zero), never silently mis-routed."""
    mesh = _mesh(hvd)
    E, D, H, T = 4, 8, 16, 32
    params = init_moe_params(jax.random.PRNGKey(2), D, H, E)
    x = jax.random.normal(jax.random.PRNGKey(3), (T, D))

    fn = make_moe_fn(mesh, n_experts=E, capacity_factor=0.5)
    y, _ = fn(params, x)
    y_ref, _ = _sharded_reference(params, x, E, 0.5, EP)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=2e-4, atol=2e-5)
    dropped = np.all(np.asarray(y) == 0.0, axis=-1)
    assert dropped.any()  # capacity 0.5 must drop something
    assert not dropped.all()


def test_moe_gradients_flow(hvd):
    mesh = _mesh(hvd)
    E, D, H, T = 4, 8, 16, 32
    params = init_moe_params(jax.random.PRNGKey(4), D, H, E)
    x = jax.random.normal(jax.random.PRNGKey(5), (T, D))
    tgt = jax.random.normal(jax.random.PRNGKey(6), (T, D))

    fn = make_moe_fn(mesh, n_experts=E, capacity_factor=2.0)

    def loss(p):
        y, aux = fn(p, x)
        return jnp.mean((y - tgt) ** 2) + 0.01 * aux

    g = jax.grad(loss)(params)
    for k in ("router", "wi", "wo"):
        assert np.isfinite(np.asarray(g[k])).all()
        assert float(jnp.abs(g[k]).sum()) > 0.0, k


def test_moe_train_step_converges(hvd):
    import optax
    mesh = _mesh(hvd)
    E, D, H, T = 4, 8, 16, 64
    params = init_moe_params(jax.random.PRNGKey(7), D, H, E)
    params = jax.device_put(params, moe_shardings(mesh, params))
    x = jax.random.normal(jax.random.PRNGKey(8), (T, D))
    tgt = jnp.tanh(x @ jax.random.normal(jax.random.PRNGKey(9), (D, D)))

    fn = make_moe_fn(mesh, n_experts=E, capacity_factor=2.0)
    opt = optax.adam(1e-2)
    state = opt.init(params)

    @jax.jit
    def step(p, s):
        def loss(q):
            y, aux = fn(q, x)
            return jnp.mean((y - tgt) ** 2) + 0.01 * aux
        l, g = jax.value_and_grad(loss)(p)
        up, s = opt.update(g, s)
        return optax.apply_updates(p, up), s, l

    losses = []
    for _ in range(30):
        params, state, l = step(params, state)
        losses.append(float(l))
    assert losses[-1] < losses[0] * 0.8, (losses[0], losses[-1])


def test_moe_rejects_indivisible_experts(hvd):
    mesh = _mesh(hvd)
    with pytest.raises(ValueError, match="not divisible"):
        make_moe_fn(mesh, n_experts=6)


# ------------------------------------------------------------ MoE model zoo
def test_moe_llama_trains_dense(hvd):
    """models/moe_llama: dense path trains (loss drops, aux finite)."""
    import optax
    from horovod_tpu.models import moe_llama

    cfg = moe_llama.CONFIGS["tiny"]
    params = moe_llama.init(jax.random.PRNGKey(0), cfg)
    ids = jnp.asarray(np.random.RandomState(0).randint(
        0, cfg.vocab, (4, 33)), jnp.int32)
    opt = optax.adam(1e-3)
    state = opt.init(params)

    @jax.jit
    def step(p, s):
        l, g = jax.value_and_grad(
            lambda q: moe_llama.loss_fn(q, ids, cfg))(p)
        up, s = opt.update(g, s)
        import optax as _o
        return _o.apply_updates(p, up), s, l

    losses = []
    for _ in range(12):
        params, state, l = step(params, state)
        losses.append(float(l))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0], losses


def test_moe_llama_ep_path_matches_dense(hvd):
    """The SAME params through the expert-parallel moe_fn must produce
    the same logits as the dense path (per-shard routing; batch shaped so
    shards align)."""
    from horovod_tpu.models import moe_llama
    from horovod_tpu.parallel.expert import make_moe_fn

    cfg = moe_llama.CONFIGS["tiny"]
    mesh = _mesh(hvd)
    params = moe_llama.init(jax.random.PRNGKey(1), cfg)
    # B*S divisible by ep, and capacity factor high so that dense
    # (global routing) and EP (per-shard routing) drop nothing.
    ids = jnp.asarray(np.random.RandomState(1).randint(
        0, cfg.vocab, (4, 17)), jnp.int32)
    big = dataclasses_replace_cf(cfg, 8.0)
    fn = make_moe_fn(mesh, n_experts=cfg.n_experts, capacity_factor=8.0)
    logits_ep, _ = moe_llama.apply(params, ids[:, :-1], big, moe_fn=fn)
    logits_dense, _ = moe_llama.apply(params, ids[:, :-1], big)
    np.testing.assert_allclose(np.asarray(logits_ep),
                               np.asarray(logits_dense),
                               rtol=5e-4, atol=5e-5)


def dataclasses_replace_cf(cfg, cf):
    import dataclasses
    return dataclasses.replace(cfg, capacity_factor=cf)


def test_moe_llama_param_count(hvd):
    from horovod_tpu.models import moe_llama
    cfg = moe_llama.CONFIGS["tiny"]
    params = moe_llama.init(jax.random.PRNGKey(2), cfg)
    n = sum(int(np.prod(l.shape))
            for l in jax.tree_util.tree_leaves(params))
    assert n == moe_llama.param_count(cfg), (n, moe_llama.param_count(cfg))


# -------------------------------------------------------------- top-k routing
def test_moe_top2_matches_dense_reference(hvd):
    mesh = _mesh(hvd)
    E, D, H, T = 8, 16, 32, 64
    params = init_moe_params(jax.random.PRNGKey(10), D, H, E)
    x = jax.random.normal(jax.random.PRNGKey(11), (T, D))

    fn = make_moe_fn(mesh, n_experts=E, capacity_factor=2.0,
                     experts_per_token=2)
    y, aux = fn(params, x)

    t_local = T // EP
    capacity = int(np.ceil(t_local * 2 * 2.0 / E))
    ys, auxs = [], []
    for s in range(EP):
        yy, aa = moe_dense_reference(params,
                                     x[s * t_local:(s + 1) * t_local],
                                     E, capacity, experts_per_token=2)
        ys.append(yy)
        auxs.append(aa)
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(jnp.concatenate(ys)),
                               rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(float(aux),
                               float(jnp.mean(jnp.stack(auxs))), rtol=1e-5)


def test_moe_top2_equals_full_soft_mixture_when_k_is_E(hvd):
    """k = E = 2 with ample capacity: every token reaches BOTH experts and
    the renormalized top-2 gates are the full softmax — the MoE output
    must equal the dense soft mixture sum_e p_e * expert_e(x)."""
    mesh = Mesh(np.array(jax.devices()[:2]), ("ep",))
    E, D, H, T = 2, 8, 16, 32
    params = init_moe_params(jax.random.PRNGKey(12), D, H, E)
    x = jax.random.normal(jax.random.PRNGKey(13), (T, D))

    fn = make_moe_fn(mesh, n_experts=E, capacity_factor=4.0,
                     experts_per_token=2)
    y, _ = fn(params, x)

    probs = jax.nn.softmax(x @ params["router"], axis=-1)
    h = jax.nn.gelu(jnp.einsum("td,edh->teh", x, params["wi"]))
    full = jnp.einsum("teh,ehd->ted", h, params["wo"])
    soft = jnp.einsum("ted,te->td", full, probs)
    np.testing.assert_allclose(np.asarray(y), np.asarray(soft),
                               rtol=2e-4, atol=2e-5)


def test_moe_llama_mixtral_config_trains(hvd):
    import optax
    from horovod_tpu.models import moe_llama

    cfg = moe_llama.CONFIGS["mixtral-tiny"]
    assert cfg.experts_per_token == 2
    params = moe_llama.init(jax.random.PRNGKey(14), cfg)
    ids = jnp.asarray(np.random.RandomState(5).randint(
        0, cfg.vocab, (4, 33)), jnp.int32)
    opt = optax.adam(1e-3)
    state = opt.init(params)

    @jax.jit
    def step(p, s):
        l, g = jax.value_and_grad(
            lambda q: moe_llama.loss_fn(q, ids, cfg))(p)
        up, s = opt.update(g, s)
        return optax.apply_updates(p, up), s, l

    losses = []
    for _ in range(10):
        params, state, l = step(params, state)
        losses.append(float(l))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0], losses


# --------------------------------------------- the expert tile's kernel
@pytest.mark.parametrize("dtype,act,tol", [
    (jnp.float32, jax.nn.silu, 1e-5), (jnp.float32, jax.nn.relu, 1e-5),
    # bfloat16 rounds where gated_ffn rounds; the blocks' float32 sums come
    # in another order: a few 1e-3 of the output's spread
    (jnp.bfloat16, jax.nn.relu, 2e-2)])
def test_tile_ffn_is_gated_ffn_of_the_numbered_expert(dtype, act, tol):
    """The kernel (interpreted here) against the plain three-matrix expert,
    the expert's number traced, a hidden width that is cut into blocks."""
    from horovod_tpu.parallel import expert as X
    E, D, F, tile = 5, 128, 384, 16
    p = X.init_held_experts(jax.random.PRNGKey(0), D, F, E, E, dtype)["experts"]
    x = jax.random.normal(jax.random.PRNGKey(1), (tile, D)).astype(dtype)
    old = X._TILE_FFN_VMEM
    X._TILE_FFN_VMEM = 2 * 3 * D * jnp.dtype(dtype).itemsize * 128  # 3 blocks
    try:
        run = jax.jit(lambda e: X.tile_ffn(p["w_gate"], p["w_up"],
                                           p["w_down"], x, e, act))
        for e in (0, 3, 4):
            want = X.gated_ffn(p["w_gate"][e], p["w_up"][e], p["w_down"][e],
                               x, act)
            got = run(jnp.int32(e))
            assert got.dtype == jnp.float32 and got.shape == (tile, D)
            assert float(jnp.max(jnp.abs(got - want))) \
                < tol * float(jnp.std(want)), e
    finally:
        X._TILE_FFN_VMEM = old


# ------------------------------------------------- the router's bias
def _route_sigmoid_topk_before_the_bias(x, router_kernel, k, scale):
    """parallel/expert.py ``route_sigmoid_topk`` as it stood before it took a
    bias (PR 32), to hold the new one to it bit for bit."""
    s = jax.nn.sigmoid(jnp.dot(x.astype(jnp.float32),
                               router_kernel.astype(jnp.float32),
                               precision=jax.lax.Precision.HIGHEST))
    top, idx = jax.lax.top_k(s, k)
    return idx, scale * top / (top.sum(-1, keepdims=True) + 1e-20)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_without_a_bias_the_router_is_what_it_was(dtype):
    from horovod_tpu.parallel import expert as X
    x = jax.random.normal(jax.random.PRNGKey(2), (96, 48)).astype(dtype)
    w = (jax.random.normal(jax.random.PRNGKey(3), (48, 16)) / 7).astype(dtype)
    for run in (lambda f: f, jax.jit):
        want = run(lambda x, w: _route_sigmoid_topk_before_the_bias(
            x, w, 4, 2.5))(x, w)
        got = run(lambda x, w: X.route_sigmoid_topk(x, w, 4, 2.5))(x, w)
        got_none = run(lambda x, w: X.route_sigmoid_topk(
            x, w, 4, 2.5, bias=None))(x, w)
        for a, b, c in zip(want, got, got_none):
            assert a.dtype == b.dtype and jnp.array_equal(a, b)
            assert jnp.array_equal(a, c)
    # ... and it lowers to the same program
    text = lambda f: jax.jit(f).lower(x, w).as_text()
    assert text(lambda x, w: X.route_sigmoid_topk(x, w, 4, 2.5)) == text(
        lambda x, w: _route_sigmoid_topk_before_the_bias(x, w, 4, 2.5))


def test_a_bias_picks_and_does_not_weigh():
    from horovod_tpu.parallel import expert as X
    x = jax.random.normal(jax.random.PRNGKey(4), (200, 32))
    w = jax.random.normal(jax.random.PRNGKey(5), (32, 16)) / 6
    bias = 0.2 * jax.random.normal(jax.random.PRNGKey(6), (16,))
    s = jax.nn.sigmoid(jnp.dot(x, w, precision=jax.lax.Precision.HIGHEST))
    idx, gates = X.route_sigmoid_topk(x, w, 4, 1.0, bias=bias, eps=1e-6)
    plain, _ = X.route_sigmoid_topk(x, w, 4, 1.0, eps=1e-6)
    # the chosen four are the largest of s + bias, another four than the
    # largest of s for a good share of the tokens
    assert jnp.array_equal(jnp.sort(idx, -1),
                           jnp.sort(jax.lax.top_k(s + bias, 4)[1], -1))
    assert float((jnp.sort(idx, -1) != jnp.sort(plain, -1)).any(-1).mean()) > .2
    # their gates are the unbiased scores over their sum: a bias that is
    # the same for every expert changes nothing at all
    chosen = jnp.take_along_axis(s, idx, -1)
    assert jnp.allclose(gates, chosen / (chosen.sum(-1, keepdims=True) + 1e-6),
                        atol=1e-7)
    same = X.route_sigmoid_topk(x, w, 4, 1.0, bias=jnp.full(16, 0.7), eps=1e-6)
    for a, b in zip(same, X.route_sigmoid_topk(x, w, 4, 1.0, eps=1e-6)):
        assert jnp.array_equal(a, b)

