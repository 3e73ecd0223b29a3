"""Pallas flash attention: numerics vs the XLA reference (interpret mode
on CPU — same kernel code path that compiles on TPU), gradients through
the custom VJP, GQA mapping, and model integration via attn_fn."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from horovod_tpu.models import layers as L
from horovod_tpu.ops.flash_attention import flash_attention


def _qkv(B=2, S=128, H=4, HK=2, D=16, dtype=jnp.float32, seed=0):
    rng = np.random.RandomState(seed)
    return (jnp.asarray(rng.randn(B, S, H, D), dtype),
            jnp.asarray(rng.randn(B, S, HK, D), dtype),
            jnp.asarray(rng.randn(B, S, HK, D), dtype))


@pytest.mark.parametrize("causal", [True, False])
def test_matches_reference(causal):
    q, k, v = _qkv()
    ref = L.causal_attention(q, k, v, causal=causal)
    out = flash_attention(q, k, v, causal, 64, 32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_gqa_head_mapping():
    # H == HK degenerate + 4:1 grouping must both match
    for H, HK in ((4, 4), (8, 2)):
        q, k, v = _qkv(H=H, HK=HK, seed=1)
        ref = L.causal_attention(q, k, v, causal=True)
        out = flash_attention(q, k, v, True, 64, 64)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)


def test_uneven_blocks_and_full_block():
    q, k, v = _qkv(S=128)
    ref = L.causal_attention(q, k, v, causal=True)
    for bq, bk in ((128, 128), (32, 128), (128, 32)):
        out = flash_attention(q, k, v, True, bq, bk)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)


def test_rejects_indivisible_seq():
    q, k, v = _qkv(S=96)
    with pytest.raises(ValueError, match="divide"):
        flash_attention(q, k, v, True, 64, 64)


def test_gradients_match_reference():
    q, k, v = _qkv(S=64)

    def f_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, True, 32, 32) ** 2)

    def f_ref(q, k, v):
        return jnp.sum(L.causal_attention(q, k, v, causal=True) ** 2)

    gf = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_llama_forward_with_flash_attn():
    from horovod_tpu.models import llama
    cfg = llama.CONFIGS["tiny"]
    params = llama.init(jax.random.PRNGKey(0), cfg)
    ids = jnp.asarray(np.random.RandomState(0).randint(
        0, cfg.vocab, (2, 64)), jnp.int32)
    ref = llama.apply(params, ids, cfg)
    out = llama.apply(params, ids, cfg,
                      attn_fn=lambda q, k, v: flash_attention(
                          q, k, v, True, 32, 32))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=5e-4, atol=5e-4)


def test_interprets_only_on_the_cpu_backend(monkeypatch):
    """interpret=None means the interpreter on `cpu` and nowhere else: a
    backend that is merely not CALLED `tpu` compiles the kernel or
    raises — it must never run a chip measurement interpreted."""
    from horovod_tpu.ops import flash_attention as FA
    for backend, want in (("cpu", True), ("tpu", False), ("other", False)):
        monkeypatch.setattr(jax, "default_backend", lambda b=backend: b)
        assert FA._resolve_blocks(128, 64, 64, None)[2] is want, backend
    # an explicit choice is never overridden
    assert FA._resolve_blocks(128, 64, 64, False)[2] is False


def test_rejects_non_divisible_gqa():
    q, _, _ = _qkv(H=8, HK=2)
    _, k, v = _qkv(H=8, HK=2)
    k3 = jnp.concatenate([k, k[:, :, :1]], axis=2)  # 3 kv heads
    v3 = jnp.concatenate([v, v[:, :, :1]], axis=2)
    with pytest.raises(ValueError, match="multiple of kv heads"):
        flash_attention(q, k3, v3, True, 64, 64)


def test_backward_kernels_gqa_and_noncausal():
    """The Pallas backward kernels (dq; dk/dv with group summation) must
    match XLA grads for GQA and non-causal attention."""
    rng = np.random.RandomState(7)
    B, S, H, HK, D = 2, 64, 8, 2, 16
    q = jnp.asarray(rng.randn(B, S, H, D), jnp.float32)
    k = jnp.asarray(rng.randn(B, S, HK, D), jnp.float32)
    v = jnp.asarray(rng.randn(B, S, HK, D), jnp.float32)

    for causal in (True, False):
        def f_flash(q, k, v):
            return jnp.sum(flash_attention(q, k, v, causal, 32, 16) ** 2)

        def f_ref(q, k, v):
            return jnp.sum(L.causal_attention(q, k, v, causal=causal) ** 2)

        gf = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
        for name, a, b in zip("qkv", gf, gr):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5,
                err_msg=f"d{name} causal={causal}")


def test_backward_bf16_inputs():
    """bf16 in, bf16 grads out; fp32 accumulation keeps them close to the
    fp32 reference."""
    rng = np.random.RandomState(8)
    B, S, H, D = 1, 32, 2, 8
    mk = lambda: jnp.asarray(rng.randn(B, S, H, D), jnp.bfloat16)
    q, k, v = mk(), mk(), mk()

    g = jax.grad(lambda q, k, v: jnp.sum(
        flash_attention(q, k, v, True, 16, 16).astype(jnp.float32)),
        argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda q, k, v: jnp.sum(
        L.causal_attention(q.astype(jnp.float32), k.astype(jnp.float32),
                           v.astype(jnp.float32), causal=True)),
        argnums=(0, 1, 2))(q.astype(jnp.float32), k.astype(jnp.float32),
                           v.astype(jnp.float32))
    for a, b in zip(g, gr):
        assert a.dtype == jnp.bfloat16
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b), rtol=0.1, atol=0.1)


def test_backward_in_jitted_train_step():
    """Full llama train step with flash attention end-to-end (the bench
    --flash path): loss drops, grads finite."""
    import dataclasses
    import optax
    from horovod_tpu.models import llama

    cfg = dataclasses.replace(llama.CONFIGS["tiny"], max_seq=64)
    params = llama.init(jax.random.PRNGKey(0), cfg)
    ids = jnp.asarray(np.random.RandomState(0).randint(
        0, cfg.vocab, (2, 33)), jnp.int32)
    opt = optax.adam(1e-3)
    state = opt.init(params)

    def attn(q, k, v):
        return flash_attention(q, k, v, True, 32, 32)

    @jax.jit
    def step(p, s):
        l, g = jax.value_and_grad(
            lambda p_: llama.loss_fn(p_, ids, cfg, attn_fn=attn))(p)
        up, s = opt.update(g, s)
        import optax as _o
        return _o.apply_updates(p, up), s, l

    losses = []
    for _ in range(8):
        params, state, l = step(params, state)
        losses.append(float(l))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0], losses


def test_score_dtype_input_matches_f32():
    """score_dtype=None stores the score slab in the input dtype (half
    the HBM traffic for bf16); numerics must stay within one bf16
    rounding of the fp32-score path, and the fp32-input path must be
    bit-identical (input dtype IS fp32 there)."""
    rng = np.random.RandomState(3)
    q = jnp.asarray(rng.randn(2, 64, 4, 16), jnp.float32)
    k = jnp.asarray(rng.randn(2, 64, 2, 16), jnp.float32)
    v = jnp.asarray(rng.randn(2, 64, 2, 16), jnp.float32)
    ref = L.causal_attention(q, k, v, causal=True)
    same = L.causal_attention(q, k, v, causal=True, score_dtype=None)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(same))

    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))
    ref_b = L.causal_attention(qb, kb, vb, causal=True)
    got_b = L.causal_attention(qb, kb, vb, causal=True, score_dtype=None)
    np.testing.assert_allclose(
        np.asarray(ref_b, np.float32), np.asarray(got_b, np.float32),
        atol=3e-2, rtol=3e-2)
    # differentiable in both modes
    g = jax.grad(lambda q: jnp.sum(L.causal_attention(
        q, kb, vb, causal=True, score_dtype=None) ** 2))(qb)
    assert np.all(np.isfinite(np.asarray(g, np.float32)))


def test_score_dtype_f16_fully_masked_row_finite():
    """float16's 5-bit exponent overflows a -1e30 mask fill to -inf, and a
    fully-masked row then softmaxes to NaN; the fill must be dtype-aware
    (finfo.min).  A user mask that blanks one query row entirely is the
    trigger (ADVICE r3)."""
    rng = np.random.RandomState(7)
    q = jnp.asarray(rng.randn(1, 8, 2, 16), jnp.float16)
    k = jnp.asarray(rng.randn(1, 8, 1, 16), jnp.float16)
    v = jnp.asarray(rng.randn(1, 8, 1, 16), jnp.float16)
    mask = np.ones((1, 2, 8, 8), bool)
    mask[:, :, 3, :] = False  # query row 3 sees nothing
    out = L.causal_attention(q, k, v, causal=False,
                             mask=jnp.asarray(mask), score_dtype=None)
    assert np.all(np.isfinite(np.asarray(out, np.float32)))
