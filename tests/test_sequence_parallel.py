"""Sequence-parallel attention tests: ring + ulysses must match full
single-chip attention (SURVEY.md §5: long-context first-class)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from horovod_tpu.models.layers import causal_attention
from horovod_tpu.parallel.sequence import ring_attention, ulysses_attention


def _qkv(B=2, S=32, H=8, D=16, Hkv=None, seed=0):
    rng = np.random.RandomState(seed)
    Hkv = Hkv or H
    q = rng.randn(B, S, H, D).astype(np.float32)
    k = rng.randn(B, S, Hkv, D).astype(np.float32)
    v = rng.randn(B, S, Hkv, D).astype(np.float32)
    return jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)


@pytest.mark.parametrize("causal", [True, False])
def test_ring_attention_matches_full(hvd, causal):
    mesh = hvd.mesh()
    q, k, v = _qkv()
    ref = causal_attention(q, k, v, causal=causal)

    f = jax.jit(shard_map(
        lambda q, k, v: ring_attention(q, k, v, axis_name="hvd",
                                       causal=causal),
        mesh=mesh, in_specs=(P(None, "hvd"),) * 3,
        out_specs=P(None, "hvd")))
    out = f(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-4, rtol=1e-3)


def test_ring_attention_gqa(hvd):
    mesh = hvd.mesh()
    q, k, v = _qkv(H=8, Hkv=4)
    ref = causal_attention(q, k, v, causal=True)
    f = jax.jit(shard_map(
        lambda q, k, v: ring_attention(q, k, v, axis_name="hvd"),
        mesh=mesh, in_specs=(P(None, "hvd"),) * 3,
        out_specs=P(None, "hvd")))
    out = f(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-4, rtol=1e-3)


@pytest.mark.parametrize("causal", [True, False])
def test_ulysses_matches_full(hvd, causal):
    mesh = hvd.mesh()
    q, k, v = _qkv()
    ref = causal_attention(q, k, v, causal=causal)
    f = jax.jit(shard_map(
        lambda q, k, v: ulysses_attention(q, k, v, axis_name="hvd",
                                          causal=causal),
        mesh=mesh, in_specs=(P(None, "hvd"),) * 3,
        out_specs=P(None, "hvd")))
    out = f(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-4, rtol=1e-3)


def test_ring_attention_long_sequence_scales(hvd):
    """Ring attention on a sequence 8x one chip's block: each chip only ever
    holds S/8 keys — the memory win that makes long context work."""
    mesh = hvd.mesh()
    q, k, v = _qkv(B=1, S=64, H=4, D=8, seed=3)
    ref = causal_attention(q, k, v, causal=True)
    f = jax.jit(shard_map(
        lambda q, k, v: ring_attention(q, k, v, axis_name="hvd"),
        mesh=mesh, in_specs=(P(None, "hvd"),) * 3,
        out_specs=P(None, "hvd")))
    np.testing.assert_allclose(np.asarray(f(q, k, v)), np.asarray(ref),
                               atol=2e-4, rtol=1e-3)


def test_ring_attention_flash_kernel_matches_full(hvd):
    """kernel='flash' routes each ring step through the Pallas kernel
    (interpret mode off-TPU) and the logsumexp merge — must match full
    single-chip attention, including GQA (k/v ride the ring unrepeated)."""
    mesh = hvd.mesh()
    for Hkv in (8, 4):
        q, k, v = _qkv(H=8, Hkv=Hkv, seed=3)
        ref = causal_attention(q, k, v, causal=True)
        # check_vma=False: pallas_call out_shapes carry no vma info (the
        # repo's train steps run shard_map the same way)
        f = jax.jit(shard_map(
            lambda q, k, v: ring_attention(q, k, v, axis_name="hvd",
                                           causal=True, kernel="flash"),
            mesh=mesh, in_specs=(P(None, "hvd"),) * 3,
            out_specs=P(None, "hvd"), check_vma=False))
        out = f(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-4, rtol=1e-3,
                                   err_msg=f"Hkv={Hkv}")


def test_ring_attention_flash_rejects_noncausal(hvd):
    mesh = hvd.mesh()
    q, k, v = _qkv(seed=4)
    with pytest.raises(NotImplementedError, match="causal-only"):
        jax.jit(shard_map(
            lambda q, k, v: ring_attention(q, k, v, axis_name="hvd",
                                           causal=False, kernel="flash"),
            mesh=mesh, in_specs=(P(None, "hvd"),) * 3,
            out_specs=P(None, "hvd"), check_vma=False))(q, k, v)


def test_ring_attention_flash_gradients_match_full(hvd):
    """The ring-level custom_vjp (a second ring over the flash backward
    kernels; dk/dv accumulators travel home with their block) must match
    full single-chip attention gradients, incl. GQA."""
    mesh = hvd.mesh()
    for Hkv in (8, 4):
        q, k, v = _qkv(H=8, Hkv=Hkv, seed=5)

        def f_ring(q, k, v):
            out = shard_map(
                lambda q, k, v: ring_attention(q, k, v, axis_name="hvd",
                                               causal=True,
                                               kernel="flash"),
                mesh=mesh, in_specs=(P(None, "hvd"),) * 3,
                out_specs=P(None, "hvd"), check_vma=False)(q, k, v)
            return jnp.sum(out ** 2)

        def f_ref(q, k, v):
            return jnp.sum(causal_attention(q, k, v, causal=True) ** 2)

        gr = jax.grad(f_ring, argnums=(0, 1, 2))(q, k, v)
        gf = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
        for name, a, b in zip("qkv", gr, gf):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=5e-4, rtol=2e-3,
                err_msg=f"d{name} Hkv={Hkv}")


def test_llama_ring_sharded_matches_unsharded(hvd):
    """End-to-end parity for the pos_offset plumbing: a sequence-sharded
    llama forward (ring attention + per-chip RoPE offsets) must equal the
    unsharded single-chip forward.  Catches a dropped pos_offset — the
    loss-goes-down example smoke stays green in that failure mode."""
    import dataclasses
    from horovod_tpu.models import llama

    mesh = hvd.mesh()
    n = 8
    cfg = dataclasses.replace(llama.CONFIGS["tiny"], max_seq=128)
    params = llama.init(jax.random.PRNGKey(0), cfg)
    ids = jnp.asarray(np.random.RandomState(0).randint(
        0, cfg.vocab, (2, 64)), jnp.int32)
    ref = llama.apply(params, ids, cfg)

    shard = ids.shape[1] // n

    def fwd(p, ids):
        off = jax.lax.axis_index("hvd") * shard
        attn = lambda q, k, v: ring_attention(q, k, v, axis_name="hvd",
                                              causal=True, kernel="flash")
        return llama.apply(p, ids, cfg, attn_fn=attn, pos_offset=off)

    out = jax.jit(shard_map(
        fwd, mesh=mesh, in_specs=(P(), P(None, "hvd")),
        out_specs=P(None, "hvd"), check_vma=False))(params, ids)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-4, rtol=1e-3)
