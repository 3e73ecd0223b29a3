"""``greedy_cached(.., read)`` of the three modules that sample where the
serving tick reads (models/llama.py, moe_llama.py, latent_moe.py;
docs/serving.md#what-a-served-model-module-exports): the tokens it returns
are the float32 argmax of ``apply_cached``'s logits at the columns ``read``,
its head runs on those ``S * W`` rows alone (models/paged.py ``Slab.at``),
and the cache and the counters are ``apply_cached``'s."""

import dataclasses
import importlib
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import paged
from horovod_tpu.serve.config import ServeConfig
from horovod_tpu.serve.engine import Scheduler, samples_read

MODULES = ["llama", "moe_llama", "latent_moe"]
#: a vocabulary that is no other axis of a `tiny` configuration
VOCAB = 251
SPEC_K = 3
S, W = 5, 1 + SPEC_K
# A tick's plan a width, a slot a row — (columns, length before the tick,
# whether it is a decode row): a verify row with two drafts, a prompt's LAST
# chunk, a CHUNK in the middle of a prompt, a carried row whose stream had
# ended (it runs nothing) and a free slot.  The packed budget holds every
# valid token and fewer rows than the slab has positions.
PLANS = {
    "narrow": (W, 12, [(3, 9, True), (2, 14, False), (4, 8, False),
                       (0, 0, True), (0, 0, False)]),
    "wide": (16, 40, [(3, 9, True), (11, 16, False), (16, 16, False),
                      (0, 0, True), (0, 0, False)]),
}


def _read(n_new, decode, C):
    """The columns a tick reads (serve/engine.py ``tick_program``): a decode
    row's from 0, another's from its last."""
    first = np.where(decode, 0, n_new - 1)
    return np.clip(first[:, None] + np.arange(W)[None, :], 0, C - 1)


def _case(name, width, packed):
    """(module, the arguments of its ``apply_cached``, ``read``, the read
    columns that hold a token) at `tiny` with the vocabulary ``VOCAB``, a
    pool of noise and every slot its own blocks in order."""
    C, budget, plan = PLANS[width]
    n_new, lengths, decode = (np.asarray(x) for x in zip(*plan))
    model = importlib.import_module("horovod_tpu.models." + name)
    cfg = dataclasses.replace(model.CONFIGS["tiny"], vocab=VOCAB,
                              max_tick_tokens=budget if packed else 0)
    params = model.init(jax.random.PRNGKey(4), cfg)
    scfg = ServeConfig(max_slots=S, block_size=4, cache_blocks=S * 12,
                       max_seq_len=48, max_batch_tokens=S * C,
                       prefill_chunk=max(C, W + 1), spec_k=SPEC_K,
                       prefix_cache=False)
    sched = Scheduler(scfg)
    tables = jnp.arange(sched.device_tables().size, dtype=jnp.int32).reshape(
        sched.device_tables().shape)
    rng = np.random.default_rng(6)
    cache = jax.tree_util.tree_map(
        lambda z: jnp.asarray(0.1 * rng.normal(size=z.shape), z.dtype),
        model.init_cache(cfg, sched.pool_blocks(), scfg.block_size))
    tokens = jnp.asarray(rng.integers(0, VOCAB, (S, C)), jnp.int32)
    read = _read(n_new, decode, C)
    return model, (params, tokens, cfg, cache, tables,
                   jnp.asarray(lengths, jnp.int32),
                   jnp.asarray(n_new, jnp.int32)), \
        jnp.asarray(read, jnp.int32), read < n_new[:, None]


@pytest.mark.parametrize("packed", [False, True], ids=["slab", "packed"])
@pytest.mark.parametrize("width", list(PLANS))
@pytest.mark.parametrize("name", MODULES)
def test_greedy_cached_is_the_argmax_of_the_logits_at_read(name, width,
                                                           packed):
    model, args, read, held = _case(name, width, packed)
    assert samples_read(model)
    logits, cache, *rest = model.apply_cached(*args)
    tokens, cache2, *rest2 = model.greedy_cached(*args, read)
    assert tokens.shape == (S, W) and tokens.dtype == jnp.int32
    want = np.take_along_axis(
        np.argmax(np.asarray(logits, np.float32), axis=-1),
        np.asarray(read), axis=1)
    # a verify row's 3 columns, a LAST chunk's last, and of a CHUNK what the
    # tick would read had it been the last: all that hold a token
    assert held[0].tolist() == [True] * 3 + [False] and held[1:3, 0].all()
    assert not held[3:].any()
    assert np.array_equal(np.asarray(tokens)[held], want[held])
    assert all(np.array_equal(a, b) for a, b in zip(
        jax.tree_util.tree_leaves((cache, rest)),
        jax.tree_util.tree_leaves((cache2, rest2))))


@pytest.mark.parametrize("packed", [False, True], ids=["slab", "packed"])
@pytest.mark.parametrize("name", MODULES)
def test_the_wide_program_holds_the_vocabulary_beside_the_read_rows(name,
                                                                    packed):
    """The lowered text of the chunk-wide ``greedy_cached``: logits exist
    for ``S * W`` rows and nothing but the weights has the vocabulary for an
    axis — no ``[S, C, vocab]`` slab, no ``[rows, vocab]`` of every packed
    row; ``apply_cached`` still makes the slab (tests and references hold
    the model to its logits)."""
    model, args, read, _ = _case(name, "wide", packed)
    C, cfg = PLANS["wide"][0], args[2]
    text = jax.jit(model.greedy_cached, static_argnums=2).lower(
        *args, read).as_text()
    dims = {tuple(map(int, d[:-1].split("x")))
            for d in re.findall(r"tensor<((?:\d+x)+)", text)}
    beside = {math.prod(d) // VOCAB for d in dims if VOCAB in d}
    assert S * W in beside and beside <= {S * W, cfg.dim}, dims
    whole = jax.jit(model.apply_cached, static_argnums=2).lower(
        *args).as_text()
    assert f"{S}x{C}x{VOCAB}" in whole and f"{S}x{C}x{VOCAB}" not in text


@pytest.mark.parametrize("packed", [False, True], ids=["slab", "packed"])
def test_slab_at_is_the_slab_at_those_columns(packed):
    """``Slab.at(a, read)`` against ``slab(a)`` indexed at ``read``, where
    the position was packed: a decode row's columns, a chunk's last, and
    columns past a row's tokens (defined by nothing: not compared)."""
    n_new = np.array([3, 7, 0, 8])
    valid = np.arange(8)[None, :] < n_new[:, None]
    take, slab = paged.pack(jnp.asarray(valid), 20 if packed else 0)
    assert (slab.rows is None) == (not packed)
    a = jnp.asarray(np.random.default_rng(1).normal(size=(4, 8, 6)),
                    jnp.float32)
    read = _read(n_new, np.array([True, False, True, False]), 8)
    got = slab.at(take(a), jnp.asarray(read))
    assert got.shape == (4, W, 6)
    held = read < n_new[:, None]
    want = np.take_along_axis(np.asarray(slab(take(a))),
                              read[:, :, None], axis=1)
    assert np.array_equal(np.asarray(got)[held], want[held])
    assert np.array_equal(want[held], np.take_along_axis(
        np.asarray(a), read[:, :, None], axis=1)[held])
