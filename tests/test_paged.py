"""How a block table addresses a paged pool (horovod_tpu/models/paged.py),
on both kinds of pool the served models keep — llama's five axes and the
latent decoder's four — and the contract ServeEngine holds a model module to
(docs/serving.md#what-a-served-model-module-exports)."""

import importlib
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import latent_moe, llama, paged
from horovod_tpu.serve.config import ServeConfig
from horovod_tpu.serve.engine import _MODEL_MODULES, ServeEngine

BLOCKS, BS = 12, 4
KINDS = {"llama": (llama, llama.CONFIGS["tiny"]),
         "latent": (latent_moe, latent_moe.CONFIGS["tiny"])}


@pytest.fixture(params=sorted(KINDS))
def kind(request):
    """(model module, its tiny config, a pool of noise as host numpy)."""
    model, cfg = KINDS[request.param]
    rng = np.random.default_rng(3)
    pool = {k: rng.normal(size=v.shape).astype(np.float32)
            for k, v in model.init_cache(cfg, BLOCKS, BS).items()}
    return model, cfg, pool


def _device(pool):
    return {k: jnp.asarray(v) for k, v in pool.items()}


# slot 0 holds 2 positions and takes 3 more (crossing into its second block),
# slot 1 is dead (n_new 0, a stale table row), slot 2 takes one: its padding
# columns fall under the table's unassigned (-1) entries
TABLES = np.array([[7, 2, 9], [4, 4, 4], [5, -1, -1]], np.int32)
LENGTHS = np.array([2, 6, 3], np.int32)
N_NEW = np.array([3, 0, 1], np.int32)
C = 4


def test_slot_positions_and_write_index_by_a_plain_loop():
    positions, valid = paged.slot_positions(jnp.asarray(LENGTHS),
                                            jnp.asarray(N_NEW), C)
    blk, off = paged.write_index(jnp.asarray(TABLES), positions, valid,
                                 BLOCKS, BS)
    for s in range(3):
        for j in range(C):
            P = LENGTHS[s] + j
            assert positions[s, j] == P and valid[s, j] == (j < N_NEW[s])
            assert off[s, j] == P % BS
            want = TABLES[s, min(P // BS, 2)] if j < N_NEW[s] else BLOCKS
            assert blk[s, j] == want, (s, j)


def test_a_position_lands_in_its_block_and_nothing_else_moves(kind):
    model, cfg, pool = kind
    positions, valid = paged.slot_positions(jnp.asarray(LENGTHS),
                                            jnp.asarray(N_NEW), C)
    blk, off = paged.write_index(jnp.asarray(TABLES), positions, valid,
                                 BLOCKS, BS)
    rng = np.random.default_rng(5)
    for name, leaf in pool.items():
        for layer in (0, leaf.shape[0] - 1):
            values = rng.normal(size=(3, C) + leaf.shape[3:]).astype(
                np.float32)
            got = np.asarray(paged.write(jnp.asarray(leaf), layer, blk, off,
                                         jnp.asarray(values)))
            want = leaf.copy()
            for s in range(3):
                for j in range(N_NEW[s]):
                    P = LENGTHS[s] + j
                    want[layer, TABLES[s, P // BS], P % BS] = values[s, j]
            assert np.array_equal(got, want), (name, layer)


def test_gathered_index_t_is_position_t(kind):
    model, cfg, pool = kind
    for name, leaf in pool.items():
        layer = leaf.shape[0] - 1
        ctx = np.asarray(paged.gather(jnp.asarray(leaf), layer,
                                      jnp.asarray(TABLES)))
        assert ctx.shape == (3, 3 * BS) + leaf.shape[3:]
        for s in range(3):
            for t in range(3 * BS):
                b = max(TABLES[s, t // BS], 0)      # -1 reads block 0
                assert np.array_equal(ctx[s, t], leaf[layer, b, t % BS])


def test_context_mask_by_a_plain_loop():
    positions, _ = paged.slot_positions(jnp.asarray(LENGTHS),
                                        jnp.asarray(N_NEW), C)
    mask = np.asarray(paged.context_mask(positions, 3 * BS))
    assert mask.shape == (3, 1, C, 3 * BS)
    for s in range(3):
        for j in range(C):
            for t in range(3 * BS):
                assert mask[s, 0, j, t] == (t <= LENGTHS[s] + j)


def test_copy_blocks_padding_and_a_source_recycled_in_the_same_call(kind):
    model, cfg, pool = kind
    # 3 -> 5; 5 -> 8 reads what 5 held BEFORE the call; (0 -> BLOCKS) and
    # (-1 -> BLOCKS) are padding pairs: dropped, their source clamped
    src = jnp.array([3, 5, 0, -1], jnp.int32)
    dst = jnp.array([5, 8, BLOCKS, BLOCKS], jnp.int32)
    for copy in (paged.copy_blocks, model.copy_blocks):
        got = copy(_device(pool), src, dst)
        assert set(got) == set(pool)
        for name, leaf in pool.items():
            want = leaf.copy()
            want[:, 5], want[:, 8] = leaf[:, 3], leaf[:, 5]
            assert np.array_equal(np.asarray(got[name]), want), name


def test_copy_blocks_jitted_on_a_bare_leaf_and_all_padding_is_identity():
    """As the tick calls it: under jit, on every tick — most of which carry
    padding pairs alone and must leave the pool as it was.  A pool is any
    pytree, a bare array included, whatever lies behind the block axes."""
    rng = np.random.default_rng(9)
    copy = jax.jit(paged.copy_blocks)
    for behind in ((128,), (40,), (2, 8)):
        leaf = rng.normal(size=(3, BLOCKS, BS) + behind).astype(np.float32)
        nothing = copy(jnp.asarray(leaf), jnp.zeros(3, jnp.int32),
                       jnp.full(3, BLOCKS, jnp.int32))
        assert np.array_equal(np.asarray(nothing), leaf), behind
        want = leaf.copy()
        want[:, 5], want[:, 8] = leaf[:, 3], leaf[:, 5]
        got = copy(jnp.asarray(leaf), jnp.array([3, 5, -1], jnp.int32),
                   jnp.array([5, 8, BLOCKS], jnp.int32))
        assert np.array_equal(np.asarray(got), want), behind


def test_read_block_write_block_round_trip(kind):
    model, cfg, pool = kind
    payload = paged.read_block(_device(pool), 7)
    assert list(payload) == sorted(pool)
    for name, leaf in pool.items():
        assert isinstance(payload[name], np.ndarray)
        assert np.array_equal(payload[name], leaf[:, 7])
    got = paged.write_block(_device(pool), 9, payload)
    for name, leaf in pool.items():
        want = leaf.copy()
        want[:, 9] = leaf[:, 7]
        assert np.array_equal(np.asarray(got[name]), want)


def test_shardings_ride_the_meshs_own_axes():
    """tests/test_serve.py test_cache_shardings_ride_existing_axes' meshes,
    for a pool with a head axis and for one without."""
    PS = jax.sharding.PartitionSpec
    devs = np.array(jax.devices()[:8])
    mesh2 = jax.sharding.Mesh(devs.reshape(4, 2), ("data", "model"))
    mesh1 = jax.sharding.Mesh(devs, ("hvd",))
    assert paged.shardings(mesh2, 64, 4).spec == PS(
        None, "data", None, "model", None)
    assert paged.shardings(mesh2, 64, 3).spec == PS(
        None, "data", None, None, None)
    assert paged.shardings(mesh1, 64, 4).spec == PS(
        None, "hvd", None, None, None)
    # blocks that no axis divides stay whole
    assert paged.shardings(mesh1, 63, 4).spec == PS(None, None, None, None,
                                                    None)
    # no head axis: four axes, blocks over the first axis that divides them
    assert paged.shardings(mesh2, 64).spec == PS(None, "data", None, None)
    assert paged.shardings(mesh2, 6).spec == PS(None, "model", None, None)
    assert latent_moe.cache_shardings(
        mesh1, latent_moe.CONFIGS["tiny"], 64).spec == PS(
            None, "hvd", None, None)


# ------------------------------------------------ the engine's contract
CONTRACT = ("init_cache", "copy_blocks", "apply_cached", "cache_shardings",
            "attn_blocks", "TICK_COUNTERS")
#: what a module MAY declare beside them (swa_moe does): its cache kinds, and
#: the tick's greedy tokens in place of its logits
OPTIONAL = ("cache_kinds", "greedy_cached")


def _engine(model, cfg, params):
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("hvd",))
    scfg = ServeConfig(max_slots=2, block_size=4, cache_blocks=32,
                       max_seq_len=32, max_batch_tokens=12, prefill_chunk=8,
                       # refused over a window cache kind
                       prefix_cache=not hasattr(model, "cache_kinds"))
    return ServeEngine(model, cfg, params, scfg, mesh=mesh)


@pytest.mark.parametrize("name", sorted(_MODEL_MODULES))
def test_every_served_module_keeps_the_contract(name):
    """The six names, a config that carries the tick's token budget, and a
    request served through them with no probe of
    the module: a stand-in that lacks one fails loudly."""
    model = importlib.import_module(_MODEL_MODULES[name])
    for attr in CONTRACT:
        assert hasattr(model, attr), (name, attr)
    assert isinstance(model.TICK_COUNTERS, tuple)
    cfg = model.CONFIGS["tiny"]
    params = model.init(jax.random.PRNGKey(0), cfg)
    engine = _engine(model, cfg, params)
    assert cfg.max_tick_tokens == 0
    assert engine.model_cfg.max_tick_tokens == engine.cfg.max_batch_tokens
    req = engine.submit([3, 1, 4, 1, 5, 9, 2, 6, 5, 3], 4, req_id="r")
    engine.flush()
    assert len(req.out_tokens) == 4
    counted = engine.stats().get("moe", {})
    assert sorted(counted) == sorted(model.TICK_COUNTERS)
    engine.close()
    for attr in ("TICK_COUNTERS", "cache_shardings", "attn_blocks"):
        stub = types.SimpleNamespace(**{
            a: getattr(model, a) for a in CONTRACT + OPTIONAL
            if a != attr and hasattr(model, a)})
        with pytest.raises(AttributeError, match=attr):
            _engine(stub, cfg, params)
