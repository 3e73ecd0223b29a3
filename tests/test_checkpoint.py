"""Sharded checkpointing (orbax-backed): save/restore distributed pytrees
with shardings preserved, retention, and the elastic JaxState integration
(reference conventions being upgraded: SURVEY.md §5 checkpoint/resume)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from horovod_tpu.checkpoint import (CheckpointManager, restore_checkpoint,
                                    save_checkpoint)


@pytest.fixture
def sharded_state(hvd):
    mesh = hvd.mesh()
    shard = NamedSharding(mesh, P("hvd"))
    repl = NamedSharding(mesh, P())
    params = {
        "w": jax.device_put(jnp.arange(32.0).reshape(8, 4), shard),
        "b": jax.device_put(jnp.ones((4,)), repl),
    }
    opt_state = {"mu": jax.device_put(jnp.zeros((8, 4)) + 0.5, shard)}
    return mesh, params, opt_state


def test_save_restore_preserves_values_and_shardings(tmp_path,
                                                     sharded_state):
    mesh, params, opt_state = sharded_state
    mgr = CheckpointManager(str(tmp_path / "ckpt"))
    assert mgr.save(0, params=params, opt_state=opt_state,
                    meta={"epoch": 3})
    mgr.wait()

    # templates carry shapes+shardings; values are garbage to be replaced
    tmpl_p = jax.tree_util.tree_map(lambda x: x * 0 - 1, params)
    tmpl_o = jax.tree_util.tree_map(lambda x: x * 0 - 1, opt_state)
    out = mgr.restore(0, params=tmpl_p, opt_state=tmpl_o)
    np.testing.assert_allclose(np.asarray(out["params"]["w"]),
                               np.arange(32.0).reshape(8, 4))
    np.testing.assert_allclose(np.asarray(out["opt_state"]["mu"]), 0.5)
    assert out["meta"]["epoch"] == 3
    # restored array lands in the template's sharding
    assert out["params"]["w"].sharding.spec == P("hvd")
    assert out["params"]["b"].sharding.spec == P()
    mgr.close()


def test_no_file_outgrows_the_bound(tmp_path, hvd):
    """A save stays writable under a per-file size limit: an array larger
    than the bound is spread over several data files (orbax's defaults
    would write it as one)."""
    import os

    from horovod_tpu import checkpoint
    bound = checkpoint._CHUNK_BYTES + checkpoint._DATA_FILE_BYTES
    w = jax.random.normal(jax.random.PRNGKey(0), (3 * bound // 4 // 1024,
                                                  1024))   # 3x the bound
    assert w.nbytes > 2 * bound
    save_checkpoint(str(tmp_path / "ckpt"), 0, params={"w": w})
    sizes = [os.path.getsize(os.path.join(r, f))
             for r, _, fs in os.walk(tmp_path / "ckpt") for f in fs]
    assert max(sizes) <= bound, sorted(sizes)[-3:]
    assert sum(sizes) > bound    # it was written, and over several files
    out = restore_checkpoint(str(tmp_path / "ckpt"), params={"w": w * 0})
    np.testing.assert_array_equal(np.asarray(out["params"]["w"]),
                                  np.asarray(w))


def test_latest_step_and_retention(tmp_path, sharded_state):
    _, params, _ = sharded_state
    mgr = CheckpointManager(str(tmp_path / "ckpt"), max_to_keep=2)
    for step in (0, 1, 2, 3):
        assert mgr.save(step, params=params, force=True)
    mgr.wait()
    assert mgr.latest_step() == 3
    assert mgr.all_steps() == [2, 3]  # max_to_keep=2 pruned older steps
    mgr.close()


def test_one_shot_helpers(tmp_path, sharded_state):
    _, params, _ = sharded_state
    save_checkpoint(str(tmp_path / "c"), 7, params=params,
                    meta={"note": "x"})
    out = restore_checkpoint(str(tmp_path / "c"), params=params)
    np.testing.assert_allclose(np.asarray(out["params"]["w"]),
                               np.asarray(params["w"]))
    assert out["meta"]["note"] == "x"


def test_restore_missing_raises(tmp_path, sharded_state):
    _, params, _ = sharded_state
    mgr = CheckpointManager(str(tmp_path / "empty"))
    with pytest.raises(FileNotFoundError):
        mgr.restore(params=params)
    mgr.close()


def test_jaxstate_sharded_commit_roundtrip(tmp_path, hvd, sharded_state):
    from horovod_tpu.elastic.state import JaxState
    mesh, params, opt_state = sharded_state
    state = JaxState(params=params, opt_state=opt_state,
                     sharded_commit_dir=str(tmp_path / "elastic"),
                     epoch=0, batch=0)
    state.epoch = 2
    state.commit()
    state.epoch = 5
    state.params = jax.tree_util.tree_map(lambda x: x + 100.0, state.params)
    state.commit()

    # a fresh incarnation (templates only) resumes from the LAST commit
    fresh = JaxState(params=jax.tree_util.tree_map(jnp.zeros_like, params),
                     opt_state=jax.tree_util.tree_map(jnp.zeros_like,
                                                      opt_state),
                     sharded_commit_dir=str(tmp_path / "elastic"),
                     epoch=0, batch=0)
    assert fresh.load_from_disk()
    assert fresh.epoch == 5
    np.testing.assert_allclose(
        np.asarray(fresh.params["w"]),
        np.arange(32.0).reshape(8, 4) + 100.0)


def test_meta_preserves_python_types(tmp_path, sharded_state):
    """meta must round-trip numpy scalars and tuples intact (regression:
    plain JSON narrowed or rejected them)."""
    _, params, _ = sharded_state
    mgr = CheckpointManager(str(tmp_path / "m"))
    mgr.save(0, params=params,
             meta={"epoch": np.int64(3), "shape": (4, 2), "lr": 1e-3})
    mgr.wait()
    out = mgr.restore(0, params=params)
    assert out["meta"]["epoch"] == 3
    assert isinstance(out["meta"]["epoch"], np.int64)
    assert out["meta"]["shape"] == (4, 2)
    mgr.close()


def test_restore_without_meta_payload(tmp_path, sharded_state):
    _, params, _ = sharded_state
    mgr = CheckpointManager(str(tmp_path / "nm"))
    mgr.save(0, params=params)  # no meta
    mgr.wait()
    out = mgr.restore(0, params=params)
    assert "meta" not in out
    np.testing.assert_allclose(np.asarray(out["params"]["w"]),
                               np.asarray(params["w"]))
    mgr.close()
