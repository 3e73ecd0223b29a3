"""Sharded checkpointing: save/restore training state from HBM.

The reference has no core checkpoint engine — three conventions instead
(reference: SURVEY.md §5): (a) elastic State commit/restore in memory,
(b) rank-0 saves + broadcast_parameters after load
(examples/pytorch/pytorch_mnist.py), (c) Spark estimators persist to the
Store.  The TPU-native upgrade called for by the survey is orbax-style
SHARDED checkpointing: every host writes its own HBM shards in parallel
(no gather-to-rank-0, no full-model host copy), and restore places shards
directly into their target sharding.

`CheckpointManager` wraps orbax with the framework's conventions:

    ckpt = hvd.CheckpointManager(path, max_to_keep=3)
    ckpt.save(step, params=params, opt_state=opt_state, meta={"epoch": 2})
    state = ckpt.restore(step=None, params=params, opt_state=opt_state)

Restore targets supply the shardings (pass the live pytrees or
jax.eval_shape structures); `meta` carries small picklable scalars.
JaxState (elastic) uses this via ``commit_path`` for crash-surviving
commits.
"""

from __future__ import annotations

import os
import pickle
from typing import Any, Dict, Optional

import jax

# No file of a checkpoint outgrows _DATA_FILE_BYTES + _CHUNK_BYTES: arrays
# are cut into chunks and OCDBT starts a new data file once one is full.
# Orbax's defaults write a shard as one chunk and pack up to 2 GiB into a
# file, which a host with a per-file size limit (RLIMIT_FSIZE) refuses
# with EFBIG — llama-1b's 3 GB of params landed in two files of ~1 GB.
_CHUNK_BYTES = 4 << 20
_DATA_FILE_BYTES = 8 << 20


class CheckpointManager:
    """Thin orbax CheckpointManager with framework conventions."""

    def __init__(self, directory: str, max_to_keep: int = 3):
        import orbax.checkpoint as ocp
        self._ocp = ocp
        self.directory = os.path.abspath(directory)
        os.makedirs(self.directory, exist_ok=True)
        self._mgr = ocp.CheckpointManager(
            self.directory,
            options=ocp.CheckpointManagerOptions(
                max_to_keep=max_to_keep, create=True))

    # ------------------------------------------------------------------ save
    def save(self, step: int, params: Any = None, opt_state: Any = None,
             meta: Optional[Dict[str, Any]] = None, force: bool = False,
             **extra_trees: Any) -> bool:
        """Write one checkpoint: each host saves ITS shards of every array
        in parallel (orbax OCDBT); returns False when the save was skipped
        (e.g. an older step with save-interval policies)."""
        ocp = self._ocp
        items = {}
        for name, tree in dict(params=params, opt_state=opt_state,
                               **extra_trees).items():
            if tree is not None:
                items[name] = ocp.args.PyTreeSave(
                    tree,
                    save_args=jax.tree_util.tree_map(
                        lambda _: ocp.SaveArgs(chunk_byte_size=_CHUNK_BYTES),
                        tree),
                    ocdbt_target_data_file_size=_DATA_FILE_BYTES)
        if meta:
            # Pickle-in-json keeps the full type surface (numpy scalars,
            # tuples, any picklable) that a plain JSON payload would narrow
            # or reject.
            items["meta"] = ocp.args.JsonSave(
                {"__pickle_hex__": pickle.dumps(meta).hex()})
        ok = self._mgr.save(step, args=ocp.args.Composite(**items),
                            force=force)
        return bool(ok)

    def wait(self) -> None:
        """Block until async writes are durable (call before exiting)."""
        self._mgr.wait_until_finished()

    # --------------------------------------------------------------- restore
    def restore(self, step: Optional[int] = None, params: Any = None,
                opt_state: Any = None, **extra_trees: Any) -> Dict[str, Any]:
        """Restore ``step`` (default: latest).  The supplied pytrees are
        TEMPLATES: their shardings/dtypes/shapes decide where shards land,
        so restored arrays arrive already distributed."""
        ocp = self._ocp
        if step is None:
            step = self.latest_step()
            if step is None:
                raise FileNotFoundError(
                    f"no checkpoint under {self.directory}")
        items = {}
        for name, tree in dict(params=params, opt_state=opt_state,
                               **extra_trees).items():
            if tree is not None:
                template = jax.tree_util.tree_map(
                    lambda x: jax.ShapeDtypeStruct(
                        x.shape, x.dtype,
                        sharding=getattr(x, "sharding", None))
                    if hasattr(x, "shape") else x, tree)
                items[name] = ocp.args.PyTreeRestore(
                    item=template,
                    restore_args=ocp.checkpoint_utils.construct_restore_args(
                        template))
        # Only request items the checkpoint actually has (a blanket
        # try/except here would mask real restore failures and re-run the
        # whole sharded read).
        saved_items = set(self._mgr.item_metadata(step).keys())
        items = {k: v for k, v in items.items() if k in saved_items}
        if "meta" in saved_items:
            items["meta"] = ocp.args.JsonRestore()
        out = self._mgr.restore(step, args=ocp.args.Composite(**items))
        result = {k: out[k] for k in out.keys()}
        meta = result.get("meta")
        if isinstance(meta, dict) and "__pickle_hex__" in meta:
            result["meta"] = pickle.loads(
                bytes.fromhex(meta["__pickle_hex__"]))
        return result

    # ------------------------------------------------------------- inventory
    def latest_step(self) -> Optional[int]:
        return self._mgr.latest_step()

    def step_mtime(self, step: int) -> Optional[float]:
        """When `step` was written (orders commits across stores with
        unrelated step counters, e.g. against the elastic fast store)."""
        try:
            return os.path.getmtime(os.path.join(self.directory,
                                                 str(step)))
        except OSError:
            return None

    def all_steps(self):
        return sorted(self._mgr.all_steps())

    def close(self) -> None:
        self._mgr.wait_until_finished()
        self._mgr.close()


def save_checkpoint(directory: str, step: int, params: Any = None,
                    opt_state: Any = None,
                    meta: Optional[Dict[str, Any]] = None) -> None:
    """One-shot convenience save (rank-0-only callers do NOT need to gate:
    every host participates and writes only its shards — the sharded
    replacement for the reference's 'checkpoint on rank 0' convention)."""
    mgr = CheckpointManager(directory, max_to_keep=10_000)
    try:
        mgr.save(step, params=params, opt_state=opt_state, meta=meta,
                 force=True)
    finally:
        mgr.close()


def restore_checkpoint(directory: str, step: Optional[int] = None,
                       params: Any = None, opt_state: Any = None
                       ) -> Dict[str, Any]:
    mgr = CheckpointManager(directory, max_to_keep=10_000)
    try:
        return mgr.restore(step, params=params, opt_state=opt_state)
    finally:
        mgr.close()
