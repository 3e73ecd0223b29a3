"""Serving plane: continuous-batching multi-host inference over the
trained ``models/`` checkpoints (docs/serving.md).

Four legs, mirroring how every training plane is built:

  * **decode path** — paged KV cache prefill/decode added to the models
    themselves (models/llama.py, models/moe_llama.py ``init_cache`` /
    ``apply_cached``), proven bit-near the full-sequence forward;
  * **engine** (:mod:`.engine`) — in-flight batching scheduler + one
    jit'd mixed prefill/decode step per tick over a static slot table;
  * **router** (:mod:`.router`) — ``POST /generate`` + ``GET
    /serve/stats`` on the rendezvous HTTP server, feeding the engine
    fleet over the existing KV transport (``hvdrun --serve`` launches
    everything);
  * **SLO observability for free** — hvd_serve_* metrics at /metrics,
    per-request NEGOTIATE/PREFILL/DECODE spans in the merged timeline,
    engine liveness on /health.

Heavy modules load lazily: importing :mod:`horovod_tpu` must not pay
for jax-model machinery a training job never uses.
"""

from __future__ import annotations

from .config import ServeConfig, from_knobs, validate_serve_knobs

_LAZY = {
    "ServeEngine": ("engine", "ServeEngine"),
    "Scheduler": ("engine", "Scheduler"),
    "BlockAllocator": ("engine", "BlockAllocator"),
    "Request": ("engine", "Request"),
    "save_servable": ("engine", "save_servable"),
    "load_servable": ("engine", "load_servable"),
    "FleetFrontend": ("worker", "FleetFrontend"),
    "JOURNAL_SCOPE": ("journal", "JOURNAL_SCOPE"),
    "redrive_plan": ("journal", "redrive_plan"),
    "emitted_prefix": ("journal", "emitted_prefix"),
}


def __getattr__(name: str):
    if name in _LAZY:
        import importlib
        mod, attr = _LAZY[name]
        return getattr(importlib.import_module(f".{mod}", __name__), attr)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


__all__ = ["ServeConfig", "from_knobs", "validate_serve_knobs",
           *_LAZY.keys()]
