"""horovod_tpu: a TPU-native distributed deep-learning training framework.

Capability surface of Horovod (reference: darkjh/horovod v0.22.0), re-designed
TPU-first: XLA collectives over ICI/DCN on a `jax.sharding.Mesh` replace
NCCL/MPI/Gloo; gradient sync is bucket-fused `psum` inside the jitted SPMD
train step; `hvdrun` spawns per-host workers on TPU VM slices with an HTTP
rendezvous; elastic training re-rendezvouses across preemptible slices.

Public API parity (reference: horovod/torch/__init__.py,
horovod/tensorflow/__init__.py):

    import horovod_tpu as hvd
    hvd.init()
    hvd.rank(), hvd.size(), hvd.local_rank(), hvd.local_size()
    hvd.allreduce / allgather / broadcast / alltoall / reducescatter
    hvd.DistributedOptimizer(optax_opt, axis_name='hvd')
    hvd.broadcast_parameters / broadcast_optimizer_state / broadcast_object
    hvd.Compression, hvd.Average / Sum / Adasum / Min / Max / Product
"""

from __future__ import annotations

__version__ = "0.3.0"

from . import runtime as _rt
from .runtime import init, shutdown, is_initialized
from .common.reduce_op import (ReduceOp, Average, Sum, Adasum, Min, Max,
                               Product)
from .common.exceptions import (HorovodInternalError, HostsUpdatedInterrupt,
                                TensorShapeMismatchError,
                                TensorDtypeMismatchError,
                                DuplicateTensorNameError, StallError)
from .ops.collectives import (allreduce, allreduce_async, grouped_allreduce,
                              allgather, allgather_async, allgather_ragged,
                              broadcast, broadcast_async, alltoall,
                              reducescatter, barrier, synchronize, poll,
                              process_allgather, process_local, Handle)
from .ops.compression import Compression
from .ops import spmd
from .ops import wire
from .ops import overlap
from .data.loader import prefetch
from .optimizer import (DistributedOptimizer, distributed_optimizer,
                        sync_gradients, sync_gradients_ef,
                        wire_residual_report, distributed_grad)
from .functions import (broadcast_parameters, broadcast_optimizer_state,
                        broadcast_object, allgather_object)
from .checkpoint import (CheckpointManager, save_checkpoint,
                         restore_checkpoint)
from .runner.api import run


def __getattr__(name):
    # hvd.flash_attention loads on first use: importing Pallas costs about
    # a second, and every launcher, worker and test process imports this
    # package, while only the ones that run the kernel need it.
    if name == "flash_attention":
        from .ops.flash_attention import flash_attention
        return flash_attention
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


# ---------------------------------------------------------------- topology API
def rank() -> int:
    """Global worker (chip) rank of this process's first chip."""
    return _rt.get().rank()


def size() -> int:
    """Total number of worker chips in the mesh."""
    return _rt.get().size()


def local_rank() -> int:
    return _rt.get().local_rank()


def local_size() -> int:
    """Chips driven by this process."""
    return _rt.get().local_size()


def cross_rank() -> int:
    """Host/process index (CROSS scope, reference: common.h:119-123)."""
    return _rt.get().cross_rank()


def cross_size() -> int:
    return _rt.get().cross_size()


def process_rank() -> int:
    return _rt.get().process_rank()


def process_size() -> int:
    return _rt.get().process_size()


def mesh():
    """The global `jax.sharding.Mesh` collectives run over."""
    return _rt.get().mesh


def autotuner():
    """The live autotuner when HOROVOD_AUTOTUNE is enabled, else None
    (reference: ParameterManager, parameter_manager.{h,cc}).  Feed it step
    measurements via ``autotuner().measure(nbytes=...)``."""
    return _rt.get().autotuner


def is_homogeneous() -> bool:
    """True when all hosts drive the same number of chips (reference:
    horovod_is_homogeneous, operations.cc:838)."""
    rt = _rt.get()
    return rt.size() == rt.local_size() * rt.process_size()


# ------------------------------------------------------------------ metrics
def metrics_snapshot() -> dict:
    """Point-in-time snapshot of every metric family this process records
    (native controller counters/histograms, collectives/fusion, stall
    inspector, elastic events) as a JSON-able dict — the same payload
    workers publish for the ``/metrics`` fleet view (``docs/metrics.md``)."""
    return _rt.get().metrics_snapshot()


def perf_report() -> dict:
    """This rank's step-time attribution report (``docs/profiling.md``):
    the measured compute / exposed-comm / host-input / stall
    decomposition (summing exactly to measured step time), the roofline
    model's predicted step and its drift, the native per-op-name
    aggregates, and the local bottleneck verdict — the same payload
    workers publish for the ``GET /perf`` fleet view.  Record steps with
    ``hvd.perf.timed_step()`` / ``hvd.perf.record_step``."""
    from .perf import report as _perf_report
    return _perf_report()


# ----------------------------------------------------------- built/enabled API
# Build-capability probes (reference: operations.cc:845-915 horovod_mpi_built
# etc.).  This framework has exactly one data plane: XLA over ICI/DCN.
# CAPABILITY_EXPORTS is the ONE list every frontend re-exports (each
# extends its __all__ from it, so the parity surface cannot drift
# between frontends).
CAPABILITY_EXPORTS = (
    "tpu_built", "xla_built", "mpi_built", "nccl_built", "gloo_built",
    "ccl_built", "ddl_built", "cuda_built", "rocm_built", "mpi_enabled",
    "gloo_enabled", "mpi_threads_supported", "start_timeline",
    "stop_timeline")

def tpu_built() -> bool:
    return True


def xla_built() -> bool:
    return True


def mpi_built() -> bool:
    return False


def nccl_built() -> bool:
    return False


def gloo_built() -> bool:
    return False


def ccl_built() -> bool:
    return False


def ddl_built() -> bool:
    return False


def cuda_built() -> bool:
    return False


def rocm_built() -> bool:
    return False


def mpi_enabled() -> bool:
    return False


def gloo_enabled() -> bool:
    return False


def mpi_threads_supported() -> bool:
    return False


# ---------------------------------------------------------------- timeline API
def start_timeline(file_path: str, mark_cycles: bool = False) -> None:
    """Start writing the Chrome-trace timeline (reference:
    horovod_start_timeline, operations.cc:740-769)."""
    _rt.get().start_timeline(file_path, mark_cycles=mark_cycles)


def stop_timeline() -> None:
    _rt.get().stop_timeline()


# xprof deep-dive profiling (NVTX-ranges analog; utils/profiler.py)
from .utils import profiler  # noqa: E402
# hyperparameter search over the native GP (reference:
# docs/hyperparameter_search.rst's Ray Tune story)
from . import tune  # noqa: E402
# deterministic fault injection (hvdrun --chaos; docs/chaos.md) —
# training loops call hvd.chaos.step(i) to clock scheduled faults
from . import chaos  # noqa: E402
# crash forensics (hvdrun --postmortem / hvdrun doctor;
# docs/postmortem.md) — training loops call
# hvd.postmortem.record_step(i) so heartbeats carry step progress
from . import postmortem  # noqa: E402
# serving plane (hvdrun --serve; docs/serving.md) — continuous-batching
# multi-host inference over the trained models; engine and router load
# lazily inside the subpackage
from . import serve  # noqa: E402
# perf-attribution plane (docs/profiling.md) — roofline cost model +
# step-time decomposition ledger; training loops record steps via
# hvd.perf.timed_step() and read hvd.perf_report()
from . import perf  # noqa: E402
# watch plane (docs/watch.md) — fleet time-series history, declarative
# alert rules (hvdrun --alerts), and training-quality sentinels:
# hvd.sentinel.wrap(step_fn) watches grad-norm/nonfinite/loss-EMA
from . import watch  # noqa: E402
from .watch import sentinel  # noqa: E402


__all__ = [
    "init", "shutdown", "is_initialized",
    "rank", "size", "local_rank", "local_size", "cross_rank", "cross_size",
    "process_rank", "process_size", "mesh", "is_homogeneous",
    "allreduce", "allreduce_async", "grouped_allreduce", "allgather",
    "allgather_async", "allgather_ragged", "broadcast", "broadcast_async",
    "alltoall", "reducescatter", "barrier", "synchronize", "poll",
    "process_allgather", "process_local", "Handle",
    "DistributedOptimizer", "distributed_optimizer", "sync_gradients",
    "sync_gradients_ef", "wire_residual_report", "wire", "overlap",
    "prefetch", "distributed_grad",
    "broadcast_parameters", "broadcast_optimizer_state", "broadcast_object",
    "allgather_object",
    "Compression", "ReduceOp", "Average", "Sum", "Adasum", "Min", "Max",
    "Product", "spmd",
    "HorovodInternalError", "HostsUpdatedInterrupt",
    "tpu_built", "xla_built", "mpi_built", "nccl_built", "gloo_built",
    "ccl_built", "ddl_built", "cuda_built", "rocm_built",
    "mpi_enabled", "gloo_enabled", "mpi_threads_supported",
    "start_timeline", "stop_timeline", "profiler", "tune",
    "CheckpointManager", "save_checkpoint", "restore_checkpoint",
    "flash_attention", "run",
    "__version__", "metrics_snapshot", "chaos",
    "postmortem", "serve", "perf", "perf_report", "watch", "sentinel",
]
