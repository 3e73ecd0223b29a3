"""horovod_tpu.spark.run: distributed training on a cluster scheduler.

Reference: horovod/spark/runner.py:47-304 — ``run(fn, num_proc)`` starts a
barrier Spark job whose tasks host the training function; the driver
assigns ranks by task, sets up the rendezvous, and collects results.

TPU-native shape: the scheduler's ONLY job is process placement.  The
orchestration core (`_run_on_executor`) is scheduler-agnostic: it brings
up the rendezvous/coordinator env exactly like hvdrun and hands each task
a (rank, env, fn) triple.  ``SparkTaskExecutor`` (gated on pyspark)
supplies placement via a barrier RDD stage; ``LocalTaskExecutor`` places
on local processes — it backs the test tier the same way the reference
tests Spark in local mode (reference: test/utils/spark_common.py:234).
"""

from __future__ import annotations

import multiprocessing
import os
import pickle
import socket
import traceback
from typing import Any, Callable, Dict, List, Optional, Sequence

from ..runner.hosts import env_for_tasks


class TaskExecutor:
    """Placement backend: run one python callable per task slot.

    ``task_fn(index, hostnames)`` receives the task's index and the full
    per-task hostname list (index-aligned), so ranks — including LOCAL and
    CROSS coordinates on multi-host clusters — are derived from the actual
    placement, not guessed."""

    def num_tasks(self) -> int:
        raise NotImplementedError

    def run_tasks(self, task_fn: Callable[[int, List[str]], Any]
                  ) -> List[Any]:
        raise NotImplementedError

    def with_num_tasks(self, n: int) -> "TaskExecutor":
        """Rebuild this executor at a different task count, preserving its
        configuration — how elastic resets shrink the placement layer.
        Subclasses with extra constructor state must override."""
        raise NotImplementedError(
            f"{type(self).__name__} does not support elastic resizing; "
            "override with_num_tasks(n)")


def _local_task_entry(index: int, payload: bytes, hostnames, q):
    try:
        fn = pickle.loads(payload)
        q.put((index, ("ok", fn(index, hostnames))))
    except BaseException as e:  # surface remote errors with traceback
        q.put((index, ("error", f"{e}\n{traceback.format_exc()}")))


class LocalTaskExecutor(TaskExecutor):
    """Local-process placement (the reference's spark local-mode analog)."""

    def __init__(self, num_tasks: int, start_method: str = "spawn"):
        self._n = num_tasks
        self._start_method = start_method
        self._ctx = multiprocessing.get_context(start_method)

    def num_tasks(self) -> int:
        return self._n

    def with_num_tasks(self, n: int) -> "LocalTaskExecutor":
        return LocalTaskExecutor(n, start_method=self._start_method)

    def run_tasks(self, task_fn: Callable[[int, List[str]], Any]
                  ) -> List[Any]:
        q = self._ctx.Queue()
        payload = pickle.dumps(task_fn)
        hostnames = [socket.gethostname()] * self._n
        procs = [self._ctx.Process(target=_local_task_entry,
                                   args=(i, payload, hostnames, q))
                 for i in range(self._n)]
        for p in procs:
            p.start()
        results: List[Any] = [None] * self._n
        error = None
        got = 0
        while got < self._n:
            try:
                i, (status, val) = q.get(timeout=1.0)
            except Exception:  # queue.Empty: check worker liveness
                dead = [i for i, p in enumerate(procs)
                        if not p.is_alive() and p.exitcode not in (0, None)
                        and results[i] is None]
                if dead:
                    for p in procs:
                        p.terminate()
                    raise RuntimeError(
                        f"task(s) {dead} died without reporting a result "
                        f"(exitcodes "
                        f"{[procs[i].exitcode for i in dead]}) — native "
                        "crash or OOM kill?")
                continue
            got += 1
            if status == "error" and error is None:
                error = (i, val)
            results[i] = val
        for p in procs:
            p.join()
        if error is not None:
            raise RuntimeError(f"task {error[0]} failed: {error[1]}")
        return results


class _spark_partition_entry:
    """Runs inside a barrier task: exchange hostnames, then run.  A
    picklable class (not a closure) so plain pickle suffices — real
    pyspark cloudpickles closures, but nothing here needs that."""

    def __init__(self, task_fn):
        self.task_fn = task_fn

    def __call__(self, it):
        from pyspark import BarrierTaskContext
        ctx = BarrierTaskContext.get()
        hostnames = ctx.allGather(socket.gethostname())
        return [self.task_fn(ctx.partitionId(), list(hostnames))]


class SparkTaskExecutor(TaskExecutor):
    """Barrier-stage placement on a live SparkContext (reference:
    spark/runner.py:47-117 uses a Spark job whose tasks host services);
    hostnames are exchanged with BarrierTaskContext.allGather.  Requires
    pyspark at call time."""

    def __init__(self, num_tasks: Optional[int] = None, spark_context=None):
        try:
            import pyspark  # noqa: F401
        except ImportError as e:
            raise ImportError(
                "horovod_tpu.spark.run on a real cluster requires pyspark; "
                "pass executor=LocalTaskExecutor(n) for local mode"
            ) from e
        from pyspark import SparkContext
        self._sc = spark_context or SparkContext.getOrCreate()
        self._n = num_tasks or int(
            self._sc.getConf().get("spark.executor.instances", "1"))

    def num_tasks(self) -> int:
        return self._n

    def with_num_tasks(self, n: int) -> "SparkTaskExecutor":
        return SparkTaskExecutor(n, spark_context=self._sc)

    def run_tasks(self, task_fn: Callable[[int, List[str]], Any]
                  ) -> List[Any]:
        rdd = self._sc.parallelize(range(self._n), self._n)
        return (rdd.barrier()
                .mapPartitions(_spark_partition_entry(task_fn))
                .collect())


def run(fn: Callable, args: Sequence[Any] = (), kwargs: Dict = None,
        num_proc: Optional[int] = None,
        executor: Optional[TaskExecutor] = None,
        env: Optional[Dict[str, str]] = None,
        coordinator_port: int = 29511,
        use_spark: Optional[bool] = None) -> List[Any]:
    """Run ``fn(*args, **kwargs)`` on ``num_proc`` distributed workers;
    returns the per-rank results as a list (reference: spark/runner.py:195
    returns one result per Spark task).

    With no ``executor``, uses Spark when pyspark is importable (or
    ``use_spark=True``), else local processes."""
    kwargs = kwargs or {}
    if executor is None:
        want_spark = use_spark
        if want_spark is None:
            try:
                import pyspark  # noqa: F401
                want_spark = True
            except ImportError:
                want_spark = False
        executor = (SparkTaskExecutor(num_proc) if want_spark
                    else LocalTaskExecutor(num_proc or 1))
    base_env = {k: v for k, v in (env or {}).items()}
    task = _Task(fn, tuple(args), dict(kwargs), coordinator_port, base_env)
    return executor.run_tasks(task)


def run_elastic(fn: Callable, args: Sequence[Any] = (),
                kwargs: Dict = None,
                num_proc: Optional[int] = None,
                min_np: Optional[int] = None,
                max_np: Optional[int] = None,
                start_timeout: Optional[float] = None,
                elastic_timeout: Optional[float] = None,
                reset_limit: Optional[int] = 3,
                env: Optional[Dict[str, str]] = None,
                executor_factory: Optional[Callable] = None,
                coordinator_port: int = 29511,
                verbose: int = 1) -> List[Any]:
    """Elastic training on a cluster scheduler (reference:
    spark/runner.py:306-334 run_elastic).

    TPU-native reshape of the reference's gloo-rendezvous elasticity: a
    jax.distributed mesh cannot shrink in place, so each membership
    change is a RESET — the barrier job is relaunched at the surviving
    worker count (bounded below by ``min_np``) and the training function
    resumes from its last durable checkpoint (the estimator tasks'
    per-epoch envelope).  ``reset_limit`` bounds relaunches exactly like
    the reference's param; ``start_timeout``/``elastic_timeout`` are
    accepted for signature parity (process spawn on a barrier stage is
    scheduler-supervised, so there is no separate registration window to
    time out).

    ``executor_factory(n)`` rebuilds the placement backend at size n per
    attempt; with None, pyspark (when importable) or local processes are
    chosen per attempt exactly as :func:`run` does.
    """
    del start_timeout, elastic_timeout  # signature parity; see docstring
    n = num_proc or 1
    lo = max(1, min_np or 1)
    if max_np is not None:
        n = min(n, max_np)
    if n < lo:
        raise ValueError(f"num_proc={n} below min_np={lo}")
    resets = 0
    while True:
        executor = executor_factory(n) if executor_factory else None
        try:
            return run(fn, args=args, kwargs=kwargs, num_proc=n,
                       executor=executor, env=env,
                       coordinator_port=coordinator_port)
        # Broad on purpose: task death surfaces as RuntimeError from
        # LocalTaskExecutor but as Py4J/Spark exception types from a real
        # barrier stage — all of them mean "reset and shrink".
        except Exception as e:
            resets += 1
            if reset_limit is not None and resets > reset_limit:
                raise RuntimeError(
                    f"elastic job failed after {resets - 1} resets "
                    f"(reset_limit={reset_limit})") from e
            n = max(lo, n - 1)
            if verbose:
                import sys as _sys
                print(f"[spark.run_elastic] task failure: {e}; reset "
                      f"#{resets} relaunching with np={n}",
                      file=_sys.stderr)


class _Task:
    """Picklable per-slot entry: derive this task's rank env from the
    exchanged hostname list, set it, run fn (reference: the mpirun/gloo
    exec_fn modules, spark/task/*_exec_fn.py).  The coordinator lands on
    rank 0's host (env_for_tasks), which every task derives identically
    from the same hostname list."""

    def __init__(self, fn, args, kwargs, coordinator_port, base_env):
        self.fn = fn
        self.args = args
        self.kwargs = kwargs
        self.coordinator_port = coordinator_port
        self.base_env = base_env

    def __call__(self, index: int, hostnames: List[str]):
        env = dict(self.base_env)
        env.update(env_for_tasks(hostnames, self.coordinator_port)[index])
        os.environ.update(env)
        return self.fn(*self.args, **self.kwargs)
