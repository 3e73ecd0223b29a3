"""RayExecutor: actor-based distributed training orchestration.

Reference: horovod/ray/runner.py:41-344 — a Coordinator collects worker
hostnames, computes ranks, writes rendezvous env into each actor, then
``run``/``execute`` drive the training function on all workers;
strategy.py packs workers onto hosts (Colocated = equal per host, Pack =
placement-group packing).

TPU-native shape: the pool abstraction carries the four operations the
orchestration needs (create, hostnames, set_env, execute).  ``RayWorkerPool``
implements them with ray actors + placement groups (gated on ray being
importable); ``LocalWorkerPool`` implements them with local processes so
the orchestration logic is exercised in environments without ray — the
reference's own tests run ray in local mode for the same reason
(test_ray.py uses ray.init local cluster).
"""

from __future__ import annotations

import multiprocessing
import os
import pickle
import socket
import traceback
from typing import Any, Callable, Dict, List, Optional

from ..runner.hosts import env_for_tasks


class BaseWorkerPool:
    """Minimal actor-pool surface the orchestration drives."""

    def create(self, num_workers: int) -> None:
        raise NotImplementedError

    def hostnames(self) -> List[str]:
        """One entry per worker, in worker order."""
        raise NotImplementedError

    def set_env(self, envs: List[Dict[str, str]]) -> None:
        raise NotImplementedError

    def execute(self, fn: Callable[[], Any]) -> List[Any]:
        raise NotImplementedError

    def shutdown(self) -> None:
        raise NotImplementedError


# ---------------------------------------------------------------- local pool
def _local_pool_worker(conn):
    env_updates: Dict[str, str] = {}
    while True:
        msg = conn.recv()
        kind, payload = msg
        if kind == "stop":
            conn.close()
            return
        if kind == "hostname":
            conn.send(("ok", socket.gethostname()))
        elif kind == "env":
            env_updates = payload
            os.environ.update(env_updates)
            conn.send(("ok", None))
        elif kind == "run":
            try:
                fn = pickle.loads(payload)
                conn.send(("ok", fn()))
            except BaseException as e:
                conn.send(("error", f"{e}\n{traceback.format_exc()}"))


class LocalWorkerPool(BaseWorkerPool):
    """Process-backed pool for ray-less environments/tests."""

    def __init__(self, start_method: str = "spawn"):
        self._ctx = multiprocessing.get_context(start_method)
        self._procs: List[Any] = []
        self._conns: List[Any] = []

    def create(self, num_workers: int) -> None:
        for _ in range(num_workers):
            parent, child = self._ctx.Pipe()
            p = self._ctx.Process(target=_local_pool_worker, args=(child,))
            p.start()
            self._procs.append(p)
            self._conns.append(parent)

    def _call_all(self, kind: str, payloads) -> List[Any]:
        for conn, payload in zip(self._conns, payloads):
            conn.send((kind, payload))
        # Drain EVERY pipe before raising: an early raise would leave
        # unread responses that desynchronize the next call's recv().
        out, error = [], None
        for i, conn in enumerate(self._conns):
            status, val = conn.recv()
            if status == "error" and error is None:
                error = (i, val)
            out.append(val)
        if error is not None:
            raise RuntimeError(f"worker {error[0]} failed: {error[1]}")
        return out

    def hostnames(self) -> List[str]:
        return self._call_all("hostname", [None] * len(self._conns))

    def set_env(self, envs: List[Dict[str, str]]) -> None:
        self._call_all("env", envs)

    def execute(self, fn: Callable[[], Any]) -> List[Any]:
        payload = pickle.dumps(fn)
        return self._call_all("run", [payload] * len(self._conns))

    def shutdown(self) -> None:
        for conn in self._conns:
            try:
                conn.send(("stop", None))
            except (BrokenPipeError, OSError):
                pass
        for p in self._procs:
            p.join(timeout=10)
            if p.is_alive():
                p.terminate()
        self._procs, self._conns = [], []


# ------------------------------------------------------------------ ray pool
class BaseHorovodWorker:
    """The actor class hosting one training slot (reference:
    horovod/ray/runner.py BaseHorovodWorker — exported so integrations
    can subclass/compose it into their own actors).  Plain class;
    RayWorkerPool wraps it with ``ray.remote`` at placement time, the
    reference's own pattern."""

    def hostname(self) -> str:
        import socket as s
        return s.gethostname()

    def set_env(self, env) -> None:
        import os as o
        o.environ.update(env)

    def run(self, payload):
        import pickle as p
        return p.loads(payload)()


class RayWorkerPool(BaseWorkerPool):
    """Ray-actor pool with Colocated/Pack placement (reference:
    strategy.py:32-204).  Requires ray at construction."""

    def __init__(self, cpus_per_worker: int = 1,
                 use_gpu: bool = False, gpus_per_worker: int = 0,
                 placement: str = "pack",
                 placement_group_timeout_s: float = 100.0):
        try:
            import ray  # noqa: F401
        except ImportError as e:
            raise ImportError(
                "RayExecutor on a real cluster requires ray; pass "
                "pool=LocalWorkerPool() for ray-less local mode") from e
        self._ray = __import__("ray")
        self.cpus_per_worker = cpus_per_worker
        self.use_gpu = use_gpu
        self.gpus_per_worker = gpus_per_worker
        self.placement = placement
        self.pg_timeout = placement_group_timeout_s
        self._actors: List[Any] = []
        self._pg = None

    def create(self, num_workers: int) -> None:
        ray = self._ray
        _Worker = ray.remote(BaseHorovodWorker)

        bundle = {"CPU": self.cpus_per_worker}
        if self.use_gpu and self.gpus_per_worker:
            bundle["GPU"] = self.gpus_per_worker
        strategy = "STRICT_PACK" if self.placement == "pack" else "SPREAD"
        self._pg = ray.util.placement_group([bundle] * num_workers,
                                            strategy=strategy)
        ray.get(self._pg.ready(), timeout=self.pg_timeout)
        self._actors = [
            _Worker.options(placement_group=self._pg,
                            num_cpus=self.cpus_per_worker,
                            num_gpus=self.gpus_per_worker
                            if self.use_gpu else 0).remote()
            for _ in range(num_workers)]

    def hostnames(self) -> List[str]:
        return self._ray.get([a.hostname.remote() for a in self._actors])

    def set_env(self, envs: List[Dict[str, str]]) -> None:
        self._ray.get([a.set_env.remote(e)
                       for a, e in zip(self._actors, envs)])

    def execute(self, fn: Callable[[], Any]) -> List[Any]:
        payload = pickle.dumps(fn)
        return self._ray.get([a.run.remote(payload) for a in self._actors])

    def shutdown(self) -> None:
        for a in self._actors:
            self._ray.kill(a)
        if self._pg is not None:
            self._ray.util.remove_placement_group(self._pg)
        self._actors, self._pg = [], None


# ----------------------------------------------------------------- executor
class RayExecutor:
    """The coordinator (reference: runner.py:128-344 + Coordinator
    runner.py:41-127): places workers, assigns ranks host-major (all
    workers on a host get consecutive local ranks), writes rendezvous env,
    and drives ``run``/``execute``."""

    def __init__(self, num_workers: int,
                 pool: Optional[BaseWorkerPool] = None,
                 coordinator_port: int = 29513,
                 env: Optional[Dict[str, str]] = None):
        self.num_workers = num_workers
        self.pool = pool if pool is not None else RayWorkerPool()
        self.coordinator_port = coordinator_port
        self.extra_env = dict(env or {})
        self._started = False

    def start(self) -> None:
        self.pool.create(self.num_workers)
        hostnames = self.pool.hostnames()
        # Rank/local/cross assignment shares the launcher's implementation
        # (runner/hosts.py env_for_tasks) — one source of truth for the
        # HOROVOD_* env conventions across hvdrun, Spark and Ray.  The
        # coordinator binds on rank 0's host, not the driver's.
        envs = env_for_tasks(hostnames, self.coordinator_port)
        merged = []
        for e in envs:
            m = dict(self.extra_env)
            m.update(e)
            merged.append(m)
        self.pool.set_env(merged)
        self._started = True

    def run(self, fn: Callable, args=(), kwargs=None) -> List[Any]:
        """Run ``fn(*args, **kwargs)`` on every worker; returns per-rank
        results (reference: runner.py:250-344 run/execute)."""
        if not self._started:
            raise RuntimeError("call start() first")
        kwargs = kwargs or {}
        return self.pool.execute(_Closure(fn, tuple(args), dict(kwargs)))

    # reference exposes both names
    execute = run

    def shutdown(self) -> None:
        self.pool.shutdown()
        self._started = False


class _Closure:
    def __init__(self, fn, args, kwargs):
        self.fn = fn
        self.args = args
        self.kwargs = kwargs

    def __call__(self):
        return self.fn(*self.args, **self.kwargs)
