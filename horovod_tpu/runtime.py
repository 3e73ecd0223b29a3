"""Process-global runtime: the TPU-native analog of HorovodGlobalState.

The reference keeps a process-wide singleton holding the background thread,
controller, tensor queue, fusion buffers and knobs (reference:
horovod/common/global_state.h:43-132, operations.cc:115) initialized once by
``horovod_init`` (operations.cc:651-699).  On TPU the data plane is XLA SPMD
over a `jax.sharding.Mesh`, so the runtime's job becomes:

  * bring up the (optionally multi-host) JAX runtime and build the mesh,
  * own the knob snapshot, bucket-plan cache, timeline and stall inspector,
  * expose the rank/size topology API.

Topology model (TPU-native reinterpretation of Horovod's 1-process-per-GPU):
the *worker unit is the chip*.  ``size()`` is the number of chips in the mesh
and ``local_size()`` the chips owned by this process.  A process controls
``local_size()`` workers at once — eager collectives therefore accept a
leading per-chip axis (see ops/collectives.py).  Process-level coordinates
(``process_rank``/``process_size``) correspond to the reference's CROSS
communicator scope, and local chips to the LOCAL scope
(reference: common.h:119-123, mpi_context.cc:147-156).
"""

from __future__ import annotations

import atexit
import threading
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from .common import hvdlogging as log
from .common.knobs import Knobs

_lock = threading.Lock()
_runtime: Optional["Runtime"] = None


def _parse_mesh_spec(spec: str) -> List[Tuple[str, int]]:
    """Parse 'data=4,model=2' into [('data', 4), ('model', 2)]."""
    axes: List[Tuple[str, int]] = []
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        name, _, size = part.partition("=")
        axes.append((name.strip(), int(size)))
    return axes


class Runtime:
    """Holds the mesh, knobs and auxiliary subsystems for this process."""

    def __init__(self,
                 knobs: Optional[Knobs] = None,
                 devices: Optional[Sequence[Any]] = None,
                 mesh_spec: Optional[str] = None):
        import jax

        self.knobs = knobs or Knobs()
        self._shutdown = False

        # Multi-host bring-up: the launcher (hvdrun) exports coordinator
        # address + process coordinates (the analog of mpirun exporting
        # HOROVOD_RANK/SIZE per slot, reference: gloo_run.py:65-77).
        # jax.distributed.initialize must run before ANY backend-touching
        # call (including jax.process_count()), so gate purely on env/knobs.
        coord = self.knobs["HOROVOD_COORDINATOR_ADDR"]
        if coord and self.knobs["HOROVOD_SIZE"] > 1:
            try:
                jax.distributed.initialize(
                    coordinator_address=coord,
                    num_processes=self.knobs["HOROVOD_SIZE"],
                    process_id=max(self.knobs["HOROVOD_RANK"], 0),
                    initialization_timeout=self.knobs[
                        "HOROVOD_START_TIMEOUT"],
                )
            except RuntimeError as e:
                # Already initialized (e.g. by user code) is fine.
                if "already" not in str(e).lower():
                    raise

        self.devices = list(devices if devices is not None else jax.devices())
        self._process_index = jax.process_index()
        self._process_count = jax.process_count()

        spec = mesh_spec if mesh_spec is not None else self.knobs["HOROVOD_TPU_MESH"]
        # 3D layout plane (parallel/layout.py; docs/parallelism.md):
        # HOROVOD_LAYOUT owns the mesh when set — validated BEFORE mesh
        # construction (the layout IS the mesh), 'auto' ranks the
        # factorizations with perf/costmodel.solve_layout under any
        # HOROVOD_TP / HOROVOD_PP constraints.
        from .parallel.layout import (validate_layout_knobs,
                                      resolve_layout, layout_mesh_spec)
        validate_layout_knobs(self.knobs, world=len(self.devices),
                              mesh_spec=str(spec))
        self.layout = resolve_layout(len(self.devices), self.knobs)
        if self.layout is not None:
            spec = layout_mesh_spec(*self.layout)
        self.mesh = self._build_mesh(spec)
        # Canonical worker numbering = flattened *mesh* position, which is
        # what lax.axis_index sees inside collectives.  create_device_mesh
        # may permute devices for ICI adjacency, so re-derive the ordered
        # device list from the mesh rather than jax.devices().
        self.devices = list(self.mesh.devices.flatten())
        self.local_devices = [d for d in self.devices
                              if d.process_index == self._process_index]

        # Bucket-plan cache: the analog of the response cache — repeat steps
        # skip re-planning (reference: response_cache.h:44-100).
        from .ops.fusion import BucketPlanCache
        self.plan_cache = BucketPlanCache(
            capacity=self.knobs["HOROVOD_CACHE_CAPACITY"])

        # Tracing plane (utils/timeline.py, docs/timeline.md): clock
        # alignment first — the NTP-style offset handshake against the
        # rendezvous server puts every rank's trace events on one fleet
        # epoch; a rank without a reachable server traces locally with
        # offset 0 and infinite uncertainty.
        self.clock_sync = None
        rdv_addr = self.knobs["HOROVOD_RENDEZVOUS_ADDR"]
        rdv_port = self.knobs["HOROVOD_RENDEZVOUS_PORT"]
        if rdv_addr and rdv_port and (self.knobs["HOROVOD_TIMELINE"]
                                      or self.knobs["HOROVOD_HEARTBEAT"]):
            # Heartbeats ride the same aligned fleet clock as the trace
            # (postmortem ordering depends on it, docs/postmortem.md).
            from .utils.clocksync import ClockSync
            self.clock_sync = ClockSync(rdv_addr, rdv_port)

        # Timeline + stall inspector are created lazily by their modules.
        self.timeline = None
        self.timeline_publisher = None
        self._trace_drainer = None
        self._timeline_path = self.knobs["HOROVOD_TIMELINE"]
        if self._timeline_path and self._timeline_path != "DYNAMIC":
            from .utils.timeline import Timeline
            self.timeline = Timeline(self._timeline_path,
                                     mark_cycles=self.knobs[
                                         "HOROVOD_TIMELINE_MARK_CYCLES"],
                                     clock=self.clock_sync,
                                     rank=self._process_index)
            self._start_timeline_publisher()

        # Wire-policy plane (ops/wire.py): validate HOROVOD_WIRE_POLICY
        # now — an unknown policy name must fail AT INIT, not as a trace
        # error deep inside the first compiled step.
        from .ops.wire import validate_policy_name
        validate_policy_name(self.knobs["HOROVOD_WIRE_POLICY"])

        # Overlap plane (ops/overlap.py): same init-validation contract
        # for HOROVOD_OVERLAP_DEPTH / HOROVOD_PREFETCH_DEPTH — plus the
        # negative-value checks the wire-era validation never grew for
        # the core numeric knobs.
        from .ops.overlap import validate_overlap_knobs
        validate_overlap_knobs(self.knobs)
        # ZeRO weight-update sharding (parallel/zero.py; docs/zero.md):
        # level and AG-prefetch depth fail AT INIT, not as a trace
        # error inside the first compiled zero step.
        from .parallel.zero import validate_zero_knobs
        validate_zero_knobs(self.knobs)
        # Serving plane (serve/; docs/serving.md): same init-validation
        # contract for the HOROVOD_SERVE_* knob surface (port range,
        # positive budgets) — config-only import, no model/jax cost.
        from .serve.config import validate_serve_knobs
        validate_serve_knobs(self.knobs)
        # Perf-attribution plane (perf/; docs/profiling.md): same
        # init-validation contract for HOROVOD_PERF_* (link class,
        # positive publish period).
        from .perf import validate_perf_knobs
        validate_perf_knobs(self.knobs)
        # Watch plane (watch/; docs/watch.md): series bounds, sentinel
        # cadence, and — when HOROVOD_ALERTS names a rules file — a full
        # parse, so a typo'd ruleset fails bring-up, not a detector.
        from .watch import validate_watch_knobs
        validate_watch_knobs(self.knobs)
        # Memory plane (perf/memstats.py; docs/memory.md): sample rate
        # limit and the OOM-proximity watermark fraction.
        from .perf import validate_mem_knobs
        validate_mem_knobs(self.knobs)
        # Scenario engine (scenario/; docs/scenarios.md): rank/tick
        # overrides, and — when HOROVOD_SCENARIO names a spec — a full
        # parse, so a typo'd scenario fails bring-up, not a replay.
        from .scenario import validate_scenario_knobs
        validate_scenario_knobs(self.knobs)
        if self.knobs["HOROVOD_FUSION_THRESHOLD"] <= 0:
            raise ValueError(
                f"HOROVOD_FUSION_THRESHOLD="
                f"{self.knobs['HOROVOD_FUSION_THRESHOLD']} invalid; the "
                "bucket threshold must be a positive byte count")
        if self.knobs["HOROVOD_CACHE_CAPACITY"] < 0:
            raise ValueError(
                f"HOROVOD_CACHE_CAPACITY="
                f"{self.knobs['HOROVOD_CACHE_CAPACITY']} invalid; use 0 "
                "to disable caching, a positive entry count otherwise")
        # Plan-epoch fast path (csrc/controller.cc; docs/tensor-fusion.md):
        # the native core reads these from env at construction, so a bad
        # value must fail HERE, not as a silently-never-locking epoch.
        if self.knobs["HOROVOD_BYPASS_STABLE_CYCLES"] < 1:
            raise ValueError(
                f"HOROVOD_BYPASS_STABLE_CYCLES="
                f"{self.knobs['HOROVOD_BYPASS_STABLE_CYCLES']} invalid; "
                "the epoch lock needs at least 1 stable step "
                "(docs/knobs.md)")
        # Sharded rendezvous KV (docs/control-plane.md): validate the
        # shard count and the launcher-stamped address list here so a
        # malformed map fails bring-up, not a KV op mid-run.  The
        # client's per-scope routing itself reads the env lazily
        # (runner/http_client), so nothing needs installing.
        if self.knobs["HOROVOD_KV_SHARDS"] < 1:
            raise ValueError(
                f"HOROVOD_KV_SHARDS={self.knobs['HOROVOD_KV_SHARDS']} "
                "invalid; the rendezvous KV needs at least one shard "
                "(docs/control-plane.md)")
        if self.knobs["HOROVOD_KV_SHARD_ADDRS"]:
            from .runner.kvshard import parse_shard_addrs
            addrs = parse_shard_addrs(self.knobs["HOROVOD_KV_SHARD_ADDRS"])
            if len(addrs) != self.knobs["HOROVOD_KV_SHARDS"]:
                raise ValueError(
                    f"HOROVOD_KV_SHARD_ADDRS lists {len(addrs)} "
                    f"shard(s) but HOROVOD_KV_SHARDS="
                    f"{self.knobs['HOROVOD_KV_SHARDS']}; the scope->"
                    "shard map is a modulus of the count, so the two "
                    "must agree (docs/control-plane.md)")

        # Autotune (reference: HOROVOD_AUTOTUNE + ParameterManager,
        # parameter_manager.{h,cc}): Bayesian optimization over (fusion
        # threshold, cycle time), native math in csrc/optim.cc.  When the
        # wire policy is 'auto', the policy dimension joins the search as
        # a bandit over policy arms (mesh-aware: dcn_int8 is only an arm
        # on a two-level mesh).
        self.autotuner = None
        if self.knobs["HOROVOD_AUTOTUNE"]:
            from .utils.autotune import Autotuner
            policy_arms = None
            if self.knobs["HOROVOD_WIRE_POLICY"] == "auto":
                policy_arms = ["auto", "none", "bf16", "int8_ring"]
                if any(str(a).startswith("dcn.")
                       for a in self.mesh.axis_names):
                    policy_arms.append("dcn_int8")
            # Overlap-depth arm dimension (ops/overlap.py): only worth
            # searching when the pipeline is on; the knob's depth stays
            # an arm so tuning can conclude it was right.
            depth_arms = None
            if self.knobs["HOROVOD_OVERLAP"]:
                knob_d = int(self.knobs["HOROVOD_OVERLAP_DEPTH"])
                depth_arms = sorted({1, 2, 4, knob_d})
            self.autotuner = Autotuner(self.knobs,
                                       process_rank=self._process_index,
                                       process_size=self._process_count,
                                       policy_arms=policy_arms,
                                       depth_arms=depth_arms)

        self.stall_inspector = None
        if not self.knobs["HOROVOD_STALL_CHECK_DISABLE"]:
            from .utils.stall import StallInspector
            self.stall_inspector = StallInspector(
                warn_seconds=self.knobs["HOROVOD_STALL_CHECK_TIME_SECONDS"],
                shutdown_seconds=self.knobs[
                    "HOROVOD_STALL_SHUTDOWN_TIME_SECONDS"])

        # Metrics plane (utils/metrics.py): when enabled, this worker
        # publishes periodic registry snapshots to the rendezvous KV so
        # the launcher's /metrics route serves a fleet-wide Prometheus
        # view and can print the end-of-run straggler report.
        self.metrics_publisher = None
        if self.knobs["HOROVOD_METRICS"]:
            from .utils.metrics import MetricsPublisher
            self.metrics_publisher = MetricsPublisher(
                addr=self.knobs["HOROVOD_RENDEZVOUS_ADDR"],
                port=self.knobs["HOROVOD_RENDEZVOUS_PORT"],
                rank=self._process_index,
                snapshot_fn=self.metrics_snapshot,
                interval=self.knobs["HOROVOD_METRICS_INTERVAL"])

        # Perf-attribution plane (perf/; docs/profiling.md): when
        # enabled, this worker publishes its step-time decomposition
        # report to the rendezvous KV scope 'perf' so GET /perf serves
        # the merged fleet view and doctor --perf can render it.  The
        # ledger itself is always live (recording costs nothing until a
        # step is recorded); the knob gates only the publisher thread.
        self.perf_publisher = None
        if self.knobs["HOROVOD_PERF"]:
            from .perf import resolve_link
            from .perf.ledger import GLOBAL as _perf_ledger
            from .perf.ledger import PerfPublisher
            _perf_ledger.configure(link=resolve_link(self.knobs,
                                                     self.mesh))
            self.perf_publisher = PerfPublisher(
                addr=self.knobs["HOROVOD_RENDEZVOUS_ADDR"],
                port=self.knobs["HOROVOD_RENDEZVOUS_PORT"],
                rank=self._process_index,
                interval=self.knobs["HOROVOD_PERF_INTERVAL"])

        # Postmortem plane (docs/postmortem.md): per-rank heartbeats to
        # the rendezvous KV scope 'health' — step progress, native cycle
        # liveness and pending-collective counts on the aligned fleet
        # clock — so the launcher can supervise progress (/health,
        # hvdrun --postmortem) and the postmortem can order last events.
        self.heartbeat = None
        if self.knobs["HOROVOD_HEARTBEAT"]:
            from .utils.health import HeartbeatPublisher
            self.heartbeat = HeartbeatPublisher(
                addr=self.knobs["HOROVOD_RENDEZVOUS_ADDR"],
                port=self.knobs["HOROVOD_RENDEZVOUS_PORT"],
                rank=self._process_index,
                payload_fn=self._heartbeat_payload,
                interval=self.knobs["HOROVOD_HEARTBEAT_INTERVAL"])

        # Chaos plane (chaos/): install this rank's deterministic fault
        # injector from the rendezvous-distributed spec (hvdrun --chaos)
        # or a local spec file.  Must precede ensure_core(): the native
        # transport reads its HOROVOD_CHAOS_* env at construction.
        from . import chaos as _chaos
        _chaos.ensure_installed(self.knobs, rank=self._process_index)

        # Native core (C++ controller/tensor-queue): negotiates a global
        # execution order for eager multi-process collectives (SPMD paths
        # don't need it — XLA programs are deterministic).  Reference:
        # the MPI/Gloo controller choice at operations.cc:654-687.
        # Created lazily by ensure_core(): only consumers that need
        # negotiation (eager/torch frontends) pay the TCP bring-up.
        self.core = None
        mode = str(self.knobs["HOROVOD_CONTROLLER"]).lower()
        if mode not in ("auto", "tcp", "none"):
            raise ValueError(
                f"HOROVOD_CONTROLLER={mode!r} not supported; use 'auto', "
                "'tcp' or 'none' (this framework's controller transport is "
                "TCP; the reference's 'mpi'/'gloo' values do not apply)")
        self._controller_mode = mode
        if mode == "tcp":
            self.ensure_core()

        log.debug("Runtime up: %d devices, %d local, mesh=%s",
                  len(self.devices), len(self.local_devices),
                  self.mesh.shape if self.mesh else None)

    # ------------------------------------------------------------------ mesh
    def _build_mesh(self, spec: str):
        import jax
        from jax.experimental import mesh_utils
        from jax.sharding import Mesh

        n = len(self.devices)
        if not spec:
            axes = [("hvd", n)]
        else:
            axes = _parse_mesh_spec(spec)
            # A single trailing -1 axis absorbs the remaining chips.
            sizes = [s for _, s in axes]
            if -1 in sizes:
                known = int(np.prod([s for s in sizes if s != -1]))
                axes = [(a, s if s != -1 else n // known) for a, s in axes]
        shape = tuple(s for _, s in axes)
        names = tuple(a for a, _ in axes)
        if int(np.prod(shape)) != n:
            raise ValueError(
                f"mesh spec {spec!r} covers {int(np.prod(shape))} chips but "
                f"{n} are visible")
        try:
            # ICI-topology-aware assignment: keeps high-traffic axes on
            # physically adjacent chips so collectives ride ICI links.
            devs = mesh_utils.create_device_mesh(shape, devices=self.devices)
        except (ValueError, AssertionError, NotImplementedError) as e:
            # A device list jax cannot map onto the physical topology (a
            # caller-chosen subset, say) still forms a correct mesh in
            # list order; on a chip that order may put ring neighbours
            # on non-adjacent chips, so say so instead of hiding it.
            if self.devices[0].platform != "cpu":
                log.warning(
                    "create_device_mesh(%s) failed (%s); falling back to "
                    "device-list order — collectives may not ride "
                    "adjacent ICI links", shape, e)
            devs = np.array(self.devices).reshape(shape)
        return Mesh(devs, names)

    # -------------------------------------------------------------- topology
    # Chip-level coordinates ("rank" = chip, matching 1-process-per-GPU in
    # the reference once you substitute chip for GPU).
    def size(self) -> int:
        return len(self.devices)

    def local_size(self) -> int:
        return len(self.local_devices)

    def rank(self) -> int:
        """Global index of this process's first chip."""
        if not self.local_devices:
            return 0
        first = self.local_devices[0]
        return self.devices.index(first)

    def local_rank(self) -> int:
        """Process index within its host when launched by hvdrun (reference
        semantics: HOROVOD_LOCAL_RANK, gloo_run.py:65-77); 0 standalone."""
        lr = self.knobs["HOROVOD_LOCAL_RANK"]
        return lr if lr >= 0 else 0

    def local_chip_positions(self) -> List[int]:
        """Mesh-flattened positions of this process's chips, in the order
        local data rows map to them (increasing mesh position)."""
        return [i for i, d in enumerate(self.devices)
                if d.process_index == self._process_index]

    def chip_positions_by_process(self) -> List[List[int]]:
        """For each process index, the mesh positions of its chips (in
        increasing order) — the host-side map between process-major data
        (process_allgather results) and chip-major collective numbering."""
        out: List[List[int]] = [[] for _ in range(self._process_count)]
        for i, d in enumerate(self.devices):
            out[d.process_index].append(i)
        return out

    # Process-level coordinates: CROSS scope in the reference.
    def process_rank(self) -> int:
        return self._process_index

    def process_size(self) -> int:
        return self._process_count

    def cross_rank(self) -> int:
        return self._process_index

    def cross_size(self) -> int:
        return self._process_count

    # ------------------------------------------------------------------ core
    def ensure_core(self):
        """Bring up the native coordination core on first use (idempotent).

        Consumers: eager frontends that need cross-process ordering (torch
        bindings, negotiated grouped ops).  In 'auto' mode single-process
        runs never create it; multi-process runs create it on demand using
        the coordinator host from HOROVOD_COORDINATOR_ADDR."""
        if self.core is not None:
            return self.core
        if self._controller_mode == "none":
            return None
        if self._controller_mode == "auto" and self._process_count <= 1:
            return None
        coord = self.knobs["HOROVOD_COORDINATOR_ADDR"]
        coord_host = coord.split(":")[0] if coord else "127.0.0.1"
        from .common.basics import CoordinationCore
        self.core = CoordinationCore.tcp(
            rank=self._process_index, size=self._process_count,
            addr=coord_host,
            port=self.knobs["HOROVOD_CONTROLLER_PORT"],
            cycle_ms=self.knobs["HOROVOD_CYCLE_TIME"],
            fusion_bytes=self.knobs["HOROVOD_FUSION_THRESHOLD"],
            cache_capacity=self.knobs["HOROVOD_CACHE_CAPACITY"],
            stall_warn_seconds=self.knobs[
                "HOROVOD_STALL_CHECK_TIME_SECONDS"])
        if self.knobs["HOROVOD_AUTOTUNE"]:
            self.core.enable_autotune(
                warmup_samples=self.knobs["HOROVOD_AUTOTUNE_WARMUP_SAMPLES"],
                steps_per_sample=self.knobs[
                    "HOROVOD_AUTOTUNE_STEPS_PER_SAMPLE"],
                max_samples=self.knobs[
                    "HOROVOD_AUTOTUNE_BAYES_OPT_MAX_SAMPLES"],
                gp_noise=self.knobs[
                    "HOROVOD_AUTOTUNE_GAUSSIAN_PROCESS_NOISE"])
        # Postmortem plane: arm the crash-time flight recorder as soon
        # as there is a core to record (csrc/postmortem.cc; the launcher
        # sets a per-rank path under --postmortem).
        if self.knobs["HOROVOD_FLIGHT_RECORD"]:
            self.core.flight_enable(self.knobs["HOROVOD_FLIGHT_RECORD"])
        self._attach_native_trace()
        return self.core

    def fusion_threshold(self) -> int:
        """Live fusion threshold: autotuned when enabled, knob otherwise."""
        if self.autotuner is not None:
            return self.autotuner.fusion_threshold
        return self.knobs["HOROVOD_FUSION_THRESHOLD"]

    def wire_policy(self) -> str:
        """Live wire-policy name for the fused gradient sync (ops/wire.py).

        Reads the knob via ``current`` (env wins, so tests and launchers
        can flip it without re-initializing) and, when tuning is on,
        refines 'auto' to the bandit's current policy arm — which rank 0
        broadcasts with the threshold, so every process compiles the same
        SPMD program.  A policy change re-traces, like a threshold change.
        """
        from .common.knobs import current
        from .ops.wire import validate_policy_name
        name = validate_policy_name(current("HOROVOD_WIRE_POLICY"))
        if name == "auto" and self.autotuner is not None:
            arm = self.autotuner.wire_policy
            if arm is not None:
                return arm
        return name

    def overlap_enabled(self) -> bool:
        """Live overlap-plane switch (env wins, the `current` contract —
        ops/overlap.py; docs/overlap.md)."""
        from .common.knobs import current
        return bool(current("HOROVOD_OVERLAP"))

    def overlap_depth(self) -> int:
        """Live microbatch-pipeline depth: the knob, refined to the
        bandit's current depth arm when tuning is on — broadcast with the
        threshold so all ranks compile identical SPMD programs (a depth
        change re-traces, like a threshold change)."""
        from .common.knobs import current
        from .ops.overlap import MAX_OVERLAP_DEPTH
        depth = int(current("HOROVOD_OVERLAP_DEPTH"))
        if not 1 <= depth <= MAX_OVERLAP_DEPTH:
            raise ValueError(
                f"HOROVOD_OVERLAP_DEPTH={depth} invalid; must be in "
                f"[1, {MAX_OVERLAP_DEPTH}] (docs/overlap.md)")
        if self.autotuner is not None:
            arm = self.autotuner.overlap_depth
            if arm is not None:
                return arm
        return depth

    def zero_level(self) -> int:
        """Live default ZeRO weight-update sharding level (env-live via
        ``current``; the zero chain's kwarg wins — parallel/zero.py,
        docs/zero.md)."""
        from .common.knobs import current
        from .parallel.zero import resolve_zero_level
        return resolve_zero_level(int(current("HOROVOD_ZERO_LEVEL")))

    def zero_ag_prefetch(self) -> int:
        """Live ZeRO-3 param all-gather prefetch depth: the knob,
        refined to the bandit's tuned overlap-depth arm when tuning is
        on — the SAME arm dimension the microbatch pipeline tunes, so
        one broadcast covers both planes and all ranks compile
        identical SPMD programs (docs/zero.md)."""
        from .common.knobs import current
        from .ops.overlap import MAX_OVERLAP_DEPTH
        depth = int(current("HOROVOD_ZERO_AG_PREFETCH"))
        if not 1 <= depth <= MAX_OVERLAP_DEPTH:
            raise ValueError(
                f"HOROVOD_ZERO_AG_PREFETCH={depth} invalid; must be in "
                f"[1, {MAX_OVERLAP_DEPTH}] (docs/zero.md)")
        if self.autotuner is not None:
            arm = self.autotuner.overlap_depth
            if arm is not None:
                return arm
        return depth

    # -------------------------------------------------------------- metrics
    def metrics_snapshot(self) -> Dict[str, Any]:
        """Point-in-time view of every metric family this process holds
        (the public ``hvd.metrics_snapshot()``): registry values refreshed
        from their live sources — native controller counters/histograms,
        bucket-plan cache, stall inspector — in one JSON-able dict."""
        from .utils import metrics as M
        M.RUNTIME_SIZE.set(self.size())
        M.RUNTIME_LOCAL_SIZE.set(self.local_size())
        # Native build tag (docs/static-analysis.md): loaded_build_info
        # never forces a library load — a pure-SPMD process that built no
        # native core reports nothing rather than paying a csrc build.
        from .common import basics as _basics
        binfo = _basics.loaded_build_info()
        if binfo is not None:
            M.NATIVE_SANITIZER_BUILD.set(
                1, sanitizer=binfo.get("sanitizer", "none"))
        M.PLAN_CACHE_HITS.set_total(self.plan_cache.hits)
        M.PLAN_CACHE_MISSES.set_total(self.plan_cache.misses)
        if self.stall_inspector is not None:
            M.STALL_PENDING.set(self.stall_inspector.pending_count())
        if self.core is not None and getattr(self.core, "_h", None):
            try:
                M.import_core_metrics(self.core.metrics())
            except Exception:
                pass  # a closing core must not break the snapshot
            # Watch plane: the natively-windowed hvd_*_rate gauges ride
            # the same snapshot (csrc/window.h; docs/watch.md).
            try:
                M.import_window_rates(self.core.metrics_window())
            except Exception:
                pass  # pre-watch library or closing core: rates absent
            # Perf plane: the native per-op-name aggregates ride the
            # same snapshot (hvd_perf_native_op_* families).
            try:
                from .perf.ledger import import_op_stats
                import_op_stats(self.core)
            except Exception:
                pass
        # Memory plane (perf/memstats.py; docs/memory.md): sample the
        # measured ledger on the snapshot cadence — the hvd_mem_*
        # families ride THIS snapshot into the publisher, the series
        # store and the committed mem-* rules.
        try:
            from .perf import memstats
            memstats.sample(core=self.core)
        except Exception:
            pass  # sampling must never break a snapshot
        return M.REGISTRY.snapshot()

    def _heartbeat_payload(self) -> Dict[str, Any]:
        """One heartbeat for the health plane (utils/health.py): step
        progress, native core liveness and the pending-collective count
        — the field fleet-stall attribution keys on."""
        from .utils.health import heartbeat_payload
        pending = None
        if self.stall_inspector is not None:
            pending = self.stall_inspector.pending_count()
        core = self.core
        if core is not None and not getattr(core, "_h", None):
            core = None  # closing core: heartbeat must not touch it
        return heartbeat_payload(self._process_index,
                                 clock=self.clock_sync, core=core,
                                 pending_collectives=pending)

    # ------------------------------------------------------------- lifecycle
    def shutdown(self) -> None:
        if self._shutdown:
            return
        self._shutdown = True
        # Final heartbeat while the core is still alive: the postmortem's
        # last-known state for this rank.
        if self.heartbeat is not None:
            self.heartbeat.close()
        # Final metrics publish while the native core is still alive, so
        # the straggler report sees complete histograms.
        if self.metrics_publisher is not None:
            self.metrics_publisher.close()
        # Final perf-report publish: the fleet /perf view keeps this
        # rank's last decomposition after it exits.
        if self.perf_publisher is not None:
            self.perf_publisher.close()
        # Tracing teardown order: final native drain while the core is
        # alive, final chunk publish while the rendezvous may still be
        # up, then close the local file.
        if self._trace_drainer is not None:
            self._trace_drainer.close()
        if self.timeline_publisher is not None:
            self.timeline_publisher.close()
        if self.timeline is not None:
            self.timeline.close()
        if self.autotuner is not None:
            self.autotuner.close()
        if self.stall_inspector is not None:
            self.stall_inspector.close()
        if self.core is not None:
            self.core.shutdown()
            self.core.close()

    # ------------------------------------------------------------- timeline
    def _start_timeline_publisher(self) -> None:
        """Chunk publishing to the rendezvous 'timeline' scope, when a
        server is known — what GET /timeline and --timeline-merge read."""
        addr = self.knobs["HOROVOD_RENDEZVOUS_ADDR"]
        port = self.knobs["HOROVOD_RENDEZVOUS_PORT"]
        if not (addr and port) or self.timeline is None:
            return
        from .utils.timeline import TimelinePublisher
        try:
            # Replica-fleet lane namespacing (docs/timeline.md): a
            # nonzero serving replica id stamps the chunks so the merged
            # view renders replica{K}.rank{N} lanes.
            replica = int(self.knobs["HOROVOD_SERVE_REPLICA_ID"])
        except Exception:
            replica = 0
        self.timeline_publisher = TimelinePublisher(
            addr=addr, port=port, rank=self._process_index,
            timeline=self.timeline,
            interval=self.knobs["HOROVOD_TIMELINE_MERGE_INTERVAL"],
            clock=self.clock_sync, replica=replica)

    def _attach_native_trace(self) -> None:
        """Pump the native core's span ring into the timeline (idempotent;
        called whenever either side comes up after the other)."""
        if self.core is None or self.timeline is None \
                or self._trace_drainer is not None:
            return
        from .utils.timeline import NativeTraceDrainer
        self._trace_drainer = NativeTraceDrainer(self.core, self.timeline)

    def start_timeline(self, path: str, mark_cycles: bool = False) -> None:
        """Runtime-activated timeline (reference: operations.cc:740-769)."""
        from .utils.timeline import Timeline
        self.stop_timeline()
        if self.clock_sync is None:
            addr = self.knobs["HOROVOD_RENDEZVOUS_ADDR"]
            port = self.knobs["HOROVOD_RENDEZVOUS_PORT"]
            if addr and port:
                from .utils.clocksync import ClockSync
                self.clock_sync = ClockSync(addr, port)
        self.timeline = Timeline(path, mark_cycles=mark_cycles,
                                 clock=self.clock_sync,
                                 rank=self._process_index)
        self._start_timeline_publisher()
        self._attach_native_trace()

    def stop_timeline(self) -> None:
        if self._trace_drainer is not None:
            self._trace_drainer.close()
            self._trace_drainer = None
        if self.timeline_publisher is not None:
            self.timeline_publisher.close()
            self.timeline_publisher = None
        if self.timeline is not None:
            self.timeline.close()
            self.timeline = None


# ----------------------------------------------------------------- module API
def init(mesh_spec: Optional[str] = None,
         devices: Optional[Sequence[Any]] = None,
         **overrides: Any) -> Runtime:
    """Initialize the process-global runtime (idempotent).

    The analog of ``hvd.init()`` -> InitializeHorovodOnce (reference:
    operations.cc:651-699); callers block until the runtime is usable.
    """
    global _runtime
    with _lock:
        if _runtime is None:
            _runtime = Runtime(knobs=Knobs(overrides or None),
                               devices=devices, mesh_spec=mesh_spec)
            atexit.register(shutdown)
        return _runtime


def is_initialized() -> bool:
    return _runtime is not None


def get() -> Runtime:
    if _runtime is None:
        raise RuntimeError(
            "horovod_tpu has not been initialized; call hvd.init() first "
            "(reference semantics: operations.cc:695-697 blocks until init)")
    return _runtime


def shutdown() -> None:
    """The analog of ``hvd.shutdown()`` (reference: operations.cc:731-738)."""
    global _runtime
    with _lock:
        if _runtime is not None:
            _runtime.shutdown()
            _runtime = None
