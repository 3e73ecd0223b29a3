"""Launcher tests (reference analog: test/single/test_run.py:63-234 CLI/env
construction, hosts tests, rendezvous KV tests)."""

import os
import subprocess
import sys
import textwrap

import pytest

from horovod_tpu.runner import hosts as H
from horovod_tpu.runner.launch import (args_to_env, build_worker_command,
                                       config_file_to_env, launch_static,
                                       make_parser, run_commandline)
from horovod_tpu.runner.http_server import RendezvousServer
from horovod_tpu.runner.http_client import put_kv, get_kv, delete_kv


# ------------------------------------------------------------------- hosts
def test_parse_hosts():
    infos = H.parse_hosts("h1:4,h2:2,h3")
    assert [(h.hostname, h.slots) for h in infos] == \
        [("h1", 4), ("h2", 2), ("h3", 1)]


def test_parse_hosts_errors():
    with pytest.raises(ValueError):
        H.parse_hosts("")
    with pytest.raises(ValueError):
        H.parse_hosts("h1:2,h1:2")


def test_host_assignments_single_host():
    slots = H.get_host_assignments(H.parse_hosts("localhost:4"), 4)
    assert [s.rank for s in slots] == [0, 1, 2, 3]
    assert all(s.size == 4 and s.local_size == 4 and s.cross_size == 1
               for s in slots)
    assert [s.local_rank for s in slots] == [0, 1, 2, 3]


def test_host_assignments_multi_host():
    """LOCAL/CROSS coordinates (reference: hosts.py:100-155)."""
    slots = H.get_host_assignments(H.parse_hosts("a:2,b:2"), 4)
    assert [(s.hostname, s.rank, s.local_rank, s.cross_rank)
            for s in slots] == \
        [("a", 0, 0, 0), ("a", 1, 1, 0), ("b", 2, 0, 1), ("b", 3, 1, 1)]
    assert all(s.cross_size == 2 for s in slots)


def test_host_assignments_partial_last_host():
    slots = H.get_host_assignments(H.parse_hosts("a:2,b:2"), 3)
    assert [s.hostname for s in slots] == ["a", "a", "b"]
    assert slots[2].local_size == 1


def test_host_assignments_oversubscribe_rejected():
    with pytest.raises(ValueError):
        H.get_host_assignments(H.parse_hosts("a:2"), 3)


def test_slot_env_block():
    slot = H.get_host_assignments(H.parse_hosts("a:2,b:2"), 4)[2]
    env = slot.to_env()
    assert env["HOROVOD_RANK"] == "2"
    assert env["HOROVOD_SIZE"] == "4"
    assert env["HOROVOD_LOCAL_RANK"] == "0"
    assert env["HOROVOD_CROSS_RANK"] == "1"


# --------------------------------------------------------------- CLI -> env
def test_args_to_env_flags():
    args = make_parser().parse_args(
        ["-np", "2", "--fusion-threshold-mb", "64", "--cycle-time-ms",
         "2.5", "--timeline-filename", "/tmp/t.json", "--no-stall-check",
         "--log-level", "debug", "--autotune", "--mesh", "data=8",
         "python", "t.py"])
    env = args_to_env(args)
    assert env["HOROVOD_FUSION_THRESHOLD"] == str(64 * 1024 * 1024)
    assert env["HOROVOD_CYCLE_TIME"] == "2.5"
    assert env["HOROVOD_TIMELINE"] == "/tmp/t.json"
    assert env["HOROVOD_STALL_CHECK_DISABLE"] == "1"
    assert env["HOROVOD_LOG_LEVEL"] == "debug"
    assert env["HOROVOD_AUTOTUNE"] == "1"
    assert env["HOROVOD_TPU_MESH"] == "data=8"


def test_config_file_to_env(tmp_path):
    """YAML schema parity (reference: single/data/config.test.yaml,
    config_parser.py:202)."""
    cfg = tmp_path / "cfg.yaml"
    cfg.write_text(textwrap.dedent("""
        params:
          fusion_threshold_mb: 32
          cycle_time_ms: 3.0
        timeline:
          filename: /tmp/tl.json
          mark_cycles: true
        stall_check:
          warning_time_seconds: 120
        autotune:
          enabled: true
    """))
    env = {}
    config_file_to_env(str(cfg), env)
    assert env["HOROVOD_FUSION_THRESHOLD"] == str(32 * 1024 * 1024)
    assert env["HOROVOD_TIMELINE"] == "/tmp/tl.json"
    assert env["HOROVOD_TIMELINE_MARK_CYCLES"] == "1"
    assert env["HOROVOD_STALL_CHECK_TIME_SECONDS"] == "120"
    assert env["HOROVOD_AUTOTUNE"] == "1"


def test_cli_flag_beats_config(tmp_path):
    cfg = tmp_path / "cfg.yaml"
    cfg.write_text("params:\n  fusion_threshold_mb: 32\n")
    args = make_parser().parse_args(
        ["-np", "1", "--config-file", str(cfg),
         "--fusion-threshold-mb", "8", "python", "t.py"])
    env = args_to_env(args)
    assert env["HOROVOD_FUSION_THRESHOLD"] == str(8 * 1024 * 1024)


def test_build_worker_command_local_vs_ssh():
    slots = H.get_host_assignments(H.parse_hosts("localhost:1,remotehost:1"), 2)
    local = build_worker_command(slots[0], ["python", "t.py"], {}, None,
                                 None)
    assert local == ["python", "t.py"]
    remote = build_worker_command(slots[1], ["python", "t.py"],
                                  {"HOROVOD_RANK": "1"}, 2222, None)
    assert remote[0] == "ssh"
    assert "-p" in remote and "2222" in remote
    assert "HOROVOD_RANK=1" in remote[-1]


# ----------------------------------------------------------------- rendezvous
def test_rendezvous_kv_roundtrip():
    srv = RendezvousServer()
    port = srv.start()
    try:
        put_kv("127.0.0.1", port, "scope", "key", b"value42")
        assert get_kv("127.0.0.1", port, "scope", "key") == b"value42"
        assert get_kv("127.0.0.1", port, "scope", "missing",
                      timeout=0) is None
        assert delete_kv("127.0.0.1", port, "scope", "key")
        assert get_kv("127.0.0.1", port, "scope", "key",
                      timeout=0) is None
        # server-side direct put (launcher publishing slot info)
        srv.put("rank", "0", b"{}")
        assert get_kv("127.0.0.1", port, "rank", "0") == b"{}"
    finally:
        srv.stop()


def test_rendezvous_blocking_get():
    import threading
    import time
    srv = RendezvousServer()
    port = srv.start()
    try:
        def later():
            time.sleep(0.3)
            put_kv("127.0.0.1", port, "s", "k", b"eventually")
        threading.Thread(target=later, daemon=True).start()
        assert get_kv("127.0.0.1", port, "s", "k", timeout=5.0) == \
            b"eventually"
    finally:
        srv.stop()


# ------------------------------------------------------------- CLI behavior
def test_cli_no_command():
    assert run_commandline(["-np", "2"]) == 2


def test_cli_version(capsys):
    assert run_commandline(["--version"]) == 0
    import horovod_tpu
    assert horovod_tpu.__version__ in capsys.readouterr().out


# --------------------------------------------------------------- integration
def test_launch_static_two_local_processes(tmp_path, monkeypatch):
    """End-to-end static run on localhost (reference analog:
    test/integration/test_static_run.py): two processes check their env and
    write rank files."""
    import horovod_tpu
    repo = os.path.dirname(os.path.dirname(horovod_tpu.__file__))
    monkeypatch.setenv("PYTHONPATH", repo)
    script = tmp_path / "worker.py"
    script.write_text(textwrap.dedent(f"""
        import os
        rank = os.environ["HOROVOD_RANK"]
        size = os.environ["HOROVOD_SIZE"]
        assert size == "2"
        assert os.environ["HOROVOD_RENDEZVOUS_ADDR"]
        # rendezvous reachable from the worker
        from horovod_tpu.runner.http_client import get_kv
        info = get_kv(os.environ["HOROVOD_RENDEZVOUS_ADDR"],
                      int(os.environ["HOROVOD_RENDEZVOUS_PORT"]),
                      "rank", rank)
        assert info is not None
        open(r"{tmp_path}/out_" + rank, "w").write(size)
    """))
    args = make_parser().parse_args(
        ["-np", "2", "--controller-port", "29601",
         sys.executable, str(script)])
    rc = launch_static(args, [sys.executable, str(script)])
    assert rc == 0
    assert (tmp_path / "out_0").read_text() == "2"
    assert (tmp_path / "out_1").read_text() == "2"


def test_launcher_parent_never_initializes_a_backend():
    """A chip belongs to one process: the hvdrun parent may import jax
    but must not touch a backend, or its workers could never get the
    chip (docs/tpus.md).  Under a platform that cannot initialize, a
    parent that tried would raise; the worker here never touches jax."""
    import horovod_tpu
    repo = os.path.dirname(os.path.dirname(horovod_tpu.__file__))
    out = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.runner.launch", "-np", "1",
         sys.executable, "-c", "print('worker ran')"],
        capture_output=True, text=True, timeout=120, cwd=repo,
        env=dict(os.environ, PYTHONPATH=repo,
                 JAX_PLATFORMS="no_such_platform"))
    assert out.returncode == 0, out.stderr[-2000:]
    assert "worker ran" in out.stdout


def test_launch_static_propagates_failure(tmp_path):
    script = tmp_path / "bad.py"
    script.write_text("import os, sys; "
                      "sys.exit(3 if os.environ['HOROVOD_RANK']=='1' "
                      "else 0)")
    args = make_parser().parse_args(
        ["-np", "2", sys.executable, str(script)])
    rc = launch_static(args, [sys.executable, str(script)])
    assert rc == 3


def test_args_to_env_new_flags():
    """Round-2 launcher flags (reference: horovodrun --disable-cache,
    hierarchical toggles, autotune fine knobs, --num-nccl-streams,
    --start-timeout)."""
    args = make_parser().parse_args(
        ["-np", "2", "--disable-cache", "--hierarchical-allreduce",
         "--no-hierarchical-allgather", "--num-streams", "4",
         "--start-timeout", "60", "--autotune-warmup-samples", "5",
         "--autotune-steps-per-sample", "20",
         "--autotune-bayes-opt-max-samples", "30",
         "--autotune-gaussian-process-noise", "0.5",
         "python", "t.py"])
    env = args_to_env(args)
    assert env["HOROVOD_CACHE_CAPACITY"] == "0"
    assert env["HOROVOD_HIERARCHICAL_ALLREDUCE"] == "1"
    assert env["HOROVOD_HIERARCHICAL_ALLGATHER"] == "0"
    assert env["HOROVOD_NUM_STREAMS"] == "4"
    assert env["HOROVOD_START_TIMEOUT"] == "60"
    assert env["HOROVOD_AUTOTUNE_WARMUP_SAMPLES"] == "5"
    assert env["HOROVOD_AUTOTUNE_STEPS_PER_SAMPLE"] == "20"
    assert env["HOROVOD_AUTOTUNE_BAYES_OPT_MAX_SAMPLES"] == "30"
    assert env["HOROVOD_AUTOTUNE_GAUSSIAN_PROCESS_NOISE"] == "0.5"
    # untouched flags contribute nothing
    plain = args_to_env(make_parser().parse_args(["-np", "2", "x"]))
    for k in env:
        assert k not in plain


def test_num_nccl_streams_alias():
    args = make_parser().parse_args(
        ["-np", "1", "--num-nccl-streams", "3", "x"])
    assert args_to_env(args)["HOROVOD_NUM_STREAMS"] == "3"


def test_check_build_output(capsys):
    from horovod_tpu.runner.launch import run_commandline
    rc = run_commandline(["--check-build"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "Available Frameworks" in out
    assert "[X] JAX" in out
    assert "[X] XLA collectives (ICI/DCN)" in out
    assert "[ ] NCCL" in out


def test_output_filename_captures_per_rank(tmp_path):
    """--output-filename must write each worker's streams to
    <dir>/rank.<N>/stdout (reference: horovodrun --output-filename)."""
    from horovod_tpu.runner.launch import run_commandline
    outdir = tmp_path / "logs"
    rc = run_commandline(
        ["-np", "2", "--output-filename", str(outdir),
         sys.executable, "-c",
         "import os; print('hello from', os.environ['HOROVOD_RANK'])"])
    assert rc == 0
    for rank in (0, 1):
        data = (outdir / f"rank.{rank}" / "stdout").read_bytes().decode()
        assert f"hello from {rank}" in data


def test_resolve_coord_host_semantics():
    """Coordinator address rules: loopback only for all-local runs; the
    real hostname when remote workers must dial in; NIC pin only when
    rank 0 is this machine (regression: multi-host runs handed remotes
    127.0.0.1)."""
    import socket
    from horovod_tpu.runner.launch import resolve_coord_host

    # all-local: loopback
    assert resolve_coord_host("localhost", None) == "127.0.0.1"
    # local rank 0 + remote workers: a remotely-dialable name
    got = resolve_coord_host("localhost", None, has_remote_workers=True)
    assert got == socket.gethostname()
    here = socket.gethostname()
    assert resolve_coord_host(here, None,
                              has_remote_workers=True) == here
    # remote rank 0: hostname passes through, NIC pin warns
    warnings = []
    assert resolve_coord_host("far-away-host", "eth0",
                              warn=warnings.append,
                              has_remote_workers=True) == "far-away-host"
    assert warnings and "eth0" in warnings[0]


# ------------------------------------------------------- TPU pod discovery
def test_tpu_discovery_from_env_matches_explicit_hosts():
    """--tpu with TPU_WORKER_HOSTNAMES must produce the same SlotInfo set
    as the equivalent explicit -H list (VERDICT-r2 #5 done-criterion)."""
    from horovod_tpu.runner.launch import resolve_hosts
    from horovod_tpu.runner.tpu_discovery import discover_tpu_hosts

    env = {"TPU_WORKER_HOSTNAMES": "tpu-vm-0,tpu-vm-1,tpu-vm-2,tpu-vm-3"}
    discovered = discover_tpu_hosts(environ=env,
                                    metadata_fetch=lambda a: None)
    explicit = H.parse_hosts("tpu-vm-0:1,tpu-vm-1:1,tpu-vm-2:1,tpu-vm-3:1")
    assert discovered == explicit
    assert H.get_host_assignments(discovered, 4) == \
        H.get_host_assignments(explicit, 4)


def test_tpu_discovery_from_gce_metadata():
    from horovod_tpu.runner.tpu_discovery import (discover_tpu_hosts,
                                                  tpu_worker_id)

    meta = {"worker-network-endpoints":
            "10.0.0.2:8470:0,10.0.0.3:8470:1",
            "agent-worker-number": "1"}
    hosts = discover_tpu_hosts(environ={}, metadata_fetch=meta.get)
    assert [h.hostname for h in hosts] == ["10.0.0.2", "10.0.0.3"]
    assert all(h.slots == 1 for h in hosts)
    assert tpu_worker_id(environ={}, metadata_fetch=meta.get) == 1


def test_tpu_discovery_single_host_slice_is_none():
    from horovod_tpu.runner.tpu_discovery import discover_tpu_hosts
    # TPU images default TPU_WORKER_HOSTNAMES=localhost on single-host
    # slices; that must NOT trigger multi-host mode
    assert discover_tpu_hosts(environ={"TPU_WORKER_HOSTNAMES": "localhost"},
                              metadata_fetch=lambda a: None) is None
    assert discover_tpu_hosts(environ={},
                              metadata_fetch=lambda a: None) is None


def test_lsf_allocation_hosts(tmp_path, monkeypatch):
    """Inside an LSF job, hvdrun consumes the granted allocation without
    -H (reference: runner/util/lsf.py); hostname multiplicity = slots;
    explicit flags still win; --tpu skips LSF."""
    from horovod_tpu.runner.launch import resolve_hosts
    from horovod_tpu.runner.lsf import lsf_hosts

    hf = tmp_path / "hostfile"
    hf.write_text("batch1\nbatch1\nnode2\nnode2\nnode2\n")
    got = lsf_hosts(environ={"LSB_DJOB_HOSTFILE": str(hf)})
    assert [(h.hostname, h.slots) for h in got] == \
        [("batch1", 2), ("node2", 3)]
    # inline fallback
    got = lsf_hosts(environ={"LSB_HOSTS": "a a b"})
    assert [(h.hostname, h.slots) for h in got] == [("a", 2), ("b", 1)]
    assert lsf_hosts(environ={}) is None

    # wired through resolve_hosts
    monkeypatch.setenv("LSB_HOSTS", "lsfa lsfa lsfb")
    monkeypatch.delenv("LSB_DJOB_HOSTFILE", raising=False)
    args = make_parser().parse_args(["-np", "3", "cmd"])
    assert [(h.hostname, h.slots) for h in resolve_hosts(args)] == \
        [("lsfa", 2), ("lsfb", 1)]
    # explicit -H beats the allocation
    args = make_parser().parse_args(["-np", "2", "-H", "x:2", "cmd"])
    assert [(h.hostname, h.slots) for h in resolve_hosts(args)] == \
        [("x", 2)]
    # -np beyond the granted slots: local fallback, not a hard error
    # (interactive 1-slot bsub shells must not break `hvdrun -np 4`)
    monkeypatch.setenv("LSB_HOSTS", "onehost")
    args = make_parser().parse_args(["-np", "4", "cmd"])
    assert [(h.hostname, h.slots) for h in resolve_hosts(args)] == \
        [("localhost", 4)]


def test_tpu_flag_requires_discovery(monkeypatch):
    from horovod_tpu.runner.launch import resolve_hosts
    monkeypatch.delenv("TPU_WORKER_HOSTNAMES", raising=False)
    monkeypatch.setattr(
        "horovod_tpu.runner.tpu_discovery._metadata_fetch",
        lambda a, timeout=2.0: None)
    args = make_parser().parse_args(["--tpu", "-np", "2", "cmd"])
    with pytest.raises(ValueError, match="no multi-host TPU slice"):
        resolve_hosts(args)


def test_tpu_flag_conflicts_with_hosts():
    from horovod_tpu.runner.launch import resolve_hosts
    args = make_parser().parse_args(["--tpu", "-H", "a:1", "cmd"])
    with pytest.raises(ValueError, match="drop -H"):
        resolve_hosts(args)


def test_tpu_discovery_wired_through_resolve_hosts(monkeypatch):
    from horovod_tpu.runner.launch import resolve_hosts
    monkeypatch.setenv("TPU_WORKER_HOSTNAMES", "vm-a,vm-b")
    args = make_parser().parse_args(["--tpu", "--slots", "4", "-np", "8",
                                     "cmd"])
    hosts = resolve_hosts(args)
    assert [(h.hostname, h.slots) for h in hosts] == [("vm-a", 4),
                                                      ("vm-b", 4)]


def test_tpu_autodetect_falls_back_when_np_exceeds_slots(monkeypatch,
                                                         capsys):
    from horovod_tpu.runner.launch import resolve_hosts
    monkeypatch.setenv("TPU_WORKER_HOSTNAMES", "vm-a,vm-b")
    monkeypatch.delenv("TPU_WORKER_ID", raising=False)
    args = make_parser().parse_args(["-np", "4", "cmd"])
    hosts = resolve_hosts(args)  # auto-detect, but -np 4 > 2 slots
    assert [(h.hostname, h.slots) for h in hosts] == [("localhost", 4)]


def test_tpu_nonzero_worker_refuses_driver_role(monkeypatch):
    from horovod_tpu.runner.launch import resolve_hosts
    monkeypatch.setenv("TPU_WORKER_HOSTNAMES", "vm-a,vm-b")
    monkeypatch.setenv("TPU_WORKER_ID", "1")
    args = make_parser().parse_args(["--tpu", "-np", "2", "cmd"])
    with pytest.raises(ValueError, match="worker 0 only"):
        resolve_hosts(args)
    # plain hvdrun on a non-zero worker quietly runs locally instead
    args = make_parser().parse_args(["-np", "2", "cmd"])
    assert resolve_hosts(args)[0].hostname == "localhost"


def test_tpu_flag_defaults_np_like_explicit_hosts(monkeypatch, tmp_path):
    """`hvdrun --tpu cmd` without -np must not be rejected: np defaults
    to the discovered slot total exactly like an explicit -H list."""
    import horovod_tpu.runner.launch as L
    monkeypatch.setenv("TPU_WORKER_HOSTNAMES", "vm-a,vm-b")
    monkeypatch.delenv("TPU_WORKER_ID", raising=False)
    seen = {}

    def fake_launch_static(args, command):
        seen["np"] = args.num_proc
        seen["hosts"] = [h.hostname for h in L.resolve_hosts(args)]
        return 0

    monkeypatch.setattr(L, "launch_static", fake_launch_static)
    rc = run_commandline(["--tpu", "echo", "ok"])
    assert rc == 0
    assert seen["np"] is None  # launch_static derives it from slots
    assert seen["hosts"] == ["vm-a", "vm-b"]


def test_prefix_output_with_timestamp(tmp_path):
    import subprocess
    import time as _time
    from horovod_tpu.runner.launch import spawn_with_output
    p = spawn_with_output(
        [sys.executable, "-c", "print('hello'); print('world')"],
        dict(os.environ), str(tmp_path), rank=3, prefix_timestamp=True)
    p.wait()
    for _ in range(50):  # pump threads flush asynchronously
        text = (tmp_path / "rank.3" / "stdout").read_text()
        if "world" in text:
            break
        _time.sleep(0.1)
    lines = text.strip().splitlines()
    assert all("<rank 3>" in ln and ln.startswith("[2") for ln in lines), \
        lines
    assert lines[0].endswith("hello") and lines[1].endswith("world")


def test_transport_selector_flags():
    assert run_commandline(["--mpi", "-np", "1", "echo", "x"]) == 2
    assert run_commandline(["--gloo", "-np", "1", "echo", "x"]) == 2
    # --tcp is the (only) default transport: accepted as a no-op
    args = make_parser().parse_args(["--tcp", "-np", "1", "cmd"])
    assert args.tcp


def test_hostnames_alias():
    args = make_parser().parse_args(["--hostnames", "a:1,b:1", "cmd"])
    assert args.hosts == "a:1,b:1"


def test_get_kv_default_patience_follows_gloo_timeout_knob(monkeypatch):
    """timeout=None reads HOROVOD_GLOO_TIMEOUT_SECONDS (reference:
    --gloo-timeout-seconds bounds worker waits on the rendezvous)."""
    import time as _time

    from horovod_tpu.runner.http_server import RendezvousServer
    from horovod_tpu.runner.http_client import get_kv

    monkeypatch.setenv("HOROVOD_GLOO_TIMEOUT_SECONDS", "1")
    srv = RendezvousServer()
    port = srv.start()
    try:
        t0 = _time.monotonic()
        assert get_kv("127.0.0.1", port, "s", "never") is None
        waited = _time.monotonic() - t0
        assert 0.8 <= waited < 5.0, waited  # knob-bounded, not 0/30s
    finally:
        srv.stop()


def test_reference_flag_spellings_funnel_to_knobs(capsys):
    """The upstream launcher's exact flag spellings must work unchanged
    (reference launch.py:469-527): stall-check pair + warning/shutdown
    names, log-timestamp pairs, gloo timeout; CPU-affinity flags are
    accepted with a warning, never silently."""
    args = make_parser().parse_args(
        ["-np", "2", "--stall-check",
         "--stall-check-warning-time-seconds", "30",
         "--stall-check-shutdown-time-seconds", "90",
         "--log-with-timestamp", "--gloo-timeout-seconds", "45",
         "--no-timeline-mark-cycles",
         "--binding-args", "-bind-to socket",
         "python", "t.py"])
    env = args_to_env(args)
    assert env["HOROVOD_STALL_CHECK_DISABLE"] == "0"
    assert env["HOROVOD_STALL_CHECK_TIME_SECONDS"] == "30"
    assert env["HOROVOD_STALL_SHUTDOWN_TIME_SECONDS"] == "90"
    assert env["HOROVOD_LOG_HIDE_TIME"] == "0"
    assert env["HOROVOD_GLOO_TIMEOUT_SECONDS"] == "45"
    assert env["HOROVOD_TIMELINE_MARK_CYCLES"] == "0"
    assert "no effect on a TPU stack" in capsys.readouterr().err

    args = make_parser().parse_args(
        ["-np", "2", "--log-hide-timestamp", "python", "t.py"])
    assert args_to_env(args)["HOROVOD_LOG_HIDE_TIME"] == "1"
