"""chip_smoke.py (the repo-root chip check): its ``--dry-run`` drives the
same phases and the same code on the CPU at ``tiny`` size, and without
the flag a host with no TPU fails in the device phase, printing no
result."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _smoke(*flags, **env):
    return subprocess.run(
        [sys.executable, SMOKE, *flags], capture_output=True, text=True,
        timeout=300, cwd=REPO, env=dict(os.environ, **env))


def test_dry_run_passes_every_phase():
    # two virtual devices: the all-reduce count and the sharded-cache
    # checks of the four-chip run are exercised here too
    out = _smoke("--dry-run", JAX_PLATFORMS="tpu",  # the parent overrides it
                 XLA_FLAGS="--xla_force_host_platform_device_count=2")
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-4000:]
    rows = [json.loads(ln) for ln in out.stdout.strip().splitlines()]
    *phases, final = rows
    assert [r["phase"] for r in phases] == ["device", "train", "serve",
                                            "kernel"]
    for r in phases:  # every line names its device and can never be
        assert r["platform"] == "cpu" and r["dry_run"] is True  # a chip pass
        assert r["device_count"] == 2 and r["smoke_observation"] is True
        assert r["versions"]["jax"] and r["wall_s"] > 0
    assert final == {"ok": True, "dry_run": True,
                     "device": {"platform": "cpu", "kind": "cpu", "count": 2}}
    by = {r["phase"]: r for r in phases}
    train = by["train"]
    assert train["hvd_size"] == 2 and train["model"] == "tiny"
    assert train["lowerings_per_call"][1] == 0
    assert train["losses"][1][-1] < train["losses"][0][0]
    assert abs(train["losses"][0][0] - train["one_device_first_loss"]) \
        <= train["loss_tol"]
    assert train["hlo_collectives"].get("all-reduce", 0) >= 1
    assert train["hlo_reduction_group_sizes"] == [2]
    assert train["compile_cache_dir"] is None  # never on the CPU
    serve = by["serve"]
    assert "platform=cpu, device_kind=cpu, devices=2," in serve["ready_line"]
    assert serve["prefix_cache"]["hits"] >= 2
    assert serve["router"]["completed"] == 4
    kernel = by["kernel"]
    assert kernel["interpret"] is True and kernel["geometries"]
    assert kernel["train_losses"][-1] < kernel["train_losses"][0]
    # the servable (the big artifact) is gone; only logs stay behind
    assert not os.path.exists(os.path.join(REPO, ".chip_smoke", "servable"))


def test_without_a_tpu_fails_in_the_device_phase():
    out = _smoke(JAX_PLATFORMS="cpu")  # not trusted: the smoke asks for tpu
    assert out.returncode != 0
    assert out.stdout.strip() == ""  # no result of any kind
    assert "FAILED phase=device" in out.stderr
