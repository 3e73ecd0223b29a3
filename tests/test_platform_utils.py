"""Process-level jax setup (utils/platform.py): the CPU forcing every
``--cpu`` smoke path depends on, and the one place the persistent
compilation cache is placed."""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code, env_extra=None):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.update(env_extra or {})
    return subprocess.run([sys.executable, "-c", code],
                          capture_output=True, text=True, timeout=120,
                          env=env)


def test_force_cpu_binds_after_jax_import_and_keeps_launcher_flags():
    """One subprocess, both contracts: called after ``import horovod_tpu``
    has imported jax (the examples' ``--cpu`` order) it still binds the
    CPU backend, and a device count the launcher already put in
    XLA_FLAGS is not clobbered."""
    out = _run("""
import os
from horovod_tpu.utils.platform import force_cpu
force_cpu(virtual_chips=4)  # launcher already set 2; must NOT clobber
import jax
assert jax.config.jax_platforms == "cpu"
assert os.environ["JAX_PLATFORMS"] == "cpu"
assert "device_count=2" in os.environ["XLA_FLAGS"], os.environ["XLA_FLAGS"]
assert len(jax.devices()) == 2 and jax.devices()[0].platform == "cpu"
os.environ["XLA_FLAGS"] = ""
force_cpu(virtual_chips=4)  # no flag yet: requests the virtual chips
assert "device_count=4" in os.environ["XLA_FLAGS"]
print("OK")
""", env_extra={"XLA_FLAGS": "--xla_force_host_platform_device_count=2",
                "JAX_PLATFORMS": ""})
    assert out.returncode == 0 and "OK" in out.stdout, out.stderr[-800:]


def test_force_cpu_raises_after_foreign_backend_init(monkeypatch):
    """Called too late (another platform's backend is already up) it must
    raise rather than silently mis-bind."""
    import jax
    from horovod_tpu.utils import platform as P

    class FakeCfg:
        jax_platforms = "tpu"

        @staticmethod
        def update(k, v):
            raise RuntimeError("backends already initialized")
    monkeypatch.setattr(jax, "config", FakeCfg())
    try:
        P.force_cpu()
    except RuntimeError as e:
        assert "before any jax-touching import" in str(e)
    else:
        raise AssertionError("force_cpu() did not raise")


class _Cfg:
    """jax.config stand-in that records what the code under test sets."""

    def __init__(self, platforms):
        self.jax_platforms = platforms
        self.updates = {}

    def update(self, key, value):
        self.updates[key] = value


def test_compile_cache_dir_from_env_sets_nothing_in_code(monkeypatch):
    import jax
    from horovod_tpu.utils import platform as P
    cfg = _Cfg(platforms="tpu")
    monkeypatch.setattr(jax, "config", cfg)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    assert P.enable_compile_cache() == "/some/dir"
    assert cfg.updates == {}


def test_compile_cache_dir_default_is_the_fixed_path(monkeypatch, tmp_path):
    import jax
    from horovod_tpu.utils import platform as P
    # fixed means fixed: inside the checkout, no pid, no time, no tempdir
    assert P.DEFAULT_COMPILE_CACHE_DIR == os.path.join(REPO, ".jax_cache")
    cfg = _Cfg(platforms=None)
    monkeypatch.setattr(jax, "config", cfg)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    fixed = str(tmp_path / ".jax_cache")  # keep the test out of the checkout
    monkeypatch.setattr(P, "DEFAULT_COMPILE_CACHE_DIR", fixed)
    assert P.enable_compile_cache() == fixed and os.path.isdir(fixed)
    # exactly one config key is set in code: the cache directory
    assert list(cfg.updates.values()) == [fixed]
    assert "cache_dir" in next(iter(cfg.updates))


def test_compile_cache_stays_off_on_cpu(monkeypatch):
    import jax
    from horovod_tpu.utils import platform as P
    cfg = _Cfg(platforms="cpu")
    monkeypatch.setattr(jax, "config", cfg)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    assert P.enable_compile_cache() is None
    assert cfg.updates == {"jax_enable_compilation_cache": False}
