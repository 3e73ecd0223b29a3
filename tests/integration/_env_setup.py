"""Shared worker bootstrap: CPU virtualization BEFORE jax backend init.

Import this as the first statement of every integration worker:

    import _env_setup  # noqa: F401

Each worker process drives 4 virtual CPU chips by default (HVD_CPU_CHIPS
overrides); with -np 2 the mesh is 8 chips across 2 real processes.
The env setup (platform, device count) lives in ONE place — scripts/_cpu_bootstrap.py — shared with the dryrun
native-controller worker and the eager bench.
"""

import importlib.util
import os

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_spec = importlib.util.spec_from_file_location(
    "_cpu_bootstrap", os.path.join(_REPO, "scripts", "_cpu_bootstrap.py"))
_mod = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_mod)
_mod.bootstrap(default_chips=4)
