"""Planted faults for the limits of ``serve-moe-blockdiff-gen``: the served
program with one thing wrong, which the cell's check must call not correct.
Python imports a ``sitecustomize`` it finds on its path when it starts, so a
run with this directory first on ``PYTHONPATH`` and ``PB_PLANT`` set plants
the fault in every process of that run, the chip-holding child among them,
with no edit to the harness or the program and no option of either:

  PB_PLANT=A PYTHONPATH=perfbench/tools/planted python3 perfbench/run.py \
      --workload serve-moe-blockdiff-gen --seed N --seconds 45 --trace 0

  A  the plain causal mask inside a block (a position sees nothing of its
     own block behind it);
  B  every masked position of a block fixed in the block's first pass, the
     steps reported honestly (all 0): the served tokens are the model's best
     in the state they were chosen in, so the gaps may pass; the family's
     ``early_unmask_share`` must not.

Never in the benchmark's own runs (nothing sets ``PB_PLANT``); the tests
plant the same faults through :func:`plant`."""
import importlib.abc
import importlib.util
import os
import sys

TARGET = "horovod_tpu.models.blockdiff_moe"


def plant(module, fault):
    """Plant ``fault`` in the program's module (models/blockdiff_moe.py)."""
    if fault == "A":
        tile = module._attend_tile
        module._attend_tile = lambda block: tile(1)
    elif fault == "B":
        rule = module.fix_positions
        module.fix_positions = lambda conf, masked, cfg: (
            masked, rule(conf, masked, cfg)[1])
    else:
        raise ValueError(f"PB_PLANT={fault!r}: A or B")


class _Finder(importlib.abc.MetaPathFinder):
    """Lets the module load as it would, then plants the fault in it."""

    def find_spec(self, name, path, target=None):
        if name != TARGET:
            return None
        sys.meta_path.remove(self)
        spec = importlib.util.find_spec(name)
        loader = spec.loader

        class Loader(importlib.abc.Loader):
            def create_module(self, spec):
                return loader.create_module(spec)

            def exec_module(self, module):
                loader.exec_module(module)
                plant(module, os.environ["PB_PLANT"])
                print(f"perfbench: PLANTED FAULT {os.environ['PB_PLANT']} in "
                      f"{name}", flush=True)
        spec.loader = Loader()
        return spec


if os.environ.get("PB_PLANT"):
    sys.meta_path.insert(0, _Finder())
