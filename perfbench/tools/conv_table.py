"""scope_table.py with the named scopes of the short-convolution expert model
(horovod_tpu/models/conv_moe.py) in its list — ``conv/in``, ``conv/state``
(the state kind's read and write), ``conv/taps``, ``conv/out`` — so that the
tick's device time is split by them at each compiled width (PERF.md §5's
table of ``serve-moe-conv-chat``):

  python3 perfbench/tools/conv_table.py CELL [--no-check] [phase_table.py's options]

``--no-check`` leaves the served-path check out (the line then says
``correct: false``).  Under scope_table.py's own list the state's scatter
(``conv/state/kv_write``) would read ``kv_write`` with the attention
layers'.  The builder's tool, never the driver's.
"""

import os
import re
import sys

import scope_table                              # noqa: E402  (sets sys.path)
from width_table import PT, show, tables        # noqa: E402

# innermost first; a longer name before the name it starts with
SCOPES = ("conv/state", "conv/in", "conv/taps", "conv/out") + scope_table.SCOPES

if __name__ == "__main__":
    if "--no-check" in sys.argv:
        sys.argv.remove("--no-check")
        sys.path.insert(0, os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        import run                      # phase_table.py's own ``import run``
        run.pick_sample = lambda *a, **k: []
    PT.SCOPES = SCOPES
    PT.SCOPE_RE = re.compile(r"(?<![\w])(" + "|".join(SCOPES) + r")(?![\w])")
    PT.tables, PT.show = tables, show
    sys.exit(PT.main())
