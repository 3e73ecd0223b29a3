"""scope_table.py with the named scopes of the decoder-hybrid-decoder
(horovod_tpu/models/sambay.py) in its list — ``ssm/in``, ``ssm/conv``,
``ssm/state`` (the two state kinds' reads and scatters), ``ssm/scan``,
``ssm/out``, ``gmu``, ``attn/window``, ``attn/shared`` (the one full layer's
scatter and the eight bounded reads of its pool), ``attn/diff`` — so that the
tick's device time is split by them at each compiled width (PERF.md §5's
table of ``serve-ssm-yoco-reason``):

  python3 perfbench/tools/ssm_table.py CELL [--no-check] [phase_table.py's options]

``--no-check`` leaves the served-path check out (the line then says
``correct: false``).  The pools' gathers and scatters stay apart by the
scope above them (``attn/window/kv_gather`` is ``attn/window``'s row here,
``ssm/state/kv_write`` ``ssm/state``'s).  The builder's tool, never the
driver's.
"""

import os
import re
import sys

import scope_table                              # noqa: E402  (sets sys.path)
from width_table import PT, show, tables        # noqa: E402

# innermost first; a longer name before the name it starts with
SCOPES = ("ssm/state", "ssm/scan", "ssm/conv", "ssm/in", "ssm/out", "gmu",
          "attn/window", "attn/shared", "attn/diff") + tuple(
              s for s in scope_table.SCOPES
              if s not in ("kv_gather", "kv_write"))

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
