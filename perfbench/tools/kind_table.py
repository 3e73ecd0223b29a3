"""scope_table.py with the named scopes of the window-and-global expert model
(horovod_tpu/models/swa_moe.py) in its list, the pool's writes and gathers
kept apart by cache kind, so that the tick's device time is split by them at
each compiled width (PERF.md §5's table of ``serve-moe-swa-longdoc``):

  python3 perfbench/tools/kind_table.py CELL [--no-check] [phase_table.py's options]

``--no-check`` leaves the served-path check out (the line then says
``correct: false``): the reference's two 14,848-position rows take minutes
of chip time that the table does not need.

Under scope_table.py's own list ``attn/window/kv_gather`` would read
``kv_gather`` with the global kind's.  The builder's tool, never the
driver's.
"""

import os
import re
import sys

import scope_table                              # noqa: E402  (sets sys.path)
from width_table import PT, show, tables        # noqa: E402

# innermost first; a longer name before the name it starts with
SCOPES = ("attn/window/kv_gather", "attn/window/kv_write",
          "attn/global/kv_gather", "attn/global/kv_write", "attn/window",
          "attn/global") + scope_table.SCOPES

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
