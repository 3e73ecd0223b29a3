"""scope_table.py with the named scopes of the block-denoising expert model
(horovod_tpu/models/blockdiff_moe.py, serve/engine.py ``block_tick_program``)
in its list — ``attn/block`` (the block-masked attention), ``kv_commit`` (the
scatter of the tick's rows' keys and values), ``tick/unmask`` (candidate,
confidence, the choice), ``tick/chain`` — so that the tick's device time is
split by them at each compiled width (PERF.md §5's table of
``serve-moe-blockdiff-gen``):

  python3 perfbench/tools/block_table.py CELL [--no-check] [phase_table.py's options]

``--no-check`` leaves the served-path check out (the line then says
``correct: false``).  Under scope_table.py's own list the commit's scatter
(``kv_commit/kv_write``) would read ``kv_write`` and the attention ``attn``.
The builder's tool, never the driver's.
"""

import os
import re
import sys

import scope_table                              # noqa: E402  (sets sys.path)
from width_table import PT, show, tables        # noqa: E402

# innermost first; a longer name before the name it starts with
SCOPES = ("kv_commit", "kv_gather", "tick/unmask", "attn/block",
          "tick/chain") + tuple(s for s in scope_table.SCOPES
                                if s != "kv_gather")

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
