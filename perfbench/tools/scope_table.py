"""width_table.py with the named scopes of a latent-attention expert model
(horovod_tpu/models/latent_moe.py, parallel/expert.py) in its list, so that
the tick's device time is split by them at each compiled width:

  python3 perfbench/tools/scope_table.py CELL [phase_table.py's options]

phase_table.py's own list is the dense decoder's; under it the new scopes
would all read ``tick/model``.  The builder's tool, never the driver's.
"""

import re
import sys

from width_table import PT, show, tables       # noqa: E402  (sets sys.path)

# innermost first; a longer name before the name it starts with
SCOPES = ("kv_gather", "kv_write", "attn/q_lora", "attn/kv_latent",
          "attn/latent_scores", "attn/out", "moe/route", "moe/dispatch",
          "moe/experts", "moe/shared", "moe/combine") + tuple(
              s for s in PT.SCOPES if s not in ("kv_gather", "kv_write"))

if __name__ == "__main__":
    PT.SCOPES = SCOPES
    PT.SCOPE_RE = re.compile(r"(?<![\w])(" + "|".join(SCOPES) + r")(?![\w])")
    PT.tables, PT.show = tables, show
    sys.exit(PT.main())
