"""scope_table.py with the named scopes that the cell's FAMILY declares
(``families/<family>.py`` ``SCOPES``, innermost first) in its list, so that
the tick's device time is split by them at each compiled width — a family
that brings named scopes adds that tuple, not a tool of its own, as
conv_table.py, ssm_table.py, block_table.py and kind_table.py each are (a
PR that is no ``benchmark`` one may not fold them in here):

  python3 perfbench/tools/family_table.py CELL [--no-check] [--trace-at S] [phase_table.py's options]

``--no-check`` leaves the served-path check out (the line then says
``correct: false``); ``--trace-at S`` begins the trace S seconds into the
window instead of where the cell's traffic file does (``serve-gdn-mixedlen``
traces a prompt mid-prefill, wide ticks alone: 15.0 there holds narrow ones).
The pools' gathers and scatters stay apart by the scope above them
(``attn/full/kv_gather`` is ``attn/full``'s row, ``gdn/state/kv_write``
``gdn/state``'s).  The builder's tool, never the driver's.
"""

import os
import re
import sys

import scope_table                              # noqa: E402  (sets sys.path)
from width_table import PT, show, tables        # noqa: E402


def scopes(cell):
    from perfbench.lib import spec
    _, config, _ = spec.cell(cell)
    own = tuple(getattr(spec.family(config), "SCOPES", ()))
    return own + tuple(s for s in scope_table.SCOPES if s not in own
                       and (not own or s not in ("kv_gather", "kv_write")))


def _flag(name, takes_value=False):
    if name not in sys.argv:
        return None
    i = sys.argv.index(name)
    got = sys.argv[i + 1] if takes_value else True
    del sys.argv[i:i + 1 + takes_value]
    return got


if __name__ == "__main__":
    no_check, at = _flag("--no-check"), _flag("--trace-at", True)
    if no_check or at:
        sys.path.insert(0, os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        import run                      # phase_table.py's own ``import run``
        if no_check:
            run.pick_sample = lambda *a, **k: []
        if at:
            cell_of = run.spec.cell

            def cell(name, *a):
                entry, config, traffic = cell_of(name, *a)
                return entry, config, dict(traffic, trace=dict(
                    traffic["trace"], start_s=float(at)))
            run.spec.cell = cell
    SCOPES = scopes(next(a for a in sys.argv[1:] if not a.startswith("-")))
    PT.SCOPES = SCOPES
    PT.SCOPE_RE = re.compile(r"(?<![\w])(" + "|".join(SCOPES) + r")(?![\w])")
    PT.tables, PT.show = tables, show
    sys.exit(PT.main())
