"""The host's critical path between two programs, a back-to-back tick: delta
between the window's marks of ``stats()["loop"]``'s ``turnaround_s`` over
that of ``turnaround_n`` (serve/engine.py ``_count_gap``: the instant
``_harvest`` found a tick's tokens ready to the return of the next tick's
``launch`` span, for every tick launched in the ``step()`` that fenced the
one before).  Prints the six parts it is made of and the busy loop's period
(``iteration_s``), each in ms a tick.  None where the marks lack the
fields."""


def read(ctx):
    a, b = (ctx["marks"][m].get("stats", {}).get("loop") or {}
            for m in ("start", "end"))
    if "turnaround_n" not in a or "turnaround_n" not in b:
        return None
    n = b["turnaround_n"] - a["turnaround_n"]
    if not n:
        return None
    per_tick = lambda x, y: 1e3 * (y - x) / n
    parts = {k: per_tick(a["turnaround_parts_s"][k], v)
             for k, v in b["turnaround_parts_s"].items()}
    print("perfbench: turnaround ms/tick "
          + " ".join(f"{k}={v:.3f}" for k, v in parts.items())
          + f" iteration={per_tick(a['iteration_s'], b['iteration_s']):.3f}"
          f" back_to_back={n}"
          f" after_idle={b['after_idle_n'] - a['after_idle_n']}", flush=True)
    return per_tick(a["turnaround_s"], b["turnaround_s"])
