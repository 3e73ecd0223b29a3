"""What the loop held at its commit point, in ms a tick: delta between the
window's marks of ``stats()["loop"]``'s ``hold_s`` (serve/worker.py
``FleetFrontend._hold``: the wait, after a fenced tick's tokens are
published, until the tick in flight is about to end, so that the next plan
is fixed late and an arrival joins the next program) over the engine ticks
between the marks.  Prints how often it held, why it did not, and what the
due time was made of at the window's end.  0 where the loop never held;
None where the marks lack the field, as on a program whose loop launches
as soon as it has fenced."""


def read(ctx):
    a, b = (ctx["marks"][m].get("stats", {}).get("loop") or {}
            for m in ("start", "end"))
    if "hold_s" not in a or "hold_s" not in b:
        return None
    ticks = ctx["marks"]["end"]["tick"] - ctx["marks"]["start"]["tick"]
    if not ticks:
        return None
    skipped = {why: n - a["hold_skipped_n"].get(why, 0)
               for why, n in b["hold_skipped_n"].items()}
    commit = b.get("commit") or {}
    ms = lambda s: None if s is None else round(1e3 * s, 3)
    estimate = {w: ms(s) for w, s in commit.get("estimate_s", {}).items()}
    print(f"perfbench: holds={b['hold_n'] - a['hold_n']} of {ticks} ticks "
          f"skipped={skipped} estimate_ms={estimate} "
          f"margin_ms={ms(commit.get('margin_s'))}", flush=True)
    return 1e3 * (b["hold_s"] - a["hold_s"]) / ticks
