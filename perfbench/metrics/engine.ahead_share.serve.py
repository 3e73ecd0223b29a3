"""Share of the tick launches that ran ahead of their fence: delta between
the window's marks of ``stats()["loop"]``'s ``ahead_n`` (serve/engine.py
``_count_gap``: launches made while the tick before was still unfenced, so
the program was queued behind it on the device) over the launches between
the marks (``turnaround_n`` + ``after_idle_n``), in percent.  Prints the
rows that were launched for a stream that had ended in the unfenced tick and
ran nothing (``ahead_idle_rows``).  None where the marks lack the fields, as
on a program whose loop fences before it launches."""


def read(ctx):
    a, b = (ctx["marks"][m].get("stats", {}).get("loop") or {}
            for m in ("start", "end"))
    if "ahead_n" not in a or "ahead_n" not in b:
        return None
    launches = (b["turnaround_n"] - a["turnaround_n"]
                + b["after_idle_n"] - a["after_idle_n"])
    if not launches:
        return None
    ahead = b["ahead_n"] - a["ahead_n"]
    print(f"perfbench: launches ahead={ahead} of {launches} "
          f"idle_rows={b['ahead_idle_rows'] - a['ahead_idle_rows']}",
          flush=True)
    return 100.0 * ahead / launches
