"""Share of the tick launches that came too late: delta between the
window's marks of ``stats()["loop"]``'s ``late_n`` (serve/engine.py
``_dispatch``: launches after a hold at the commit point that found the
tick in flight already ended — the device idled, which ``ahead_n`` cannot
see because that tick was still unfenced) over the launches between the
marks (``turnaround_n`` + ``after_idle_n``), in percent.  None where the
marks lack the field, as on a program whose loop never holds."""


def read(ctx):
    a, b = (ctx["marks"][m].get("stats", {}).get("loop") or {}
            for m in ("start", "end"))
    if "late_n" not in a or "late_n" not in b:
        return None
    launches = (b["turnaround_n"] - a["turnaround_n"]
                + b["after_idle_n"] - a["after_idle_n"])
    if not launches:
        return None
    late = b["late_n"] - a["late_n"]
    print(f"perfbench: launches late={late} of {launches}", flush=True)
    return 100.0 * late / launches
