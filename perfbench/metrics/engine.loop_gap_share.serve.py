"""Share of the loop's busy period in which the host's critical path ran and
no program could: delta between the window's marks of ``stats()["loop"]``'s
``turnaround_s`` over that of ``iteration_s`` (serve/engine.py
``_count_gap``; both over back-to-back ticks only, launch return to launch
return), in percent.  Over the whole window; a floor under the device's idle
share while the loop is busy, since the runtime's enqueue latency is not in
it.  Prints the share of launches that were back to back.  None where the
marks lack the fields."""


def read(ctx):
    a, b = (ctx["marks"][m].get("stats", {}).get("loop") or {}
            for m in ("start", "end"))
    if "iteration_s" not in a or "iteration_s" not in b:
        return None
    busy = b["iteration_s"] - a["iteration_s"]
    if not busy > 0:
        return None
    n = b["turnaround_n"] - a["turnaround_n"]
    idle = b["after_idle_n"] - a["after_idle_n"]
    print(f"perfbench: launches back_to_back={n} after_idle={idle} "
          f"back_to_back_share={100.0 * n / (n + idle):.2f}% "
          f"busy_s={busy:.3f}", flush=True)
    return 100.0 * (b["turnaround_s"] - a["turnaround_s"]) / busy
