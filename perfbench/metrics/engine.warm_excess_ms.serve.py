"""What the window's first seconds cost the host, a tick: from
``stats()["loop"]["timeline"]`` at the ``end`` mark (utils/profiler.py
``PhaseClock.timeline``, one bucket a wall second), host ms a tick over the
window's first 8 whole seconds less the same over the rest: the seconds
after them and, in a traced run, before the profiler session began
(``ctx["trace"]["t0"]``; from there on the host carries the profiler's own
work, in the session and for seconds after it).  Host ms a tick is the sum,
over every phase but ``harvest_wait`` and ``idle``, of the phase's seconds
over its entries: a tick passes each phase once, and the polls of an idle
loop weigh as one busy poll and no more.  Prints, for each of the first 12
seconds, the ticks by width and every phase's ms an entry.  None where the
mark has no timeline."""
import math

NOT_HOST = ("harvest_wait", "idle")
FIRST_S, SHOWN_S = 8, 12


def read(ctx):
    a, b = ctx["marks"]["start"], ctx["marks"]["end"]
    tl = (b.get("stats", {}).get("loop") or {}).get("timeline")
    if not tl or "phase_n" not in tl:
        return None
    first = math.ceil(a["t"])       # the window's first whole second
    last = min(b["t"], (ctx.get("trace") or {}).get("t0", b["t"]))
    rows = [i for i, sec in enumerate(tl["sec"]) if first <= sec < int(last)]

    def phase_ms(name, rows):
        n = sum(tl["phase_n"][name][i] for i in rows)
        return 1e3 * sum(tl["phase_s"][name][i] for i in rows) / n if n else 0.0

    def host_ms(rows):
        return sum(phase_ms(name, rows) for name in tl["phase_s"]
                   if name not in NOT_HOST)

    ticks = lambda rows: sum(tl["narrow"][i] + tl["wide"][i] for i in rows)
    early = [i for i in rows if tl["sec"][i] < first + FIRST_S]
    late = [i for i in rows if tl["sec"][i] >= first + FIRST_S]
    if not ticks(early) or not ticks(late):
        return None
    for i in rows[:SHOWN_S]:
        print(f"perfbench: second {tl['sec'][i] - first:2d} "
              f"narrow={tl['narrow'][i]:.0f} wide={tl['wide'][i]:.0f} "
              + " ".join(f"{name}={phase_ms(name, [i]):.3f}"
                         for name in tl["phase_s"])
              + f" host={host_ms([i]):.3f}", flush=True)
    print(f"perfbench: host ms/tick first_{FIRST_S}s={host_ms(early):.3f} "
          f"rest={host_ms(late):.3f} ticks={ticks(early):.0f}|"
          f"{ticks(late):.0f}", flush=True)
    return host_ms(early) - host_ms(late)
