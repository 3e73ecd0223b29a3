"""The four readers of the serving loop's own record of the gap between two
programs (ISSUE 37: ``stats()["loop"]`` at the window's marks) on hand-made
marks, on marks of a program that lacks the fields (the parent commit), and
their entries in BENCHMARK.json.  No chip, no jax."""

import pytest

from perfbench.lib import spec

TURNAROUND = "engine.turnaround_ms.serve"
FENCE_COPY = "engine.fence_copy_ms.serve"
GAP_SHARE = "engine.loop_gap_share.serve"
WARM = "engine.warm_excess_ms.serve"
NAMES = [TURNAROUND, FENCE_COPY, GAP_SHARE, WARM]
SERVING = ["serve-decode", "serve-moe-mla-decode", "serve-moe-swa-longdoc",
           "serve-moe-conv-chat"]
PHASES = ("poll", "submit", "harvest_wait", "harvest_emit", "plan", "stage",
          "launch", "publish", "idle")


def _loop(ticks, n, idle, scale):
    """A loop table after ``ticks`` ticks, ``n`` of them back to back, whose
    every second-valued figure is its per-tick cost times ``scale``."""
    parts = {"fence_copy": 0.0003, "harvest_emit": 0.0001, "plan": 0.0001,
             "stage": 0.0015, "launch": 0.0008, "unspanned": 0.0002}
    return {"ticks": ticks, "turnaround_n": n, "after_idle_n": idle,
            "turnaround_s": 0.003 * scale, "iteration_s": 0.010 * scale,
            "fence_ready_s": 0.006 * scale, "fence_copy_s": 0.0003 * scale,
            "turnaround_parts_s": {k: v * scale for k, v in parts.items()},
            "phase_s": {}, "phase_n": {}}


def _timeline(seconds, t0, extra_stage_s=0.0, extra_for=0):
    """``seconds`` buckets from wall second ``t0``: 50 ticks a second (45
    narrow), every host phase 1 ms an entry, ``stage`` ``extra_stage_s``
    more in the first ``extra_for`` of them; 10 idle iterations a second,
    each a poll and a sleep and no tick."""
    cols = lambda value: [value] * seconds
    stage = [50 * (0.001 + (extra_stage_s if i < extra_for else 0.0))
             for i in range(seconds)]
    busy = ("submit", "harvest_emit", "plan", "launch", "publish")
    return {"sec": [t0 + i for i in range(seconds)],
            "narrow": cols(45.0), "wide": cols(5.0), "used": cols(400.0),
            "phase_s": dict({p: cols(0.05) for p in busy}, stage=stage,
                            poll=cols(0.06), harvest_wait=cols(0.5),
                            idle=cols(0.2)),
            "phase_n": dict({p: cols(50.0) for p in busy + ("stage",
                                                            "harvest_wait")},
                            poll=cols(60.0), idle=cols(10.0))}


def _ctx(start_loop, end_loop, t0=1000.25, t1=1045.75):
    return {"records": [], "marks": {
        "start": {"t": t0, "tick": 0, "stats": {"loop": start_loop}},
        "end": {"t": t1, "tick": 0, "stats": {"loop": end_loop}}}}


# 1000 ticks in the window, 900 back to back, 100 after an idle engine
GAPS = _ctx(_loop(200, 150, 50, 150), _loop(1200, 1050, 150, 1050))
# the parent's marks: the loop table of PR 36, none of the new fields
PARENT = _ctx({"ticks": 200, "narrow_ticks": 150, "phase_s": {}},
              {"ticks": 1200, "narrow_ticks": 1000, "phase_s": {}})


def test_turnaround_a_tick_and_its_parts(capsys):
    assert spec.metric_reader(TURNAROUND)(GAPS) == pytest.approx(3.0)
    out = capsys.readouterr().out
    assert ("fence_copy=0.300 harvest_emit=0.100 plan=0.100 stage=1.500 "
            "launch=0.800 unspanned=0.200 iteration=10.000 "
            "back_to_back=900 after_idle=100") in out


def test_fence_copy_a_tick_over_every_tick(capsys):
    # 0.0003 s x 900 over the window's 1000 ticks
    assert spec.metric_reader(FENCE_COPY)(GAPS) == pytest.approx(0.27)
    assert "ready=5.400 ticks=1000" in capsys.readouterr().out


def test_gap_share_of_the_busy_period(capsys):
    assert spec.metric_reader(GAP_SHARE)(GAPS) == pytest.approx(30.0)
    assert "back_to_back_share=90.00%" in capsys.readouterr().out


def test_warm_excess_of_a_flat_timeline_is_zero():
    ctx = _ctx({}, {"timeline": _timeline(60, 995)})
    assert spec.metric_reader(WARM)(ctx) == pytest.approx(0.0, abs=1e-9)


def test_warm_excess_finds_3_ms_more_in_stage_for_five_seconds(capsys):
    # the window's first whole second is 1001: buckets 995..1000 lie before
    # it, the excess is in 1001..1005, five of the first eight seconds
    tl = _timeline(60, 995, extra_stage_s=0.003, extra_for=11)
    ctx = _ctx({}, {"timeline": tl})
    assert spec.metric_reader(WARM)(ctx) == pytest.approx(3.0 * 5 / 8)
    lines = [ln for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("perfbench: second")]
    assert len(lines) == 12
    assert all("stage=4.000" in ln for ln in lines[:5])
    assert all("stage=1.000" in ln for ln in lines[5:])
    # an idle loop's polls weigh as a busy one's: 60 polls of 1 ms a second
    assert all("poll=1.000" in ln and "narrow=45 wide=5" in ln
               for ln in lines)


def test_warm_excess_leaves_the_parts_of_seconds_at_both_ends_out():
    # the buckets of 1000 (the start mark's) and 1045 (the end mark's) hold
    # parts of seconds: a fault planted there must not show
    tl = _timeline(52, 996)
    for sec in (1000, 1045):
        tl["phase_s"]["stage"][tl["sec"].index(sec)] = 5.0
    assert spec.metric_reader(WARM)(_ctx({}, {"timeline": tl})) == \
        pytest.approx(0.0, abs=1e-9)


def test_warm_excess_stops_where_the_profiler_session_began():
    # from second 1020 on every stage costs 2 ms more (the profiler's work
    # on the host): with the session's start known, those seconds are out
    tl = _timeline(60, 995)
    for i, sec in enumerate(tl["sec"]):
        if sec >= 1020:
            tl["phase_s"]["stage"][i] += 50 * 0.002
    ctx = _ctx({}, {"timeline": tl})
    assert spec.metric_reader(WARM)(ctx) == pytest.approx(-2.0 * 25 / 36)
    ctx["trace"] = {"t0": 1020.4, "t1": 1023.4}
    assert spec.metric_reader(WARM)(ctx) == pytest.approx(0.0, abs=1e-9)


@pytest.mark.parametrize("name", NAMES)
def test_nothing_to_read_on_the_parents_marks_is_none(name, capsys):
    assert spec.metric_reader(name)(PARENT) is None
    bare = {"records": [], "marks": {"start": {"t": 1.0}, "end": {"t": 46.0}}}
    assert spec.metric_reader(name)(bare) is None
    assert capsys.readouterr().out == ""


@pytest.mark.parametrize("name", NAMES[:3])
def test_a_window_without_a_back_to_back_tick_is_none(name):
    still = _loop(200, 150, 50, 150)
    assert spec.metric_reader(name)(_ctx(still, dict(still))) is None


def test_warm_excess_needs_ticks_on_both_sides():
    # a window of six whole seconds has no "rest"
    ctx = _ctx({}, {"timeline": _timeline(60, 995)}, t1=1007.5)
    assert spec.metric_reader(WARM)(ctx) is None


@pytest.mark.parametrize("name", NAMES)
def test_benchmark_entries(name, serving_cells):
    bench = spec.benchmark()
    entries = [m for m in bench["per_layer"] if m["name"] == name]
    assert len(entries) == 1
    entry = entries[0]
    assert set(SERVING) <= set(serving_cells)
    assert entry["workloads"] == serving_cells
    assert entry["layer"] == "serving engine: tick"
    assert entry["moves"] == "ttft_p50_ms" and entry["better"] == "lower"
    assert entry["source"] == "program_span"
    assert entry["unit"] == ("%" if name == GAP_SHARE else "ms")
    for cell in serving_cells:
        assert entry in spec.cell_metrics(cell, bench)[1]
    for cell in ("train-dp1", "train-dp4"):
        assert entry not in spec.cell_metrics(cell, bench)[1]
