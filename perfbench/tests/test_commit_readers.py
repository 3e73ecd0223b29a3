"""The two readers of the loop's commit point (ISSUE 45:
``stats()["loop"]``'s ``hold_s`` / ``hold_n`` / ``hold_skipped_n`` /
``late_n`` at the window's marks) on hand-made marks, on marks of a program
that lacks the fields (the parent commit), on an empty window, and their
entries in BENCHMARK.json, found by name.  No chip, no jax."""

import pytest

from perfbench.lib import spec

HOLD = "engine.hold_ms.serve"
LATE = "engine.late_launch_share.serve"
NO_SKIPS = {"nothing_in_flight": 0, "no_estimate": 0, "no_room": 0,
            "not_worth_it": 0}


def _loop(hold_n=0, hold_s=0.0, late_n=0, launches=0, after_idle=0, **skips):
    return {"hold_n": hold_n, "hold_s": hold_s, "late_n": late_n,
            "hold_skipped_n": dict(NO_SKIPS, **skips),
            "turnaround_n": launches - after_idle, "after_idle_n": after_idle,
            "commit": {"estimate_s": {"narrow": 0.0157, "wide": 0.0201},
                       "margin_s": 0.0061}}


def _ctx(start_loop, end_loop, ticks=(1000, 3800)):
    return {"records": [], "marks": {
        "start": {"t": 1000.25, "tick": ticks[0],
                  "stats": {"loop": start_loop}},
        "end": {"t": 1045.75, "tick": ticks[1], "stats": {"loop": end_loop}}}}


def test_hold_seconds_over_the_windows_ticks(capsys):
    # 2,800 ticks in the window, 2,600 of them behind a hold of 9.5 ms
    ctx = _ctx(_loop(hold_n=400, hold_s=3.8, no_estimate=30),
               _loop(hold_n=3000, hold_s=28.5, no_estimate=30, no_room=200))
    assert spec.metric_reader(HOLD)(ctx) == pytest.approx(
        1e3 * 24.7 / 2800)
    out = capsys.readouterr().out
    assert "holds=2600 of 2800 ticks" in out and "'no_room': 200" in out
    assert "'no_estimate': 0" in out and "margin_ms=6.1" in out
    assert "'narrow': 15.7" in out


def test_a_loop_that_never_held_reads_zero_not_none(capsys):
    idle = _loop(nothing_in_flight=900)
    ctx = _ctx(idle, _loop(nothing_in_flight=2100, not_worth_it=700))
    assert spec.metric_reader(HOLD)(ctx) == 0.0
    assert "holds=0 of 2800" in capsys.readouterr().out


def test_late_launches_over_the_windows_launches(capsys):
    ctx = _ctx(_loop(late_n=2, launches=1000, after_idle=10),
               _loop(late_n=16, launches=3800, after_idle=20))
    assert spec.metric_reader(LATE)(ctx) == pytest.approx(100.0 * 14 / 2800)
    assert "late=14 of 2800" in capsys.readouterr().out


@pytest.mark.parametrize("name", [HOLD, LATE])
def test_nothing_to_read_on_the_parents_marks_is_none(capsys, name):
    parent = {"ticks": 1200, "ahead_n": 1190, "turnaround_n": 1195,
              "after_idle_n": 5}
    assert spec.metric_reader(name)(_ctx(dict(parent), parent)) is None
    bare = {"records": [], "marks": {"start": {"t": 1.0, "tick": 0},
                                     "end": {"t": 46.0, "tick": 9}}}
    assert spec.metric_reader(name)(bare) is None
    # ... and a window in which no tick was launched
    still = _loop(hold_n=5, hold_s=0.05, launches=40)
    assert spec.metric_reader(name)(_ctx(still, dict(still),
                                         ticks=(40, 40))) is None
    assert capsys.readouterr().out == ""


@pytest.mark.parametrize("name,unit,better,moves", [
    (HOLD, "ms", "higher", "ttft_p50_ms"),
    (LATE, "%", "lower", "serve_out_tokens_per_s")])
def test_the_entry_in_the_benchmark_by_name(serving_cells, name, unit,
                                            better, moves):
    bench = spec.benchmark()
    entry, = (m for m in bench["per_layer"] if m["name"] == name)
    assert entry == {"name": name, "unit": unit, "better": better,
                     "source": "program_counter",
                     "layer": "serving engine: tick", "moves": moves,
                     "workloads": entry["workloads"]}
    # every serving cell the benchmark had when the readers came; a later
    # cell is appended by the PR that brings it
    assert entry["workloads"] == serving_cells[:len(entry["workloads"])]
    assert len(entry["workloads"]) >= 6
    moved, = (m for m in bench["end_to_end"] if m["name"] == moves)
    assert set(entry["workloads"]) <= set(moved["workloads"])
