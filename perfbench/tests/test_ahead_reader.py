"""The reader of the serving loop's launches ahead of their fence (ISSUE 38:
``stats()["loop"]``'s ``ahead_n`` at the window's marks) on hand-made marks,
on marks of a program that lacks the field (the parent commit), beside the
four readers of ISSUE 37 on the marks a launch-ahead loop leaves, and its
entry in BENCHMARK.json.  No chip, no jax."""

import json
import os

import pytest

from perfbench.lib import spec

AHEAD = "engine.ahead_share.serve"
SERVING = ["serve-decode", "serve-moe-mla-decode", "serve-moe-swa-longdoc",
           "serve-moe-conv-chat"]


def _loop(ticks, ahead, timed, idle, scale):
    """A loop table after ``ticks`` launches: ``ahead`` of them with the
    tick before unfenced (a turnaround of 0), ``timed`` with nothing in
    flight behind a fence (3 ms each), ``idle`` after an idle engine."""
    parts = {"fence_copy": 0.0003, "harvest_emit": 0.0001, "plan": 0.0001,
             "stage": 0.0015, "launch": 0.0008, "unspanned": 0.0002}
    return {"ticks": ticks, "turnaround_n": ahead + timed,
            "after_idle_n": idle, "ahead_n": ahead, "ahead_idle_rows": idle,
            "turnaround_s": 0.003 * timed, "iteration_s": 0.010 * scale,
            "fence_ready_s": 0.006 * scale, "fence_copy_s": 0.0003 * scale,
            "turnaround_parts_s": {k: v * timed for k, v in parts.items()},
            "phase_s": {}, "phase_n": {}}


def _ctx(start_loop, end_loop):
    return {"records": [], "marks": {
        "start": {"t": 1000.25, "tick": 0, "stats": {"loop": start_loop}},
        "end": {"t": 1045.75, "tick": 0, "stats": {"loop": end_loop}}}}


# 1000 launches in the window: 940 ahead, 10 timed, 50 after an idle engine
AHEAD_CTX = _ctx(_loop(200, 150, 0, 50, 200), _loop(1200, 1090, 10, 100, 1200))


def test_ahead_share_of_the_windows_launches(capsys):
    assert spec.metric_reader(AHEAD)(AHEAD_CTX) == pytest.approx(94.0)
    assert "launches ahead=940 of 1000 idle_rows=50" in \
        capsys.readouterr().out


def test_the_gap_readers_on_a_launch_ahead_loops_marks():
    # 10 timed launches of 3 ms among 950 counted: none of the four is null
    assert spec.metric_reader("engine.turnaround_ms.serve")(AHEAD_CTX) == \
        pytest.approx(1e3 * 0.030 / 950)
    assert spec.metric_reader("engine.loop_gap_share.serve")(AHEAD_CTX) == \
        pytest.approx(100 * 0.030 / 10.0)
    assert spec.metric_reader("engine.fence_copy_ms.serve")(AHEAD_CTX) == \
        pytest.approx(0.3)


def test_nothing_to_read_on_the_parents_marks_is_none(capsys):
    parent = _loop(1200, 0, 1050, 150, 1200)
    del parent["ahead_n"], parent["ahead_idle_rows"]
    assert spec.metric_reader(AHEAD)(_ctx(dict(parent), parent)) is None
    bare = {"records": [], "marks": {"start": {"t": 1.0}, "end": {"t": 46.0}}}
    assert spec.metric_reader(AHEAD)(bare) is None
    assert capsys.readouterr().out == ""


def test_a_window_without_a_launch_is_none():
    still = _loop(200, 150, 0, 50, 200)
    assert spec.metric_reader(AHEAD)(_ctx(still, dict(still))) is None


def test_the_entry_in_the_benchmark():
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = bench["per_layer"][-1]
    assert entry == {"name": AHEAD, "unit": "%", "better": "higher",
                     "source": "program_counter",
                     "layer": "serving engine: tick", "moves": "ttft_p50_ms",
                     "workloads": SERVING}
