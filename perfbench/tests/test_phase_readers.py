"""The readers of the program's own clocks (done record ``timing.pickup``,
``timing.publish`` and ``loop``) on a synthetic ctx, and on records of a
program that lacks the fields.  No chip, no jax."""

import pytest

from perfbench.lib import spec


def _record(timing, loop=None):
    done = {"done": True, "tokens": [1], "timing": timing}
    if loop is not None:
        done["loop"] = loop
    return {"done": done, "part_t": [1.0], "sent": 0.5}


NEW = [_record({"queue": 0.13, "prefill": 0.27, "decode": 1.0,
                "pickup": 0.060, "publish": 0.002},
               {"ticks": 10, "prefill_ticks": 3, "compiles": 0,
                "phase_s": {"harvest_wait": 1.30, "harvest_emit": 0.02,
                            "plan": 0.01, "stage": 0.02, "launch": 0.01,
                            "poll": 0.005, "submit": 0.001,
                            "publish": 0.004, "idle": 0.0}}),
       _record({"queue": 0.01, "prefill": 0.14, "decode": 2.0,
                "pickup": 0.080, "publish": 0.004},
               {"ticks": 30, "prefill_ticks": 2, "compiles": 0,
                "phase_s": {"harvest_wait": 3.90, "harvest_emit": 0.06,
                            "plan": 0.03, "stage": 0.06, "launch": 0.03,
                            "poll": 0.015, "submit": 0.003,
                            "publish": 0.012, "idle": 0.1}}),
       {"done": None, "part_t": [], "sent": 0.7}]       # unfinished
OLD = [_record({"queue": 0.13, "prefill": 0.27, "decode": 1.0}),
       _record({}), {"done": None, "part_t": [], "sent": 0.7}]
MARKS = {"start": {"t": 100.0, "tick": 50}, "end": {"t": 145.0, "tick": 376}}


@pytest.mark.parametrize("name,want", [
    ("front.pickup_ms.serve", 70.0),
    ("front.publish_ms.serve", 3.0),
    ("engine.prefill_ticks.serve", 2.5),
    # (0.07 + 0.21) s of host phases over 40 ticks; idle and the wait left out
    ("engine.loop_host_ms.serve", 7.0),
    ("engine.device_wait_ms.serve", 130.0),
])
def test_reader_on_known_records_and_on_records_without_the_fields(
        name, want, capsys):
    read = spec.metric_reader(name)
    assert read({"records": NEW, "marks": MARKS}) == pytest.approx(want)
    out = capsys.readouterr().out
    if name == "engine.loop_host_ms.serve":
        # every phase in ms a tick, and what is left of the tick: 45 s over
        # 326 ticks less the 139.5 ms of phases
        assert "harvest_wait=130.000" in out and "idle=2.500" in out
        assert "tick_ms=138.037" in out and "residual=-1.463" in out
    assert read({"records": OLD, "marks": MARKS}) is None
    assert read({"records": [], "marks": {}}) is None


def test_benchmark_lists_the_five_for_the_serving_cells_only(serving_cells):
    names = {"front.pickup_ms.serve", "front.publish_ms.serve",
             "engine.prefill_ticks.serve", "engine.loop_host_ms.serve",
             "engine.device_wait_ms.serve"}
    bench = spec.benchmark()
    mine = [m for m in bench["per_layer"] if m["name"] in names]
    assert len(mine) == 5
    assert "serve-decode" in serving_cells
    assert all(m["workloads"] == serving_cells for m in mine)
    layer = {m["name"] for m in spec.cell_metrics("serve-decode", bench)[1]}
    assert names <= layer
    assert not names & {m["name"] for m in
                        spec.cell_metrics("train-dp1", bench)[1]}
