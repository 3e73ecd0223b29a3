"""The reader of the done record's ``loop.narrow_ticks`` (ISSUE 25) on a
synthetic ctx, on records of a program that lacks the field (the parent
commit), its entry in BENCHMARK.json, and tools/width_table.py's split of
one program name into its compiled widths.  No chip, no jax."""

import pytest

from perfbench.lib import spec

NAME = "engine.narrow_tick_share.serve"


def _record(loop):
    done = {"done": True, "tokens": [1], "timing": {"queue": 0.1}}
    if loop is not None:
        done["loop"] = loop
    return {"done": done, "part_t": [1.0], "sent": 0.5}


WAIT = {"harvest_wait": 1.30, "plan": 0.01}
NEW = [_record({"ticks": 10, "narrow_ticks": 8, "narrow_wait_s": 0.36,
                "prefill_ticks": 3, "compiles": 0, "phase_s": WAIT}),
       _record({"ticks": 30, "narrow_ticks": 28, "narrow_wait_s": 1.26,
                "prefill_ticks": 2, "compiles": 0,
                "phase_s": {"harvest_wait": 1.52}}),
       {"done": None, "part_t": [], "sent": 0.7}]       # unfinished
# the parent's done record: the loop table of PR 24, no narrow counters
PARENT = [_record({"ticks": 10, "prefill_ticks": 3, "compiles": 0,
                   "phase_s": WAIT}),
          _record(None), {"done": None, "part_t": [], "sent": 0.7}]


@pytest.mark.parametrize("records,want,printed", [
    # 36 of 40 ticks narrow; 1.62 s over 36 and (2.82 - 1.62) s over 4
    (NEW, 90.0, "narrow=45.000 wide=300.000 narrow_ticks=36 wide_ticks=4"),
    # a decode-role engine: every tick narrow, no wide tick to divide by
    ([_record({"ticks": 5, "narrow_ticks": 5, "narrow_wait_s": 0.2,
               "phase_s": {"harvest_wait": 0.2}})], 100.0,
     "narrow=40.000 wide=0.000 narrow_ticks=5 wide_ticks=0"),
    # a prefill-role engine: none
    ([_record({"ticks": 4, "narrow_ticks": 0, "narrow_wait_s": 0.0,
               "phase_s": {"harvest_wait": 0.6}})], 0.0,
     "narrow=0.000 wide=150.000 narrow_ticks=0 wide_ticks=4"),
    # old and new records mixed (a fleet mid-upgrade): the new ones count
    (PARENT + NEW, 90.0, "requests=2"),
])
def test_share_and_the_two_waits_on_known_records(records, want, printed,
                                                  capsys):
    read = spec.metric_reader(NAME)
    assert read({"records": records, "marks": {}}) == pytest.approx(want)
    assert printed in capsys.readouterr().out


@pytest.mark.parametrize("records", [PARENT, [], [_record({"ticks": 0})]],
                         ids=["parent", "no-records", "no-ticks"])
def test_nothing_to_read_is_none_and_prints_nothing(records, capsys):
    assert spec.metric_reader(NAME)({"records": records, "marks": {}}) is None
    assert capsys.readouterr().out == ""


def test_benchmark_lists_it_for_the_serving_cells_only(serving_cells):
    bench = spec.benchmark()
    entries = [m for m in bench["per_layer"] if m["name"] == NAME]
    assert "serve-decode" in serving_cells
    assert entries == [{"name": NAME, "unit": "%", "better": "higher",
                     "source": "program_counter",
                     "layer": "serving engine: tick",
                     "moves": "serve_out_tokens_per_s",
                     "workloads": serving_cells}]
    assert NAME in {m["name"] for m in
                    spec.cell_metrics("serve-decode", bench)[1]}
    assert NAME not in {m["name"] for m in
                        spec.cell_metrics("train-dp1", bench)[1]}


def test_width_table_keeps_the_programs_of_one_name_apart():
    from perfbench.tools import width_table
    attn, ffn = "jit(step_fn)/tick/model/attn/x", "jit(step_fn)/tick/model/ffn/d"
    lines = {
        "XLA Ops": [("%cut = f32[1]", ffn, -1.0, -0.9),
                    ("%g = f32[1]", attn + "/kv_gather/g", 1.0, 1.01),
                    ("%d = f32[2]", ffn, 1.01, 1.04),
                    ("%d = f32[2]", ffn, 2.0, 2.1),
                    ("%u = bf16[3]", "", 2.1, 2.12),
                    ("%d = f32[2]", ffn, 3.0, 3.03),
                    ("%other = f32[1]", "", 9.0, 10.0)],
        "XLA Modules": [("jit_step_fn(22)", "", -1.0, -0.9),  # cut at the edge
                        ("jit_step_fn(11)", "", 1.0, 1.05),
                        ("jit_step_fn(22)", "", 2.0, 2.13),
                        ("jit_step_fn(11)", "", 3.0, 3.05),
                        ("jit_other(3)", "", 9.0, 10.0)]}
    progs = width_table.programs(lines)
    assert list(progs) == ["jit_step_fn(11)", "jit_step_fn(22)"]  # by time
    narrow, wide = progs.values()
    assert narrow["runs"] == 2 and wide["runs"] == 1
    assert narrow["program_ms"] == pytest.approx(50.0)
    assert wide["program_ms"] == pytest.approx(130.0)
    assert narrow["by_scope"]["ffn"]["ms"] == pytest.approx(30.0)
    assert narrow["by_scope"]["kv_gather"]["ms"] == pytest.approx(5.0)
    assert wide["by_scope"]["ffn"]["ms"] == pytest.approx(100.0)
    assert wide["largest_without_scope_ms"] == \
        {"u bf16[3]": pytest.approx(20.0)}
    assert width_table.programs({}) == {}
