"""The reader of the rows a wide tick's head ran on (ISSUE 42:
``stats()["loop"]``'s ``head_rows`` / ``packed_rows`` at the window's marks)
on hand-made marks, on marks of a program that lacks the fields (the parent
commit), on a window without a wide tick, and its entry in BENCHMARK.json,
found by name.  No chip, no jax."""

import json
import os

import pytest

from perfbench.lib import spec

HEAD = "engine.head_rows_share.serve"


def _ctx(start_loop, end_loop):
    return {"records": [], "marks": {
        "start": {"t": 1000.25, "tick": 0, "stats": {"loop": start_loop}},
        "end": {"t": 1045.75, "tick": 0, "stats": {"loop": end_loop}}}}


@pytest.mark.parametrize("head,packed,want", [
    # serve-decode: 40 wide ticks of 512 packed rows in the window, the 80
    # rows of 16 slots x (1 + spec_k) read in each
    (80, 512, 15.625),
    # serve-moe-mla-decode: 32 slots x 5 of a budget of 512
    (160, 512, 31.25),
    # a module without the ``read`` form: every packed row
    (320, 320, 100.0),
])
def test_head_rows_over_packed_rows_of_the_windows_wide_ticks(
        capsys, head, packed, want):
    ctx = _ctx({"head_rows": 10 * head, "packed_rows": 10 * packed},
               {"head_rows": 50 * head, "packed_rows": 50 * packed})
    assert spec.metric_reader(HEAD)(ctx) == pytest.approx(want)
    assert f"head={40 * head} of {40 * packed}" in capsys.readouterr().out


def test_nothing_to_read_on_the_parents_marks_is_none(capsys):
    parent = {"ticks": 1200, "wide_rows_share": 0.3}
    assert spec.metric_reader(HEAD)(_ctx(dict(parent), parent)) is None
    bare = {"records": [], "marks": {"start": {"t": 1.0}, "end": {"t": 46.0}}}
    assert spec.metric_reader(HEAD)(bare) is None
    assert capsys.readouterr().out == ""


def test_a_window_without_a_wide_tick_is_none(capsys):
    still = {"head_rows": 800, "packed_rows": 5120}
    assert spec.metric_reader(HEAD)(_ctx(still, dict(still))) is None
    assert capsys.readouterr().out == ""


def test_the_entry_in_the_benchmark_by_name():
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry, = (m for m in bench["per_layer"] if m["name"] == HEAD)
    # the two cells whose module takes ``read``: elsewhere it reads 100
    assert entry == {"name": HEAD, "unit": "%", "better": "lower",
                     "source": "program_counter",
                     "layer": "serving engine: tick", "moves": "ttft_p50_ms",
                     "workloads": ["serve-decode", "serve-moe-mla-decode"]}
    assert set(entry["workloads"]) <= {w["name"] for w in bench["workloads"]}
    moved, = (m for m in bench["end_to_end"] if m["name"] == entry["moves"])
    assert set(entry["workloads"]) <= set(moved["workloads"])
