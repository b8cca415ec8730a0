"""`sample_rows_frac` (ISSUE 47): the reader against hand-made runs with known
answers, None on a run of a program that has no such counters (the parent of
the PR that added them) or that took no step in the window, its entry as the
issue names it, and the line of a traced run through the harness's loader."""

import json

import pytest

from harness import cell as cells

from test_benchmark_json import FILE

SERVED = ["serve-1.5b-chat", "serve-axk1-docqa", "serve-smallthinker-longshort",
          "serve-lfm2-chat", "serve-trinity-reason", "serve-sdar-blockgen"]


def run_of(start, end):
    base = {"serving/decode_steps": 100, "serving/held_experts_hit": 0}
    return {"counters": {"start": {**base, **start}, "end": {**base, **end}}}


def counted(rows0, slots0, rows1, slots1):
    return run_of({"serving/sample_rows": rows0, "serving/sample_slots": slots0},
                  {"serving/sample_rows": rows1, "serving/sample_slots": slots1})


def reader():
    cell = cells.load_cell(FILE, "serve-sdar-blockgen")
    path = cells.find_under_paths(cell.root, cell.paths, "layer_metrics",
                                  "sample_rows_frac.py")
    return cells.load_module(path, "bench_layer_metric_sample_rows_frac").read


@pytest.mark.parametrize("run,want", [
    (counted(800, 6400, 800 + 8 * 500, 6400 + 64 * 500), 12.5),   # one row of 64
    (counted(0, 0, 128 * 90 + 64 * 10, 256 * 100), 47.5),         # blocks of 4
    (counted(64, 64, 64 * 11, 64 * 11), 100.0),                   # a full batch
])
def test_the_reader_gives_the_known_answer(run, want):
    assert reader()(run) == pytest.approx(want)


@pytest.mark.parametrize("run", [
    {}, {"counters": None}, run_of({}, {}),                 # the parent's program
    counted(640, 6400, 640, 6400),                          # no step in the window
])
def test_nothing_to_read_is_none(run):
    assert reader()(run) is None


def test_the_entry_is_the_issues():
    entry = json.load(open(FILE))["per_layer"][-1]
    assert entry == {
        "name": "sample_rows_frac", "unit": "%", "better": "lower",
        "source": "program_counter", "layer": "serving",
        "moves": "tpot_p95_ms", "workloads": SERVED}


@pytest.mark.parametrize("name", SERVED)
def test_a_served_cells_line_carries_it(name):
    cell = cells.load_cell(FILE, name)
    cell = cells.Cell(**{**cell.__dict__, "per_layer": tuple(
        m for m in cell.per_layer if m["name"] == "sample_rows_frac")})
    line = cells.read_layer_metrics(cell, counted(0, 0, 16, 64), {"tpot_p95_ms"})
    assert line == {"sample_rows_frac": {"value": 25.0, "unit": "%"}}
    assert cells.read_layer_metrics(cell, run_of({}, {}), {"tpot_p95_ms"}) == {}
