"""`select_rows_frac` (ISSUE 54): the reader against hand-made runs with
known answers, None on a run of a program that has no such counters (the
parent of the PR that added them) or whose sparse layers took no step in the
window, its entry looked up BY NAME, and the cell's line through the
harness's loader."""

import json

import pytest

from harness import cell as cells

from test_benchmark_json import FILE

CELL = "serve-sala-docchat"


def run_of(start, end):
    base = {"serving/decode_steps": 100, "serving/sparse_rows": 7}
    return {"counters": {"start": {**base, **start}, "end": {**base, **end}}}


def counted(ran0, resident0, ran1, resident1):
    return run_of({"serving/select_rows_run": ran0,
                   "serving/select_rows_resident": resident0},
                  {"serving/select_rows_run": ran1,
                   "serving/select_rows_resident": resident1})


def reader():
    cell = cells.load_cell(FILE, CELL)
    path = cells.find_under_paths(cell.root, cell.paths, "layer_metrics",
                                  "select_rows_frac.py")
    return cells.load_module(path, "bench_layer_metric_select_rows_frac").read


@pytest.mark.parametrize("run,want", [
    (counted(40, 640, 40 + 2 * 33, 640 + 2 * 320), 10.3125),   # 3.3 rows of 32
    (counted(0, 0, 0, 6400), 0.0),                  # steps, and no row selects
    (counted(64, 64, 64 * 11, 64 * 11), 100.0),     # every resident row does
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
    entries = [m for m in json.load(open(FILE))["per_layer"]
               if m["name"] == "select_rows_frac"]
    assert entries == [{
        "name": "select_rows_frac", "unit": "%", "better": "lower",
        "source": "program_counter", "layer": "model",
        "moves": "tpot_p95_ms", "workloads": [CELL]}]


def test_the_cells_line_carries_it():
    cell = cells.load_cell(FILE, CELL)
    cell = cells.Cell(**{**cell.__dict__, "per_layer": tuple(
        m for m in cell.per_layer if m["name"] == "select_rows_frac")})
    line = cells.read_layer_metrics(cell, counted(0, 0, 16, 64), {"tpot_p95_ms"})
    assert line == {"select_rows_frac": {"value": 25.0, "unit": "%"}}
    assert cells.read_layer_metrics(cell, run_of({}, {}), {"tpot_p95_ms"}) == {}
