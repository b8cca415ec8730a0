"""The seven per-layer metrics that read a request's decode account
(ISSUE 51): each reader against a hand-made `run["counters"]` with known
answers, None where the program exports no such key (the parent of the PR
that added them) or nothing was counted, and the entries in BENCHMARK.json
found by name."""

import pytest

from harness import cell as cells

from test_benchmark_json import FILE, ROOT

SERVING_CELLS = [
    "serve-1.5b-chat", "serve-axk1-docqa", "serve-smallthinker-longshort",
    "serve-lfm2-chat", "serve-trinity-reason", "serve-sdar-blockgen",
    "serve-falcon-h1-assist"]

# name -> (unit, better, source, the answer on `serve_run`)
EXPECTED = {
    "beat_clean_ms": ("ms", "lower", "program_span", 20.0),
    "beat_loaded_ms": ("ms", "lower", "program_span", 35.0),
    "loaded_beat_frac": ("%", "lower", "program_counter", 12.5),
    "slow_tpot_ms": ("ms", "lower", "program_span", 8.0),
    "slow_loaded_share": ("%", "lower", "program_span", 60.0),
    "slow_wait_share": ("%", "higher", "program_span", 75.0),
    "last_token_lag_ms": ("ms", "lower", "program_span", 1.5),
}


def serve_run():
    """A window of 1,600 beats that took a decode step, 200 of them behind
    an admission forward: 20 ms a clean beat, 35 a loaded one. 180 requests
    finished, 18 of them in the slow tenth at 8 ms a token, 60 % of whose
    decode seconds lay in loaded beats and 75 % of them the host stood
    waiting; 150 streamed last tokens took 1.5 ms to leave. No counter
    starts at zero, as after set-up's warm-up requests."""
    start = {
        "serving/beats_clean": 40, "serving/beats_loaded": 11,
        "serving/beat_clean_s": 0.9, "serving/beat_loaded_s": 0.5,
        "serving/foreign_forwards": 11,
        "serving/all_requests": 9, "serving/all_tpot_s_sum": 0.05,
        "serving/slow_requests": 1,
        "serving/slow_tpot_s_sum": 0.007, "serving/slow_decode_s": 0.035,
        "serving/slow_wait_s": 0.02, "serving/slow_loaded_s": 0.01,
        "serving/last_token_lag_s_sum": 0.004,
        "serving/last_token_lag_s_count": 3,
        "serving/admitted": 12}
    gains = {
        "serving/beats_clean": 1400, "serving/beats_loaded": 200,
        "serving/beat_clean_s": 1400 * 0.020,
        "serving/beat_loaded_s": 200 * 0.035,
        "serving/foreign_forwards": 210,
        "serving/all_requests": 180, "serving/all_tpot_s_sum": 180 * 0.005,
        "serving/slow_requests": 18,
        "serving/slow_tpot_s_sum": 18 * 0.008,
        "serving/slow_decode_s": 18 * 0.8,
        "serving/slow_wait_s": 18 * 0.8 * 0.75,
        "serving/slow_loaded_s": 18 * 0.8 * 0.60,
        "serving/last_token_lag_s_sum": 150 * 0.0015,
        "serving/last_token_lag_s_count": 150,
        "serving/admitted": 185}
    end = {k: start[k] + gains[k] for k in start}
    return {"kind": "serve", "counters": {"start": start, "end": end}}


def reader(name):
    path = cells.find_under_paths(ROOT, ["benchmark"],
                                  "layer_metrics", name + ".py")
    return cells.load_module(path, "request_account_" + name)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_gives_the_known_answer(name):
    assert reader(name).read(serve_run()) == pytest.approx(EXPECTED[name][3])


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_returns_none_without_its_keys(name):
    """The parent's program has no such counter: the line leaves the metric
    out and nothing raises."""
    parent = {"kind": "serve", "counters": {
        "start": {"serving/admitted": 3, "serving/loop_beats": 10},
        "end": {"serving/admitted": 9, "serving/loop_beats": 90}}}
    for run in (parent, {}, {"counters": None}, {"kind": "rl", "rows": []}):
        assert reader(name).read(run) is None


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_nothing_counted_in_the_window_is_none_not_a_division(name):
    """No loaded beat, no slow request or no streamed request in the window:
    the keys are there and the metric is left out."""
    run = serve_run()
    run["counters"]["end"] = dict(run["counters"]["start"])
    assert reader(name).read(run) is None


def test_a_key_missing_at_one_end_is_none():
    run = serve_run()
    del run["counters"]["start"]["serving/slow_decode_s"]
    assert reader("slow_loaded_share").read(run) is None
    assert reader("slow_wait_share").read(run) is None
    assert reader("slow_tpot_ms").read(run) == pytest.approx(8.0)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_the_entry_is_declared_as_the_issue_names_it(name):
    """By name, wherever later entries put it."""
    bench = cells.load_benchmark(FILE)
    found = [m for m in bench["per_layer"] if m["name"] == name]
    assert len(found) == 1
    unit, better, source, _ = EXPECTED[name]
    assert found[0] == {
        "name": name, "unit": unit, "better": better, "source": source,
        "layer": "serving", "moves": "tpot_p95_ms",
        "workloads": SERVING_CELLS}


@pytest.mark.parametrize("cell_name", SERVING_CELLS)
def test_read_layer_metrics_reports_them_in_a_traced_line(cell_name):
    """Through the harness's own loader, as `run.py --trace 1` does, in
    each of the seven serving cells."""
    cell = cells.load_cell(FILE, cell_name)
    cell = cells.Cell(**{**cell.__dict__, "per_layer": tuple(
        m for m in cell.per_layer if m["name"] in EXPECTED)})
    line = cells.read_layer_metrics(cell, serve_run(), {"tpot_p95_ms"})
    assert {k: v["unit"] for k, v in line.items()} == {
        name: unit for name, (unit, *_) in EXPECTED.items()}
    assert line["slow_tpot_ms"]["value"] == pytest.approx(8.0)


def test_the_training_cells_do_not_list_them():
    for cell_name in ("grpo-1.5b-r512", "grpo-7b-x4-r512", "grpo-olmoe-r512"):
        cell = cells.load_cell(FILE, cell_name)
        assert not {m["name"] for m in cell.per_layer} & set(EXPECTED)
