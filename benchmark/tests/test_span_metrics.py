"""The seven per-layer metrics that read the program's spans (ISSUE 24): each
reader against a hand-made `run` with known answers, and None on a run of a
program that exports no such key (the parent of the PR that added them)."""

import pytest

from harness import cell as cells

from test_benchmark_json import FILE, ROOT

LOOP = {"wait": 1.0, "admit": 2.0, "reap": 0.5, "step": 16.0, "deliver": 0.5}


def serve_run():
    """A 20 s window: 40 admissions of 50 ms that waited 120 ms each, 64 beats
    of 250 ms of which the host waited 225 ms for the device, 30 streamed
    first tokens that took 4 ms to leave. Every counter starts off zero, as
    after set-up's warm-up requests."""
    start = {f"serving/loop_{k}_s": 10.0 + i for i, k in enumerate(LOOP)}
    start.update({
        "serving/loop_beats": 100, "serving/admitted": 12,
        "serving/session_sync_s": 7.0, "serving/session_dispatch_s": 1.0,
        "serving/queue_wait_s_sum": 3.0, "serving/queue_wait_s_count": 12,
        "serving/first_token_lag_s_sum": 0.25,
        "serving/first_token_lag_s_count": 5,
        "serving/prefix_hit_tokens": 0})
    end = dict(start)
    for k, v in LOOP.items():
        end[f"serving/loop_{k}_s"] += v
    end["serving/loop_beats"] += 64
    end["serving/admitted"] += 40
    end["serving/session_sync_s"] += 14.4
    end["serving/queue_wait_s_sum"] += 40 * 0.120
    end["serving/queue_wait_s_count"] += 40
    end["serving/first_token_lag_s_sum"] += 30 * 0.004
    end["serving/first_token_lag_s_count"] += 30
    return {"kind": "serve", "counters": {"start": start, "end": end}}


def rl_run():
    """Three updates whose phases leave 0.07, 0.05 and 0.30 s unaccounted."""
    rows = []
    for between in (0.07, 0.05, 0.30):
        phases = {"time/rollout_s": 7.5, "time/reward_s": 0.004,
                  "time/logprob_s": 0.6, "time/update_s": 1.1}
        rows.append({"episode": 64, **phases, "time/rollout_overlap_frac": 0.0,
                     "trainer/iteration_s": sum(phases.values()) + between})
    return {"kind": "rl", "rows": rows}


EXPECTED = {
    "queue_wait_ms": (serve_run, 120.0),
    "admit_ms": (serve_run, 50.0),
    "admit_share": (serve_run, 10.0),
    "chunk_ms": (serve_run, 250.0),
    "chunk_sync_share": (serve_run, 90.0),
    "first_token_lag_ms": (serve_run, 4.0),
    "host_between_phases_s": (rl_run, 0.07),
}


def reader(name):
    path = cells.find_under_paths(ROOT, ["benchmark"],
                                  "layer_metrics", name + ".py")
    return cells.load_module(path, "span_metric_" + name)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_gives_the_known_answer(name):
    make, answer = EXPECTED[name]
    assert reader(name).read(make()) == pytest.approx(answer)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_returns_none_without_its_keys(name):
    """The parent's program has no such counter and no such row key: the
    line leaves the metric out and nothing raises."""
    read = reader(name).read
    parent_serve = {"kind": "serve", "counters": {
        "start": {"serving/admitted": 3, "serving/prefix_hit_tokens": 0},
        "end": {"serving/admitted": 9, "serving/prefix_hit_tokens": 5}}}
    parent_rl = {"kind": "rl", "rows": [
        {"episode": 64, "time/rollout_s": 7.5, "time/update_s": 1.1}]}
    for run in (parent_serve, parent_rl, {}, {"rows": []}, {"counters": None}):
        assert read(run) is None


@pytest.mark.parametrize("name", sorted(n for n, (make, _) in EXPECTED.items()
                                        if make is serve_run))
def test_nothing_counted_in_the_window_is_none_not_a_division(name):
    run = serve_run()
    run["counters"]["end"] = dict(run["counters"]["start"])
    assert reader(name).read(run) is None


def test_the_seven_are_declared_as_the_issue_names_them():
    bench = cells.load_benchmark(FILE)
    entries = {m["name"]: m for m in bench["per_layer"]}
    assert [m["name"] for m in bench["per_layer"]][-7:] == [
        "queue_wait_ms", "admit_ms", "admit_share", "chunk_ms",
        "chunk_sync_share", "first_token_lag_ms", "host_between_phases_s"]
    for name, (make, _) in EXPECTED.items():
        m = entries[name]
        assert m["source"] == "program_span"
        if make is serve_run:
            assert (m["workloads"], m["moves"], m["layer"]) == (
                ["serve-1.5b-chat"], "tpot_p95_ms", "serving")
        else:
            assert (m["workloads"], m["moves"], m["layer"]) == (
                ["grpo-1.5b-r512", "grpo-7b-x4-r512"], "tokens_per_s",
                "trainer loop")
    assert entries["chunk_sync_share"]["better"] == "higher"


def test_read_layer_metrics_reports_them_in_a_traced_line():
    """Through the harness's own loader, as `run.py --trace 1` does."""
    serve = cells.load_cell(FILE, "serve-1.5b-chat")
    only = {m["name"] for m in serve.per_layer if m["name"] in EXPECTED}
    cell = cells.Cell(**{**serve.__dict__, "per_layer": tuple(
        m for m in serve.per_layer if m["name"] in only)})
    line = cells.read_layer_metrics(cell, serve_run(), {"tpot_p95_ms"})
    assert {k: v["unit"] for k, v in line.items()} == {
        "queue_wait_ms": "ms", "admit_ms": "ms", "admit_share": "%",
        "chunk_ms": "ms", "chunk_sync_share": "%", "first_token_lag_ms": "ms"}
    rl = cells.load_cell(FILE, "grpo-1.5b-r512")
    cell = cells.Cell(**{**rl.__dict__, "per_layer": tuple(
        m for m in rl.per_layer if m["name"] == "host_between_phases_s")})
    line = cells.read_layer_metrics(cell, rl_run(), {"tokens_per_s"})
    assert line == {"host_between_phases_s": {"value": pytest.approx(0.07),
                                              "unit": "s"}}
