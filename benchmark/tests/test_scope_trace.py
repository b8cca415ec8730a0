"""harness/scope_trace.py on the small recorded trace beside it
(data/scoped.xplane.pb, written by make_scoped_trace.py, whose docstring
works the numbers), and the seven readers that rest on it (ISSUE 36)."""

import os
import shutil

import pytest

import make_scoped_trace
import make_small_trace
from harness import cell as cells
from harness import scope_trace

from test_benchmark_json import ROOT

US = 1e-6


@pytest.fixture(scope="module")
def table():
    return scope_trace.scope_seconds(make_scoped_trace.PATH)


def test_committed_trace_is_what_the_generator_writes():
    with open(make_scoped_trace.PATH, "rb") as f:
        assert f.read() == make_scoped_trace.build()


def test_the_metadata_plane_gives_each_program_its_op_table():
    programs = scope_trace.op_scopes(make_scoped_trace.PATH)
    assert sorted(programs) == ["jit_serving_chunk(11)",
                                "jit_suffix_logits(22)",
                                "jit_update_minibatch(33)"]
    chunk = programs["jit_serving_chunk(11)"]
    assert chunk["op_name"]["while.2"] == "jit(chunk)/decode/while"
    assert chunk["op_name"]["copy.15"] == ""
    assert chunk["body"]["fusion.12"] == "body.3"
    assert chunk["body"]["while.2"] == "main.1"
    assert [code for code, _ in chunk["fused"]["fusion.11"]] == [
        "parameter", "dot", "add"]
    # the same instruction name in two programs, with two meanings
    assert scope_trace.instruction_scope(
        programs["jit_suffix_logits(22)"], "fusion.1") == "prefill/mlp"
    assert scope_trace.instruction_scope(
        programs["jit_update_minibatch(33)"], "fusion.1") == "update/mlp bwd"
    # a fusion is its matmul's: the root is `mlp/add`, the dot `attn.out`
    assert scope_trace.instruction_scope(chunk, "fusion.11") == \
        "decode/attn/attn.out"
    assert scope_trace.instruction_scope(chunk, "copy.15") == ""
    assert scope_trace.instruction_scope(chunk, "copy.99") is None


def test_a_trace_without_the_plane_has_no_table():
    assert scope_trace.op_scopes(make_small_trace.PATH) == {}
    t = scope_trace.scope_seconds(make_small_trace.PATH)
    assert t["by_scope"] == {} and t["steps"] == 0
    assert t["unscoped_s"] == pytest.approx(t["busy_s"]) and t["busy_s"] > 0
    assert t["programs_in_table"] == 0


def test_reduction_gives_the_known_numbers(table):
    assert {k: round(v / US) for k, v in table["by_scope"].items()} == {
        "decode/mlp/moe.experts": 1200, "decode/head": 840,
        "decode/attn/attn.out": 600, "decode/attn/attn.read": 400,
        "update/mlp bwd": 400, "prefill/mlp": 400, "decode/sample": 120,
        "prefill/attn/attn.global/attn.write": 160, "decode": 100,
        "update/optim": 100, "prefill/attn/attn.global": 40}
    assert list(table["by_scope"])[:2] == ["decode/mlp/moe.experts",
                                           "decode/head"]
    assert table["busy_s"] == pytest.approx(4410 * US)
    assert table["unscoped_s"] == pytest.approx(50 * US)
    assert table["unjoined_s"] == pytest.approx(10 * US)
    assert table["steps"] == 4
    assert table["programs_in_table"] == 3
    chunk = table["by_program"]["jit_serving_chunk(11)"]
    assert chunk["calls"] == 2 and chunk["seconds"] == pytest.approx(3300 * US)
    assert chunk["scopes"] == pytest.approx({"decode": 3260 * US, "": 40 * US})
    suffix = table["by_program"]["jit_suffix_logits(22)"]
    assert suffix["calls"] == 2
    assert suffix["scopes"] == pytest.approx({"prefill": 600 * US})
    assert table["by_program"]["jit_update_minibatch(33)"]["scopes"] == \
        pytest.approx({"update": 500 * US})
    scope, name, seconds = table["ops"][0]
    assert (scope, name.split()[0]) == ("decode/mlp/moe.experts", "%fusion.16")
    assert seconds == pytest.approx(1200 * US)
    assert [[n.split()[0], round(sec / US)] for n, sec in
            table["unscoped_ops"]] == [["%copy.15", 40], ["%copy.99", 10]]


EXPECTED = {
    "decode_device_step_ms": 0.815,
    "decode_attn_share": 100 * 1000 / 3260,
    "decode_mlp_share": 100 * 1200 / 3260,
    "decode_head_sample_share": 100 * 960 / 3260,
    "prefill_device_ms": 0.3,
    "prefill_device_share": 100 * 600 / 4410,
    "scoped_share": 100 * (1 - 50 / 4410),
}


def reader(name):
    path = cells.find_under_paths(ROOT, ["benchmark"], "layer_metrics",
                                  name + ".py")
    return cells.load_module(path, "scope_metric_" + name)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_gives_the_known_answer(name, table):
    assert reader(name).read({"scope_trace": table}) == pytest.approx(
        EXPECTED[name])


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_returns_none_without_its_keys(name):
    """A run without a trace, a trace of a program without the scopes (the
    parent of the PR that wrote them) and a trace without the metadata
    plane: the line leaves the metric out and nothing raises."""
    read = reader(name).read
    assert read({"cell": "no-such-cell", "trace": None}) is None
    assert read({"cell": "no-such-cell", "trace": {"busy_s": 1.0}}) is None
    assert read({"scope_trace": None}) is None
    bare = scope_trace.scope_seconds(make_small_trace.PATH)
    assert read({"scope_trace": bare}) is None


def test_the_readers_find_the_runs_trace_and_reduce_it_once(capsys):
    """They get only `run`: the trace is the newest under
    `<benchmark>/out/<cell>/trace`, reduced at the first reader's call, kept
    in the run (so `run.json` holds it) and printed as a `scopes` line."""
    out = os.path.join(ROOT, "benchmark", "out", "test-scope-trace")
    where = os.path.join(out, "trace", "plugins", "profile", "1")
    os.makedirs(where)
    try:
        shutil.copy(make_scoped_trace.PATH, os.path.join(where, "t.xplane.pb"))
        run = {"cell": "test-scope-trace", "trace": {"busy_s": 1.0}}
        assert reader("decode_device_step_ms").read(run) == pytest.approx(0.815)
        assert run["scope_trace"]["steps"] == 4
        assert run["scope_trace"]["reduce_s"] >= 0
        kept = run["scope_trace"]
        assert reader("scoped_share").read(run) > 98
        assert run["scope_trace"] is kept
        assert capsys.readouterr().out.count('"phase": "scopes"') == 1
    finally:
        shutil.rmtree(out)
