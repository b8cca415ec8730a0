"""The Ouro configuration and its cell, pinned BY NAME (this cell and these
entries, not the file's length or its neighbours): published keys, the
mix's parameters, the `serve_loop_ref` driver end to end at a tiny size on
the CPU (steered by rehearsal/cells_ouro.json), its refusal of a program
without the loop, the comparison's negative controls, and the seven new
readers on a run they can and a run they cannot read."""

import json
import os
import time

import pytest

import run as bench
from harness import cell as cells
from harness import ops_bytes_ouro as ob

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REHEARSAL = os.path.join(HERE, "rehearsal", "cells_ouro.json")
MAIN = os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CELL = "serve-ouro-tutor"
NEW = ("ouro_decode_step_ms", "ouro_decode_roofline", "ouro_paged_attn_roofline",
       "ouro_prefill_roofline", "loop_passes_per_token",
       "loop_weight_bytes_frac", "pool_live_frac")


def the_file():
    return json.load(open(os.path.join(BENCH, "configs", "ouro-2.6b.json")))


def test_the_file_holds_the_published_keys_unchanged():
    c = the_file()
    assert c["reduced"] == [] and c["reference"] == "reference_ouro"
    assert (c["total_ut_steps"], c["early_exit_threshold"]) == (4, 1)
    assert c["source"].endswith("ByteDance/Ouro-2.6B/blob/main/config.json")
    assert {"dtype", "weights", "init", "norms", "final_norm_in_loop",
            "exit_gate", "attention_bias", "cache", "rope", "hf_names"} <= set(
                c["assumed"])
    assert "8 rows x 5 pages of 128 tokens x 192 cache layers" in c["deployment"]
    entry = next(e for e in cells.load_benchmark(MAIN)["configs"]
                 if e["name"] == "ouro-2.6b")
    assert entry["reduced"] == [] and entry["source"] == c["source"]
    assert entry["file"] == "benchmark/configs/ouro-2.6b.json"
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    row = next(r for r in map(json.loads, open(CATALOG))
               if r["name"] == "Ouro-2.6B")
    assert row["source_url"] == c["source"]
    assert not [k for k, v in row["config"].items() if c.get(k, "missing") != v]


def test_the_cell_is_the_issues():
    cell = cells.load_cell(MAIN, CELL)
    assert cell.kind == "serve_loop_ref" and cell.chips == 1
    assert (cell.config_name, cell.traffic_name) == ("ouro-2.6b", "tutor-steady")
    mix = cell.traffic
    assert mix["engine"] == {"rows": 8, "page_size": 128, "prompt_len": 256,
                             "max_new_tokens": 384, "max_queue": 256,
                             "headroom": 0.0, "sync_every": 4}
    assert mix["tenants"] == 0 and mix["arrival"] == "poisson"
    assert mix["prompt_len"] == {"median": 128, "sigma": 0.6, "min": 16,
                                 "max": 256}
    assert mix["max_tokens"] == {"median": 128, "sigma": 0.6, "min": 32,
                                 "max": 384}
    assert mix["sampling"] == {"greedy_frac": 0.5, "temperature": [0.7, 1.0],
                               "top_p": [0.9, 1.0]}
    assert mix["eos_unreachable"] and "schedule_seed" in mix
    assert (mix["ramp_s"], mix["drain_s"], mix["trace_s"]) == (15.0, 45.0, 5.0)
    assert 0.7 <= mix["rate_rps"] / mix["knee_rps"] <= 0.8
    assert mix["knee_sweep"]["rows"]        # the sweep's rows are in the mix
    # the pool the deployment states: 40 pages of 192 MiB
    e = mix["engine"]
    slots = e["prompt_len"] + e["max_new_tokens"]
    assert slots == 5 * e["page_size"]
    assert e["rows"] * slots * ob.kv_bytes_per_token(cell.config) == 8_053_063_680
    chk = mix["greedy_check"]
    assert len(chk["steady_lengths"]) == e["rows"]
    assert max(chk["steady_lengths"]) == e["prompt_len"]
    # the traced five seconds hold at least three admissions
    from harness import trafficgen
    reqs = trafficgen.serve_requests(mix, 1, (mix["ramp_s"], 45.0, mix["drain_s"]),
                                     cell.config["vocab_size"])
    into = [r["t"] - mix["ramp_s"] for r in reqs]
    assert sum(3.0 <= t < 8.0 for t in into) >= 3
    names = {m["name"] for m in cell.per_layer}
    assert set(NEW) <= names
    assert {"row_occupancy", "chunk_ms", "admit_ms", "queue_wait_ms",
            "peak_hbm_gb", "window_compiles", "kv_bytes_per_token",
            "decode_device_step_ms", "decode_attn_share", "decode_mlp_share",
            "decode_head_sample_share", "prefill_device_ms", "scoped_share",
            "slow_tpot_ms", "beat_loaded_ms"} <= names
    assert not {"prefix_hit_frac", "expert_layer_share"} & names
    assert {m["name"] for m in cell.end_to_end} == {"tokens_per_s", "tpot_p95_ms",
                                                    "setup_s"}
    for m in cells.load_benchmark(MAIN)["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL] and m["moves"] == "tpot_p95_ms"
            assert os.path.isfile(os.path.join(BENCH, "layer_metrics",
                                               m["name"] + ".py"))


def test_a_program_without_the_loop_is_refused(monkeypatch, capsys):
    from drivers import serve_loop_ref
    from nanorlhf_tpu.core import ModelConfig

    cell = cells.load_cell(REHEARSAL, "serve-tiny-ouro")
    serve_loop_ref.refuse_a_program_without_the_model(cell)     # this program
    real = ModelConfig.from_hf_config

    class Parent:       # the parent's generic branch: no loop, two norms
        def __init__(self, mcfg):
            self.attention_bias, self.branch_norms = True, False
            self.vocab_size = mcfg.vocab_size

    monkeypatch.setattr(ModelConfig, "from_hf_config",
                        classmethod(lambda cls, hf: Parent(real(hf))))
    monkeypatch.setattr("harness.model.dataclasses.replace",
                        lambda mcfg, **kw: mcfg)
    with pytest.raises(SystemExit) as e:
        serve_loop_ref.refuse_a_program_without_the_model(cell)
    assert e.value.code == 4
    err = capsys.readouterr().err
    assert "not a model this program builds" in err and "loop_passes" in err


@pytest.fixture(scope="module")
def rehearsed(tmp_path_factory):
    out = tmp_path_factory.mktemp("ouro")
    # (4.5 s: the traced second starts 3 s into the window)
    line = bench.run_cell(REHEARSAL, "serve-tiny-ouro", 2**31 + 9, 4.5,
                          True, require_tpu=False, out_root=str(out),
                          t_process_start=time.time())
    return line, json.load(open(out / "serve-tiny-ouro" / "run.json"))["run"]


def test_serve_loop_ref_cell_rehearses(rehearsed):
    line, run = rehearsed
    assert line["correct"], line
    assert line["failed"] == 0 and line["attempted"] >= 8
    # (the CPU's trace has no scope table: the three rooflines read nothing)
    assert {"ouro_decode_step_ms", "loop_passes_per_token",
            "loop_weight_bytes_frac", "pool_live_frac", "kv_bytes_per_token",
            "chunk_ms", "row_occupancy", "window_compiles"} <= set(line["metrics"])
    assert line["metrics"]["loop_passes_per_token"]["value"] == 3.0
    assert line["metrics"]["kv_bytes_per_token"]["value"] == 6 * 2 * 4 * 16 * 4
    assert 0 < line["metrics"]["pool_live_frac"]["value"] < 100
    assert 50 < line["metrics"]["loop_weight_bytes_frac"]["value"] < 100
    assert line["metrics"]["window_compiles"]["value"] == 0
    assert run["kind"] == "serve_loop_ref" and len(run["traced_counters"]) == 2
    g = run["greedy_check"]
    assert g["tokens"] == 2 * 4 * 10 and g["full"]["tokens"] == 2 * 20
    assert g["widths"] == {"steady": 24 + 10, "full": 24 + 20}
    assert g["cached"]["cache_layers"] == 6 and g["cached"]["decode_steps"] == 6
    assert g["cached"]["tested_vs_float32"]["max_abs"] < 1e-4
    end = run["counters"]["end"]
    assert end["serving/loop_passes_per_token"] == 3
    assert end["serving/cache_layers"] == 6
    assert 0 < end["serving/pool_live_slots"] < end["serving/pool_reserved_slots"]


def test_new_readers_read_nothing_from_another_program(rehearsed):
    """The parent commit and every other model: no such counters or scopes,
    and a run of another kind has no such keys at all."""
    _, run = rehearsed
    readers = {n: cells.load_module(os.path.join(BENCH, "layer_metrics", n + ".py"),
                                    "ouro_reader_" + n) for n in NEW}
    bare = {"counters": {"start": {}, "end": {}}, "traffic": run["traffic"],
            "config": {"hidden_size": 64}, "snapshots": run["snapshots"],
            "records": run["records"], "chips": 1, "peaks": run["peaks"],
            "trace": None}
    assert all(r.read(bare) is None for r in readers.values())
    assert all(r.read({"counters": None}) is None for r in readers.values())
    # the file's configuration on a program without the counters (the parent)
    parent = dict(bare, config=run["config"])
    assert all(r.read(parent) is None for r in readers.values())
    # and on the chip's kind of trace they read what the tables hold
    table = {"steps": 40, "by_scope": {
        "decode/attn/attn.qkv": 4e-3, "decode/attn/attn.read": 2e-3,
        "decode/mlp": 2e-2, "decode/head": 1e-2, "decode": 1e-3,
        "prefill/attn/attn.qkv": 0.5, "prefill/mlp": 0.5},
        "by_program": {"jit_suffix_logits(1)": {
            "seconds": 1.0, "calls": 4.0, "scopes": {"prefill": 1.0}}}}
    # (the rehearsal's traced second may hold no admission: the window's own
    # counters stand in for the profiler's)
    between = [run["counters"]["start"], run["counters"]["end"]]
    traced = dict(run, trace={"busy_s": 1.0}, scope_trace=table,
                  traced_counters=between)
    got = {n: r.read(traced) for n, r in readers.items()}
    assert all(v is not None and v > 0 for v in got.values()), got
    before, after = between
    steps = after["serving/decode_steps"] - before["serving/decode_steps"]
    slots = (after["serving/global_slots_read"]
             - before["serving/global_slots_read"]) / steps
    bytes_ = 40 * slots * ob.kv_bytes_per_token(run["config"])
    assert got["ouro_paged_attn_roofline"] == pytest.approx(
        100 * bytes_ / run["peaks"]["hbm_bytes_per_s"] / 2e-3)


def test_the_comparison_can_fail():
    """tools/loop_control.py at the rehearsal's size: the sound readings
    pass, and one pass fewer, no norm between the passes, no branch norms, a
    slot shared by the passes, a decode that reads pass 1's slots and float8
    weights are each refused."""
    tool = cells.load_module(os.path.join(BENCH, "tools", "loop_control.py"),
                             "bench_tool_loop_control")
    out = os.path.join(os.path.dirname(BENCH), "chiprun_out")
    rc = tool.main(["serve-tiny-ouro", "5", REHEARSAL])
    lines = json.load(open(os.path.join(
        out, "loop_control_serve-tiny-ouro_5.json")))
    by = {(ln["control"], ln["verdict"]): ln["ok"] for ln in lines}
    assert by[("sound", "steady")] and by[("sound", "full")]
    for control in tool.CONTROLS:
        assert not (by[(control, "steady")] and by[(control, "full")]), control
    assert not any(ln["a_reading"] for ln in lines)
    assert rc == 0


def test_the_float32_witness_lies_on_the_reference_and_can_fail(capsys):
    """`loop_control.py --witness`: a row through its pages to its last slot
    in float32 lies on the reference; with the passes sharing a slot a layer
    the same witness does not, so it would show a fault of the cache that a
    weights' spread hides from the bf16 verdicts."""
    tool = cells.load_module(os.path.join(BENCH, "tools", "loop_control.py"),
                             "bench_tool_loop_control")
    args = ["serve-tiny-ouro", "5", REHEARSAL, "--witness", "1.0"]
    assert tool.main(args) == 0
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("{")]
    assert [ln["dtype"] for ln in lines][-1] == "float32"
    assert lines[-1]["float32_at_reference"] and lines[-1]["greedy"]["flips"] == 0
    assert lines[-1]["decode_steps"] + lines[-1]["prompt"] == 44    # every slot
    with tool.cache_fault("shared_slot"):
        assert tool.main(args) == 1
