"""The Falcon-H1 configuration and its cell: published widths, the
`serve_ssm_ref` driver end to end at a tiny size on the CPU (steered by
rehearsal/cells_falcon_h1.json), its refusal of a program without the model,
the comparison's controls, the counts of ops_bytes_falcon_h1 against counts
made by hand, and the new readers on a run they can and a run they cannot
read."""

import json
import os
import time

import pytest

import run as bench
from harness import cell as cells
from harness import ops_bytes_falcon_h1 as ob

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REHEARSAL = os.path.join(HERE, "rehearsal", "cells_falcon_h1.json")
MAIN = os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CELL = "serve-falcon-h1-assist"
NEW = ("fh1_decode_step_ms", "fh1_decode_roofline", "ssm_layer_share",
       "fh1_ssm_update_roofline", "fh1_ssd_scan_roofline",
       "fh1_paged_attn_roofline", "state_carry_frac")


def the_file():
    return json.load(open(os.path.join(BENCH, "configs",
                                       "falcon-h1-34b-l5.json")))


def test_widths_are_the_published_ones():
    c = the_file()
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    rows = [json.loads(line) for line in open(CATALOG)]
    row = next(r for r in rows if r["name"] == "Falcon-H1-34B-Instruct")
    assert row["source_url"] == c["source"]
    differs = sorted(k for k, v in row["config"].items() if c.get(k, "missing") != v)
    assert differs == c["reduced"] == ["num_hidden_layers"]
    assert c["num_hidden_layers"] == 5 and c["published"]["num_hidden_layers"] == 72
    assert c["reference"] == "reference_falcon_h1" and c["chips"] == 1
    assert {"dtype", "state_dtype", "in_proj_order", "mixer", "layer",
            "attention", "hf_names", "weights", "init"} <= set(c["assumed"])
    assert "pipeline" in c["deployment"] and "67 layers" in c["deployment"]
    # the arithmetic the deployment states
    assert ob.attention_params(c) == 31_457_280
    assert ob.mixer_params(c) == 68_351_072
    assert ob.mlp_params(c) == 330_301_440
    assert ob.layer_params(c) == 430_120_032
    assert round(ob.n_params(c) / 1e9, 2) == 4.82
    assert ob.kv_bytes_per_token_layer(c) == 2048
    assert ob.state_bytes_per_row_layer(c) == {"recurrent": 4_194_304,
                                               "tail": 30_720}
    assert ob.state_bytes_per_row(c) == 5 * 4_225_024
    assert round(48 * ob.state_bytes_per_row(c) / 1e9, 2) == 1.01


def test_the_cell_is_the_issues():
    cell = cells.load_cell(MAIN, CELL)
    assert cell.kind == "serve_ssm_ref" and cell.chips == 1
    assert cell.traffic_name == "assist-steady"
    mix = cell.traffic
    assert mix["engine"] == {"rows": 48, "page_size": 128, "prompt_len": 4096,
                             "max_new_tokens": 1024, "max_queue": 512,
                             "headroom": 0.0, "sync_every": 4,
                             "prefill_chunk": 1024}
    assert mix["prompt_len"] == {"median": 1024, "sigma": 0.8, "min": 64,
                                 "max": 4096}
    assert mix["max_tokens"] == {"median": 256, "sigma": 0.7, "min": 32,
                                 "max": 1024}
    assert mix["tenants"] == 0 and mix["arrival"] == "poisson"
    assert mix["sampling"] == {"greedy_frac": 0.5, "temperature": [0.7, 1.0],
                               "top_p": [0.9, 1.0]}
    assert mix["eos_unreachable"] and "schedule_seed" in mix
    assert 0.75 <= mix["rate_rps"] / mix["knee_rps"] <= 0.8
    assert mix["knee_sweep"]["rows"]
    chk, chunk = mix["greedy_check"], mix["engine"]["prefill_chunk"]
    # four pieces, three carries, and a last piece of a few tokens
    assert chk["long_len"] // chunk == 3 and 0 < chk["long_len"] % chunk <= 8
    assert chk["long_max_tokens"] == 256
    assert chk["short_rows"] < mix["engine"]["rows"]    # beside the long row
    assert set(NEW) <= {m["name"] for m in cell.per_layer}
    for w in cells.load_benchmark(MAIN)["workloads"]:
        if w["name"] != CELL:
            other = cells.load_cell(MAIN, w["name"])
            assert not set(NEW) & {m["name"] for m in other.per_layer}
    assert {m["name"] for m in cell.end_to_end} == {"tokens_per_s", "tpot_p95_ms",
                                                    "setup_s"}
    assert {"row_occupancy", "chunk_ms", "admit_ms", "queue_wait_ms",
            "peak_hbm_gb", "window_compiles", "kv_bytes_per_token",
            "state_bytes_per_row", "decode_attn_share", "scoped_share",
            "prefill_device_ms", "sample_rows_frac"} <= {
                m["name"] for m in cell.per_layer}


def test_ops_and_bytes_against_counts_made_by_hand():
    c = the_file()
    b = ob.decode_step_bytes(c, rows=30, slots=40_000)
    assert b["attention"] == 5 * 31_457_280 * 2
    assert b["mixer"] == 5 * 68_351_072 * 2
    assert b["mlp_norms"] == 5 * (330_301_440 + 10_240) * 2
    assert b["kv"] == 5 * 40_000 * 2048
    # 30 live rows, both leaves, read and written
    assert b["state"] == 2 * 30 * 5 * (32 * 128 * 256 * 4 + 3 * 5120 * 2)
    assert b["head"] == (5120 * 261120 + 5120) * 2 + 30 * 261120 * 4
    assert b["total"] == sum(v for k, v in b.items() if k != "total")
    assert 6.9e9 < b["attention"] + b["mixer"] + b["mlp_norms"] + (
        5120 * 261120 + 5120) * 2 < 7.0e9          # the issue's 6.97 GB
    # a layer's pass over 30 rows' state: S and the tail both ways, and a
    # token's xs, y (4096 each), B, C (512 each) and dt (32) in float32
    assert ob.ssm_update_bytes(c, rows=30) == 30 * (
        2 * (4_194_304 + 30_720) + (2 * 4096 + 2 * 512 + 32) * 4)
    cost = ob.ssd_scan_cost(c, tokens=1024, pieces=1)
    assert cost["flops"] == 5 * 32 * 128 * 256 * 1024
    assert cost["bytes"] == 1024 * (2 * 4096 + 2 * 512 + 32) * 4 + 2 * 4_194_304
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert ob.ssd_scan_floor_s(c, peaks, tokens=1024, pieces=1) == pytest.approx(
        cost["bytes"] / 819e9)                      # bytes bind, not operations


def test_a_program_without_the_model_is_refused(monkeypatch, capsys):
    from drivers import serve_ssm_ref
    from nanorlhf_tpu.core import ModelConfig

    cell = cells.load_cell(REHEARSAL, "serve-tiny-falcon-h1")
    serve_ssm_ref.refuse_a_program_without_the_model(cell)      # this program
    # the parent's from_hf_config on these keys builds a dense decoder
    dense = classmethod(lambda cls, hf: ModelConfig.qwen2_tiny())
    monkeypatch.setattr(ModelConfig, "from_hf_config", dense)
    with pytest.raises(SystemExit) as e:
        serve_ssm_ref.refuse_a_program_without_the_model(cell)
    assert e.value.code == 4
    assert "not a model this program builds" in capsys.readouterr().err

    def raises(cls, hf):
        raise ValueError("model_type='falcon_h1' is not built")

    monkeypatch.setattr(ModelConfig, "from_hf_config", classmethod(raises))
    with pytest.raises(SystemExit):
        serve_ssm_ref.refuse_a_program_without_the_model(cell)


@pytest.fixture(scope="module")
def rehearsed(tmp_path_factory):
    out = tmp_path_factory.mktemp("falcon_h1")
    # (4.5 s: the traced second starts 3 s into the window)
    line = bench.run_cell(REHEARSAL, "serve-tiny-falcon-h1", 2**31 + 9, 4.5,
                          True, require_tpu=False, out_root=str(out),
                          t_process_start=time.time())
    return line, json.load(open(out / "serve-tiny-falcon-h1" / "run.json"))["run"]


def test_serve_ssm_ref_cell_rehearses(rehearsed):
    line, run = rehearsed
    assert line["correct"], line
    assert line["failed"] == 0 and line["attempted"] >= 10
    # (the CPU's trace has no `%attn.*` kernel and no device plane: no
    # device roofline and no share of the mixer here)
    assert {"fh1_decode_step_ms", "fh1_decode_roofline", "state_bytes_per_row",
            "state_carry_frac", "chunk_ms", "row_occupancy", "window_compiles",
            "kv_bytes_per_token"} <= set(line["metrics"])
    assert not {"fh1_paged_attn_roofline", "fh1_ssm_update_roofline",
                "fh1_ssd_scan_roofline"} & set(line["metrics"])
    assert line["metrics"]["window_compiles"]["value"] == 0
    # two layers, each a tail 3 x 96 and a state 4 x 16 x 8, float32 here
    assert line["metrics"]["state_bytes_per_row"]["value"] == 2 * (288 + 512) * 4
    assert line["metrics"]["kv_bytes_per_token"]["value"] == 2 * 2 * 2 * 16 * 4
    assert 0 < line["metrics"]["state_carry_frac"]["value"] < 100
    assert run["kind"] == "serve_ssm_ref"
    g = run["greedy_check"]
    # 27 tokens in pieces of 8: three carries; one reset a request
    # and four prompts of one or two whole pieces and a bit: 1 + 2 + 1 + 2
    assert g["state_carries"] >= 3 + 6 and g["prefix_hit_tokens"] == 0
    assert g["state_resets"] == 1 + 2 + 4 + 4
    assert g["carry"]["tokens"] == 4 * 4
    assert g["tokens"] == 12 and g["short"]["tokens"] == 2 * 6
    assert g["reuse"]["tokens"] == 4 * 8        # as many as the engine has rows
    # the engine's recurrent state after the long row and a carry row against
    # the reference's: float32 activations here, so rounding in the sums alone
    for name in ("long", "carry"):
        state = g["state"][name]
        assert state["ok"] and state["first_layer_slow"] < 1e-5, state
        assert max(state["layer_max"]) < 1e-5 and state["next_row"] > 0.5, state
        assert state["state_dtype"] == "float32"
    end = run["counters"]["end"]
    assert end["serving/state_layers"] == 2 and end["serving/window_layers"] == 0
    assert end["serving/prefix_hit_tokens"] == 0
    assert end["serving/state_tokens"] > 0
    assert len(run["traced_counters"]) == 2


def test_new_readers_read_nothing_from_another_program(rehearsed):
    """The parent of PR 49 and every other model: no `mamba_*` key, no
    `attn.ssm` scope, no state counters; a run of another kind has no such
    keys at all."""
    _, run = rehearsed
    readers = {n: cells.load_module(os.path.join(BENCH, "layer_metrics", n + ".py"),
                                    "fh1_reader_" + n) for n in NEW}
    bare = {"counters": {"start": {}, "end": {}}, "traffic": run["traffic"],
            "config": {"hidden_size": 64}, "snapshots": run["snapshots"],
            "records": run["records"], "chips": 1, "peaks": run["peaks"],
            "trace": None, "cell": "no-such-cell"}
    assert all(r.read(bare) is None for r in readers.values())
    assert all(r.read({"counters": None, "cell": "no-such-cell"}) is None
               for r in readers.values())
    # and on the chip's kind of trace they read what the tables hold
    counters = [dict(run["counters"]["start"]), dict(run["counters"]["start"])]
    for key, gain in (("serving/decode_steps", 40), ("serving/live_row_steps", 100),
                      ("serving/global_slots_read", 2000),
                      ("serving/state_tokens", 64), ("serving/state_resets", 4),
                      ("serving/state_piece_carries", 6)):
        counters[1][key] = counters[0].get(key, 0) + gain
    traced = dict(run, traced_counters=counters,
                  attn_trace={"global": {"events": 80.0, "seconds": 1e-3},
                              "window": {"events": 0.0, "seconds": 0.0}},
                  scope_trace={"steps": 40.0, "by_scope": {
                      "decode/attn/attn.ssm/attn.ssm.update": 1.0,
                      "decode/attn/attn.ssm/attn.write": 0.5,
                      "decode/attn/attn.ssm/attn.ssm.in": 0.5,
                      "decode/attn/attn.qkv": 1.0,
                      "decode/mlp": 7.0,
                      "prefill/attn/attn.ssm/attn.ssm.scan": 0.25}})
    assert readers["ssm_layer_share"].read(traced) == pytest.approx(20.0)
    cfg, peaks = run["config"], run["peaks"]
    assert readers["fh1_ssm_update_roofline"].read(traced) == pytest.approx(
        100 * 40 * 2 * ob.ssm_update_bytes(cfg, rows=2.5)
        / peaks["hbm_bytes_per_s"] / 1.5)
    assert readers["fh1_ssd_scan_roofline"].read(traced) == pytest.approx(
        100 * 2 * ob.ssd_scan_floor_s(cfg, peaks, tokens=64, pieces=10) / 0.25)
    assert readers["fh1_paged_attn_roofline"].read(traced) == pytest.approx(
        100 * 80 * 50 * ob.kv_bytes_per_token_layer(cfg)
        / peaks["hbm_bytes_per_s"] / 1e-3)


def test_the_comparison_can_fail():
    """tools/ssm_control.py at the rehearsal's size: the sound readings
    pass; the model without its mixer, without its attention and without
    `mup`, and a state not carried between pieces, are each refused where
    they must be. A bfloat16 state reads a thousand times further from the
    reference's than the sound engine's (the limit itself belongs to the
    chip's bfloat16 activations: PERF.md section 4), and its bytes a row
    are not the file's."""
    tool = cells.load_module(os.path.join(BENCH, "tools", "ssm_control.py"),
                             "bench_tool_ssm_control")
    out = os.path.join(os.path.dirname(BENCH), "chiprun_out")
    rc = tool.main(["serve-tiny-falcon-h1", "5", REHEARSAL])
    lines = json.load(open(os.path.join(
        out, "ssm_control_serve-tiny-falcon-h1_5.json")))
    by = {(ln["control"], ln["verdict"]): ln["ok"] for ln in lines}
    assert all(by[("sound", v)] for v in ("long", "short", "carry", "reuse"))
    for control in ("no_mixer", "no_attention", "no_mup"):
        assert not by[(control, "long")], control
    assert not by[("state_not_carried", "carry")]
    # the token verdicts need not tell a bfloat16 state; the state's own
    # reading does, and the bytes a row
    assert ("state_bf16", "long") in by
    far = {(ln["control"], ln["verdict"]): ln["first_layer_slow"]
           for ln in lines if "first_layer_slow" in ln}
    for verdict in ("state_long", "state_carry"):
        assert by[("sound", verdict)] and far[("sound", verdict)] < 1e-5
        assert far[("state_bf16", verdict)] > 1e-3
        assert not by[("state_not_carried", verdict)]
    assert by[("sound", "state_bytes_per_row")]
    assert not by[("state_bf16", "state_bytes_per_row")]
    assert not any(ln["a_reading"] for ln in lines)
    assert rc in (0, 1)
