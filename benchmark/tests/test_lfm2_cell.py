"""The LFM2 configuration and its cell: published widths, the
`serve_state_ref` driver end to end at a tiny size on the CPU (steered by
rehearsal/cells_lfm2.json), its refusal of a program without the model, the
comparison's controls, and the new readers on a run they can and a run they
cannot read."""

import json
import os
import time

import pytest

import run as bench
from harness import cell as cells
from harness import ops_bytes_lfm2 as ob

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REHEARSAL = os.path.join(HERE, "rehearsal", "cells_lfm2.json")
MAIN = os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CELL = "serve-lfm2-chat"
NEW = ("lfm2_decode_step_ms", "lfm2_decode_roofline", "conv_layer_share",
       "lfm2_gmm_roofline", "lfm2_paged_attn_roofline", "state_bytes_per_row")


def the_file():
    return json.load(open(os.path.join(BENCH, "configs", "lfm2-24b-l10.json")))


def test_widths_are_the_published_ones():
    c = the_file()
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    rows = [json.loads(line) for line in open(CATALOG)]
    row = next(r for r in rows if r["name"] == "LFM2-24B-A2B")
    assert row["source_url"] == c["source"]
    differs = sorted(k for k, v in row["config"].items() if c.get(k, "missing") != v)
    assert differs == sorted(c["reduced"]) == ["layer_types", "num_hidden_layers"]
    assert c["num_hidden_layers"] == 10 and c["published"]["num_hidden_layers"] == 40
    assert c["layer_types"] == row["config"]["layer_types"][:10]
    assert c["published"]["layer_types"] == row["config"]["layer_types"]
    # the two dense layers once, then two whole periods [a, c, c, c]
    assert c["layer_types"] == ["conv", "conv"] + ["full_attention", "conv",
                                                   "conv", "conv"] * 2
    assert c["reference"] == "reference_lfm2" and c["chips"] == 1
    assert {"dtype", "head_dim", "tie_word_embeddings", "norms", "conv",
            "router", "attention", "hf_names", "weights", "init"} <= set(c["assumed"])
    assert "pipeline" in c["deployment"] and "ALL 64 experts" in c["deployment"]
    # the arithmetic the deployment states
    assert ob.expert_params(c) == 9_437_184
    assert ob.conv_params(c) == 16_783_360
    assert ob.attention_params(c) == 10_485_888
    assert round(ob.n_params(c) / 1e9, 2) == 5.27
    assert ob.kv_bytes_per_token_layer(c) == 2048
    assert ob.state_bytes_per_row(c) == 65_536
    w = ob.widths(c)
    assert (w["Lc"], w["La"], w["Ld"], w["Le"]) == (8, 2, 2, 8)


def test_the_cell_is_the_issues():
    cell = cells.load_cell(MAIN, CELL)
    assert cell.kind == "serve_state_ref" and cell.chips == 1
    assert cell.traffic_name == "chat-rows-steady"
    mix = cell.traffic
    assert mix["engine"] == {"rows": 64, "page_size": 128, "prompt_len": 4096,
                             "max_new_tokens": 1024, "max_queue": 512,
                             "headroom": 0.0, "sync_every": 4,
                             "prefill_chunk": 1024}
    assert mix["prompt_len"] == {"median": 512, "sigma": 0.8, "min": 32,
                                 "max": 4096}
    assert mix["max_tokens"] == {"median": 256, "sigma": 0.7, "min": 32,
                                 "max": 1024}
    assert mix["tenants"] == 0 and mix["arrival"] == "poisson"
    assert mix["sampling"] == {"greedy_frac": 0.5, "temperature": [0.7, 1.0],
                               "top_p": [0.9, 1.0]}
    assert mix["eos_unreachable"] and "schedule_seed" in mix
    assert 0.7 <= mix["rate_rps"] / mix["knee_rps"] <= 0.8
    assert mix["knee_sweep"]["rows"]
    chk, chunk = mix["greedy_check"], mix["engine"]["prefill_chunk"]
    # four pieces, three carries, and a last piece of a few tokens
    assert chk["long_len"] // chunk == 3 and 0 < chk["long_len"] % chunk <= 8
    assert chk["long_max_tokens"] == 256 and chk["short_rows"] == 32
    assert chk["carry_rows"] * chk["carry_max_tokens"] >= 1500
    # the short verdict's 32 rows of 576 are one part, the carry rows several
    assert 32 * (512 + 64) <= chk["tokens_at_once"] < 12 * (2 * chunk + 35)
    assert (chk["short_min"], chk["short_max"]) == (64, 512)
    assert chk["reuse_max"] <= 16
    assert set(NEW) <= {m["name"] for m in cell.per_layer}
    for w in cells.load_benchmark(MAIN)["workloads"]:
        if w["name"] != CELL:
            other = cells.load_cell(MAIN, w["name"])
            assert not set(NEW) & {m["name"] for m in other.per_layer}
    assert {m["name"] for m in cell.end_to_end} == {"tokens_per_s", "tpot_p95_ms",
                                                    "setup_s"}
    assert {"row_occupancy", "chunk_ms", "admit_ms", "queue_wait_ms",
            "expert_layer_share", "peak_hbm_gb", "window_compiles",
            "kv_bytes_per_token", "decode_attn_share", "scoped_share",
            "prefill_device_ms"} <= {m["name"] for m in cell.per_layer}


def test_ops_and_bytes():
    c = the_file()
    b = ob.decode_step_bytes(c, rows=24, experts_hit=50, slots=20_000)
    assert b["experts"] == 8 * 50 * 3 * 2048 * 1536 * 2
    assert b["kv"] == 2 * 20_000 * 2048
    assert b["state"] == 2 * 24 * 65_536
    assert b["head"] == (2048 * 65536 + 2048) * 2 + 24 * 65536 * 4
    assert b["operators_dense_router"] == 2 * (
        8 * 16_783_360 + 2 * 10_485_888 + 10 * 2 * 2048
        + 2 * 3 * 2048 * 11776 + 8 * (2048 * 64 + 64))
    assert b["total"] == sum(v for k, v in b.items() if k != "total")
    assert ob.experts_hit_expected(c, 1024) == pytest.approx(64.0, abs=1e-6)
    assert 3.9 < ob.experts_hit_expected(c, 1) <= 4.0
    step = ob.grouped_matmul_cost(c, m=256, k=2048, n=1536, tokens=24, kernels=50)
    assert step["bytes"] == (96 * 2048 + 50 * 2048 * 1536 + 96 * 1536) * 2


def test_a_program_without_the_model_is_refused(monkeypatch, capsys):
    from drivers import serve_state_ref
    from nanorlhf_tpu.core import ModelConfig

    cell = cells.load_cell(REHEARSAL, "serve-tiny-lfm2")
    serve_state_ref.refuse_a_program_without_the_model(cell)    # this program

    def parent(cls, hf):        # the parent's from_hf_config on these keys
        raise ValueError("model_type='lfm2_moe' with expert keys")

    monkeypatch.setattr(ModelConfig, "from_hf_config", classmethod(parent))
    with pytest.raises(SystemExit) as e:
        serve_state_ref.refuse_a_program_without_the_model(cell)
    assert e.value.code == 4 and "not a model this program builds" in capsys.readouterr().err
    dense = classmethod(lambda cls, hf: ModelConfig.qwen2_tiny())
    monkeypatch.setattr(ModelConfig, "from_hf_config", dense)
    with pytest.raises(SystemExit):
        serve_state_ref.refuse_a_program_without_the_model(cell)


@pytest.fixture(scope="module")
def rehearsed(tmp_path_factory):
    out = tmp_path_factory.mktemp("lfm2")
    # (4.5 s: the traced second starts 3 s into the window)
    line = bench.run_cell(REHEARSAL, "serve-tiny-lfm2", 2**31 + 9, 4.5,
                          True, require_tpu=False, out_root=str(out),
                          t_process_start=time.time())
    return line, json.load(open(out / "serve-tiny-lfm2" / "run.json"))["run"]


def test_serve_state_ref_cell_rehearses(rehearsed):
    line, run = rehearsed
    assert line["correct"], line
    assert line["failed"] == 0 and line["attempted"] >= 10
    # (the CPU's trace has no `%gmm`, no `%attn.*` kernel and no device
    # plane: neither device roofline nor the conv share here)
    assert {"lfm2_decode_step_ms", "lfm2_decode_roofline", "state_bytes_per_row",
            "chunk_ms", "row_occupancy", "window_compiles",
            "kv_bytes_per_token"} <= set(line["metrics"])
    assert not {"lfm2_gmm_roofline", "lfm2_paged_attn_roofline"} & set(line["metrics"])
    assert line["metrics"]["window_compiles"]["value"] == 0
    assert line["metrics"]["state_bytes_per_row"]["value"] == 8 * 2 * 64 * 4
    assert line["metrics"]["kv_bytes_per_token"]["value"] == 2 * 2 * 2 * 16 * 4
    assert run["kind"] == "serve_state_ref" and run["moe"]["moe/dropped_tokens"] == 0
    assert 0 < run["moe"]["moe/bias_changed_frac"] < 1
    g = run["greedy_check"]
    # 27 tokens in pieces of 8: three carries; one reset a request
    # and four prompts of one or two whole pieces and a bit: 1 + 2 + 1 + 2
    assert g["state_piece_carries"] >= 3 + 6 and g["prefix_hit_tokens"] == 0
    assert g["state_resets"] == 1 + 2 + 4 + 4
    assert g["carry"]["tokens"] == 4 * 4
    assert g["tokens"] == 12 and g["short"]["tokens"] == 2 * 6
    assert g["reuse"]["tokens"] == 4 * 8        # as many as the engine has rows
    end = run["counters"]["end"]
    assert end["serving/state_layers"] == 8 and end["serving/window_layers"] == 0
    assert end["serving/prefix_hit_tokens"] == 0
    assert len(run["traced_counters"]) == 2
    # the steps really taken, not beats x sync_every
    steps = end["serving/decode_steps"] - run["counters"]["start"]["serving/decode_steps"]
    beats = end["serving/loop_beats"] - run["counters"]["start"]["serving/loop_beats"]
    assert 0 < steps <= 4 * beats


def test_new_readers_read_nothing_from_another_program(rehearsed):
    """The parent of PR 38 and every other model: no state counters, no
    `attn.conv` scope, and a run of another kind has no such keys at all."""
    _, run = rehearsed
    readers = {n: cells.load_module(os.path.join(BENCH, "layer_metrics", n + ".py"),
                                    "lfm2_reader_" + n) for n in NEW}
    bare = {"counters": {"start": {}, "end": {}}, "traffic": run["traffic"],
            "config": {"hidden_size": 64}, "snapshots": run["snapshots"],
            "records": run["records"], "chips": 1, "peaks": run["peaks"],
            "trace": None, "cell": "no-such-cell"}
    assert all(r.read(bare) is None for r in readers.values())
    assert all(r.read({"counters": None, "cell": "no-such-cell"}) is None
               for r in readers.values())
    # and on the chip's kind of trace they read what the tables hold
    traced = dict(run, moe_trace={"kernel": [
        {"m": 4 * 2, "k": 64, "n": 32, "events": 10.0, "seconds": 1e-3}]},
        attn_trace={"global": {"events": 5.0, "seconds": 1e-4},
                    "window": {"events": 0.0, "seconds": 0.0}},
        scope_trace={"by_scope": {"decode/attn/attn.conv/attn.conv.mix": 1.0,
                                  "decode/attn/attn.conv/attn.write": 0.5,
                                  "decode/attn/attn.qkv": 0.5,
                                  "decode/mlp/moe.experts": 8.0}})
    assert readers["lfm2_gmm_roofline"].read(traced) > 0
    assert readers["lfm2_paged_attn_roofline"].read(traced) > 0
    assert readers["conv_layer_share"].read(traced) == pytest.approx(15.0)


def test_the_comparison_can_fail():
    """tools/state_control.py at the rehearsal's size: the sound readings
    pass; the model without the bias, the model without the q/k norms, a
    state zeroed at every piece and a state not reset on re-use are each
    refused where they must be."""
    tool = cells.load_module(os.path.join(BENCH, "tools", "state_control.py"),
                             "bench_tool_state_control")
    out = os.path.join(os.path.dirname(BENCH), "chiprun_out")
    rc = tool.main(["serve-tiny-lfm2", "5", REHEARSAL])
    lines = json.load(open(os.path.join(
        out, "state_control_serve-tiny-lfm2_5.json")))
    by = {(ln["control"], ln["verdict"]): ln["ok"] for ln in lines}
    assert all(by[("sound", v)] for v in ("long", "short", "carry", "reuse"))
    for control in ("no_bias", "no_qk_norm"):
        assert not by[(control, "long")], control
    assert not by[("state_zeroed_at_every_piece", "carry")]
    assert not by[("state_not_reset_on_reuse", "reuse")]
    # (whichever request takes the row the warm-up used fails with it: only
    # a row nobody used before is indifferent to a reset that never comes)
    assert not any(ln["a_reading"] for ln in lines)
    # (float8 at these widths, float32 weights and a dozen tokens is no
    # reading either way; the chip's is in PERF.md)
    assert rc in (0, 1)
