"""The Granite 4.0-H configuration and its cell: published widths and the
chip's share, the cell's traffic as ISSUE 59 gives it, the `serve_mamba_ref`
driver end to end at a tiny size on the CPU (steered by
rehearsal/cells_granite_h.json), its refusal of a program without the model,
the comparison's controls, and the new readers on a run they can and a run
they cannot read."""

import json
import os
import time

import pytest

import run as bench
from harness import cell as cells
from harness import ops_bytes_granite_h as ob

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REHEARSAL = os.path.join(HERE, "rehearsal", "cells_granite_h.json")
MAIN = os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CELL, TINY = "serve-granite-h-ragchat", "serve-tiny-granite-h"
NEW = ("gh_decode_step_ms", "gh_decode_roofline", "gh_ssm_update_roofline",
       "gh_ssd_scan_roofline", "gh_gmm_roofline", "gh_paged_attn_roofline",
       "state_live_bytes_frac")
REDUCED = ["layer_types", "num_hidden_layers", "num_local_experts",
           "vocab_size"]


def the_file():
    return json.load(open(os.path.join(
        BENCH, "configs", "granite-4.0-h-small-ep2-l10.json")))


def test_widths_are_the_published_ones_and_the_cut_is_the_issues():
    c = the_file()
    assert sorted(c["reduced"]) == REDUCED
    assert c["published"] == {
        "num_hidden_layers": 40, "num_local_experts": 72, "vocab_size": 100352,
        "layer_types": (["mamba"] * 5 + ["attention"] + ["mamba"] * 4) * 4}
    # a whole period, half of the experts and of the vocabulary: the floors
    assert c["layer_types"] == c["published"]["layer_types"][:10]
    assert (c["num_hidden_layers"], c["vocab_size"]) == (10, 50176)
    assert (c["num_local_experts"], c["num_experts_held"],
            c["num_experts_offset"]) == (72, 36, 0)
    assert c["reference"] == "reference_granite_h" and c["chips"] == 1
    assert {"dtype", "state_dtype", "in_proj_order", "mixer", "experts",
            "attention", "multipliers", "hf_names", "weights", "init"} \
        <= set(c["assumed"])
    for said in ("v5e-8", "TWO chips", "experts 0-35", "WITHOUT the exchange",
                 "HALF"):
        assert said in c["deployment"], said
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    rows = [json.loads(line) for line in open(CATALOG)]
    row = next(r for r in rows if r["name"] == "granite-4.0-h-small")
    assert row["source_url"] == c["source"]
    differs = sorted(k for k, v in row["config"].items()
                     if c.get(k, "missing") != v)
    # (the experts' key keeps the router's width, as Trinity's file does; the
    # share is `num_experts_held`)
    assert differs == [k for k in REDUCED if k != "num_local_experts"]


def test_the_cell_is_the_issues():
    cell = cells.load_cell(MAIN, CELL)
    assert cell.kind == "serve_mamba_ref" and cell.chips == 1
    assert cell.traffic_name == "ragchat-steady"
    assert cell.config_name == "granite-4.0-h-small-ep2-l10"
    mix = cell.traffic
    assert mix["engine"] == {"rows": 48, "page_size": 128, "prompt_len": 8192,
                             "max_new_tokens": 768, "max_queue": 512,
                             "headroom": 0.0, "sync_every": 4,
                             "prefill_chunk": 1024}
    rag, turn = mix["classes"]
    assert (rag["name"], rag["share"], turn["name"], turn["share"]) \
        == ("rag", 0.4, "turn", 0.6)
    assert rag["prompt_len"] == {"median": 4096, "sigma": 0.5, "min": 2048,
                                 "max": 8192}
    assert rag["max_tokens"] == {"median": 192, "sigma": 0.6, "min": 32,
                                 "max": 512}
    assert turn["prompt_len"] == {"median": 512, "sigma": 0.8, "min": 32,
                                  "max": 2048}
    assert mix["max_tokens"] == {"median": 192, "sigma": 0.7, "min": 32,
                                 "max": 768}
    assert mix["tenants"] == 0 and mix["arrival"] == "poisson"
    assert mix["sampling"] == {"greedy_frac": 0.5, "temperature": [0.7, 1.0],
                               "top_p": [0.9, 1.0]}
    assert mix["eos_unreachable"] and "schedule_seed" in mix
    assert 0.5 <= mix["rate_rps"] / mix["knee_rps"] <= 0.8
    assert mix["knee_sweep"]["rows"]
    chk, chunk = mix["greedy_check"], mix["engine"]["prefill_chunk"]
    # whole pieces and a last one of a few tokens: the state carried each time
    assert chk["long_len"] // chunk >= 3 and 0 < chk["long_len"] % chunk <= 8
    assert chk["short_rows"] < mix["engine"]["rows"]    # beside the long row
    assert set(NEW) <= {m["name"] for m in cell.per_layer}
    bench_file = cells.load_benchmark(MAIN)
    assert len(bench_file["workloads"]) == 13
    assert sum(w["chips"] == 4 for w in bench_file["workloads"]) == 1
    for w in bench_file["workloads"]:
        if w["name"] != CELL:
            other = cells.load_cell(MAIN, w["name"])
            assert not set(NEW) & {m["name"] for m in other.per_layer}
    assert {m["name"] for m in cell.end_to_end} == {"tokens_per_s", "tpot_p95_ms",
                                                    "setup_s"}
    assert {"row_occupancy", "chunk_ms", "admit_ms", "queue_wait_ms",
            "peak_hbm_gb", "window_compiles", "kv_bytes_per_token",
            "state_bytes_per_row", "state_carry_frac", "ssm_layer_share",
            "expert_layer_share", "routed_here_frac", "held_experts_hit_frac",
            "decode_attn_share", "scoped_share", "prefill_device_ms",
            "sample_rows_frac", "slow_tpot_ms"} <= {
                m["name"] for m in cell.per_layer}


def test_a_program_without_the_model_is_refused(monkeypatch, capsys):
    from drivers import serve_mamba_ref
    from nanorlhf_tpu.core import ModelConfig

    cell = cells.load_cell(REHEARSAL, TINY)
    serve_mamba_ref.refuse_a_program_without_the_model(cell)    # this program
    dense = classmethod(lambda cls, hf: ModelConfig.qwen2_tiny())
    monkeypatch.setattr(ModelConfig, "from_hf_config", dense)
    with pytest.raises(SystemExit) as e:
        serve_mamba_ref.refuse_a_program_without_the_model(cell)
    assert e.value.code == 4
    assert "not a model this program builds" in capsys.readouterr().err

    def raises(cls, hf):    # the parent of PR 59 on these keys
        raise ValueError("model_type='granitemoehybrid' with expert keys")

    monkeypatch.setattr(ModelConfig, "from_hf_config", classmethod(raises))
    with pytest.raises(SystemExit):
        serve_mamba_ref.refuse_a_program_without_the_model(cell)


@pytest.fixture(scope="module")
def rehearsed(tmp_path_factory):
    out = tmp_path_factory.mktemp("granite_h")
    # (4.5 s: the traced second starts 3 s into the window)
    line = bench.run_cell(REHEARSAL, TINY, 2**31 + 9, 4.5, True,
                          require_tpu=False, out_root=str(out),
                          t_process_start=time.time())
    return line, json.load(open(out / TINY / "run.json"))["run"]


def test_serve_mamba_ref_cell_rehearses(rehearsed):
    line, run = rehearsed
    assert line["correct"], line
    assert line["failed"] == 0 and line["attempted"] >= 10
    # (the CPU's trace has no kernel and no device plane: no device roofline)
    assert {"gh_decode_step_ms", "state_live_bytes_frac", "state_bytes_per_row",
            "state_carry_frac", "kv_bytes_per_token", "routed_here_frac",
            "held_experts_hit_frac", "chunk_ms", "row_occupancy",
            "window_compiles"} <= set(line["metrics"])
    assert not {"gh_paged_attn_roofline", "gh_ssm_update_roofline",
                "gh_ssd_scan_roofline", "gh_gmm_roofline",
                "gh_decode_roofline"} & set(line["metrics"])
    value = lambda name: line["metrics"][name]["value"]         # noqa: E731
    assert value("window_compiles") == 0
    # six mixer layers, each a tail 3 x 80 and a state 4 x 16 x 8, float32
    # here; the pages of the two attention layers alone
    assert value("state_bytes_per_row") == 6 * (240 + 512) * 4
    assert value("kv_bytes_per_token") == 2 * 2 * 2 * 16 * 4
    assert 0 < value("state_carry_frac") < 100
    assert 50 < value("state_live_bytes_frac") < 100
    assert 30 < value("routed_here_frac") < 70          # 4 of 8 held
    assert 0 < value("held_experts_hit_frac") <= 100
    assert run["kind"] == "serve_mamba_ref"
    # both classes were drawn into the one stream
    assert {r["tenant"] for r in run["records"]} == {-1, -2}
    g = run["greedy_check"]
    assert g["state_carries"] >= 3 + 6 and g["prefix_hit_tokens"] == 0
    assert g["state_resets"] == 1 + 2 + 4 + 4
    assert g["moe"]["moe/dropped_tokens"] == 0
    for name in ("long", "carry"):
        state = g["state"][name]
        assert state["ok"] and state["first_layer_slow"] < 1e-5, state
        assert len(state["layer_max"]) == 6 and max(state["layer_max"]) < 1e-5
        assert state["next_row"] > 0.5 and state["state_dtype"] == "float32"
    end = run["counters"]["end"]
    assert (end["serving/state_layers"], end["serving/page_layers"]) == (6, 2)
    assert end["serving/prefix_hit_tokens"] == 0
    assert len(run["traced_counters"]) == 2


def test_new_readers_read_nothing_from_another_program(rehearsed):
    """The parent of PR 59 and every other model: another `model_type`, no
    such counters; and on the chip's kind of trace they read what the tables
    hold."""
    _, run = rehearsed
    readers = {n: cells.load_module(os.path.join(BENCH, "layer_metrics", n + ".py"),
                                    "gh_reader_" + n) for n in NEW}
    bare = {"counters": {"start": {}, "end": {}}, "traffic": run["traffic"],
            "config": {"hidden_size": 64, "model_type": "falcon_h1"},
            "snapshots": run["snapshots"], "records": run["records"],
            "chips": 1, "peaks": run["peaks"], "trace": None,
            "cell": "no-such-cell"}
    assert all(r.read(bare) is None for r in readers.values())
    assert all(r.read({"counters": None, "cell": "no-such-cell"}) is None
               for r in readers.values())
    counters = [dict(run["counters"]["start"]), dict(run["counters"]["start"])]
    for key, gain in (("serving/decode_steps", 40), ("serving/live_row_steps", 100),
                      ("serving/global_slots_read", 2000),
                      ("serving/held_experts_hit", 40 * 8 * 3),
                      ("serving/state_tokens", 64), ("serving/state_resets", 4),
                      ("serving/state_piece_carries", 6)):
        counters[1][key] = counters[0].get(key, 0) + gain
    rows = run["traffic"]["engine"]["rows"]
    cfg, peaks = run["config"], run["peaks"]
    k = cfg["num_experts_per_tok"]
    traced = dict(run, traced_counters=counters,
                  attn_trace={"global": {"events": 80.0, "seconds": 1e-3},
                              "window": {"events": 0.0, "seconds": 0.0}},
                  moe_trace={"moe_s": 1.0, "kernel": [
                      {"m": rows * k, "k": 64, "n": 32, "events": 10.0,
                       "seconds": 1e-3}]},
                  scope_trace={"steps": 40.0, "by_scope": {
                      "decode/attn/attn.ssm/attn.ssm.update": 1.0,
                      "decode/attn/attn.ssm/attn.write": 0.5,
                      "decode/attn/attn.ssm/attn.ssm.in": 0.5,
                      "decode/mlp": 7.0,
                      "prefill/attn/attn.ssm/attn.ssm.scan": 0.25}})
    assert readers["gh_ssm_update_roofline"].read(traced) == pytest.approx(
        100 * 40 * 6 * ob.ssm_update_bytes(cfg, rows=2.5, dtype_bytes=2)
        / peaks["hbm_bytes_per_s"] / 1.5)
    assert readers["gh_ssd_scan_roofline"].read(traced) == pytest.approx(
        100 * 6 * ob.ssd_scan_floor_s(cfg, peaks, tokens=64, pieces=10) / 0.25)
    assert readers["gh_paged_attn_roofline"].read(traced) == pytest.approx(
        100 * 80 * 50 * ob.kv_bytes_per_token_layer(cfg)
        / peaks["hbm_bytes_per_s"] / 1e-3)
    assert readers["gh_gmm_roofline"].read(traced) == pytest.approx(
        100 * 10 * ob.grouped_matmul_floor_s(
            cfg, peaks, m=rows * k, k=64, n=32, tokens=2.5, kernels=3.0) / 1e-3)
    # the step's share: its bytes over the device's seconds a step
    step = cells.load_module(os.path.join(
        BENCH, "layer_metrics", "decode_device_step_ms.py"), "gh_step_ms")
    floor = ob.decode_step_bytes(cfg, rows=2.5, slots=50, experts_hit=3.0)
    assert readers["gh_decode_roofline"].read(traced) == pytest.approx(
        100 * 1e3 * floor["total"] / peaks["hbm_bytes_per_s"]
        / step.read(traced))


def test_the_comparison_can_fail():
    """tools/mamba_control.py at the rehearsal's size: the sound readings
    pass; the model without its attention, without its shared expert, with
    rotary, with the scale 1 / sqrt(hd), without the residual multiplier on
    the mixture, without renormalising, with the norm before the gate, and a
    state not carried between pieces are each refused where they must be. A
    bfloat16 state reads a thousand times further from the reference's than
    the sound engine's (the limit itself belongs to the chip's bfloat16
    activations), and its bytes a row are not the file's."""
    tool = cells.load_module(os.path.join(BENCH, "tools", "mamba_control.py"),
                             "bench_tool_mamba_control")
    out = os.path.join(os.path.dirname(BENCH), "chiprun_out")
    rc = tool.main([TINY, "5", REHEARSAL])
    lines = json.load(open(os.path.join(out, f"mamba_control_{TINY}_5.json")))
    by = {(ln["control"], ln["verdict"]): ln["ok"] for ln in lines}
    assert all(by[("sound", v)] for v in ("long", "short", "carry", "reuse"))
    for control in tool.OTHER_MODELS:
        assert not by[(control, "long")], control
    assert not by[("state_not_carried", "carry")]
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
