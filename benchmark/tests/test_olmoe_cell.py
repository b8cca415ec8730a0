"""The OLMoE configuration and its cell: published widths, the `rl_ref`
driver end to end at a tiny size on the CPU (steered by rehearsal/
cells_moe.json), its refusal of a program without the experts, and the
`moe.*` reduction of a device trace."""

import json
import os
import time

import pytest

import run as bench
from harness import cell as cells
from harness import moe_trace

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REHEARSAL = os.path.join(HERE, "rehearsal", "cells_moe.json")
MAIN = os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")


def test_widths_are_the_published_ones():
    c = json.load(open(os.path.join(BENCH, "configs", "olmoe-1b-7b.json")))
    published = {"attention_bias": False, "clip_qkv": None, "hidden_act": "silu",
                 "hidden_size": 2048, "intermediate_size": 1024,
                 "max_position_embeddings": 4096, "model_type": "olmoe",
                 "norm_topk_prob": False, "num_attention_heads": 16,
                 "num_experts": 64, "num_experts_per_tok": 8,
                 "num_hidden_layers": 16, "num_key_value_heads": 16,
                 "rms_norm_eps": 1e-05, "rope_scaling": None,
                 "rope_theta": 10000, "tie_word_embeddings": False,
                 "vocab_size": 50304}
    differs = [k for k, v in published.items() if c.get(k, "missing") != v]
    assert differs == c["reduced"] == ["num_hidden_layers"]
    assert c["num_hidden_layers"] in (4, 5, 6)
    assert c["reference"] == "reference_olmoe" and c["chips"] == 1
    assert {"dtype", "weights", "head_dim", "lora"} <= set(c["assumed"])
    assert "deployment" in c and c["source"].endswith(
        "OLMoE-1B-7B-0125-Instruct/blob/main/config.json")


def test_the_cell_is_grpo_r512_on_another_model():
    cell = cells.load_cell(MAIN, "grpo-olmoe-r512")
    dense = cells.load_cell(MAIN, "grpo-1.5b-r512")
    assert cell.kind == "rl_ref" and cell.chips == 1
    same = ("algo", "prompts", "sample_n", "per_device_train_batch_size",
            "gradient_accumulation_steps", "num_mini_batches",
            "dataset_prompts", "prompt_len_min", "prompt_len_max",
            "response_length", "temperature", "rollout_page_size",
            "rollout_decode_rows", "min_updates", "reference_rows")
    assert all(cell.traffic[k] == dense.traffic[k] for k in same)
    mine = {m["name"] for m in cell.per_layer}
    assert {"moe_decode_step_ms", "moe_decode_roofline", "moe_score_update_s",
            "expert_layer_share", "expert_load_max_over_mean", "gmm_roofline",
            "setup_compile_s", "window_compiles", "peak_hbm_gb"} == mine
    assert not mine & {m["name"] for m in dense.per_layer
                       if "workloads" in m}


def test_rl_ref_cell_rehearses(tmp_path):
    def rehearse(trace):
        return bench.run_cell(REHEARSAL, "rl-tiny-moe", 3, 2.0, trace,
                              require_tpu=False, out_root=str(tmp_path),
                              t_process_start=time.time())

    line = rehearse(False)
    assert line["correct"] and line["failed"] == 0 and line["attempted"] >= 1
    assert set(line["metrics"]) == {"tokens_per_s", "setup_s"}
    traced = rehearse(True)
    # (the CPU's trace has no TPU plane, so no `expert_layer_share` here)
    assert {"moe_decode_step_ms", "moe_decode_roofline", "moe_score_update_s",
            "expert_load_max_over_mean", "window_compiles"} <= set(traced["metrics"])
    assert 1.0 <= traced["metrics"]["expert_load_max_over_mean"]["value"] <= 8.0
    saved = json.load(open(tmp_path / "rl-tiny-moe" / "run.json"))
    assert saved["run"]["kind"] == "rl_ref"
    # eos_unreachable: every row of every update ran its whole budget
    assert set(saved["run"]["tokens"]) == {4 * 2 * 16}
    assert all(r["moe/dropped_tokens"] == 0 for r in saved["run"]["rows"])
    # float32 on the CPU: the program's scorer and the plain reference agree
    assert saved["run"]["logprobs"]["tested_vs_float32"]["max_abs"] < 1e-4


def test_a_program_without_the_experts_is_refused_before_it_builds(monkeypatch):
    """What the parent commit does with this configuration: `from_hf_config`
    drops the expert keys and builds a dense model of the expert's width."""
    import dataclasses

    from harness import model

    cell = cells.load_cell(REHEARSAL, "rl-tiny-moe")
    driver = cells.load_driver(cell)
    dense = dataclasses.replace(model.model_config(cell.config), num_experts=0)
    monkeypatch.setattr(driver.model, "model_config", lambda *a, **k: dense)
    monkeypatch.setattr(driver.model, "init_weights",
                        lambda *a, **k: pytest.fail("weights were built"))
    with pytest.raises(SystemExit) as stop:
        driver.run(cell, {"seed": 1, "seconds": 1.0, "trace": False,
                          "out_dir": "unused", "t_process_start": time.time()})
    assert stop.value.code not in (0, None)


class _Event:
    def __init__(self, name, start, dur):
        self.name, self.start_ns, self.duration_ns = name, start, dur
        self.stats = [("device_offset_ps", "0")]


class _Line:
    def __init__(self, name, events):
        self.name, self.events = name, events


class _Plane:
    def __init__(self, name, lines):
        self.name, self.lines = name, lines


ROWS = "%fusion.587 = bf16[512,2048]{1,0:T(8,128)(2,1)} fusion(bf16[64,1,2048]{2,0,1} %x, s32[512]{0} %sort.3), kind=kLoop"
GATE = ("%gmm.24 = bf16[512,1024]{1,0:T(8,128)(2,1)S(1)} custom-call(s32[]{:T(128)} %gte.1, "
        "bf16[512,2048]{1,0:T(8,128)(2,1)} %fusion.587, bf16[256,2048,1024]{2,1,0} %bitcast.561), "
        'custom_call_target="tpu_custom_call"')
UP = GATE.replace("%gmm.24", "%gmm.25").replace("%bitcast.561", "%bitcast.562")
SWIGLU = "%fusion.590 = bf16[512,1024]{1,0} fusion(bf16[512,1024]{1,0} %gmm.24, bf16[512,1024]{1,0} %gmm.25), kind=kLoop"
DOWN = ("%gmm.26 = bf16[512,2048]{1,0} custom-call(s32[]{:T(128)} %gte.1, bf16[512,1024]{1,0} %fusion.590, "
        'bf16[256,1024,2048]{2,1,0} %bitcast.563), custom_call_target="tpu_custom_call"')
COMBINE = "%fusion.595 = bf16[64,1,2048]{2,0,1} fusion(bf16[512,2048]{1,0} %gmm.26, f32[64,8]{1,0} %w), kind=kCustom"
HEAD = "%fusion.9 = f32[64,50304]{1,0} fusion(bf16[64,1,2048]{2,0,1} %fusion.595), kind=kOutput"


def test_the_expert_layer_is_read_from_the_hlo_lines_dataflow():
    ops = _Line("XLA Ops", [
        _Event("%while.1 = (s32[]) while((s32[]) %t), body=%b", 0, 20_000),
        _Event(ROWS, 1_000, 500), _Event(GATE, 2_000, 4_000),        # nested
        _Event(UP, 6_000, 4_000), _Event(SWIGLU, 10_000, 200),
        _Event(DOWN, 11_000, 3_000), _Event(COMBINE, 14_000, 800),
        _Event(HEAD, 15_000, 2_000),
        # another module reuses a name: not this module's grouped matmul's feed
        _Event("%fusion.587 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop", 30_000, 700),
    ])
    modules = _Line("XLA Modules", [_Event("jit_generate_tokens(1)", 0, 20_000),
                                    _Event("jit_update(2)", 29_000, 5_000)])

    class Data:
        planes = [_Plane("/device:TPU:0", [ops, modules]),
                  _Plane("/host:CPU", [_Line("python", [_Event("%gmm.1 = x", 0, 9)])])]
    got = moe_trace.scope_seconds_of(Data())
    assert got["by_scope"] == pytest.approx({
        "moe.experts": 11.2e-6, "moe.dispatch": 0.5e-6, "moe.combine": 0.8e-6})
    assert got["moe_s"] == pytest.approx(12.5e-6)
    assert got["ops"][0][0].startswith("moe.experts %gmm.2")
    assert got["kernel"] == [
        {"m": 512, "k": 1024, "n": 2048, "events": 1, "seconds": pytest.approx(3e-6)},
        {"m": 512, "k": 2048, "n": 1024, "events": 2, "seconds": pytest.approx(8e-6)}]
    xla = moe_trace.scope_seconds_of(type("D", (), {"planes": [_Plane(
        "/device:TPU:0", [_Line("XLA Ops", [_Event(
            "%ragged-dot-none.3 = bf16[512,1024]{1,0} custom-call(s32[1]{0} %m, "
            "bf16[512,2048]{1,0} %fusion.1, bf16[256,2048,1024]{2,1,0} %b)", 0, 900)])])]})())
    assert xla["by_scope"] == pytest.approx({"moe.experts": 0.9e-6}) and xla["kernel"] == []


def test_a_trace_without_a_grouped_matmul_reads_as_nothing():
    class Data:
        planes = [_Plane("/device:TPU:0", [_Line("XLA Ops", [
            _Event("%fusion.1 = bf16[8]{0} fusion(bf16[8]{0} %p)", 0, 100)])])]
    got = moe_trace.scope_seconds_of(Data())
    assert got["moe_s"] == 0 and got["by_scope"] == {} and got["kernel"] == []
    reader = cells.load_module(os.path.join(
        BENCH, "layer_metrics", "expert_layer_share.py"), "els")
    assert reader.read({"trace": {"busy_s": 1.0}, "moe_trace": got}) is None
    assert reader.read({"trace": None}) is None
    assert reader.read({"trace": {"busy_s": 2.0},
                        "moe_trace": {"moe_s": 1.0}}) == pytest.approx(50.0)
    roof = cells.load_module(os.path.join(
        BENCH, "layer_metrics", "gmm_roofline.py"), "gr")
    assert roof.read({"moe_trace": got}) is None and roof.read({}) is None
    cfg = json.load(open(os.path.join(BENCH, "configs", "olmoe-1b-7b.json")))
    run = {"config": cfg, "peaks": {"bf16_flops_per_s": 197e12,
                                    "hbm_bytes_per_s": 819e9},
           "moe_trace": {"kernel": [{"m": 512, "k": 2048, "n": 1024,
                                     "events": 100, "seconds": 100 * 0.4e-3}]}}
    assert roof.read(run) == pytest.approx(100 * 0.3316 / 0.4, rel=2e-3)
