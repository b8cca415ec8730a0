"""The A.X-K1 configuration and its cell: published widths, the `serve_ref`
driver end to end at a tiny size on the CPU (steered by rehearsal/
cells_axk1.json), its refusal of a program without the model, and the new
readers on a run they can and a run they cannot read."""

import json
import os
import time

import pytest

import run as bench
from harness import cell as cells

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REHEARSAL = os.path.join(HERE, "rehearsal", "cells_axk1.json")
MAIN = os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = ("axk1_decode_step_ms", "axk1_decode_roofline", "kv_bytes_per_token",
       "routed_here_frac", "axk1_gmm_roofline")


def test_widths_are_the_published_ones():
    c = json.load(open(os.path.join(BENCH, "configs", "axk1-ep16.json")))
    published = {
        "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
        "hidden_act": "silu", "hidden_size": 7168, "intermediate_size": 18432,
        "kv_lora_rank": 512, "max_position_embeddings": 131072,
        "model_type": "axk1", "moe_intermediate_size": 2048,
        "moe_layer_freq": 1, "n_group": 8, "n_routed_experts": 192,
        "n_shared_experts": 1, "norm_topk_prob": True,
        "num_attention_heads": 64, "num_experts_per_tok": 8,
        "num_hidden_layers": 61, "num_key_value_heads": 64,
        "q_lora_rank": 1536, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "rms_norm_eps": 1e-06, "rope_theta": 10000,
        "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 32,
                         "mscale": 1, "mscale_all_dim": 1,
                         "original_max_position_embeddings": 4096,
                         "type": "yarn"},
        "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
        "seq_aux": True, "tie_word_embeddings": False, "topk_group": 4,
        "topk_method": "none", "v_head_dim": 128, "vocab_size": 163840}
    if os.path.exists(CATALOG):     # the catalog's row, where the guide is
        rows = [json.loads(line) for line in open(CATALOG)]
        row = next(r for r in rows if r["name"] == "A.X-K1")
        assert row["config"] == published and row["source_url"] == c["source"]
    differs = [k for k, v in published.items() if c.get(k, "missing") != v]
    assert differs == ["num_hidden_layers", "vocab_size"]
    assert c["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    # the floors: the dense layer + at least 4 expert layers, 8 experts,
    # an eighth of the vocabulary; what is held of the router's 192
    assert c["num_hidden_layers"] in (6, 7) and c["vocab_size"] * 8 == 163840
    assert (c["n_routed_experts_held"], c["n_routed_experts_offset"]) == (12, 0)
    assert c["published"] == {"num_hidden_layers": 61, "n_routed_experts": 192,
                              "vocab_size": 163840}
    assert c["reference"] == "reference_axk1" and c["chips"] == 1
    assert {"dtype", "weights", "topk_method", "yarn", "softmax_scale",
            "rope_pairs", "head_dim", "lora"} <= set(c["assumed"])
    assert "16 chips share each layer" in c["deployment"]


def test_the_cell_is_the_issues():
    cell = cells.load_cell(MAIN, "serve-axk1-docqa")
    assert cell.kind == "serve_ref" and cell.chips == 1
    mix, e = cell.traffic, cell.traffic["engine"]
    assert (mix["tenants"], mix["tenant_prompt_len"], mix["tenant_frac"]) == (8, 4096, 0.75)
    assert mix["tenant_turn"] == {"median": 128, "sigma": 0.6, "min": 16, "max": 512}
    assert mix["prompt_len"] == {"median": 2048, "sigma": 0.8, "min": 512, "max": 8192}
    assert mix["max_tokens"] == {"median": 128, "sigma": 0.7, "min": 16, "max": 512}
    assert e == {"rows": 32, "page_size": 128, "prompt_len": 8192,
                 "max_new_tokens": 512, "max_queue": 256, "headroom": 0.25,
                 "sync_every": 4, "prefill_chunk": 1024}
    # the issue's prompts, judged alone on 2,048 tokens (512 a row where
    # the issue had 48: drivers/serve_ref.py says why), and 28 rows at once
    assert mix["greedy_check"] == {"cold_lengths": [600, 3000],
                                   "shared_prefix": 4096, "turn": 144,
                                   "max_tokens": 512, "short_rows": 28,
                                   "short_len": 96, "short_max_tokens": 48}
    assert mix["rate_rps"] == pytest.approx(0.8 * mix["knee_rps"])
    assert mix["knee_sweep"]["rows"] and "schedule_seed" in mix
    chat = cells.load_cell(MAIN, "serve-1.5b-chat")
    shared = {m["name"] for m in chat.per_layer} & {m["name"] for m in cell.per_layer}
    assert {"prefix_hit_frac", "row_occupancy", "chunk_ms", "admit_ms",
            "queue_wait_ms", "peak_hbm_gb", "window_compiles"} <= shared
    assert set(NEW) <= {m["name"] for m in cell.per_layer}
    assert not set(NEW) & {m["name"] for m in chat.per_layer}
    assert {m["name"] for m in cell.end_to_end} == {"tokens_per_s", "tpot_p95_ms",
                                                    "setup_s"}


def test_serve_ref_cell_rehearses(tmp_path):
    # (4.5 s: the traced second starts 3 s into the window)
    line = bench.run_cell(REHEARSAL, "serve-tiny-axk1", 3, 4.5, True,
                          require_tpu=False, out_root=str(tmp_path),
                          t_process_start=time.time())
    assert line["correct"], line
    assert line["failed"] == 0 and line["attempted"] >= 3
    # (the CPU's trace has no `%gmm` kernel, so no `axk1_gmm_roofline` here;
    # and tiny requests end between two snapshots: no live rows, no roofline)
    assert {"axk1_decode_step_ms", "kv_bytes_per_token",
            "routed_here_frac", "prefix_hit_frac", "chunk_ms",
            "window_compiles"} <= set(line["metrics"])
    assert line["metrics"]["kv_bytes_per_token"]["value"] == 3 * 40 * 4
    assert 5.0 < line["metrics"]["routed_here_frac"]["value"] < 60.0
    assert line["metrics"]["axk1_decode_step_ms"]["value"] > 0
    saved = json.load(open(tmp_path / "serve-tiny-axk1" / "run.json"))
    run = saved["run"]
    assert run["kind"] == "serve_ref"
    assert run["moe"]["moe/dropped_tokens"] == 0
    assert run["moe"]["moe/held_experts"] == 4
    assert run["moe"]["moe/absent_assignments"] > 0
    # counted on the device: a live row reaches at most its 4 assignments'
    # worth of the 4 held experts, and three tiny rows mostly fewer
    end, begin = run["counters"]["end"], run["counters"]["start"]
    steps = end["serving/decode_steps"] - begin["serving/decode_steps"]
    hit = end["serving/held_experts_hit"] - begin["serving/held_experts_hit"]
    assert steps > 0 and run["moe"]["moe/held_experts_hit"] == hit / (steps * 2)
    assert 0 < run["moe"]["moe/held_experts_hit"] <= 4
    assert 0 <= run["moe"]["moe/held_experts_hit_traced"] <= 4
    assert end["serving/latent_cache"] == 1
    check = run["greedy_check"]
    assert check["radix_hit_tokens"] >= 15 and check["chunked_admissions"] >= 1
    # float32: exact, on the long rows (4 x 6 tokens) and on the short (3 x 4)
    assert (check["tokens"], check["flips"]) == (24, 0)
    assert (check["short"]["tokens"], check["short"]["flips"]) == (12, 0)
    assert run["compile"]["window"]["compiles"] == 0


@pytest.mark.parametrize("before, after, want", [
    ({"serving/decode_steps": 10, "serving/held_experts_hit": 7},
     {"serving/decode_steps": 110, "serving/held_experts_hit": 907}, 1.5),
    ({"serving/decode_steps": 10, "serving/held_experts_hit": 7},
     {"serving/decode_steps": 10, "serving/held_experts_hit": 7}, None),
    ({"serving/loop_beats": 1}, {"serving/loop_beats": 2}, None),
], ids=["counted", "no_step", "no_counter"])
def test_experts_hit_a_step_is_the_counters_quotient(before, after, want):
    """900 kernels reached in 100 steps of the full cell's 6 expert layers."""
    cell = cells.load_cell(MAIN, "serve-axk1-docqa")
    driver = cells.load_driver(cell)
    assert driver.experts_hit_a_step(cell.config, before, after) == want


def test_a_program_without_the_model_is_refused_before_it_builds(monkeypatch, capsys):
    """What the parent commit does with this configuration: `from_hf_config`
    raises on the expert keys of a family it does not know; and a program
    that dropped the latent keys would build another model."""
    import dataclasses

    from harness import model

    cell = cells.load_cell(REHEARSAL, "serve-tiny-axk1")
    driver = cells.load_driver(cell)

    def raises(config, attention_impl="auto"):
        raise ValueError("model_type='axk1' with expert keys [...]")

    monkeypatch.setattr(model, "model_config", raises)
    with pytest.raises(SystemExit) as e:
        driver.run(cell, {"seed": 0})
    assert e.value.code == 4 and "Nothing was built" in capsys.readouterr().err
    monkeypatch.undo()
    real = model.model_config

    def dense(config, attention_impl="auto"):
        return dataclasses.replace(real(config, attention_impl), kv_lora_rank=0)

    monkeypatch.setattr(model, "model_config", dense)
    with pytest.raises(SystemExit):
        driver.refuse_a_program_without_the_model(cell)


def test_the_new_readers_return_nothing_where_there_is_nothing_to_read():
    """As on a program without the counters (the parent), or a dense cell."""
    cell = cells.load_cell(MAIN, "serve-axk1-docqa")
    empty = {"config": {"hidden_size": 8}, "traffic": cell.traffic,
             "counters": {"start": {}, "end": {}}, "records": [], "chips": 1}
    for name in NEW:
        path = cells.find_under_paths(cell.root, cell.paths, "layer_metrics",
                                      name + ".py")
        assert cells.load_module(path, "m_" + name).read(empty) is None, name
        assert cells.load_module(path, "m_" + name).read(
            {"config": {}, "traffic": cell.traffic}) is None, name


def test_the_step_and_its_roofline_from_a_runs_counters():
    cell = cells.load_cell(MAIN, "serve-axk1-docqa")
    read = lambda name, run: cells.load_module(cells.find_under_paths(   # noqa: E731
        cell.root, cell.paths, "layer_metrics", name + ".py"), "r_" + name).read(run)
    counters = lambda step, beats: {"serving/loop_step_s": step,         # noqa: E731
                                    "serving/loop_beats": beats,
                                    "serving/latent_cache": 1,
                                    "serving/kv_bytes_per_token": 8064}
    run = {"config": cell.config, "traffic": cell.traffic, "chips": 1,
           "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
           "counters": {"start": counters(10.0, 100), "end": counters(50.0, 600)},
           "snapshots": [{"active": 30, "pending": 0}, {"active": 34, "pending": 2}],
           "records": [{"status": "ok", "n": 100, "prompt_len": 3950},
                       {"status": "shed:queue_full", "n": 0, "prompt_len": 9}],
           "moe": {"moe/held_experts_hit": 8.927}}
    # 40 s over 500 beats of 4 steps: 20 ms a step
    assert read("axk1_decode_step_ms", run) == pytest.approx(20.0)
    assert read("kv_bytes_per_token", run) == 8064
    # 32 live rows at 3950 + 50 tokens: test_ops_bytes_axk1's 10.7 ms floor
    assert read("axk1_decode_roofline", run) == pytest.approx(100 * 10.7 / 20, rel=0.02)
    # decode calls (32 rows x 8) go by the kernels the live rows reached in
    # the traced seconds, as counted; any other shape by its own tokens
    run["moe_trace"] = {"kernel": [{"m": 256, "k": 7168, "n": 2048,
                                    "events": 10.0, "seconds": 10 * 0.64e-3}]}
    assert read("axk1_gmm_roofline", run) is None       # no count of the trace
    run["moe"]["moe/held_experts_hit_traced"] = 8.927
    assert read("axk1_gmm_roofline", run) == pytest.approx(50.0, rel=0.03)
    run["moe"]["moe/held_experts_hit_traced"] = 2.09
    assert read("axk1_gmm_roofline", run) == pytest.approx(
        100 * 0.075 / 0.64, rel=0.05)
    # ... and the step's roofline needs the window's count
    del run["moe"]["moe/held_experts_hit"]
    assert read("axk1_decode_roofline", run) is None
