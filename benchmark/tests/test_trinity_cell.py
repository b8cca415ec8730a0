"""The Trinity configuration and its cell: published widths, the byte
arithmetic of the cut pinned against hand counts, the `serve_reason_ref`
driver end to end at a tiny size on the CPU (steered by
rehearsal/cells_trinity.json), its refusal of a program without the model,
the comparison's negative controls, and the new readers on a run they can
and a run they cannot read."""

import json
import os
import time

import pytest

import run as bench
from harness import cell as cells
from harness import ops_bytes_trinity as ob

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REHEARSAL = os.path.join(HERE, "rehearsal", "cells_trinity.json")
MAIN = os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CELL = "serve-trinity-reason"
NEW = ("trinity_decode_roofline", "trinity_gmm_roofline",
       "trinity_paged_attn_roofline", "held_experts_hit_frac",
       "rows_past_window_frac", "attn_gate_share")


def the_file():
    return json.load(open(os.path.join(BENCH, "configs",
                                       "trinity-large-ep8-l5.json")))


def test_widths_are_the_published_ones():
    c = the_file()
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    rows = [json.loads(line) for line in open(CATALOG)]
    row = next(r for r in rows if r["name"] == "Trinity-Large-Preview")
    assert row["source_url"] == c["source"]
    differs = sorted(k for k, v in row["config"].items() if c.get(k, "missing") != v)
    # (num_experts is in `reduced` for what is HELD of it: the key stays 256)
    assert differs == ["layer_types", "num_dense_layers", "num_hidden_layers",
                       "vocab_size"]
    assert sorted(c["reduced"]) == sorted(differs + ["num_experts"])
    assert (c["num_experts"], c["num_experts_held"], c["num_experts_offset"]) == (
        256, 32, 0)
    assert c["num_hidden_layers"] == 5 and c["published"]["num_hidden_layers"] == 60
    # one dense window layer, then one whole published period
    assert c["layer_types"] == row["config"]["layer_types"][:1] + \
        row["config"]["layer_types"][8:12]
    assert c["vocab_size"] * 8 == row["config"]["vocab_size"]
    assert c["reference"] == "reference_trinity" and c["chips"] == 1
    assert {"dtype", "weights", "embed_scale", "attention_gate", "nope", "window",
            "norms", "router", "num_experts", "vocab_slice", "hf_names",
            "init"} <= set(c["assumed"])
    assert "8 chips share each layer" in c["deployment"]


def test_the_cuts_arithmetic():
    """Hand counts at the published widths (ISSUE 42): an expert 28,311,552
    parameters, a layer's share 998.0 M, 4,096 B a token a layer."""
    c = the_file()
    assert ob.expert_params(c) == 3 * 3072 * 3072 == 28_311_552
    assert ob.attention_params(c) == 3072 * (6144 + 1024 + 1024 + 6144) \
        + 6144 * 3072 == 62_914_560
    assert ob.gate_params(c) == 18_874_368
    # beside its routed experts: attention, shared expert, router (+ norms)
    beside = ob.expert_layer_params(c, experts=0)
    assert beside == 62_914_560 + 28_311_552 + 3072 * 256 + 256 + 4 * 3072 + 256
    assert round(beside / 1e6, 1) == 92.0
    assert round(ob.expert_layer_params(c) / 1e6, 1) == 998.0
    assert 32 * ob.expert_params(c) == 905_969_664
    assert ob.dense_layer_params(c) == 62_914_560 + 3 * 3072 * 12288 + 4 * 3072 + 256
    assert round(ob.dense_layer_params(c) / 1e6, 1) == 176.2
    assert round(ob.n_params(c) * 2 / 1e9, 2) == 8.64
    assert ob.kv_bytes_per_token_layer(c) == 4096
    w = ob.widths(c)
    assert (w["Lw"], w["Lg"], w["Ld"], w["Le"], w["held"]) == (4, 1, 1, 4, 32)
    # the pools the deployment states: a 128-token page 512 KiB a layer
    page = 128 * ob.kv_bytes_per_token_layer(c)
    assert page == 512 * 1024
    assert round((32 * 72 + 72) * page * 1 / 1e9, 2) == 1.25
    assert round(32 * 42 * page * 4 / 1e9, 2) == 2.82


def test_ops_and_bytes():
    c = the_file()
    b = ob.decode_step_bytes(c, rows=26, experts_hit=10.7, global_slots=60_000,
                             window_slots=50_000)
    assert b["experts"] == pytest.approx(4 * 10.7 * 28_311_552 * 2)
    assert b["kv"] == (1 * 60_000 + 4 * 50_000) * 4096
    assert b["head"] == (3072 * 25024 + 3072) * 2 + 26 * 25024 * 4
    assert b["dense_layers"] == ob.dense_layer_params(c) * 2
    assert b["total"] == sum(v for k, v in b.items() if k != "total")
    # ISSUE 42's estimate: 104 assignments over 256 reach 10.7 of the 32 held
    assert ob.held_experts_hit(c, 26) == pytest.approx(10.7, abs=0.1)
    assert ob.held_experts_hit(c, 1024) == pytest.approx(32.0, abs=1e-4)
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    step = ob.grouped_matmul_cost(c, m=128, k=3072, n=3072, tokens=26, kernels=10)
    rows = 26 * 4 * 32 / 256                # an eighth of the assignments
    assert step["bytes"] == (rows * 3072 + 10 * 3072 * 3072 + rows * 3072) * 2
    # a decode call is bound by its kernels' bytes, a piece's too at 1/8 rows
    assert ob.grouped_matmul_floor_s(c, peaks, m=128, k=3072, n=3072, tokens=26,
                                     kernels=10) == step["bytes"] / 819e9
    piece = ob.grouped_matmul_cost(c, m=4096, k=3072, n=3072)
    assert piece["flops"] / 197e12 < piece["bytes"] / 819e9


def test_the_cell_is_the_issues():
    cell = cells.load_cell(MAIN, CELL)
    assert cell.kind == "serve_reason_ref" and cell.chips == 1
    assert cell.config_name == "trinity-large-ep8-l5"
    assert cell.traffic_name == "reason-steady"
    mix = cell.traffic
    assert mix["engine"] == {"rows": 32, "page_size": 128, "prompt_len": 3072,
                             "max_new_tokens": 6144, "max_queue": 256,
                             "headroom": 0.0, "sync_every": 4,
                             "prefill_chunk": 1024}
    assert mix["tenants"] == 0 and mix["arrival"] == "poisson"
    assert mix["prompt_len"] == {"median": 768, "sigma": 0.7, "min": 64,
                                 "max": 3072}
    assert mix["max_tokens"] == {"median": 2048, "sigma": 0.6, "min": 256,
                                 "max": 6144}
    assert mix["sampling"] == {"greedy_frac": 0.5, "temperature": [0.7, 1.0],
                               "top_p": [0.9, 1.0]}
    assert mix["eos_unreachable"] and "schedule_seed" in mix
    assert mix["ramp_s"] >= 25.0
    assert 0.5 <= mix["rate_rps"] / mix["knee_rps"] <= 0.8
    chk, eng = mix["greedy_check"], mix["engine"]
    window = cell.config["sliding_window"]
    long_len, n_long = chk["long_lengths"][0], chk["long_max_tokens"]
    # three pieces, past the window while it decodes, and a ring that wraps
    # under decode: more blocks than window + chunk + 2 pages
    assert -(-long_len // eng["prefill_chunk"]) == 3
    assert long_len < window < long_len + n_long
    ring = (window + eng["prefill_chunk"]) // eng["page_size"] + 2
    first = (eng["prompt_len"] - long_len) // eng["page_size"]
    last = (eng["prompt_len"] + n_long - 1) // eng["page_size"]
    assert ring == 42 and last - first + 1 > ring
    for n in chk["tight_lengths"]:
        last_piece = (n - 1) % eng["prefill_chunk"] + 1
        bucket = 1 << (last_piece - 1).bit_length()
        assert bucket - last_piece >= chk["short_max_tokens"] + eng["page_size"]
    assert set(NEW) <= {m["name"] for m in cell.per_layer}
    for w in cells.load_benchmark(MAIN)["workloads"]:
        if w["name"] != CELL:
            other = cells.load_cell(MAIN, w["name"])
            assert not set(NEW) & {m["name"] for m in other.per_layer}
    assert {m["name"] for m in cell.end_to_end} == {"tokens_per_s", "tpot_p95_ms",
                                                    "setup_s"}
    assert {"row_occupancy", "chunk_ms", "admit_ms", "queue_wait_ms",
            "expert_layer_share", "peak_hbm_gb", "window_compiles",
            "kv_bytes_per_token", "routed_here_frac", "window_read_frac",
            "decode_device_step_ms", "scoped_share"} <= {
                m["name"] for m in cell.per_layer}


def test_a_program_without_the_model_is_refused(monkeypatch, capsys):
    from drivers import serve_reason_ref
    from nanorlhf_tpu.core import ModelConfig

    cell = cells.load_cell(REHEARSAL, "serve-tiny-trinity")
    serve_reason_ref.refuse_a_program_without_the_model(cell)   # this program
    real = ModelConfig.from_hf_config

    def raising(cls, hf):       # the parent's: expert keys under `afmoe`
        raise ValueError("model_type='afmoe' with expert keys")

    monkeypatch.setattr(ModelConfig, "from_hf_config", classmethod(raising))
    with pytest.raises(SystemExit) as e:
        serve_reason_ref.refuse_a_program_without_the_model(cell)
    assert e.value.code == 4
    assert "not a model this program builds" in capsys.readouterr().err
    # a program that builds the widths and drops the gate is refused too
    import dataclasses

    ungated = classmethod(lambda cls, hf: dataclasses.replace(
        real(hf), attention_gate=False))
    monkeypatch.setattr(ModelConfig, "from_hf_config", ungated)
    with pytest.raises(SystemExit):
        serve_reason_ref.refuse_a_program_without_the_model(cell)
    assert "attention_gate" in capsys.readouterr().err


@pytest.fixture(scope="module")
def rehearsed(tmp_path_factory):
    out = tmp_path_factory.mktemp("trinity")
    # (4.5 s: the traced second starts 3 s into the window)
    line = bench.run_cell(REHEARSAL, "serve-tiny-trinity", 2**31 + 9, 4.5,
                          True, require_tpu=False, out_root=str(out),
                          t_process_start=time.time())
    return line, json.load(open(out / "serve-tiny-trinity" / "run.json"))["run"]


def test_serve_reason_ref_cell_rehearses(rehearsed):
    line, run = rehearsed
    assert line["correct"], line
    assert line["failed"] == 0 and line["attempted"] >= 8
    # (the CPU's trace has no `%gmm`, no `%attn.*` kernel and no scope table)
    assert {"held_experts_hit_frac", "rows_past_window_frac", "window_read_frac",
            "routed_here_frac", "chunk_ms", "row_occupancy",
            "window_compiles"} <= set(line["metrics"])
    assert 0 < line["metrics"]["rows_past_window_frac"]["value"] <= 100
    assert 0 < line["metrics"]["held_experts_hit_frac"]["value"] <= 100
    assert 0 < line["metrics"]["routed_here_frac"]["value"] < 60
    assert line["metrics"]["window_compiles"]["value"] == 0
    assert run["kind"] == "serve_reason_ref"
    assert run["moe"]["moe/dropped_tokens"] == 0
    assert run["moe"]["moe/absent_assignments"] > 0
    assert run["moe"]["moe/bias_changed_frac"] > 0
    assert 0 < run["moe"]["moe/held_experts_hit"] <= 4
    g = run["greedy_check"]
    assert g["window_pages_reused_in_decode"] > 0 and g["prefix_hit_tokens"] == 0
    assert g["rows_past_window"] > 0
    assert g["chunked_admissions"] >= 1 and g["tokens"] == 2 * 30
    assert g["short"]["tokens"] == (2 + 1) * 6      # the tight row's with them
    end = run["counters"]["end"]
    assert end["serving/window_layers"] == 4 and end["serving/prefix_hit_tokens"] == 0
    assert end["serving/kv_bytes_per_token_window"] == \
        4 * end["serving/kv_bytes_per_token_global"]
    assert end["serving/live_row_steps"] >= end["serving/rows_past_window"] > 0
    assert len(run["traced_counters"]) == 2


def test_new_readers_read_nothing_from_another_program(rehearsed):
    """The parent commit and every other model: no such counters, kernels or
    scopes, and a run of another kind has no such keys at all."""
    _, run = rehearsed
    readers = {n: cells.load_module(os.path.join(BENCH, "layer_metrics", n + ".py"),
                                    "trinity_reader_" + n) for n in NEW}
    bare = {"counters": {"start": {}, "end": {}}, "traffic": run["traffic"],
            "config": {"hidden_size": 64}, "snapshots": run["snapshots"],
            "records": run["records"], "chips": 1, "peaks": run["peaks"],
            "trace": None}
    assert all(r.read(bare) is None for r in readers.values())
    assert all(r.read({"counters": None}) is None for r in readers.values())
    # and on the chip's kind of trace they read what the tables hold
    table = {"steps": 40, "by_scope": {
        "decode/attn/attn.qkv": 4e-3, "decode/attn/attn.gate": 1e-3,
        "decode/attn/attn.window": 5e-3, "decode/mlp/moe.experts": 2e-2,
        "decode/head": 1e-2, "prefill/attn/attn.qkv": 1.0}}
    traced = dict(run, trace={"busy_s": 1.0}, scope_trace=table, moe_trace={
        "kernel": [{"m": 128, "k": 64, "n": 32, "events": 10.0, "seconds": 1e-3},
                   {"m": 32, "k": 64, "n": 32, "events": 10.0, "seconds": 1e-3}]},
        attn_trace={"global": {"events": 5.0, "seconds": 1e-4},
                    "window": {"events": 20.0, "seconds": 4e-4}})
    got = {n: r.read(traced) for n, r in readers.items()}
    assert all(v is not None and v > 0 for v in got.values()), got
    projections = 2 * 64 * 64 + 2 * 64 * 32
    assert got["attn_gate_share"] == pytest.approx(
        100 * (1e-3 + 4e-3 * 64 * 64 / projections) / 4e-2)


def test_the_comparison_can_fail():
    """tools/gate_control.py at the rehearsal's size: the sound readings pass,
    and the models without the gate, the branch norms, the window or the
    embedding's scale and with rotary everywhere are refused."""
    tool = cells.load_module(os.path.join(BENCH, "tools", "gate_control.py"),
                             "bench_tool_gate_control")
    out = os.path.join(os.path.dirname(BENCH), "chiprun_out")
    rc = tool.main(["serve-tiny-trinity", "5", REHEARSAL])
    lines = json.load(open(os.path.join(
        out, "gate_control_serve-tiny-trinity_5.json")))
    by = {(ln["control"], ln["verdict"]): ln["ok"] for ln in lines}
    assert by[("sound", "long")] and by[("sound", "short")]
    for control in ("no_gate", "no_branch_norms", "rope_everywhere", "no_window",
                    "no_embed_scale"):
        assert not by[(control, "long")], control
    assert ("zero_bias", "long") in by
    assert not any(ln["a_reading"] for ln in lines)
    # (a zero bias and float8 at these widths, float32 weights and 60 tokens
    # are no reading either way; the chip's are in PERF.md)
    assert rc in (0, 1)
