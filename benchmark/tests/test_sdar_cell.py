"""The SDAR configuration and its cell: published widths, the byte arithmetic
of the cut pinned against hand counts, the `serve_block_ref` driver end to
end at a tiny size on the CPU (steered by rehearsal/cells_sdar.json), its
child's schedule, its refusal of a program without the model, the
comparison's negative controls, and the new readers on a run they can and a
run they cannot read."""

import json
import os
import time

import pytest

import run as bench
from harness import cell as cells
from harness import ops_bytes_sdar as ob

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REHEARSAL = os.path.join(HERE, "rehearsal", "cells_sdar.json")
MAIN = os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CELL = "serve-sdar-blockgen"
NEW = ("sdar_block_roofline", "sdar_gmm_roofline", "sdar_block_attn_roofline",
       "tokens_per_forward", "commit_forward_frac", "unmask_share")
GENERIC = ("row_occupancy", "ttft_p50_ms", "ttft_p90_ms", "ttft_p95_ms",
           "loadgen_late_p95_ms", "queue_wait_ms", "admit_ms", "admit_share",
           "chunk_ms", "chunk_sync_share", "first_token_lag_ms",
           "expert_layer_share", "kv_bytes_per_token", "decode_device_step_ms",
           "decode_attn_share", "decode_mlp_share", "decode_head_sample_share",
           "prefill_device_ms", "prefill_device_share", "scoped_share")


def the_file():
    return json.load(open(os.path.join(BENCH, "configs", "sdar-30b-a3b-l7.json")))


def test_widths_are_the_published_ones():
    c = the_file()
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    rows = [json.loads(line) for line in open(CATALOG)]
    row = next(r for r in rows if r["name"] == "SDAR-30B-A3B-Chat")
    assert row["source_url"] == c["source"]
    differs = sorted(k for k, v in row["config"].items()
                     if c.get(k, "missing") != v)
    assert differs == ["num_hidden_layers"] == c["reduced"]
    assert c["num_hidden_layers"] == 7 and c["published"] == {
        "num_hidden_layers": 48}
    assert c["reference"] == "reference_sdar" and c["chips"] == 1
    assert {"dtype", "block_length", "mask_token_id", "denoising_steps",
            "remasking", "confidence_threshold", "generation", "weights",
            "hf_names", "init"} <= set(c["assumed"])
    assert (c["assumed"]["block_length"], c["assumed"]["mask_token_id"]) == (
        4, 151669)
    assert "7 of 48 layers" in c["deployment"]


def test_the_cuts_arithmetic():
    """Hand counts at the published widths (ISSUE 46): a layer 623,120,640
    parameters, seven with embedding and head 4,984,176,384 = 9.97 GB."""
    c = the_file()
    assert ob.attention_params(c) == 2 * 2048 * 4096 + 2 * 2048 * 512 == 18_874_368
    assert ob.expert_params(c) == 3 * 2048 * 768 == 4_718_592
    assert ob.norm_params(c) == 4_352
    assert ob.layer_params(c) == 18_874_368 + 262_144 + 4_352 + 603_979_776 \
        == 623_120_640
    assert ob.n_params(c) == 4_984_176_384
    assert round(ob.n_params(c) * 2 / 1e9, 2) == 9.97
    assert ob.kv_bytes_per_token_layer(c) == 2048
    page = 128 * ob.kv_bytes_per_token_layer(c)
    assert page == 256 * 1024
    assert round((64 * 25 + 25) * page * 7 / 1e9, 2) == 2.98


def test_ops_and_bytes():
    c = the_file()
    cost = ob.block_forward_cost(c, rows=40, experts_hit=120.0, slots=48_000)
    assert cost["experts"] == 7 * 120.0 * 4_718_592 * 2
    assert cost["kv"] == 7 * 48_000 * 2048
    assert cost["head"] == (2048 * 151_936 + 2048) * 2 + 160 * 151_936 * 4
    assert cost["attention_router"] == 7 * (18_874_368 + 262_144 + 4_352) * 2
    assert cost["bytes"] == sum(cost[k] for k in (
        "attention_router", "experts", "kv", "head"))
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    # a forward of 40 live rows is bound by its bytes, ISSUE 46's ~11 ms
    assert ob.block_forward_floor_s(c, peaks, rows=40, experts_hit=120.0,
                                    slots=48_000) == cost["bytes"] / 819e9
    assert 0.009 < cost["bytes"] / 819e9 < 0.013
    assert ob.experts_hit(c, 64) == pytest.approx(126.0, abs=0.5)
    call = ob.grouped_matmul_cost(c, m=2048, k=2048, n=768, tokens=160,
                                  kernels=120)
    assert call["bytes"] == (1280 * 2048 + 120 * 2048 * 768 + 1280 * 768) * 2
    assert call["flops"] / 197e12 < call["bytes"] / 819e9


def test_the_cell_is_the_issues():
    cell = cells.load_cell(MAIN, CELL)
    assert cell.kind == "serve_block_ref" and cell.chips == 1
    assert cell.config_name == "sdar-30b-a3b-l7"
    assert cell.traffic_name == "blockgen-steady"
    mix = cell.traffic
    assert mix["engine"] == {"rows": 64, "page_size": 128, "prompt_len": 2048,
                             "max_new_tokens": 1024, "max_queue": 256,
                             "headroom": 0.0, "sync_every": 4,
                             "prefill_chunk": 1024}
    assert mix["tenants"] == 0 and mix["arrival"] == "poisson"
    assert mix["prompt_len"] == {"median": 512, "sigma": 0.7, "min": 64,
                                 "max": 2048}
    assert mix["max_tokens_choices"] == [256, 512, 1024]
    assert mix["denoising_steps_choices"] == [4, 2]
    assert mix["remasking"] == "low_confidence_static"
    assert mix["block_length"] == cell.config["assumed"]["block_length"] == 4
    assert mix["sampling"] == {"greedy_frac": 0.5, "temperature": [0.7, 1.0],
                               "top_p": [0.9, 1.0]}
    assert mix["eos_unreachable"] and "schedule_seed" in mix
    assert 0.7 <= mix["rate_rps"] / mix["knee_rps"] <= 0.8
    chk, eng = mix["greedy_check"], mix["engine"]
    assert chk["long_lengths"] == [1900, 1025] and chk["long_max_tokens"] == 512
    assert [n % 4 for n in chk["long_lengths"]] == [0, 1]      # the tails
    # the first in two pieces; the second's whole blocks are ONE piece of
    # 1,024 and its last token opens its first block (ISSUE 46 reckoned two
    # pieces for it, as an autoregressive prompt of 1,025 has)
    assert chk["long_lengths"][0] // 4 * 4 > eng["prefill_chunk"]
    assert chk["long_lengths"][1] // 4 * 4 == eng["prefill_chunk"]
    assert (chk["short_rows"], chk["short_len"], chk["short_max_tokens"],
            chk["dynamic_rows"]) == (16, 96, 64, 2)
    assert set(NEW) <= {m["name"] for m in cell.per_layer}
    assert set(GENERIC) <= {m["name"] for m in cell.per_layer}
    bench_file = cells.load_benchmark(MAIN)
    for w in bench_file["workloads"]:
        if w["name"] != CELL:
            other = cells.load_cell(MAIN, w["name"])
            assert not set(NEW) & {m["name"] for m in other.per_layer}
    assert {m["name"] for m in cell.end_to_end} == {"tokens_per_s", "tpot_p95_ms",
                                                    "setup_s"}
    assert len(bench_file["workloads"]) == 9
    assert sum(w["chips"] == 4 for w in bench_file["workloads"]) == 1


def test_the_childs_schedule_is_the_mixs():
    """Equal thirds of the budgets and halves of the steps in every segment,
    the same for every seed; the prompts' tokens are the seed's."""
    from harness import loadgen_child_block as child

    mix = cells.load_cell(MAIN, CELL).traffic
    a = child.block_requests(mix, 5, [20.0, 45.0, 25.0], 151_936)
    b = child.block_requests(mix, 2**31 + 7, [20.0, 45.0, 25.0], 151_936)
    shape = lambda rs: [(r["t"], len(r["tokens"]), r["max_tokens"],   # noqa: E731
                         r["denoising_steps"], r["greedy"]) for r in rs]
    assert shape(a) == shape(b) and a[0]["tokens"] != b[0]["tokens"]
    window = [r for r in a if 20.0 <= r["t"] < 65.0]
    assert len(window) == round(mix["rate_rps"] * 45)
    for choices, key in ((mix["max_tokens_choices"], "max_tokens"),
                         (mix["denoising_steps_choices"], "denoising_steps")):
        counts = [sum(r[key] == c for r in window) for c in choices]
        assert max(counts) - min(counts) <= 1, (key, counts)
    assert {r["remasking"] for r in a} == {"low_confidence_static"}


def test_a_body_without_the_block_keys_is_counted():
    """The two keys ride in by the identity of the request's token list: a
    body built from another list gains nothing, and the child's summary (and
    with it the driver's `correct`) says so."""
    from harness import loadgen_child_block as child

    shim, tokens = child._Json(), [5, 6, 7]
    shim.extra = {id(tokens): {"denoising_steps": 2,
                               "remasking": "low_confidence_static"}}
    body = json.loads(shim.dumps({"tokens": tokens, "max_tokens": 8}))
    assert body["denoising_steps"] == 2 and (shim.laid, shim.missed) == (1, 0)
    body = json.loads(shim.dumps({"tokens": list(tokens), "max_tokens": 8}))
    assert "denoising_steps" not in body and (shim.laid, shim.missed) == (1, 1)
    summary = json.loads(shim.dumps({"scheduled": 2, "digest": "x"}))
    assert (summary["block_bodies"], summary["plain_bodies"]) == (1, 1)
    assert json.loads(shim.dumps({"index": 0, "status": "ok"})) == {
        "index": 0, "status": "ok"}


def test_a_program_without_the_model_is_refused(monkeypatch, capsys):
    from drivers import serve_block_ref
    from nanorlhf_tpu.core import ModelConfig

    cell = cells.load_cell(REHEARSAL, "serve-tiny-sdar")
    serve_block_ref.refuse_a_program_without_the_model(cell)    # this program
    real = ModelConfig.from_hf_config

    def raising(cls, hf):       # the parent's: expert keys under `sdar_moe`
        raise ValueError("model_type='sdar_moe' with expert keys")

    monkeypatch.setattr(ModelConfig, "from_hf_config", classmethod(raising))
    with pytest.raises(SystemExit) as e:
        serve_block_ref.refuse_a_program_without_the_model(cell)
    assert e.value.code == 4
    assert "not a model this program builds" in capsys.readouterr().err
    # a program that builds the widths and decodes it one token a step
    import dataclasses

    causal = classmethod(lambda cls, hf: dataclasses.replace(
        real(hf), block_length=0))
    monkeypatch.setattr(ModelConfig, "from_hf_config", causal)
    with pytest.raises(SystemExit):
        serve_block_ref.refuse_a_program_without_the_model(cell)
    assert "block_generation" in capsys.readouterr().err


@pytest.fixture(scope="module")
def rehearsed(tmp_path_factory):
    out = tmp_path_factory.mktemp("sdar")
    # (4.5 s: the traced second starts 3 s into the window)
    line = bench.run_cell(REHEARSAL, "serve-tiny-sdar", 2**31 + 9, 4.5,
                          True, require_tpu=False, out_root=str(out),
                          t_process_start=time.time())
    return line, json.load(open(out / "serve-tiny-sdar" / "run.json"))["run"]


def test_serve_block_ref_cell_rehearses(rehearsed):
    line, run = rehearsed
    assert line["correct"], line
    assert line["failed"] == 0 and line["attempted"] >= 8
    # (the CPU's trace has no `%gmm`, no `%attn.block` kernel, no scope table)
    assert {"tokens_per_forward", "commit_forward_frac", "chunk_ms",
            "row_occupancy", "window_compiles", "kv_bytes_per_token"} <= set(
                line["metrics"])
    # 4 tokens in 5 forwards and in 3: between 0.8 and 1.33 a forward
    assert 0.7 < line["metrics"]["tokens_per_forward"]["value"] < 1.4
    assert 19 < line["metrics"]["commit_forward_frac"]["value"] < 34
    assert line["metrics"]["window_compiles"]["value"] == 0
    assert run["kind"] == "serve_block_ref"
    assert run["moe"]["moe/dropped_tokens"] == 0
    assert 0 < run["moe"]["moe/held_experts_hit"] <= 8
    g = run["greedy_check"]
    assert g["commit_forwards"] == g["blocks_done"] > 0
    assert g["tokens_unmasked"] == g["tokens_streamed"] + g["tokens_cut"]
    assert g["prefix_hit_tokens"] == 0 and g["chunked_admissions"] >= 1
    assert g["tokens"] >= 2 * 20 and g["short"]["tokens"] >= 5 * 6
    assert g["gap"]["max_abs"] < 1e-3       # float32 weights: the same tokens
    end = run["counters"]["end"]
    assert end["serving/block_length"] == 4
    assert end["serving/prefix_hit_tokens"] == 0
    assert run["block"]["serving/block_forwards"] > 0
    assert len(run["traced_counters"]) == 2
    budgets = {r["budget"] for r in run["records"]}
    assert budgets <= {6, 12, 24} and len(budgets) >= 2


def test_new_readers_read_nothing_from_another_program(rehearsed):
    """The parent commit and every other model: no such counters, kernels or
    scopes, and a run of another kind has no such keys at all."""
    _, run = rehearsed
    readers = {n: cells.load_module(os.path.join(BENCH, "layer_metrics", n + ".py"),
                                    "sdar_reader_" + n) for n in NEW}
    bare = {"counters": {"start": {}, "end": {}}, "traffic": run["traffic"],
            "config": {"hidden_size": 64}, "snapshots": run["snapshots"],
            "records": run["records"], "chips": 1, "peaks": run["peaks"],
            "trace": None}
    assert all(r.read(bare) is None for r in readers.values())
    assert all(r.read({"counters": None}) is None for r in readers.values())
    # and on the chip's kind of trace they read what the tables hold
    table = {"steps": 40, "by_scope": {
        "decode/attn/attn.qkv": 4e-3, "decode/attn/attn.read/attn.block": 3e-3,
        "decode/mlp/moe.experts": 2e-2, "decode/head": 1e-2,
        "decode/sample": 2e-3, "decode/sample/sample.unmask": 1e-3,
        "prefill/attn/attn.qkv": 1.0}}
    traced = dict(run, trace={"busy_s": 1.0}, scope_trace=table, moe_trace={
        "kernel": [{"m": 128, "k": 64, "n": 32, "events": 10.0, "seconds": 1e-3},
                   {"m": 32, "k": 64, "n": 32, "events": 10.0, "seconds": 1e-3}]},
        block_trace={"events": 80.0, "seconds": 4e-4})
    got = {n: r.read(traced) for n, r in readers.items()}
    assert all(v is not None and v > 0 for v in got.values()), got
    assert got["unmask_share"] == pytest.approx(100 * 1e-3 / 4e-2)


def test_the_comparison_can_fail():
    """tools/block_control.py at the rehearsal's size: the sound readings
    pass and every control model is refused on some verdict."""
    tool = cells.load_module(os.path.join(BENCH, "tools", "block_control.py"),
                             "bench_tool_block_control")
    out = os.path.join(os.path.dirname(BENCH), "chiprun_out")
    rc = tool.main(["serve-tiny-sdar", "5", REHEARSAL])
    lines = json.load(open(os.path.join(
        out, "block_control_serve-tiny-sdar_5.json")))
    by = {(ln["control"], ln["verdict"]): ln["ok"] for ln in lines}
    assert by[("sound", "long")] and by[("sound", "short")]
    for control in ("causal", "shift", "block_1", "block_8", "no_head_norm",
                    "no_renorm", "stale_commit"):
        assert not by[(control, "long")] and not by[(control, "short")], control
    assert ("float8", "long") in by
    assert not any(ln["a_reading"] for ln in lines)
    # (float8 at these widths and float32 weights is no reading either way;
    # the chip's are in PERF.md)
    assert rc in (0, 1)
