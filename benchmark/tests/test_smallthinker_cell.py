"""The SmallThinker configuration and its cell: published widths, the
`serve_mix_ref` driver end to end at a tiny size on the CPU (steered by
rehearsal/cells_smallthinker.json), its child's merged classes, its refusal
of a program without the model, the comparison's two negative controls, and
the new readers on a run they can and a run they cannot read."""

import dataclasses
import json
import os
import sys
import time

import numpy as np
import pytest

import run as bench
from harness import cell as cells
from harness import loadgen_child_classes, ops_bytes_smallthinker as ob

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REHEARSAL = os.path.join(HERE, "rehearsal", "cells_smallthinker.json")
MAIN = os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CELL = "serve-smallthinker-longshort"
NEW = ("st_decode_step_ms", "st_decode_roofline", "window_read_frac",
       "st_paged_attn_roofline", "st_gmm_roofline")


def the_file():
    return json.load(open(os.path.join(BENCH, "configs", "smallthinker-21b-l8.json")))


def test_widths_are_the_published_ones():
    c = the_file()
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    rows = [json.loads(line) for line in open(CATALOG)]
    row = next(r for r in rows if r["name"] == "SmallThinker-21BA3B-Instruct")
    assert row["source_url"] == c["source"]
    differs = sorted(k for k, v in row["config"].items() if c.get(k, "missing") != v)
    assert differs == sorted(c["reduced"]) == [
        "num_hidden_layers", "rope_layout", "sliding_window_layout"]
    assert c["num_hidden_layers"] == 8 and c["published"]["num_hidden_layers"] == 52
    # two whole periods of the published layouts
    assert c["rope_layout"] == row["config"]["rope_layout"][:8] == [0, 1, 1, 1] * 2
    assert c["sliding_window_layout"] == row["config"]["sliding_window_layout"][:8]
    assert c["reference"] == "reference_smallthinker" and c["chips"] == 1
    assert {"dtype", "weights", "attention_biases", "window", "nope", "router",
            "experts", "secondary_experts", "hf_names"} <= set(c["assumed"])
    assert "pipeline" in c["deployment"] and "ALL 64 experts" in c["deployment"]
    # the arithmetic the deployment states
    assert ob.layer_params(c) == 398_627_840
    assert round(ob.n_params(c) * 2 / 1e9, 2) == 7.93
    assert ob.kv_bytes_per_token_layer(c) == 2048


def test_the_cell_is_the_issues():
    cell = cells.load_cell(MAIN, CELL)
    assert cell.kind == "serve_mix_ref" and cell.chips == 1
    mix = cell.traffic
    assert mix["engine"] == {"rows": 32, "page_size": 128, "prompt_len": 14336,
                             "max_new_tokens": 2048, "max_queue": 256,
                             "headroom": 0.0, "sync_every": 4,
                             "prefill_chunk": 1024}
    long_, short = mix["classes"]
    assert (long_["share"], short["share"]) == (0.4, 0.6) and mix["tenants"] == 0
    assert long_["prompt_len"] == {"median": 8192, "sigma": 0.4, "min": 4608,
                                   "max": 14336}
    assert short["prompt_len"] == {"median": 384, "sigma": 0.8, "min": 32,
                                   "max": 2048}
    assert mix["max_tokens"] == {"median": 256, "sigma": 0.8, "min": 16,
                                 "max": 2048}
    assert mix["sampling"] == {"greedy_frac": 0.5, "temperature": [0.7, 1.0],
                               "top_p": [0.9, 1.0]}
    assert mix["eos_unreachable"] and "schedule_seed" in mix
    # four fifths of the knee where the tail is still a yardstick there, else
    # the highest rate at which it is (the mix file's `at_four_fifths`)
    assert 0.7 <= mix["rate_rps"] / mix["knee_rps"] <= 0.8
    assert mix["knee_sweep"]["rows_final"][1][:4] == [mix["knee_rps"], 202, 202, 0]
    chk = mix["greedy_check"]
    assert chk["long_lengths"][0] >= 9000 and chk["long_max_tokens"] >= 512
    # a suffix bucket past the row's last block (REVIEW, PR 34): the last
    # forward of each tight prompt writes beyond prompt_len + its budget
    eng = mix["engine"]
    for n in chk["tight_lengths"]:
        last = (n - 1) % eng["prefill_chunk"] + 1       # the final piece
        bucket = 1 << (last - 1).bit_length()
        assert bucket - last >= chk["short_max_tokens"] + eng["page_size"]
    assert long_["prompt_len"]["min"] > cell.config["sliding_window_size"]
    assert set(NEW) <= {m["name"] for m in cell.per_layer}
    others = cells.load_benchmark(MAIN)["workloads"]
    for w in others:
        if w["name"] != CELL:
            other = cells.load_cell(MAIN, w["name"])
            assert not set(NEW) & {m["name"] for m in other.per_layer}
    assert {m["name"] for m in cell.end_to_end} == {"tokens_per_s", "tpot_p95_ms",
                                                    "setup_s"}
    assert {"row_occupancy", "chunk_ms", "admit_ms", "queue_wait_ms",
            "expert_layer_share", "peak_hbm_gb", "window_compiles",
            "kv_bytes_per_token"} <= {
                m["name"] for m in cell.per_layer}


def test_classes_merge_into_one_stream():
    mix = cells.load_cell(MAIN, CELL).traffic
    reqs = loadgen_child_classes.class_requests(mix, 2**31 + 5, [10, 45, 45], 151936)
    again = loadgen_child_classes.class_requests(mix, 2**31 + 5, [10, 45, 45], 151936)
    assert reqs == again and [r["index"] for r in reqs] == list(range(len(reqs)))
    assert all(a["t"] <= b["t"] for a, b in zip(reqs, reqs[1:]))
    window = [r for r in reqs if 10 <= r["t"] < 55]
    long_ = [r for r in window if r["tenant"] == -1]
    short = [r for r in window if r["tenant"] == -2]
    rate = mix["rate_rps"]
    assert len(long_) == round(0.4 * rate * 45) and len(short) == round(0.6 * rate * 45)
    assert all(4608 <= len(r["tokens"]) <= 14336 for r in long_)
    assert all(32 <= len(r["tokens"]) <= 2048 for r in short)
    assert all(16 <= r["max_tokens"] <= 2048 for r in window)
    # the schedule's shape is the mix's, the token ids the seed's
    other = loadgen_child_classes.class_requests(mix, 7, [10, 45, 45], 151936)
    assert [(r["t"], len(r["tokens"]), r["max_tokens"]) for r in other] == [
        (r["t"], len(r["tokens"]), r["max_tokens"]) for r in reqs]
    assert other[0]["tokens"] != reqs[0]["tokens"]


def test_ops_and_bytes():
    c = the_file()
    b = ob.decode_step_bytes(c, rows=10, experts_hit=40, global_slots=50_000,
                             window_slots=25_000)
    assert b["experts"] == 8 * 40 * 3 * 2560 * 768 * 2
    assert b["kv"] == (2 * 50_000 + 6 * 25_000) * 2048
    assert b["head"] == (2560 * 151936 + 2560) * 2 + 10 * 151936 * 4
    assert b["total"] == sum(v for k, v in b.items() if k != "total")
    assert ob.experts_hit_expected(c, 1024) == pytest.approx(64.0, abs=1e-6)
    assert 5.9 < ob.experts_hit_expected(c, 1) <= 6.0
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    step = ob.grouped_matmul_cost(c, m=256, k=2560, n=768, tokens=10, kernels=38)
    assert step["bytes"] == (60 * 2560 + 38 * 2560 * 768 + 60 * 768) * 2
    chunk = ob.grouped_matmul_floor_s(c, peaks, m=6144, k=2560, n=768)
    assert chunk == pytest.approx((6144 * 2560 + 64 * 2560 * 768 + 6144 * 768)
                                  * 2 / 819e9)


def test_a_program_without_the_model_is_refused(monkeypatch, capsys):
    from drivers import serve_mix_ref
    from nanorlhf_tpu.core import ModelConfig

    cell = cells.load_cell(REHEARSAL, "serve-tiny-smallthinker")
    serve_mix_ref.refuse_a_program_without_the_model(cell)      # this program
    # a program whose from_hf_config knows no such model builds a dense one
    dense = classmethod(lambda cls, hf: ModelConfig.qwen2_tiny())
    monkeypatch.setattr(ModelConfig, "from_hf_config", dense)
    with pytest.raises(SystemExit) as e:
        serve_mix_ref.refuse_a_program_without_the_model(cell)
    assert e.value.code == 4 and "not a model this program builds" in capsys.readouterr().err


@pytest.fixture(scope="module")
def rehearsed(tmp_path_factory):
    out = tmp_path_factory.mktemp("st")
    # (4.5 s: the traced second starts 3 s into the window)
    line = bench.run_cell(REHEARSAL, "serve-tiny-smallthinker", 2**31 + 9, 4.5,
                          True, require_tpu=False, out_root=str(out),
                          t_process_start=time.time())
    return line, json.load(open(out / "serve-tiny-smallthinker" / "run.json"))["run"]


def test_serve_mix_ref_cell_rehearses(rehearsed):
    line, run = rehearsed
    assert line["correct"], line
    assert line["failed"] == 0 and line["attempted"] >= 10
    # (the CPU's trace has no `%gmm` and no `%attn.*` kernel: neither device
    # roofline here)
    assert {"st_decode_step_ms", "st_decode_roofline", "window_read_frac",
            "chunk_ms", "row_occupancy", "window_compiles"} <= set(line["metrics"])
    assert not {"st_gmm_roofline", "st_paged_attn_roofline"} & set(line["metrics"])
    assert 0 < line["metrics"]["window_read_frac"]["value"] < 100
    assert line["metrics"]["window_compiles"]["value"] == 0
    assert run["kind"] == "serve_mix_ref" and run["moe"]["moe/dropped_tokens"] == 0
    g = run["greedy_check"]
    assert g["window_pages_reused"] > 0 and g["prefix_hit_tokens"] == 0
    assert g["chunked_admissions"] >= 2 and g["tokens"] == 2 * 12
    assert g["short"]["tokens"] == (2 + 1) * 6      # the tight row's with them
    end = run["counters"]["end"]
    assert end["serving/window_layers"] == 3 and end["serving/prefix_hit_tokens"] == 0
    assert end["serving/pool_pages_window"] == 4 * 6
    # the guard that the cache is the two-kind cache: a slot is held once a
    # layer of each kind
    assert line["metrics"]["kv_bytes_per_token"]["value"] == (
        end["serving/kv_bytes_per_token_global"]
        + end["serving/kv_bytes_per_token_window"])
    assert end["serving/kv_bytes_per_token_window"] == \
        3 * end["serving/kv_bytes_per_token_global"]
    assert len(run["traced_counters"]) == 2
    assert {r["tenant"] for r in run["records"]} == {-1, -2}
    # (no device plane in the CPU's trace: not reduced, or reduced to nothing)
    assert run.get("attn_trace") in (None, {k: {"events": 0.0, "seconds": 0.0}
                                            for k in ("global", "window")})


def test_new_readers_read_nothing_from_another_program(rehearsed):
    """The parent of PR 34 and every other model: no window counters, no
    `attn.*` kernel, and a run of another kind has no such keys at all."""
    _, run = rehearsed
    readers = {n: cells.load_module(os.path.join(BENCH, "layer_metrics", n + ".py"),
                                    "st_reader_" + n) for n in NEW}
    bare = {"counters": {"start": {}, "end": {}}, "traffic": run["traffic"],
            "config": {"hidden_size": 64}, "snapshots": run["snapshots"],
            "records": run["records"], "chips": 1, "peaks": run["peaks"],
            "trace": None}
    assert all(r.read(bare) is None for r in readers.values())
    assert all(r.read({"counters": None}) is None for r in readers.values())
    # and on the chip's kind of trace they read what the tables hold
    traced = dict(run, moe_trace={"kernel": [
        {"m": 4 * 2, "k": 64, "n": 32, "events": 10.0, "seconds": 1e-3}]},
        attn_trace={"global": {"events": 5.0, "seconds": 1e-4},
                    "window": {"events": 15.0, "seconds": 2e-4}})
    assert readers["st_gmm_roofline"].read(traced) > 0
    assert readers["st_paged_attn_roofline"].read(traced) > 0


def test_attn_trace_names():
    from harness import attn_trace

    hit = ("%attn.window.31 = bf16[32,28,128]{2,1,0} custom-call(s32[] %a, "
           "bf16[6,1344,4,128,128]{4,3,2,1,0} %b), custom_call_target=tpu_custom_call")
    assert attn_trace.KERNEL.match(hit).group(1) == "window"
    assert attn_trace.KERNEL.match(hit.replace("window.31", "global.10")).group(1) == "global"
    assert not attn_trace.KERNEL.match("%fusion.12 = bf16[1,2]{1,0} fusion(%attn.window.3)")
    assert not attn_trace.KERNEL.match("%gmm.4 = bf16[256,768]{1,0} custom-call(%x)")


def test_the_comparison_can_fail(tmp_path):
    """tools/window_control.py at the rehearsal's size: the sound readings
    pass, the model without the window and the model that rotates every
    layer are refused."""
    tool = cells.load_module(os.path.join(BENCH, "tools", "window_control.py"),
                             "bench_tool_window_control")
    out = os.path.join(os.path.dirname(BENCH), "chiprun_out")
    rc = tool.main(["serve-tiny-smallthinker", "5", REHEARSAL])
    lines = json.load(open(os.path.join(
        out, "window_control_serve-tiny-smallthinker_5.json")))
    by = {(ln["control"], ln["verdict"]): ln["ok"] for ln in lines}
    assert by[("sound", "long")] and by[("sound", "short")]
    assert not by[("no_window", "long")]
    assert not by[("rope_everywhere", "long")]
    assert not any(ln["a_reading"] for ln in lines)
    # (float8 at these widths, float32 weights and 24 tokens is no reading
    # either way; the chip's is in PERF.md)
    assert rc in (0, 1)
