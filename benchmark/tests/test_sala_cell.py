"""The MiniCPM-SALA configuration and its cell: published widths, the
`serve_sala_ref` driver end to end at a tiny size on the CPU (steered by
rehearsal/cells_sala.json), its refusal of a program without the model, the
comparison's controls, the counts of ops_bytes_sala against counts made by
hand, and the new readers on a run they can and a run they cannot read."""

import json
import os
import time

import pytest

import run as bench
from harness import cell as cells
from harness import ops_bytes_sala as ob

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REHEARSAL = os.path.join(HERE, "rehearsal", "cells_sala.json")
MAIN = os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CELL = "serve-sala-docchat"
NEW = ("sala_decode_step_ms", "sala_decode_roofline", "linear_layer_share",
       "sala_state_update_roofline", "sala_linear_scan_roofline",
       "sala_select_roofline", "sala_sparse_read_roofline",
       "sala_sparse_prefill_roofline", "sparse_read_frac", "sparse_rows_frac")


def the_file():
    return json.load(open(os.path.join(BENCH, "configs",
                                       "minicpm-sala-l8.json")))


def test_widths_are_the_published_ones():
    c = the_file()
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    rows = [json.loads(line) for line in open(CATALOG)]
    row = next(r for r in rows if r["name"] == "MiniCPM-SALA")
    assert row["source_url"] == c["source"]
    differs = sorted(k for k, v in row["config"].items() if c.get(k, "missing") != v)
    assert differs == sorted(c["reduced"]) == ["mixer_types", "num_hidden_layers"]
    assert c["published"] == {"num_hidden_layers": 32,
                              "mixer_types": row["config"]["mixer_types"]}
    # a contiguous slice of the published order, both kinds in its ratio
    first = c["first_published_layer"]
    assert c["mixer_types"] == row["config"]["mixer_types"][first:first + 8]
    assert c["mixer_types"].count("minicpm4") * 32 == 8 * 8
    assert c["published_layers"] == 32 and c["num_hidden_layers"] == 8
    assert c["reference"] == "reference_sala" and c["chips"] == 1
    assert {"dtype", "state_dtype", "cut", "sparse_config", "selection",
            "dense_len", "lightning", "sparse_layer", "scales", "hf_names",
            "weights", "init"} <= set(c["assumed"])
    assert c["sparse_config"] == {
        "kernel_size": 32, "kernel_stride": 16, "block_size": 64, "topk": 64,
        "init_blocks": 1, "window_size": 2048, "dense_len": 8192}
    assert "pipeline" in c["deployment"] and "24 layers" in c["deployment"]
    # the arithmetic the deployment states
    assert ob.sparse_params(c) + ob.mlp_params(c) + 2 * 4096 == 253_763_840
    assert ob.lightning_params(c) + ob.mlp_params(c) + 2 * 4096 == 285_225_216
    assert round(ob.n_params(c) / 1e9, 2) == 2.82
    assert ob.kv_bytes_per_token(c) == 2 * (2048 // 2 + 32)
    assert ob.state_bytes_per_row_layer(c) == 2 * 1024 * 1024
    assert ob.state_bytes_per_row(c) == 6 * 2 * 1024 * 1024
    assert round(32 * ob.state_bytes_per_row(c) / 1e9, 2) == 0.40


def test_the_cell_is_the_issues():
    cell = cells.load_cell(MAIN, CELL)
    assert cell.kind == "serve_sala_ref" and cell.chips == 1
    assert cell.traffic_name == "docchat-steady"
    mix = cell.traffic
    assert mix["engine"] == {"rows": 32, "page_size": 128, "prompt_len": 65536,
                             "max_new_tokens": 1024, "max_queue": 256,
                             "headroom": 0.0, "sync_every": 4,
                             "prefill_chunk": 1024}
    doc, chat = mix["classes"]
    assert (doc["name"], doc["share"]) == ("doc", 0.3)
    assert doc["prompt_len"] == {"median": 16384, "sigma": 0.5, "min": 8448,
                                 "max": 65536}
    assert doc["max_tokens"] == {"median": 256, "sigma": 0.6, "min": 64,
                                 "max": 1024}
    assert (chat["name"], chat["share"]) == ("chat", 0.7)
    assert chat["prompt_len"] == {"median": 1024, "sigma": 0.8, "min": 64,
                                  "max": 4096}
    assert chat["max_tokens"] == {"median": 256, "sigma": 0.7, "min": 32,
                                  "max": 1024}
    # every document is past dense_len, every chat prompt under it
    dense_len = cell.config["sparse_config"]["dense_len"]
    assert doc["prompt_len"]["min"] > dense_len > chat["prompt_len"]["max"] + 1024
    assert mix["tenants"] == 0 and mix["arrival"] == "poisson"
    assert mix["sampling"] == {"greedy_frac": 0.5, "temperature": [0.7, 1.0],
                               "top_p": [0.9, 1.0]}
    assert mix["eos_unreachable"] and "schedule_seed" in mix
    assert 0.7 <= mix["rate_rps"] / mix["knee_rps"] <= 0.8
    # the knee is the sweep's highest rate whose backlog did not grow
    sweep = mix["knee_sweep"]
    first, second = (sweep["columns"].index(f"in_system_{half}_half")
                     for half in ("first", "second"))
    grew = {row[0]: row[second] > row[first] for row in sweep["rows"]}
    assert not grew[mix["knee_rps"]]
    assert any(rate > mix["knee_rps"] for rate in grew)
    assert all(g for rate, g in grew.items() if rate > mix["knee_rps"])
    assert not any(row[sweep["columns"].index("failed")] for row in sweep["rows"])
    chk, chunk = mix["greedy_check"], mix["engine"]["prefill_chunk"]
    # twenty whole pieces and a last piece of a few tokens, past dense_len
    assert chk["long_len"] // chunk == 20 and 0 < chk["long_len"] % chunk <= 8
    assert chk["long_max_tokens"] >= 256
    # rows that start under dense_len and decode past it
    assert chk["cross_max"] < dense_len < chk["cross_min"] + chk["cross_max_tokens"]
    assert chk["short_rows"] < mix["engine"]["rows"]
    assert max(chk["steady_rows"], chk["cross_rows"], chk["carry_rows"]) <= \
        mix["engine"]["rows"]
    assert set(NEW) <= {m["name"] for m in cell.per_layer}
    for w in cells.load_benchmark(MAIN)["workloads"]:
        if w["name"] != CELL:
            other = cells.load_cell(MAIN, w["name"])
            assert not set(NEW) & {m["name"] for m in other.per_layer}
    assert {m["name"] for m in cell.end_to_end} == {"tokens_per_s", "tpot_p95_ms",
                                                    "setup_s"}
    assert {"row_occupancy", "chunk_ms", "admit_ms", "queue_wait_ms",
            "peak_hbm_gb", "window_compiles", "kv_bytes_per_token",
            "state_bytes_per_row", "state_carry_frac", "decode_attn_share",
            "scoped_share", "prefill_device_ms", "sample_rows_frac"} <= {
                m["name"] for m in cell.per_layer}
    assert not {m["name"] for m in cell.per_layer
                if m["name"].startswith("fh1_")} and "ssm_layer_share" not in {
                    m["name"] for m in cell.per_layer}


def test_ops_and_bytes_against_counts_made_by_hand():
    c = the_file()
    b = ob.decode_step_bytes(c, rows=20, slots_read=8 * 4096,
                             slots_held=8 * 20_000, dense_slots=12 * 1500)
    sparse = 3 * 4096 * 4096 + 2 * 4096 * 256 + 2 * 128
    light = 5 * 4096 * 4096 + 2 * 128 + 4096
    assert b["sparse"] == 2 * sparse * 2 and b["lightning"] == 6 * light * 2
    assert b["mlp_norms"] == 8 * (3 * 4096 * 16384 + 2 * 4096) * 2
    # two layers: K and V of the chosen and the dense rows' slots, and a
    # compressed key (2 heads x 128 x 2 B) for every 16 slots a selecting
    # row holds
    assert b["kv"] == 2 * (2 * 512 * (8 * 4096 + 12 * 1500)
                           + 512 * 8 * 20_000 / 16)
    assert b["state"] == 2 * 20 * 6 * 32 * 128 * 128 * 4
    assert b["head"] == (4096 * 73448 + 4096) * 2 + 20 * 73448 * 4
    assert b["total"] == sum(v for k, v in b.items() if k != "total")
    weights = b["sparse"] + b["lightning"] + b["mlp_norms"] + (
        4096 * 73448 + 4096) * 2
    assert 5.0e9 < weights < 5.1e9      # 5.64 GB less the embedding's 0.60
    assert ob.state_update_bytes(c, rows=20) == 20 * (
        2 * 2 * 1024 * 1024 + 4 * 4096 * 4)
    cost = ob.linear_scan_cost(c, tokens=1024, pieces=1)
    assert cost["flops"] == 4 * 32 * 128 * 128 * 1024
    assert cost["bytes"] == 1024 * 4 * 4096 * 4 + 2 * 2 * 1024 * 1024
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert ob.linear_scan_floor_s(c, peaks, tokens=1024, pieces=1) == pytest.approx(
        cost["bytes"] / 819e9)                      # bytes bind, not operations
    # a step's selection over 160,000 held slots: 10,000 keys of 512 B
    assert ob.select_floor_s(c, peaks, slots_held=160_000) == pytest.approx(
        max(10_000 * 512 / 819e9, 2 * 10_000 * 32 * 128 / 197e12))
    assert ob.sparse_read_floor_s(c, peaks, slots=1000) == pytest.approx(
        1000 * 1024 / 819e9)
    # a prompt under dense_len reads its causal triangle; past it a query
    # reads 64 blocks, its own to itself
    assert ob.prefill_query_slots(c, 1000) == 1000 * 1001 / 2
    assert ob.prefill_query_slots(c, 20_000) == pytest.approx(
        4096 * 4097 / 2 + (20_000 - 4096) * (4096 - 32))
    assert ob.sparse_prefill_floor_s(c, peaks, query_slots=1e6) == pytest.approx(
        4 * 32 * 128 * 1e6 / 197e12)


def test_a_program_without_the_model_is_refused(monkeypatch, capsys):
    from drivers import serve_sala_ref
    from nanorlhf_tpu.core import ModelConfig

    cell = cells.load_cell(REHEARSAL, "serve-tiny-sala")
    serve_sala_ref.refuse_a_program_without_the_model(cell)     # this program
    # the parent's from_hf_config on these keys builds a dense decoder
    dense = classmethod(lambda cls, hf: ModelConfig.qwen2_tiny())
    monkeypatch.setattr(ModelConfig, "from_hf_config", dense)
    with pytest.raises(SystemExit) as e:
        serve_sala_ref.refuse_a_program_without_the_model(cell)
    assert e.value.code == 4
    assert "not a model this program builds" in capsys.readouterr().err

    def raises(cls, hf):
        raise ValueError("model_type='minicpm_sala' is not built")

    monkeypatch.setattr(ModelConfig, "from_hf_config", classmethod(raises))
    with pytest.raises(SystemExit):
        serve_sala_ref.refuse_a_program_without_the_model(cell)


@pytest.fixture(scope="module")
def rehearsed(tmp_path_factory):
    out = tmp_path_factory.mktemp("sala")
    line = bench.run_cell(REHEARSAL, "serve-tiny-sala", 2**31 + 9, 4.5,
                          True, require_tpu=False, out_root=str(out),
                          t_process_start=time.time())
    return line, json.load(open(out / "serve-tiny-sala" / "run.json"))["run"]


def test_serve_sala_ref_cell_rehearses(rehearsed):
    line, run = rehearsed
    assert line["correct"], line
    assert line["failed"] == 0 and line["attempted"] >= 10
    # (the CPU's trace has no device plane: no device roofline here)
    assert {"sala_decode_step_ms", "sala_decode_roofline", "sparse_read_frac",
            "sparse_rows_frac", "state_bytes_per_row", "state_carry_frac",
            "chunk_ms", "row_occupancy", "window_compiles",
            "kv_bytes_per_token"} <= set(line["metrics"])
    assert not {"sala_state_update_roofline", "sala_linear_scan_roofline",
                "sala_select_roofline", "sala_sparse_read_roofline",
                "sala_sparse_prefill_roofline"} & set(line["metrics"])
    assert line["metrics"]["window_compiles"]["value"] == 0
    # two lightning layers of 4 heads of 16 x 16, float32
    assert line["metrics"]["state_bytes_per_row"]["value"] == 2 * 4 * 256 * 4
    # two sparse layers: K, V and a compressed key every 4 slots, float32 here
    assert line["metrics"]["kv_bytes_per_token"]["value"] == 2 * (256 + 32)
    assert 0 < line["metrics"]["sparse_read_frac"]["value"] < 100
    assert 0 < line["metrics"]["sparse_rows_frac"]["value"] < 100
    assert run["kind"] == "serve_sala_ref"
    g = run["greedy_check"]
    # 131 tokens in pieces of 16: eight carries; 90: five; the carry prompts
    assert g["state_carries"] >= 8 + 2 * 5 + 6 and g["prefix_hit_tokens"] == 0
    assert g["state_resets"] == 1 + 2 + 2 + 2 + 4 + 4
    assert g["tokens"] == 12 and g["short"]["tokens"] == 2 * 6
    assert g["steady"]["tokens"] == 2 * 12
    assert g["state"]["steady"]["limit"] == 1e-4        # the floor, here
    assert g["cross"]["tokens"] == 2 * 12 and g["carry"]["tokens"] == 4 * 4
    assert g["reuse"]["tokens"] == 4 * 8        # as many as the engine has rows
    # the long row's steps and the cross rows' from 96 keys on
    assert g["sparse_rows"] >= 11 + 2 * 4
    for name in ("long", "steady", "carry"):
        state = g["state"][name]
        assert state["ok"] and state["first_layer_slow"] < 1e-5, state
        assert max(state["layer_max"]) < 1e-5 and state["next_row"] > 0.5, state
        assert state["state_dtype"] == "float32"
    sel = g["selection"]
    # every decode step's query (the last answered token was never fed),
    # over the compressed keys the engine cached
    assert sel["ok"] and sel["blocks_differ"] == 0 and sel["queries"] == 2 * 11
    assert sel["chosen_a_query"] == [4, 4] and sel["largest_distance"] == 0
    assert set(g["seconds"]) == {"before_warm_up", "warm_up", "served",
                                 "state", "reference", "plain", "selection"}
    # the comparison's programs are the mix's shapes, not the seed's draws
    # (the check draws a seed a run: a drawn width is a compile a run)
    assert g["widths"] == {"long": 131 + 12, "short": 30 + 6,
                           "steady": 44 + 12, "cross": 92 + 12,
                           "carry": 2 * 16 + 3 + 4, "reuse": 5 + 8}
    end = run["counters"]["end"]
    assert end["serving/state_layers"] == 2 and end["serving/window_layers"] == 0
    assert end["serving/prefix_hit_tokens"] == 0
    assert end["serving/sparse_slots_read"] == 64 * end["serving/sparse_rows"]
    assert len(run["traced_counters"]) == 2


def test_new_readers_read_nothing_from_another_program(rehearsed):
    """The parent of PR 53 and every other model: no `mixer_types` key, no
    `attn.linear` scope, no sparse counters; a run of another kind has no
    such keys at all."""
    _, run = rehearsed
    readers = {n: cells.load_module(os.path.join(BENCH, "layer_metrics", n + ".py"),
                                    "sala_reader_" + n) for n in NEW}
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
                      ("serving/global_slots_read", 9000),
                      ("serving/sparse_rows", 30),
                      ("serving/sparse_slots_read", 30 * 64),
                      ("serving/sparse_slots_held", 30 * 120),
                      ("serving/state_tokens", 64), ("serving/state_resets", 4),
                      ("serving/state_piece_carries", 6)):
        counters[1][key] = counters[0].get(key, 0) + gain
    window = {"start": dict(counters[0]), "end": dict(counters[1])}
    window["end"]["serving/state_tokens"] = window["start"].get(
        "serving/state_tokens", 0) + 128
    traced = dict(run, traced_counters=counters, counters=window,
                  scope_trace={"steps": 40.0, "by_scope": {
                      "decode/attn/attn.linear/attn.linear.update": 1.0,
                      "decode/attn/attn.linear/attn.linear.in": 0.5,
                      "decode/attn/attn.qkv": 0.5,
                      "decode/attn/attn.select": 0.25,
                      "decode/attn/attn.read": 0.5,
                      "decode/mlp": 7.25,
                      "prefill/attn/attn.linear/attn.linear.scan": 0.25,
                      "prefill/attn/attn.select": 0.1,
                      "prefill/attn/attn.read/attn.paged_flash": 0.1,
                      "prefill/attn/attn.read": 0.3}})
    assert readers["linear_layer_share"].read(traced) == pytest.approx(15.0)
    cfg, peaks = run["config"], run["peaks"]
    assert readers["sala_state_update_roofline"].read(traced) == pytest.approx(
        100 * 40 * 2 * ob.state_update_floor_s(cfg, peaks, rows=2.5) / 1.0)
    assert readers["sala_linear_scan_roofline"].read(traced) == pytest.approx(
        100 * 2 * ob.linear_scan_floor_s(cfg, peaks, tokens=64, pieces=10) / 0.25)
    assert readers["sala_select_roofline"].read(traced) == pytest.approx(
        100 * 40 * 2 * ob.select_floor_s(cfg, peaks, slots_held=90.0) / 0.25)
    # the selecting rows' 1,920 chosen slots and the others' 9,000 - 3,600
    assert readers["sala_sparse_read_roofline"].read(traced) == pytest.approx(
        100 * 40 * 2 * ob.sparse_read_floor_s(
            cfg, peaks, slots=(1920 + 9000 - 3600) / 40) / 0.5)
    slots = sum(ob.prefill_query_slots(cfg, r["prompt_len"])
                for r in run["records"])
    assert readers["sala_sparse_prefill_roofline"].read(traced) == pytest.approx(
        100 * 2 * ob.sparse_prefill_floor_s(cfg, peaks, query_slots=slots / 2)
        / 0.5)
    assert readers["sparse_read_frac"].read(traced) == pytest.approx(
        100 * 64 / 120)
    assert readers["sparse_rows_frac"].read(traced) == pytest.approx(30.0)


def test_the_comparison_can_fail():
    """tools/sparse_control.py at the rehearsal's size: the sound readings
    pass; the model that reads the newest blocks, that does not sum a
    group's heads, that ignores `dense_len`, that never decays and that
    does not scale its branches, and a state not carried between pieces, are
    each refused where they must be. A bfloat16 state reads a thousand times
    further from the reference's than the sound engine's, and its bytes a
    row are not the file's."""
    tool = cells.load_module(os.path.join(BENCH, "tools", "sparse_control.py"),
                             "bench_tool_sparse_control")
    out = os.path.join(os.path.dirname(BENCH), "chiprun_out")
    rc = tool.main(["serve-tiny-sala", "5", REHEARSAL])
    lines = json.load(open(os.path.join(
        out, "sparse_control_serve-tiny-sala_5.json")))
    by = {(ln["control"], ln["verdict"]): ln["ok"] for ln in lines}
    assert all(by[("sound", v)] for v in ("long", "short", "steady", "cross",
                                          "carry", "reuse", "selection"))
    for control, verdicts in tool.MUST_FAIL.items():
        if control != "state_bf16":
            for verdict in verdicts:
                assert not by[(control, verdict)], (control, verdict)
    far = {(ln["control"], ln["verdict"]): ln["first_layer_slow"]
           for ln in lines if "first_layer_slow" in ln}
    for verdict in ("state_long", "state_steady", "state_carry"):
        assert by[("sound", verdict)] and far[("sound", verdict)] < 1e-5
        assert far[("state_bf16", verdict)] > 1e-3
    assert not by[("state_bf16", "state_steady")]
    shifted = next(ln for ln in lines if ln["control"] == "pooling_shifted")
    assert shifted["outside_margin"] > 0 and shifted["largest_distance"] > 0.15
    assert far[("state_not_carried", "state_carry")] > 0.3
    assert by[("sound", "state_bytes_per_row")]
    assert not by[("state_bf16", "state_bytes_per_row")]
    assert not any(ln["a_reading"] for ln in lines)
    assert rc in (0, 1)
