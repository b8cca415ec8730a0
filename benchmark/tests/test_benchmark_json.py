"""BENCHMARK.json against the contract's form, and the data-driven rule: a
cell made only of three new files plus entries loads, no existing file edited."""

import json
import os
import re
import shutil

import pytest

from harness import cell as cells

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
FILE = os.path.join(ROOT, "BENCHMARK.json")
B = json.load(open(FILE))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_keys_and_limits():
    assert set(B) == {"command", "paths", "run_seconds", "configs", "workloads",
                      "end_to_end", "per_layer"}
    assert 1 <= B["run_seconds"] <= 51 and len(json.dumps(B)) < 64 * 1024
    assert 2 <= len(B["workloads"]) <= 24
    four = [w for w in B["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(B["workloads"]) // 4)
    assert all(w["chips"] in (1, 4) for w in B["workloads"])
    pairs = [(w["config"], w["traffic"]) for w in B["workloads"]]
    assert len(set(pairs)) == len(pairs)
    assert not any(w.startswith("/") or ".." in w for w in B["command"])


def test_names_units_and_lines():
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        group_names = [e["name"] for e in B[group]]
        assert len(set(group_names)) == len(group_names)
        names += group_names
    names += [w["config"] for w in B["workloads"]]
    names += [w["traffic"] for w in B["workloads"]]
    names += [k for c in B["configs"] for k in c["reduced"]]
    assert all(NAME.match(n) for n in names), [n for n in names if not NAME.match(n)]
    metrics = B["end_to_end"] + B["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in B["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    e2e = {m["name"] for m in B["end_to_end"]}
    assert "setup_s" in e2e
    for m in B["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves",
                          "workloads"}
        assert m["moves"] in e2e and "\n" not in m["layer"]
    for e in B["configs"] + B["workloads"]:
        assert 1 <= len(e["why"]) <= 200 and "\n" not in e["why"] and "\t" not in e["why"]
    for c in B["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert 1 <= len(c["source"]) <= 200
        assert any(c["file"].startswith(p + "/") for p in B["paths"])


def test_every_named_file_exists_and_every_cell_loads():
    for w in B["workloads"]:
        cell = cells.load_cell(FILE, w["name"])
        assert cell.chips == cell.config["chips"]
        cells.load_driver(cell)
        reported = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in reported and len(reported) >= 2
        layer = [m for m in cell.per_layer if m["moves"] in reported]
        assert layer
        for m in cell.per_layer:
            path = cells.find_under_paths(cell.root, cell.paths, "layer_metrics",
                                          m["name"] + ".py")
            assert hasattr(cells.load_module(path, "m"), "read")
    used = {w["config"] for w in B["workloads"]}
    assert used == {c["name"] for c in B["configs"]}
    for root, _, files in os.walk(BENCH_DIR):
        if "out" in root.split(os.sep) or "__pycache__" in root:
            continue
        for f in files:
            assert re.match(r"^[A-Za-z0-9_.\-]+$", f), os.path.join(root, f)


def test_widths_are_the_published_ones():
    c15 = json.load(open(os.path.join(BENCH_DIR, "configs", "qwen2.5-1.5b.json")))
    c7 = json.load(open(os.path.join(BENCH_DIR, "configs", "qwen2.5-7b-x4.json")))
    assert (c15["hidden_size"], c15["intermediate_size"], c15["num_hidden_layers"],
            c15["num_attention_heads"], c15["num_key_value_heads"],
            c15["vocab_size"], c15["tie_word_embeddings"]) == (
                1536, 8960, 28, 12, 2, 151936, True)
    assert (c7["hidden_size"], c7["intermediate_size"], c7["num_hidden_layers"],
            c7["num_attention_heads"], c7["num_key_value_heads"],
            c7["vocab_size"], c7["tie_word_embeddings"]) == (
                3584, 18944, 16, 28, 4, 152064, False)
    assert c15["reduced"] == [] and c7["reduced"] == ["num_hidden_layers"]


def test_a_cell_of_three_new_files_and_entries_loads(tmp_path):
    """A later PR adds a configuration, a mix and a reader as files, and a
    cell and a metric as entries; nothing that exists is edited."""
    root = tmp_path / "checkout"
    shutil.copytree(BENCH_DIR, root / "benchmark",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    before = {p: p.read_bytes() for p in (root / "benchmark").rglob("*") if p.is_file()}
    config = json.load(open(os.path.join(BENCH_DIR, "configs", "qwen2.5-1.5b.json")))
    config["name"] = "new-model"
    (root / "benchmark" / "configs" / "new-model.json").write_text(json.dumps(config))
    mix = json.load(open(os.path.join(BENCH_DIR, "traffic", "chat-steady.json")))
    mix["rate_rps"] = 30.0
    (root / "benchmark" / "traffic" / "chat-saturated.json").write_text(json.dumps(mix))
    (root / "benchmark" / "layer_metrics" / "queue_depth_mean.py").write_text(
        "def read(run):\n    s = run.get('snapshots')\n"
        "    return sum(x['pending'] for x in s) / len(s) if s else None\n")
    b = json.loads(json.dumps(B))
    b["configs"].append({"name": "new-model", "source": "https://example.org/x",
                         "file": "benchmark/configs/new-model.json",
                         "reduced": [], "why": "a test"})
    b["workloads"].append({"name": "new-cell", "config": "new-model",
                           "traffic": "chat-saturated", "chips": 1, "why": "a test"})
    b["per_layer"].append({"name": "queue_depth_mean", "unit": "count",
                           "better": "lower", "source": "program_counter",
                           "layer": "serving", "moves": "tokens_per_s",
                           "workloads": ["new-cell"]})
    for m in b["end_to_end"] + b["per_layer"]:
        if "workloads" in m and "serve-1.5b-chat" in m["workloads"]:
            m["workloads"] = m["workloads"] + ["new-cell"]
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    cell = cells.load_cell(str(root / "BENCHMARK.json"), "new-cell")
    assert cell.kind == "serve" and cell.traffic["rate_rps"] == 30.0
    assert cell.config["name"] == "new-model"
    assert "tpot_p95_ms" in {m["name"] for m in cell.end_to_end}
    run = {"snapshots": [{"pending": 2}, {"pending": 4}], "device":
           {"memory_peak_bytes": 0}, "compile": {"setup": {"compile_seconds": 1.0},
                                                 "window": {"compiles": 0}},
           "records": [], "rows_total": 4}
    got = cells.read_layer_metrics(cell, run, {"tokens_per_s", "setup_s"})
    assert got["queue_depth_mean"] == {"value": 3.0, "unit": "count"}
    assert "window_compiles" in got and "row_occupancy" not in got
    assert all(p.read_bytes() == data for p, data in before.items())


def test_unknown_workload_is_an_error():
    with pytest.raises(KeyError, match="no workload"):
        cells.load_cell(FILE, "nope")
