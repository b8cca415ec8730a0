"""The harness end to end at a tiny size on the CPU, steered by the test
benchmark file under rehearsal/ (the command itself has no platform switch:
without the cell's TPU chips it exits non-zero and prints no result)."""

import json
import os
import subprocess
import sys
import time

import pytest

import run as bench

HERE = os.path.dirname(os.path.abspath(__file__))
REHEARSAL = os.path.join(HERE, "rehearsal", "cells.json")
KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def rehearse(cell, trace, tmp_path):
    return bench.run_cell(REHEARSAL, cell, 3, 2.0, trace, require_tpu=False,
                          out_root=str(tmp_path), t_process_start=time.time())


@pytest.mark.parametrize("cell", ["rl-tiny", "rl-tiny-x4"])
def test_rl_cell_rehearses(cell, tmp_path):
    line = rehearse(cell, False, tmp_path)
    assert set(line) == KEYS and line["correct"] and line["failed"] == 0
    assert set(line["metrics"]) == {"tokens_per_s", "setup_s"}
    assert line["attempted"] >= 1 and line["metrics"]["tokens_per_s"]["value"] > 0
    traced = rehearse(cell, True, tmp_path)
    assert {"setup_compile_s", "window_compiles", "rollout_share",
            "score_update_s", "decode_step_ms", "mfu",
            "decode_step_roofline"} <= set(traced["metrics"])
    assert traced["metrics"]["window_compiles"]["value"] == 0
    assert 0 < traced["metrics"]["rollout_share"]["value"] < 100
    saved = json.load(open(tmp_path / cell / "run.json"))
    assert saved["run"]["auto"] and saved["run"]["logprobs"]["tokens"] > 0
    # float32 on the CPU: the program's scorer and the plain reference agree
    assert saved["run"]["logprobs"]["tested_vs_float32"]["max_abs"] < 1e-4


def test_serve_cell_rehearses(tmp_path):
    line = rehearse("serve-tiny", True, tmp_path)
    assert set(line) == KEYS and line["correct"], line
    assert line["failed"] == 0 and line["attempted"] >= 3
    assert {"setup_compile_s", "window_compiles", "prefix_hit_frac",
            "row_occupancy", "ttft_p50_ms", "ttft_p90_ms", "ttft_p95_ms",
            "loadgen_late_p95_ms"} <= set(line["metrics"])
    saved = json.load(open(tmp_path / "serve-tiny" / "run.json"))
    e2e = saved["run"]["end_to_end"]
    assert {"tokens_per_s", "tpot_p95_ms", "setup_s"} <= set(e2e)
    assert saved["run"]["greedy_check"]["radix_hit_tokens"] >= 15
    assert saved["run"]["greedy_check"]["flips"] == 0      # float32: exact


def test_no_chip_no_number():
    out = subprocess.run(
        [sys.executable, os.path.join(os.path.dirname(HERE), "run.py"),
         "--workload", "grpo-1.5b-r512", "--seed", "0", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode != 0
    assert '"metrics"' not in out.stdout and "No chip, no number" in out.stderr
