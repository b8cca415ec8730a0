"""ops_bytes_moe.py against hand-worked values for OLMoE-1B-7B."""

import json
import os

import pytest

from harness import ops_bytes_moe as ob

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = json.load(open(os.path.join(BENCH, "configs", "olmoe-1b-7b.json")))
FULL = dict(CELL, num_hidden_layers=16)    # the published depth; the cell cuts it


def test_parameter_counts_are_the_published_ones():
    # attention 4 * 2048 * 2048 = 16,777,216; an expert 3 * 2048 * 1024 =
    # 6,291,456, x64 = 402,653,184; router 2048 * 64 = 131,072; norms
    # 2 * 2048 + q_norm 2048 + k_norm 2048 = 8,192
    assert ob.attention_params(FULL) == 16_777_216
    assert ob.expert_params(FULL) == 6_291_456
    assert ob.layer_params(FULL) == 419_569_664            # the 419.6 M
    # embedding and head 50304 * 2048 = 103,022,592 each; final norm 2048
    assert ob.n_params(FULL) == 2 * 103_022_592 + 16 * 419_569_664 + 2048 \
        == 6_919_161_856                                   # the 6.9 B
    assert ob.n_params(CELL) == 1_884_325_888              # 3.77 GB in bf16
    # r=64 on q, k, v, o: 64 * 4 * (2048 + 2048) a layer
    assert ob.lora_params(CELL, 64) == 4 * 64 * 4 * 4096 == 4_194_304


def test_a_tokens_matmul_flops_by_part():
    f = ob.layer_matmul_flops_per_token(FULL)
    assert f["experts"] == 2 * 8 * 6_291_456 == 100_663_296    # 100.7 MFLOP
    assert f["attention"] == 33_554_432 and f["router"] == 262_144
    assert f["experts"] / sum(f.values()) == pytest.approx(0.7485, abs=1e-4)


def test_experts_hit():
    assert ob.experts_hit(FULL, 1) == pytest.approx(8.0)
    assert ob.experts_hit(FULL, 8) == pytest.approx(64 * (1 - 0.875 ** 8))
    assert ob.experts_hit(FULL, 64) == pytest.approx(64 * (1 - 0.875 ** 64))
    assert 63.98 < ob.experts_hit(FULL, 64) < 64


def test_decode_step_bytes_by_part():
    b = ob.decode_step_bytes(CELL, rows=64, filled_mean=416, lora_r=64)
    assert ob.kv_bytes_per_token(CELL) == 2 * 4 * 16 * 128 * 2 == 32_768
    assert b["kv"] == 64 * 416 * 32_768 == 872_415_232
    assert b["router"] == 4 * 131_072 * 2
    # every expert is reached: 4 layers x 64 x 6,291,456 x 2 B = 3.22 GB
    assert b["experts"] == pytest.approx(4 * 64 * 6_291_456 * 2, rel=3e-4)
    assert b["attention"] == 4 * (16_777_216 + 8192) * 2 + 4_194_304 * 2
    assert b["head"] == (103_022_592 + 2048) * 2 + 64 * 50304 * 4
    assert b["total"] == sum(v for k, v in b.items() if k != "total")
    # 3.22 + 0.87 + 0.22 + 0.14 GB: 5.4 ms at 819 GB/s, experts 72 % of it
    assert b["total"] / 819e9 == pytest.approx(5.45e-3, rel=0.01)
    assert b["experts"] / b["total"] == pytest.approx(0.722, abs=0.005)


def test_grouped_matmul_cost_and_floor():
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    # decode: 64 tokens x 8 = 512 rows, every expert's [2048, 1024] kernel read
    c = ob.grouped_matmul_cost(CELL, m=512, k=2048, n=1024)
    assert c["flops"] == 2 * 512 * 2048 * 1024
    assert c["bytes"] == pytest.approx(
        (512 * 2048 + 64 * 2048 * 1024 + 512 * 1024) * 2, rel=3e-4)
    floor = ob.grouped_matmul_floor_s(CELL, peaks, m=512, k=2048, n=1024)
    assert floor == pytest.approx(c["bytes"] / 819e9) == pytest.approx(0.3316e-3, rel=2e-3)
    # scoring: 12,288 tokens x 8 rows are bound by operations
    floor = ob.grouped_matmul_floor_s(CELL, peaks, m=98304, k=1024, n=2048)
    assert floor == pytest.approx(2 * 98304 * 1024 * 2048 / 197e12)
