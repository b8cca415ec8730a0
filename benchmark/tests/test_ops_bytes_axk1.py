"""ops_bytes_axk1.py against hand-worked values for A.X-K1 as one chip of
sixteen holds it (ISSUE 31's arithmetic)."""

import json
import os

import pytest

from harness import ops_bytes_axk1 as ob

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = json.load(open(os.path.join(BENCH, "configs", "axk1-ep16.json")))


def test_parameter_counts_are_the_issues():
    # q_a 7168*1536 = 11,010,048; q_b 1536*64*192 = 18,874,368; kv_a
    # 7168*576 = 4,128,768; kv_b 512*64*256 = 8,388,608; o 8192*7168 =
    # 58,720,256: 101,122,048 (the 101.1 M) + norms 1536 + 512 + 2*7168
    assert ob.mla_params(CELL) == 101_122_048 + 16_384
    assert ob.expert_params(CELL) == 3 * 7168 * 2048 == 44_040_192
    assert ob.dense_layer_params(CELL) == ob.mla_params(CELL) + 3 * 7168 * 18432
    assert ob.dense_layer_params(CELL) / 1e6 == pytest.approx(497.5, abs=0.1)
    # beside the routed experts: MLA + router 7168*192 + the shared expert
    beside = ob.expert_layer_params(CELL, experts=0)
    assert beside == ob.mla_params(CELL) + 1_376_256 + 44_040_192
    assert beside / 1e6 == pytest.approx(146.5, abs=0.1)
    assert ob.expert_layer_params(CELL) / 1e6 == pytest.approx(675.0, abs=0.1)
    # the dense layer + 6 expert layers + vocabulary/8 twice + final norm
    assert ob.n_params(CELL) * 2 / 1e9 == pytest.approx(9.68, abs=0.01)


def test_the_cache_is_the_latent():
    assert ob.kv_bytes_per_token(CELL) == 7 * 576 * 2 == 8_064
    assert ob.per_head_kv_bytes_per_token(CELL) == 7 * 64 * 320 * 2 == 286_720
    assert ob.per_head_kv_bytes_per_token(CELL) / ob.kv_bytes_per_token(CELL) \
        == pytest.approx(35.6, abs=0.05)


def test_held_experts_hit():
    assert ob.held_experts_hit(CELL, 1) == pytest.approx(12 * 8 / 192)
    assert ob.held_experts_hit(CELL, 32) == pytest.approx(
        12 * (1 - (23 / 24) ** 32))
    assert ob.held_experts_hit(CELL, 32) / 12 == pytest.approx(0.744, abs=1e-3)
    whole = dict(CELL, n_routed_experts_held=0)
    assert ob.held_experts_hit(whole, 32) == pytest.approx(16 * ob.held_experts_hit(CELL, 32))


def test_decode_step_bytes_by_part():
    b = ob.decode_step_bytes(CELL, rows=32, filled_mean=4000,
                             experts_hit=ob.held_experts_hit(CELL, 32))
    assert b["dense_layers"] == ob.dense_layer_params(CELL) * 2
    assert b["attention_router_shared"] == 6 * ob.expert_layer_params(CELL, 0) * 2
    assert b["experts"] == pytest.approx(
        6 * 12 * 0.7439 * 44_040_192 * 2, rel=1e-3)
    assert b["kv"] == 32 * 4000 * 8_064
    assert b["head"] == (7168 * 20480 + 7168) * 2 + 32 * 20480 * 4
    assert b["total"] == sum(v for k, v in b.items() if k != "total")
    # 0.995 + 1.76 + 4.72 + 1.03 + 0.30 GB: 10.7 ms at 819 GB/s
    assert b["total"] / 819e9 == pytest.approx(10.7e-3, rel=0.02)
    fewer = ob.decode_step_bytes(CELL, rows=32, filled_mean=4000, experts_hit=6.0)
    assert fewer["experts"] == 6 * 6.0 * 44_040_192 * 2


def test_grouped_matmul_cost_and_floor():
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    # decode: 32 rows x 8 = 256 assignment rows, 16 of them held's
    c = ob.grouped_matmul_cost(CELL, m=256, k=7168, n=2048)
    assert c["flops"] == 2 * 16 * 7168 * 2048
    assert c["bytes"] == pytest.approx(
        (16 * 7168 + 8.927 * 7168 * 2048 + 16 * 2048) * 2, rel=1e-3)
    floor = ob.grouped_matmul_floor_s(CELL, peaks, m=256, k=7168, n=2048)
    assert floor == c["bytes"] / 819e9          # bound by the kernels' bytes
    assert floor == pytest.approx(0.32e-3, rel=0.02)
    # of the 32 rows 4.5 live: 1.8 rows of the held experts, 2.09 kernels
    c = ob.grouped_matmul_cost(CELL, m=256, k=7168, n=2048, tokens=4.5)
    assert c["flops"] == pytest.approx(2 * 2.25 * 7168 * 2048)
    assert ob.held_experts_hit(CELL, 4.5) == pytest.approx(2.09, abs=0.01)
    assert ob.grouped_matmul_floor_s(CELL, peaks, m=256, k=7168, n=2048,
                                     tokens=4.5) == pytest.approx(0.075e-3, rel=0.03)
    # ... or the kernels the run counted: 3 of them, 88 MB in 0.1075 ms
    counted = ob.grouped_matmul_cost(CELL, m=256, k=7168, n=2048, tokens=4.5,
                                     kernels=3.0)
    assert counted["flops"] == c["flops"]
    assert counted["bytes"] - c["bytes"] == pytest.approx(
        (3.0 - ob.held_experts_hit(CELL, 4.5)) * 7168 * 2048 * 2)
    assert ob.grouped_matmul_floor_s(
        CELL, peaks, m=256, k=7168, n=2048, tokens=4.5,
        kernels=3.0) == pytest.approx(3 * 7168 * 2048 * 2 / 819e9, rel=1e-3)
    # a prefill chunk: 1,024 tokens reach every held expert; 512 real rows
    c = ob.grouped_matmul_cost(CELL, m=8192, k=7168, n=2048)
    assert c["flops"] == 2 * 512 * 7168 * 2048
    assert ob.held_experts_hit(CELL, 1024) == pytest.approx(12.0, abs=1e-6)
    floor = ob.grouped_matmul_floor_s(CELL, peaks, m=8192, k=7168, n=2048)
    assert floor == c["bytes"] / 819e9 > c["flops"] / 197e12
