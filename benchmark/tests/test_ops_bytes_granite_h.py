"""harness/ops_bytes_granite_h.py against hand counts at granite-4.0-h-small's
published sizes (ISSUE 59's arithmetic): the two kinds of layer, the chip's
share, a state a row and a page a token, a decode step's bytes, the kernels'
floors."""

import json
import os

import pytest

from harness import ops_bytes_granite_h as ob

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def c():
    return json.load(open(os.path.join(
        BENCH, "configs", "granite-4.0-h-small-ep2-l10.json")))


def test_the_models_sizes(c):
    w = ob.widths(c)
    assert (w["L"], w["Lm"], w["La"], w["held"], w["E"], w["k"]) \
        == (10, 9, 1, 36, 72, 10)
    assert (w["I"], w["W"], w["hd"]) == (8192, 8448, 128)
    assert ob.mixer_params(c) == 4096 * 16768 + 8192 * 4096 + 8448 * 5 \
        + 3 * 128 + 8192 == 102_286_976
    assert ob.attention_params(c) == 2 * 4096 ** 2 + 2 * 4096 * 1024 \
        == 41_943_040
    assert ob.expert_params(c) == 3 * 4096 * 768 == 9_437_184
    assert ob.beside_params(c) == 18_874_368 + 294_912 + 8192
    # the whole model's layers, every expert counted: 801 M and 741 M
    assert ob.layer_params(c, "mamba", experts=72) == 800_941_696
    assert ob.layer_params(c, "attention", experts=72) == 740_597_760
    # this chip: 9 x 461.2 M + 400.9 M + 205.5 M of embedding = 9.51 GB
    assert round(ob.layer_params(c, "mamba") / 1e6, 1) == 461.2
    assert round(ob.layer_params(c, "attention") / 1e6, 1) == 400.9
    assert round(ob.n_params(c) * 2 / 1e9, 2) == 9.51


def test_a_state_a_row_and_a_page_a_token(c):
    assert ob.kv_bytes_per_token_layer(c) == 2 * 8 * 128 * 2 == 4096
    assert ob.kv_bytes_per_token(c) == 4096         # ONE layer of ten
    leaf = ob.state_bytes_per_row_layer(c)
    assert leaf == {"recurrent": 128 * 64 * 128 * 4, "tail": 3 * 8448 * 2}
    assert ob.state_bytes_per_row(c) == 9 * (4_194_304 + 50_688) \
        == 38_204_928
    assert round(48 * ob.state_bytes_per_row(c) / 1e9, 2) == 1.83


def test_a_decode_steps_bytes_by_part(c):
    b = ob.decode_step_bytes(c, rows=35, slots=35 * 2400, experts_hit=36)
    assert b["total"] == sum(v for k, v in b.items() if k != "total")
    assert b["mixer"] == 9 * 102_286_976 * 2
    assert b["experts"] == 10 * 36 * 9_437_184 * 2
    assert b["state"] == 2 * 35 * 38_204_928
    assert b["kv"] == 35 * 2400 * 4096
    # ISSUE 59's shares at 35 live rows: mixers' weights and state ~36 %,
    # the held experts ~54 %, the one attention layer's pages under 4 %
    assert 0.33 < (b["mixer"] + b["state"]) / b["total"] < 0.38
    assert 0.50 < b["experts"] / b["total"] < 0.56
    assert b["kv"] / b["total"] < 0.04
    assert 14 < 1e3 * b["total"] / PEAKS["hbm_bytes_per_s"] < 16.5   # ms


def test_the_kernels_floors(c):
    # the state pass: a row's S and tail read and written once, a layer
    rows = 35
    assert ob.ssm_update_bytes(c, rows=rows) == rows * (
        2 * (4_194_304 + 50_688) + (2 * 8192 + 2 * 128 + 128) * 4)
    assert ob.ssm_update_floor_s(c, PEAKS, rows=rows) == pytest.approx(
        ob.ssm_update_bytes(c, rows=rows) / 819e9)
    # a 1,024-token piece's recurrence, a layer: 5 P N a head a token
    cost = ob.ssd_scan_cost(c, tokens=1024, pieces=1)
    assert cost["flops"] == 5 * 128 * 64 * 128 * 1024
    assert cost["bytes"] == 1024 * (2 * 8192 + 2 * 128 + 128) * 4 \
        + 2 * 4_194_304
    assert ob.ssd_scan_floor_s(c, PEAKS, tokens=1024, pieces=1) \
        == pytest.approx(cost["bytes"] / 819e9)     # bound by its bytes
    # the grouped matmul of a decode step: 35 live rows x 10 assignments,
    # half on held experts, every held kernel reached
    g = ob.grouped_matmul_cost(c, m=512, k=4096, n=768, tokens=35, kernels=36)
    assert g["flops"] == 2.0 * 175 * 4096 * 768
    assert g["bytes"] == (175 * 4096 + 36 * 4096 * 768 + 175 * 768) * 2
    assert ob.grouped_matmul_floor_s(c, PEAKS, m=512, k=4096, n=768,
                                     tokens=35, kernels=36) \
        == pytest.approx(g["bytes"] / 819e9)
    # a prefill piece's: every held kernel reached, and 5,120 rows of 768
    # still pay less in operations than the 36 kernels cost in bytes
    assert ob.held_experts_hit(c, 1024) == pytest.approx(36.0)
    p = ob.grouped_matmul_cost(c, m=10240, k=4096, n=768)
    assert p["flops"] == 2.0 * 5120 * 4096 * 768
    assert p["flops"] / 197e12 < p["bytes"] / 819e9 < 2.2 * p["flops"] / 197e12
    assert ob.paged_read_bytes(c, slots=1000) == 4_096_000
