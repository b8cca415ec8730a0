"""harness/ops_bytes_ouro.py against hand counts at Ouro-2.6B's published
sizes (ISSUE 55's arithmetic): the shared stack, the cache of a slot a pass a
layer, a decode step's bytes, an admission's operations."""

import json
import os

import pytest

from harness import ops_bytes_ouro as ob

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def c():
    return json.load(open(os.path.join(BENCH, "configs", "ouro-2.6b.json")))


def test_the_models_sizes(c):
    assert ob.widths(c)["T"] == 4 and ob.cache_layers(c) == 192
    assert ob.layer_matmul_params(c) == 4 * 2048 ** 2 + 3 * 2048 * 5632 \
        == 51_380_224
    assert ob.layer_params(c) == 51_380_224 + 4 * 2048
    assert round(ob.stack_params(c) / 1e6, 1) == 2466.6
    assert ob.head_params(c) == 2048 * 49152 == 100_663_296
    assert ob.n_params(c) == 48 * (51_380_224 + 8192) + 2 * 100_663_296 \
        + 2048 + 2048 + 1
    assert round(ob.n_params(c) * 2 / 1e9, 2) == 5.34


def test_the_cache_is_a_slot_a_pass_a_layer(c):
    assert ob.kv_bytes_per_token_layer(c) == 2 * 16 * 128 * 2 == 8192
    assert ob.kv_bytes_per_token(c) == 192 * 8192 == 1_572_864
    page = 128 * ob.kv_bytes_per_token(c)
    assert page == 192 * 2 ** 20                    # 192 MiB a page
    assert 8 * 5 * page == 8_053_063_680            # the cell's pool, 7.5 GiB
    assert ob.kv_bytes_per_token(c, itemsize=4) == 2 * 1_572_864


def test_a_decode_step_reads_the_stack_once_a_pass(c):
    b = ob.decode_step_bytes(c, rows=5, slots=1100)
    assert b["weights"] == 4 * (ob.stack_params(c) + 2048) * 2
    assert b["head"] == 100_663_296 * 2 + 5 * 49152 * 4
    assert b["kv_read"] == 1100 * 1_572_864
    assert b["kv_write"] == 5 * 1_572_864
    assert b["total"] == sum(v for k, v in b.items() if k != "total")
    assert round(b["total"] / 1e9, 1) == 21.7       # ISSUE 55's estimate
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert ob.decode_step_floor_s(c, peaks, 5, 1100) == pytest.approx(
        b["total"] / 819e9)
    assert 0.026 < ob.decode_step_floor_s(c, peaks, 5, 1100) < 0.027
    # the weights' four reads set the step, not the cache
    assert b["weights"] / b["total"] > 0.9


def test_an_admissions_operations(c):
    f = ob.admission_flops(c, tokens=256)
    assert f["matmuls"] == 2 * 256 * 4 * 48 * 51_380_224
    assert f["attention"] == 4 * 48 * 4 * 16 * 128 * 256 * 257 / 2
    assert f["head"] == 2 * 100_663_296
    assert round(f["total"] / 1e12, 1) == 5.1       # ISSUE 55's estimate
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert ob.admission_floor_s(c, peaks, 256) == pytest.approx(
        f["total"] / 197e12)
    # two forwards of 128 tokens: the same kernels' work, half the attention
    two = ob.admission_flops(c, tokens=256, forwards=2)
    assert two["matmuls"] == f["matmuls"] and two["head"] == 2 * f["head"]
    assert two["attention"] == 4 * 48 * 2 * 4 * 16 * 128 * 128 * 129 / 2
    # a 16-token admission is bound by its one read of the weights a pass
    small = ob.admission_floor_s(c, peaks, 16)
    assert small == pytest.approx((4 * ob.stack_params(c) + ob.head_params(c))
                                  * 2 / 819e9)
