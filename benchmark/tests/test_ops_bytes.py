"""ops_bytes.py against hand-worked values for both configurations."""

import json
import os

import pytest

from harness import ops_bytes

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
C15 = json.load(open(os.path.join(BENCH, "configs", "qwen2.5-1.5b.json")))
C7 = json.load(open(os.path.join(BENCH, "configs", "qwen2.5-7b-x4.json")))
C7 = dict(C7, num_hidden_layers=28)     # the published depth; the cell cuts it


def test_parameter_counts_are_the_published_ones():
    # embed 151936*1536 = 233,373,696; a layer: q 2,359,296+1536, k and v
    # 393,216+256 each, o 2,359,296, mlp 3*13,762,560, norms 3072
    # = 46,797,824; x28 + final norm 1536
    assert ops_bytes.n_params(C15) == 1_543_714_304
    # embed and head 152064*3584 = 544,997,376 each; a layer 233,057,792
    assert ops_bytes.n_params(C7) == 7_615_616_512
    assert ops_bytes.layer_matmul_params(C15) == 46_792_704
    assert ops_bytes.layer_matmul_params(C7) == 233_046_016
    assert ops_bytes.weight_bytes(C7) == 15_231_233_024      # the 15.2 GB


def test_kv_and_lora_sizes():
    assert ops_bytes.kv_bytes_per_token(C15) == 2 * 28 * 2 * 128 * 2 == 28_672
    assert ops_bytes.kv_bytes_per_token(C7) == 57_344
    # r=64: per layer 64 * sum(in+out) = 64 * (3072+1792+1792+3072+3*10496)
    assert ops_bytes.lora_params(C15, 64) == 28 * 64 * 41_216 == 73_859_072


def test_forward_flops_per_token():
    # 2 * 28 * 46,792,704 + attention 4*28*12*128*ctx + head 2*1536*151936
    f = ops_bytes.forward_flops_per_token(C15, context=500)
    assert f == 2 * 28 * 46_792_704 + 4 * 28 * 12 * 128 * 500 + 2 * 1536 * 151936
    assert f == pytest.approx(3.173e9, rel=1e-3)


def test_decode_step_bytes_1_5b():
    b = ops_bytes.decode_step_bytes(C15, rows=128, filled_mean=416, lora_r=64)
    # weights: 28 layers * (46,792,704 + 2048 biases + 3072 norms) + 1536
    # + head 233,373,696 = 1,543,714,304 -> x2 bytes, + LoRA 73,859,072 x2
    assert b["weights"] == 2 * (1_543_714_304 + 73_859_072)
    assert b["kv"] == 128 * 416 * 28_672
    assert b["logits"] == 128 * 151936 * 4
    assert b["total"] / 819e9 == pytest.approx(5.91e-3, rel=5e-3)   # the floor, s


def test_grpo_update_flops_phases():
    f = ops_bytes.grpo_update_flops(
        C15, prompts=32, sample_n=4, context=256, prompt_mean=160,
        response=512, kept_rows=32, lora_r=64)
    assert f["total"] == f["prefill"] + f["decode"] + f["score"] + f["update"]
    body = 2 * 28 * 46_792_704
    lora, head = 2 * 73_859_072, 2 * 1536 * 151936
    decode = 128 * 512 * (body + 4 * 28 * 12 * 128 * (160 + 256) + lora + head)
    assert f["decode"] == pytest.approx(decode)
    # 65,536 decoded tokens against 4 passes (2 scoring, forward + backward)
    # over 32 x 768 = 24,576 kept tokens: decode is under half the operations
    seq = 32 * 768 * (body + 4 * 28 * 12 * 128 * 384 + lora)
    assert f["score"] == pytest.approx(2 * (seq + 32 * 512 * head))
    assert f["update"] == pytest.approx(
        2 * seq + 2 * 32 * 768 * lora + 3 * 32 * 512 * head)
    assert f["decode"] / f["total"] == pytest.approx(0.384, abs=0.005)


def test_unknown_device_kind_is_an_error():
    assert ops_bytes.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert ops_bytes.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks"):
        ops_bytes.peaks("TPU v9 imaginary")
