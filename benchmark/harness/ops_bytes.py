"""Operations and bytes from shapes: the benchmark's own arithmetic.

Everything is a function of the configuration file's widths and the traffic
file's sizes; nothing is read from the program. Recomputation (gradient
checkpointing) is never counted. `peaks()` is the table of chip peaks, keyed
by the exact `device_kind`; a kind that is not in it is an error.
"""

from __future__ import annotations

import json
import os

PROJECTIONS = ("q_proj", "k_proj", "v_proj", "o_proj",
               "gate_proj", "up_proj", "down_proj")


def peaks(device_kind: str) -> dict:
    with open(os.path.join(os.path.dirname(__file__), "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(
            f"no peaks for device_kind {device_kind!r} in peaks.json "
            f"({sorted(table)}): add the kind with its source")
    return table[device_kind]


def widths(cfg: dict) -> dict:
    D, H, KV = (cfg["hidden_size"], cfg["num_attention_heads"],
                cfg["num_key_value_heads"])
    hd = cfg.get("head_dim") or D // H
    return dict(D=D, F=cfg["intermediate_size"], V=cfg["vocab_size"], H=H,
                KV=KV, hd=hd, L=cfg["num_hidden_layers"],
                tied=bool(cfg["tie_word_embeddings"]))


def proj_dims(cfg: dict) -> dict:
    w = widths(cfg)
    D, F, H, KV, hd = w["D"], w["F"], w["H"], w["KV"], w["hd"]
    return {"q_proj": (D, H * hd), "k_proj": (D, KV * hd),
            "v_proj": (D, KV * hd), "o_proj": (H * hd, D),
            "gate_proj": (D, F), "up_proj": (D, F), "down_proj": (F, D)}


def layer_matmul_params(cfg: dict) -> int:
    """Weights of one layer's seven projections (what a token multiplies)."""
    return sum(a * b for a, b in proj_dims(cfg).values())


def n_params(cfg: dict) -> int:
    """Every parameter of the model, biases and norms included."""
    w = widths(cfg)
    D, V, L, H, KV, hd = w["D"], w["V"], w["L"], w["H"], w["KV"], w["hd"]
    per_layer = layer_matmul_params(cfg) + (H + 2 * KV) * hd + 2 * D
    return V * D + L * per_layer + D + (0 if w["tied"] else D * V)


def lora_params(cfg: dict, r: int) -> int:
    return widths(cfg)["L"] * sum(r * (a + b) for a, b in proj_dims(cfg).values())


def forward_flops_per_token(cfg: dict, context: float, head: bool = True) -> float:
    """2 x (matmul weights a token meets) + attention against `context` keys
    (QK^T and PV: 4 x H x hd per key and layer); `head` adds the D x V
    output projection."""
    w = widths(cfg)
    flops = 2.0 * w["L"] * layer_matmul_params(cfg)
    flops += 4.0 * w["L"] * w["H"] * w["hd"] * context
    if head:
        flops += 2.0 * w["D"] * w["V"]
    return flops


def grpo_update_flops(cfg: dict, *, prompts: int, sample_n: int,
                      context: int, prompt_mean: float, response: int,
                      kept_rows: int, lora_r: int) -> dict:
    """Model operations one GRPO update requires, by phase.

    rollout: one prefill per PROMPT (the samples share it) over the padded
    context, then `response` decode steps for prompts x sample_n rows, each
    through every layer and the head, attending to the real prompt plus the
    tokens generated so far (mean response/2).
    score: policy and reference forward over the kept rows, all layers on
    every position, the head on response positions only.
    update: forward, backward through the activations (the same matmuls
    again) and weight gradients only where weights train: the LoRA factors
    and the output embedding (response positions). Recomputation under
    gradient checkpointing is not counted.
    """
    w = widths(cfg)
    rows = prompts * sample_n
    total = context + response
    body = lambda ctx: forward_flops_per_token(cfg, ctx, head=False)  # noqa: E731
    head = 2.0 * w["D"] * w["V"]
    lora = 2.0 * lora_params(cfg, lora_r)
    prefill = prompts * (context * (body(prompt_mean / 2) + lora) + head)
    decode = rows * response * (body(prompt_mean + response / 2) + lora + head)
    seq = kept_rows * total * (body(total / 2) + lora)
    score = 2 * (seq + kept_rows * response * head)
    update = (2 * seq + kept_rows * total * lora       # fwd + bwd activations
              + kept_rows * total * lora               # LoRA weight grads
              + 3 * kept_rows * response * head)       # fwd, bwd act, dW
    return {"prefill": prefill, "decode": decode, "score": score,
            "update": update,
            "total": prefill + decode + score + update}


def kv_bytes_per_token(cfg: dict, dtype_bytes: int = 2) -> int:
    w = widths(cfg)
    return 2 * w["L"] * w["KV"] * w["hd"] * dtype_bytes


def weight_bytes(cfg: dict, dtype_bytes: int = 2) -> int:
    return n_params(cfg) * dtype_bytes


def decode_step_bytes(cfg: dict, *, rows: int, filled_mean: float,
                      lora_r: int = 0, dtype_bytes: int = 2) -> dict:
    """Bytes one decode step MUST move: every weight a token meets once
    (layers, norms, biases, the V x D output matrix, the LoRA factors), the
    filled part of every row's KV cache, and the f32 logits written once.
    The embedding gather (rows x D) is left out. A floor, not what the
    program moves: a masked full-width cache read moves more."""
    w = widths(cfg)
    D, V, L, H, KV, hd = w["D"], w["V"], w["L"], w["H"], w["KV"], w["hd"]
    per_layer = layer_matmul_params(cfg) + (H + 2 * KV) * hd + 2 * D
    weights = (L * per_layer + D + D * V) * dtype_bytes
    weights += lora_params(cfg, lora_r) * dtype_bytes
    kv = rows * filled_mean * kv_bytes_per_token(cfg, dtype_bytes)
    logits = rows * V * 4
    return {"weights": weights, "kv": kv, "logits": logits,
            "total": weights + kv + logits}
