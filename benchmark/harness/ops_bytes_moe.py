"""Operations and bytes of the sparse-expert layer (OLMoE), from shapes.

The sibling of harness/ops_bytes.py, which counts seven dense projections a
layer. Here a layer is four attention projections (no biases), the two
norms' and the QK-norm's vectors, a router `[D, E]` and E experts of three
kernels `[D, F]`, `[D, F]`, `[F, D]`, of which a token meets k. Everything is
a function of the configuration file's widths and the traffic file's sizes.
"""

from __future__ import annotations


def widths(cfg: dict) -> dict:
    D, H, KV = (cfg["hidden_size"], cfg["num_attention_heads"],
                cfg["num_key_value_heads"])
    hd = cfg.get("head_dim") or D // H
    return dict(D=D, F=cfg["intermediate_size"], V=cfg["vocab_size"], H=H,
                KV=KV, hd=hd, L=cfg["num_hidden_layers"], E=cfg["num_experts"],
                k=cfg["num_experts_per_tok"],
                tied=bool(cfg["tie_word_embeddings"]))


def attention_params(cfg: dict) -> int:
    """One layer's q, k, v, o kernels."""
    w = widths(cfg)
    return w["D"] * (2 * w["H"] + 2 * w["KV"]) * w["hd"]


def expert_params(cfg: dict) -> int:
    """ONE expert's three kernels."""
    w = widths(cfg)
    return 3 * w["D"] * w["F"]


def layer_params(cfg: dict) -> int:
    """Every parameter of one layer: attention, all E experts, the router,
    two norms of D, q_norm of H·hd and k_norm of KV·hd."""
    w = widths(cfg)
    return (attention_params(cfg) + w["E"] * expert_params(cfg)
            + w["D"] * w["E"] + 2 * w["D"] + (w["H"] + w["KV"]) * w["hd"])


def n_params(cfg: dict) -> int:
    w = widths(cfg)
    return (w["V"] * w["D"] + w["L"] * layer_params(cfg) + w["D"]
            + (0 if w["tied"] else w["D"] * w["V"]))


def lora_params(cfg: dict, r: int) -> int:
    """The adapter sits on the four attention projections only."""
    w = widths(cfg)
    dims = [(w["D"], w["H"] * w["hd"]), (w["D"], w["KV"] * w["hd"]),
            (w["D"], w["KV"] * w["hd"]), (w["H"] * w["hd"], w["D"])]
    return w["L"] * sum(r * (a + b) for a, b in dims)


def layer_matmul_flops_per_token(cfg: dict) -> dict:
    """2 x the kernel weights a token meets in one layer, by part: the k
    experts it is routed to, attention's projections, the router."""
    w = widths(cfg)
    return {"experts": 2.0 * w["k"] * expert_params(cfg),
            "attention": 2.0 * attention_params(cfg),
            "router": 2.0 * w["D"] * w["E"]}


def experts_hit(cfg: dict, rows: int) -> float:
    """Expected number of a layer's experts that at least one of `rows`
    tokens reaches, each token choosing k of E (taken as uniform): the
    experts whose kernels a step has to read."""
    w = widths(cfg)
    return w["E"] * (1.0 - (1.0 - w["k"] / w["E"]) ** rows)


def kv_bytes_per_token(cfg: dict, dtype_bytes: int = 2) -> int:
    w = widths(cfg)
    return 2 * w["L"] * w["KV"] * w["hd"] * dtype_bytes


def decode_step_bytes(cfg: dict, *, rows: int, filled_mean: float,
                      lora_r: int = 0, dtype_bytes: int = 2) -> dict:
    """Bytes one decode step of `rows` rows MUST move, by part: attention's
    kernels, norm vectors and adapter factors once; the kernels of every
    expert some row reaches (`experts_hit`: all 64 at 64 rows) once; the
    router; the filled part of every row's KV cache at its mean fill; the
    output matrix, the final norm and the f32 logits. The embedding gather
    (rows x D) is left out. A floor, not what the program moves."""
    w = widths(cfg)
    D, V, L, H, KV, hd, E = (w[k] for k in ("D", "V", "L", "H", "KV", "hd", "E"))
    attention = L * (attention_params(cfg) + 2 * D + (H + KV) * hd) * dtype_bytes
    attention += lora_params(cfg, lora_r) * dtype_bytes
    experts = L * experts_hit(cfg, rows) * expert_params(cfg) * dtype_bytes
    router = L * D * E * dtype_bytes
    kv = rows * filled_mean * kv_bytes_per_token(cfg, dtype_bytes)
    head = (D * V + D) * dtype_bytes + rows * V * 4
    parts = {"attention": attention, "experts": experts, "router": router,
             "kv": kv, "head": head}
    return {**parts, "total": sum(parts.values())}


def grouped_matmul_cost(cfg: dict, *, m: int, k: int, n: int,
                        dtype_bytes: int = 2) -> dict:
    """One call of the grouped matmul kernel (`gmm`): `m` sorted assignment
    rows `[m, k]` against the kernels `[k, n]` of the experts they reach.
    Operations 2·m·k·n; bytes: the rows in and out once, and each kernel
    that one of the m / top_k tokens reaches once (`experts_hit`)."""
    w = widths(cfg)
    kernels = experts_hit(cfg, max(m // w["k"], 1))
    return {"flops": 2.0 * m * k * n,
            "bytes": (m * k + kernels * k * n + m * n) * dtype_bytes}


def grouped_matmul_floor_s(cfg: dict, peaks: dict, *, m: int, k: int,
                           n: int) -> float:
    """The least seconds the chip could take for that call: the larger of
    operations over the bf16 peak and bytes over the HBM bandwidth."""
    c = grouped_matmul_cost(cfg, m=m, k=k, n=n)
    return max(c["flops"] / peaks["bf16_flops_per_s"],
               c["bytes"] / peaks["hbm_bytes_per_s"])
