"""Operations and bytes of LFM2-MoE's layers as one pipeline stage holds
them, from shapes: conv layers (an input projection of three parts, K
depthwise taps, an output projection, a state of K - 1 values a row) beside
GQA attention layers of heads of `hidden_size / num_attention_heads` with a
per-head q/k norm; `num_dense_layers` leading layers with a dense SwiGLU,
the rest a router with a bias and every one of the `num_experts` experts of
width `moe_intermediate_size`; a head tied to the embedding. Everything is
a function of the configuration file's keys and of what the run observed
(rows, slots read, experts reached).
"""

from __future__ import annotations


def widths(cfg: dict) -> dict:
    L = cfg["num_hidden_layers"]
    kinds = cfg["layer_types"][:L]
    Lc = sum(1 for t in kinds if t == "conv")
    dense = int(cfg.get("num_dense_layers") or 0)
    D, H = cfg["hidden_size"], cfg["num_attention_heads"]
    return dict(
        D=D, F=cfg["moe_intermediate_size"], Fd=cfg["intermediate_size"],
        V=cfg["vocab_size"], H=H, KV=cfg["num_key_value_heads"],
        hd=cfg.get("head_dim") or D // H, L=L, Lc=Lc, La=L - Lc, Ld=dense,
        Le=L - dense, E=cfg["num_experts"], k=cfg["num_experts_per_tok"],
        K=cfg["conv_L_cache"], tied=bool(cfg.get("tie_word_embeddings", True)))


def conv_params(cfg: dict) -> int:
    """in_proj [D, 3D], the taps [K, D], out_proj [D, D]."""
    w = widths(cfg)
    return w["D"] * 3 * w["D"] + w["K"] * w["D"] + w["D"] * w["D"]


def attention_params(cfg: dict) -> int:
    """q, k, v, o and the two per-head norms."""
    w = widths(cfg)
    return (2 * w["D"] * w["H"] * w["hd"] + 2 * w["D"] * w["KV"] * w["hd"]
            + 2 * w["hd"])


def expert_params(cfg: dict) -> int:
    """ONE expert's three kernels."""
    w = widths(cfg)
    return 3 * w["D"] * w["F"]


def beside_params(cfg: dict) -> float:
    """Every weight a decode step reads whatever its rows choose: the
    operators, each layer's two norms, the dense MLPs, the routers with
    their biases."""
    w = widths(cfg)
    return (w["Lc"] * conv_params(cfg) + w["La"] * attention_params(cfg)
            + w["L"] * 2 * w["D"] + w["Ld"] * 3 * w["D"] * w["Fd"]
            + w["Le"] * (w["D"] * w["E"] + w["E"]))


def n_params(cfg: dict) -> float:
    w = widths(cfg)
    return (w["V"] * w["D"] + beside_params(cfg)
            + w["Le"] * w["E"] * expert_params(cfg) + w["D"]
            + (0 if w["tied"] else w["D"] * w["V"]))


def kv_bytes_per_token_layer(cfg: dict, dtype_bytes: int = 2) -> int:
    """K and V of one token in one attention layer (two heads of 64 a
    128-lane row: the same bytes)."""
    w = widths(cfg)
    return 2 * w["KV"] * w["hd"] * dtype_bytes


def state_bytes_per_row(cfg: dict, dtype_bytes: int = 2) -> int:
    """The conv state of one row over every conv layer: K - 1 values of g."""
    w = widths(cfg)
    return w["Lc"] * (w["K"] - 1) * w["D"] * dtype_bytes


def decode_step_bytes(cfg: dict, *, rows: float, experts_hit: float,
                      slots: float, dtype_bytes: int = 2) -> dict:
    """Bytes one decode step MUST move, by part: the weights beside the
    experts (`beside_params`); the expert kernels some live row reached
    (`experts_hit` an expert layer: what the program counted), each once;
    the K and V slots inside the bounds of an attention layer (`slots`,
    summed over the live rows: what the session counted); the live rows'
    state, read and written; the tied head, the final norm and the live
    rows' f32 logits. The embedding gather is left out. A floor."""
    w = widths(cfg)
    beside = beside_params(cfg) * dtype_bytes
    experts = w["Le"] * experts_hit * expert_params(cfg) * dtype_bytes
    kv = w["La"] * slots * kv_bytes_per_token_layer(cfg, dtype_bytes)
    state = 2 * rows * state_bytes_per_row(cfg, dtype_bytes)
    head = (w["D"] * w["V"] + w["D"]) * dtype_bytes + rows * w["V"] * 4
    parts = {"operators_dense_router": beside, "experts": experts, "kv": kv,
             "state": state, "head": head}
    return {**parts, "total": sum(parts.values())}


def experts_hit_expected(cfg: dict, tokens: float) -> float:
    """Expected experts of a layer that at least one of `tokens` tokens
    reaches under a uniform router: a prefill piece's calls, which nobody
    counts (from a few hundred tokens on: all of them)."""
    w = widths(cfg)
    return w["E"] * (1.0 - (1.0 - w["k"] / w["E"]) ** tokens)


def grouped_matmul_cost(cfg: dict, *, m: int, k: int, n: int,
                        tokens: float | None = None,
                        kernels: float | None = None,
                        dtype_bytes: int = 2) -> dict:
    """One call of the grouped matmul at 2,048 x 1,536 (or back): `m` sorted
    assignment rows [m, k] against the kernels [k, n] their tokens reach.
    `tokens`: how many of the call's m / top_k tokens were dispatched (a
    decode step runs every resident row and dispatches the live ones;
    default all); `kernels`: the kernels those reached where the run counted
    it, else a uniform router's expectation. Operations 2 x rows x k x n;
    bytes: the rows in and out and each reached kernel once."""
    w = widths(cfg)
    tokens = max(m // w["k"], 1) if tokens is None else tokens
    rows = tokens * w["k"]
    if kernels is None:
        kernels = experts_hit_expected(cfg, tokens)
    return {"flops": 2.0 * rows * k * n,
            "bytes": (rows * k + kernels * k * n + rows * n) * dtype_bytes}


def grouped_matmul_floor_s(cfg: dict, peaks: dict, **kw) -> float:
    """The larger of operations over the bf16 peak and bytes over the HBM
    bandwidth (`ops_bytes_moe.grouped_matmul_floor_s`'s rule)."""
    c = grouped_matmul_cost(cfg, **kw)
    return max(c["flops"] / peaks["bf16_flops_per_s"],
               c["bytes"] / peaks["hbm_bytes_per_s"])
