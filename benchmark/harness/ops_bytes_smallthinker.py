"""Operations and bytes of SmallThinker's layers as one pipeline stage holds
them, from shapes: GQA attention without biases, a router, every one of the
`moe_num_primary_experts` experts of width `moe_ffn_hidden_size` (ReLU-gated,
three kernels), two kinds of attention layer whose caches differ in what a
step reads. Everything is a function of the configuration file's keys and of
what the run observed (rows, slots read, experts reached).
"""

from __future__ import annotations


def widths(cfg: dict) -> dict:
    L = cfg["num_hidden_layers"]
    Lw = sum(1 for w in cfg["sliding_window_layout"][:L] if w)
    return dict(
        D=cfg["hidden_size"], F=cfg["moe_ffn_hidden_size"],
        V=cfg["vocab_size"], H=cfg["num_attention_heads"],
        KV=cfg["num_key_value_heads"], hd=cfg["head_dim"], L=L, Lw=Lw,
        Lg=L - Lw, E=cfg["moe_num_primary_experts"],
        k=cfg["moe_num_active_primary_experts"],
        W=cfg["sliding_window_size"],
        tied=bool(cfg.get("tie_word_embeddings")))


def attention_params(cfg: dict) -> int:
    """q, k, v, o and the layer's two norms."""
    w = widths(cfg)
    return (2 * w["D"] * w["H"] * w["hd"] + 2 * w["D"] * w["KV"] * w["hd"]
            + 2 * w["D"])


def expert_params(cfg: dict) -> int:
    """ONE expert's three kernels."""
    w = widths(cfg)
    return 3 * w["D"] * w["F"]


def layer_params(cfg: dict, experts: float | None = None) -> float:
    w = widths(cfg)
    n = w["E"] if experts is None else experts
    return attention_params(cfg) + w["D"] * w["E"] + n * expert_params(cfg)


def n_params(cfg: dict) -> float:
    w = widths(cfg)
    return (w["V"] * w["D"] + w["L"] * layer_params(cfg) + w["D"]
            + (0 if w["tied"] else w["D"] * w["V"]))


def kv_bytes_per_token_layer(cfg: dict, dtype_bytes: int = 2) -> int:
    """K and V of one token in one layer."""
    w = widths(cfg)
    return 2 * w["KV"] * w["hd"] * dtype_bytes


def decode_step_bytes(cfg: dict, *, rows: float, experts_hit: float,
                      global_slots: float, window_slots: float,
                      dtype_bytes: int = 2) -> dict:
    """Bytes one decode step MUST move, by part: every layer's attention
    projections, norms and router; the expert kernels some live row reached
    (`experts_hit` a layer: what the program counted), each once; the K and
    V slots inside the bounds, `global_slots` a global layer and
    `window_slots` a window layer (summed over the live rows: what the
    session counted); the head, the final norm and the live rows' f32
    logits. The embedding gather is left out. A floor."""
    w = widths(cfg)
    beside = w["L"] * layer_params(cfg, experts=0) * dtype_bytes
    experts = w["L"] * experts_hit * expert_params(cfg) * dtype_bytes
    per = kv_bytes_per_token_layer(cfg, dtype_bytes)
    kv = (w["Lg"] * global_slots + w["Lw"] * window_slots) * per
    head = (w["D"] * w["V"] + w["D"]) * dtype_bytes + rows * w["V"] * 4
    parts = {"attention_router": beside, "experts": experts, "kv": kv,
             "head": head}
    return {**parts, "total": sum(parts.values())}


def experts_hit_expected(cfg: dict, tokens: float) -> float:
    """Expected experts of a layer that at least one of `tokens` tokens
    reaches under a uniform router: a prefill chunk's calls, which nobody
    counts (from a few hundred tokens on: all of them)."""
    w = widths(cfg)
    return w["E"] * (1.0 - (1.0 - w["k"] / w["E"]) ** tokens)


def grouped_matmul_cost(cfg: dict, *, m: int, k: int, n: int,
                        tokens: float | None = None,
                        kernels: float | None = None,
                        dtype_bytes: int = 2) -> dict:
    """One call of the grouped matmul: `m` sorted assignment rows [m, k]
    against the kernels [k, n] their tokens reach. `tokens`: how many of the
    call's m / top_k tokens were dispatched (a decode step runs every
    resident row and dispatches the live ones; default all); `kernels`: the
    kernels those reached where the run counted it, else a uniform router's
    expectation. Operations 2 x rows x k x n; bytes: the rows in and out and
    each reached kernel once."""
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
