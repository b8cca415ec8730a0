"""Operations and bytes of SDAR-MoE's layers (`model_type: sdar_moe`,
docs/BLOCKDIFF.md) from shapes: GQA attention with per-head q/k norms, a
router over all `num_experts` and the routed experts a forward's live rows
reach, every layer alike; and of one BLOCK FORWARD, the unit the session's
chunk program runs: `block_length` tokens a live row against the row's
committed slots and the block's own. Everything is a function of the
configuration file's keys and of what the run observed (live rows, experts
reached, pages read).
"""

from __future__ import annotations


def widths(cfg: dict) -> dict:
    return dict(
        D=cfg["hidden_size"], Fe=cfg["moe_intermediate_size"],
        V=cfg["vocab_size"], H=cfg["num_attention_heads"],
        KV=cfg["num_key_value_heads"], hd=cfg["head_dim"],
        L=cfg["num_hidden_layers"], Le=cfg["num_hidden_layers"],
        E=cfg["num_experts"], k=cfg["num_experts_per_tok"],
        B=int(cfg["assumed"]["block_length"]) if "assumed" in cfg
        else int(cfg["block_length"]),
        tied=bool(cfg.get("tie_word_embeddings")))


def attention_params(cfg: dict) -> int:
    """q and o (hidden x heads x head_dim each), k and v."""
    w = widths(cfg)
    return 2 * w["D"] * w["H"] * w["hd"] + 2 * w["D"] * w["KV"] * w["hd"]


def norm_params(cfg: dict) -> int:
    """Two norms of D and two per-head norms of head_dim."""
    w = widths(cfg)
    return 2 * w["D"] + 2 * w["hd"]


def expert_params(cfg: dict) -> int:
    """ONE routed expert's three kernels."""
    w = widths(cfg)
    return 3 * w["D"] * w["Fe"]


def layer_params(cfg: dict, experts: float | None = None) -> float:
    """A layer: attention, norms, router and `experts` routed experts
    (default: all of them)."""
    w = widths(cfg)
    n = w["E"] if experts is None else experts
    return (attention_params(cfg) + norm_params(cfg) + w["D"] * w["E"]
            + n * expert_params(cfg))


def n_params(cfg: dict) -> float:
    """Every parameter the configuration holds."""
    w = widths(cfg)
    return (w["V"] * w["D"] + w["L"] * layer_params(cfg) + w["D"]
            + (0 if w["tied"] else w["D"] * w["V"]))


def kv_bytes_per_token_layer(cfg: dict, dtype_bytes: int = 2) -> int:
    """K and V of one token in one layer."""
    w = widths(cfg)
    return 2 * w["KV"] * w["hd"] * dtype_bytes


def block_forward_cost(cfg: dict, *, rows: float, experts_hit: float,
                       slots: float, dtype_bytes: int = 2) -> dict:
    """What one block forward MUST move and compute: `rows` live rows of B
    tokens; every layer's attention, norms and router; the experts some
    live token reached (`experts_hit` a layer: what the program counted),
    each once; the K and V of the `slots` slots the live rows' block reads
    span (summed over the rows, a layer); the head, the final norm and the
    live positions' f32 logits. Operations: 2 x tokens x the parameters a
    token meets (attention, router, its k experts, the head) plus the
    scores and the weighted sum over the slots. The embedding gather is
    left out. A floor."""
    w = widths(cfg)
    tokens = rows * w["B"]
    beside = w["L"] * layer_params(cfg, experts=0) * dtype_bytes
    experts = w["L"] * experts_hit * expert_params(cfg) * dtype_bytes
    kv = w["L"] * slots * kv_bytes_per_token_layer(cfg, dtype_bytes)
    head = (w["D"] * w["V"] + w["D"]) * dtype_bytes + tokens * w["V"] * 4
    met = (w["L"] * (attention_params(cfg) + w["D"] * w["E"]
                     + w["k"] * expert_params(cfg)) + w["D"] * w["V"])
    flops = 2.0 * tokens * met + w["L"] * 4.0 * w["B"] * slots * w["H"] * w["hd"]
    parts = {"attention_router": beside, "experts": experts, "kv": kv,
             "head": head}
    return {**parts, "bytes": sum(parts.values()), "flops": flops}


def block_forward_floor_s(cfg: dict, peaks: dict, **kw) -> float:
    """The larger of the forward's bytes over the HBM bandwidth and its
    operations over the bf16 peak."""
    c = block_forward_cost(cfg, **kw)
    return max(c["bytes"] / peaks["hbm_bytes_per_s"],
               c["flops"] / peaks["bf16_flops_per_s"])


def experts_hit(cfg: dict, tokens: float) -> float:
    """Expected number of a layer's experts that at least one of `tokens`
    tokens reaches, each choosing k of E (taken as uniform): what to expect
    of a prefill piece's call; a block forward's are counted by the program
    (`serving/held_experts_hit`)."""
    w = widths(cfg)
    return w["E"] * (1.0 - (1.0 - w["k"] / w["E"]) ** tokens)


def grouped_matmul_cost(cfg: dict, *, m: int, k: int, n: int,
                        tokens: float | None = None,
                        kernels: float | None = None,
                        dtype_bytes: int = 2) -> dict:
    """One call of the grouped matmul (`gmm`): `tokens` dispatched tokens
    (default: all of the call's m / top_k) times top_k rows `[., k]`, each
    reached kernel `[k, n]` once (`kernels`: what the run counted, else a
    uniform router's expectation). Operations 2 x rows x k x n."""
    w = widths(cfg)
    tokens = max(m // w["k"], 1) if tokens is None else tokens
    rows = tokens * w["k"]
    if kernels is None:
        kernels = experts_hit(cfg, tokens)
    return {"flops": 2.0 * rows * k * n,
            "bytes": (rows * k + kernels * k * n + rows * n) * dtype_bytes}


def grouped_matmul_floor_s(cfg: dict, peaks: dict, **kw) -> float:
    c = grouped_matmul_cost(cfg, **kw)
    return max(c["flops"] / peaks["bf16_flops_per_s"],
               c["bytes"] / peaks["hbm_bytes_per_s"])
