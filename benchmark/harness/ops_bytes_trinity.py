"""Operations and bytes of Trinity's layers (`model_type: afmoe`,
docs/AFMOE.md) as one chip of an eight-chip expert-parallel group holds
them, from shapes: gated GQA attention (four projections of the normed state
and the output's), four norms and two per-head norms a layer, a leading
dense stack, and expert layers of a router over all `num_experts`, one shared
expert and the `num_experts_held` routed experts this chip holds; window
layers and global layers whose caches differ in what a step reads.
Everything is a function of the configuration file's keys and of what the
run observed (rows, slots read, experts reached).
"""

from __future__ import annotations


def widths(cfg: dict) -> dict:
    L, Ld = cfg["num_hidden_layers"], int(cfg.get("num_dense_layers") or 0)
    Lw = sum(t == "sliding_attention" for t in cfg["layer_types"][:L])
    E = cfg["num_experts"]
    return dict(
        D=cfg["hidden_size"], F=cfg["intermediate_size"],
        Fe=cfg["moe_intermediate_size"], V=cfg["vocab_size"],
        H=cfg["num_attention_heads"], KV=cfg["num_key_value_heads"],
        hd=cfg["head_dim"], L=L, Ld=Ld, Le=L - Ld, Lw=Lw, Lg=L - Lw, E=E,
        held=int(cfg.get("num_experts_held") or E),
        k=cfg["num_experts_per_tok"],
        shared=int(cfg.get("num_shared_experts") or 0),
        W=cfg["sliding_window"], tied=bool(cfg.get("tie_word_embeddings")))


def gate_params(cfg: dict) -> int:
    """The attention gate's projection, as wide as q_proj."""
    w = widths(cfg)
    return w["D"] * w["H"] * w["hd"]


def attention_params(cfg: dict) -> int:
    """q, k, v, the gate and o: the projections of one layer."""
    w = widths(cfg)
    return (3 * w["D"] * w["H"] * w["hd"] + 2 * w["D"] * w["KV"] * w["hd"])


def norm_params(cfg: dict) -> int:
    """Four norms of D and two per-head norms of head_dim."""
    w = widths(cfg)
    return 4 * w["D"] + 2 * w["hd"]


def expert_params(cfg: dict) -> int:
    """ONE routed expert's three kernels (the shared expert's too)."""
    w = widths(cfg)
    return 3 * w["D"] * w["Fe"]


def dense_layer_params(cfg: dict) -> int:
    w = widths(cfg)
    return attention_params(cfg) + norm_params(cfg) + 3 * w["D"] * w["F"]


def expert_layer_params(cfg: dict, experts: float | None = None) -> float:
    """An expert layer of this chip: attention, norms, router (and its
    bias), the shared expert and `experts` routed ones (default: the held)."""
    w = widths(cfg)
    n = w["held"] if experts is None else experts
    return (attention_params(cfg) + norm_params(cfg) + w["D"] * w["E"] + w["E"]
            + (w["shared"] + n) * expert_params(cfg))


def n_params(cfg: dict) -> float:
    """Every parameter this chip holds."""
    w = widths(cfg)
    return (w["V"] * w["D"] + w["Ld"] * dense_layer_params(cfg)
            + w["Le"] * expert_layer_params(cfg) + w["D"]
            + (0 if w["tied"] else w["D"] * w["V"]))


def kv_bytes_per_token_layer(cfg: dict, dtype_bytes: int = 2) -> int:
    """K and V of one token in one layer."""
    w = widths(cfg)
    return 2 * w["KV"] * w["hd"] * dtype_bytes


def held_experts_hit(cfg: dict, tokens: float) -> float:
    """Expected number of a layer's HELD experts that at least one of
    `tokens` tokens reaches, each choosing k of all E (taken as uniform):
    what to expect of a prefill piece's call; a decode step's few rows are
    counted by the program instead (`serving/held_experts_hit`)."""
    w = widths(cfg)
    return w["held"] * (1.0 - (1.0 - w["k"] / w["E"]) ** tokens)


def decode_step_bytes(cfg: dict, *, rows: float, experts_hit: float,
                      global_slots: float, window_slots: float,
                      dtype_bytes: int = 2) -> dict:
    """Bytes one decode step MUST move, by part: the dense layers; every
    expert layer's attention, norms, router and shared expert; the held
    experts some live row reached (`experts_hit` an expert layer: what the
    program counted), each once; the K and V slots inside the bounds,
    `global_slots` a global layer and `window_slots` a window layer (summed
    over the live rows: what the session counted, a window layer's at most
    the window a row); the head, the final norm and the live rows' f32
    logits. The embedding gather is left out. A floor."""
    w = widths(cfg)
    dense = w["Ld"] * dense_layer_params(cfg) * dtype_bytes
    beside = w["Le"] * expert_layer_params(cfg, experts=0) * dtype_bytes
    experts = w["Le"] * experts_hit * expert_params(cfg) * dtype_bytes
    per = kv_bytes_per_token_layer(cfg, dtype_bytes)
    kv = (w["Lg"] * global_slots + w["Lw"] * window_slots) * per
    head = (w["D"] * w["V"] + w["D"]) * dtype_bytes + rows * w["V"] * 4
    parts = {"dense_layers": dense, "attention_router_shared": beside,
             "experts": experts, "kv": kv, "head": head}
    return {**parts, "total": sum(parts.values())}


def grouped_matmul_cost(cfg: dict, *, m: int, k: int, n: int,
                        tokens: float | None = None,
                        kernels: float | None = None,
                        dtype_bytes: int = 2) -> dict:
    """One call of the grouped matmul (`gmm`) in a chip's share: of the
    sorted assignment rows `[m, k]` only those of held experts are in a
    group (held / E of them for a uniform router); the others are computed
    by no one. `tokens`: how many of the call's m / top_k tokens were
    dispatched (a decode step runs every resident row and dispatches the
    live ones; default all). `kernels`: the held kernels `[k, n]` those
    reached where the run counted it, else a uniform router's expectation
    (`held_experts_hit`: all 32 from a few hundred tokens on, a prefill
    piece's case). Operations 2 x rows x k x n; bytes: those rows in and
    out, and each reached kernel once."""
    w = widths(cfg)
    tokens = max(m // w["k"], 1) if tokens is None else tokens
    rows = tokens * w["k"] * w["held"] / w["E"]
    if kernels is None:
        kernels = held_experts_hit(cfg, tokens)
    return {"flops": 2.0 * rows * k * n,
            "bytes": (rows * k + kernels * k * n + rows * n) * dtype_bytes}


def grouped_matmul_floor_s(cfg: dict, peaks: dict, **kw) -> float:
    """The larger of operations over the bf16 peak and bytes over the HBM
    bandwidth (`ops_bytes_moe.grouped_matmul_floor_s`'s rule)."""
    c = grouped_matmul_cost(cfg, **kw)
    return max(c["flops"] / peaks["bf16_flops_per_s"],
               c["bytes"] / peaks["hbm_bytes_per_s"])
