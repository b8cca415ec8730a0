"""Operations and bytes of A.X-K1's layers as one chip of an expert-parallel
group holds them, from shapes.

The sibling of harness/ops_bytes_moe.py (OLMoE: four attention projections
and E whole experts a layer). Here a layer's attention is MLA's five
projections and two inner norms; the leading `first_k_dense_replace` layers
carry a dense SwiGLU of `intermediate_size`; every later layer a router over
all `n_routed_experts`, the HELD experts (`n_routed_experts_held`, default
all) of width `moe_intermediate_size`, and `n_shared_experts` shared experts
of that width; the cache is one latent of `kv_lora_rank + qk_rope_head_dim`
values a token a layer. Everything is a function of the configuration file's
keys and of what the run observed (rows, fill).
"""

from __future__ import annotations


def widths(cfg: dict) -> dict:
    E = cfg["n_routed_experts"]
    L, Ld = cfg["num_hidden_layers"], cfg.get("first_k_dense_replace", 0)
    return dict(
        D=cfg["hidden_size"], F=cfg["intermediate_size"],
        Fe=cfg["moe_intermediate_size"], V=cfg["vocab_size"],
        H=cfg["num_attention_heads"], dq=cfg["q_lora_rank"],
        r=cfg["kv_lora_rank"], dn=cfg["qk_nope_head_dim"],
        dr=cfg["qk_rope_head_dim"], dv=cfg["v_head_dim"], L=L,
        Ld=min(Ld, L), Le=L - min(Ld, L), E=E,
        held=cfg.get("n_routed_experts_held") or E,
        shared=cfg.get("n_shared_experts") or 0, k=cfg["num_experts_per_tok"],
        tied=bool(cfg.get("tie_word_embeddings")))


def mla_params(cfg: dict) -> int:
    """One layer's attention: W_qa, W_qb, W_kva, W_kvb, W_o, the two inner
    norms and the two layer norms."""
    w = widths(cfg)
    D, H = w["D"], w["H"]
    return (D * w["dq"] + w["dq"] * H * (w["dn"] + w["dr"])
            + D * (w["r"] + w["dr"]) + w["r"] * H * (w["dn"] + w["dv"])
            + H * w["dv"] * D + w["dq"] + w["r"] + 2 * D)


def expert_params(cfg: dict) -> int:
    """ONE routed expert's three kernels."""
    w = widths(cfg)
    return 3 * w["D"] * w["Fe"]


def dense_layer_params(cfg: dict) -> int:
    w = widths(cfg)
    return mla_params(cfg) + 3 * w["D"] * w["F"]


def expert_layer_params(cfg: dict, experts: float | None = None) -> float:
    """An expert layer as this chip holds it: attention, router, shared
    experts and `experts` routed ones (default: all it holds)."""
    w = widths(cfg)
    n = w["held"] if experts is None else experts
    return (mla_params(cfg) + w["D"] * w["E"]
            + (w["shared"] + n) * expert_params(cfg))


def n_params(cfg: dict) -> float:
    """Every parameter this chip holds."""
    w = widths(cfg)
    return (w["V"] * w["D"] + w["Ld"] * dense_layer_params(cfg)
            + w["Le"] * expert_layer_params(cfg) + w["D"]
            + (0 if w["tied"] else w["D"] * w["V"]))


def held_experts_hit(cfg: dict, rows: float) -> float:
    """Expected number of a layer's HELD experts that at least one of `rows`
    tokens reaches, each token choosing k of all E (taken as uniform). What
    to expect of a call of many tokens; a decode step's few rows are counted
    by the program instead (`serving/held_experts_hit`)."""
    w = widths(cfg)
    return w["held"] * (1.0 - (1.0 - w["k"] / w["E"]) ** rows)


def kv_bytes_per_token(cfg: dict, dtype_bytes: int = 2) -> int:
    """The latent cache: `[c_kv | k_rope]` a token a layer."""
    w = widths(cfg)
    return w["L"] * (w["r"] + w["dr"]) * dtype_bytes


def per_head_kv_bytes_per_token(cfg: dict, dtype_bytes: int = 2) -> int:
    """What per-head K and V would take: the cache MLA does not keep."""
    w = widths(cfg)
    return w["L"] * w["H"] * (w["dn"] + w["dr"] + w["dv"]) * dtype_bytes


def decode_step_bytes(cfg: dict, *, rows: float, filled_mean: float,
                      experts_hit: float, dtype_bytes: int = 2) -> dict:
    """Bytes one decode step of `rows` live rows MUST move, by part: the
    dense layers; every expert layer's attention, router and shared expert;
    the held experts some live row reaches (`experts_hit` a layer: what the
    run counted, not an expectation) once; the live rows' filled latent cache
    at its mean fill, read once a layer; the output matrix, the final norm
    and the f32 logits. The embedding gather (rows x D) is left out. A floor,
    not what the program moves."""
    w = widths(cfg)
    hit = experts_hit
    dense = w["Ld"] * dense_layer_params(cfg) * dtype_bytes
    beside = w["Le"] * expert_layer_params(cfg, experts=0) * dtype_bytes
    experts = w["Le"] * hit * expert_params(cfg) * dtype_bytes
    kv = rows * filled_mean * kv_bytes_per_token(cfg, dtype_bytes)
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
    by no one. `tokens` says how many of the call's m / top_k tokens had to
    be computed (a decode step runs every resident row and dispatches the
    live ones); by default all. `kernels` is the number of
    held kernels `[k, n]` those tokens reached where the run counted it (a
    decode step's, `moe/held_experts_hit_traced`); by default what a
    uniform router reaches (`held_experts_hit`: all of them from a few
    hundred tokens on, a prefill chunk's case). Operations 2 x rows x k x n;
    bytes: those rows in and out, and each reached kernel once."""
    w = widths(cfg)
    tokens = max(m // w["k"], 1) if tokens is None else tokens
    rows = tokens * w["k"] * w["held"] / w["E"]
    if kernels is None:
        kernels = held_experts_hit(cfg, tokens)
    return {"flops": 2.0 * rows * k * n,
            "bytes": (rows * k + kernels * k * n + rows * n) * dtype_bytes}


def grouped_matmul_floor_s(cfg: dict, peaks: dict, *, m: int, k: int,
                           n: int, tokens: float | None = None,
                           kernels: float | None = None) -> float:
    """`ops_bytes_moe.grouped_matmul_floor_s`'s rule at this model's shapes:
    the larger of operations over the bf16 peak and bytes over the HBM
    bandwidth."""
    c = grouped_matmul_cost(cfg, m=m, k=k, n=n, tokens=tokens, kernels=kernels)
    return max(c["flops"] / peaks["bf16_flops_per_s"],
               c["bytes"] / peaks["hbm_bytes_per_s"])
