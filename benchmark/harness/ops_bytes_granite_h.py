"""Operations and bytes of Granite 4.0-H's layers as one chip of a two-chip
expert-parallel pipeline stage holds them, from shapes (docs/GRANITE_H.md):
`layer_types[l]` a Mamba-2 mixer ALONE (`mamba_n_heads` heads of
`mamba_d_head`, B and C in `mamba_n_groups` groups of `mamba_d_state`,
`mamba_d_conv` taps with a bias, a gated norm; a state and no pages) or a GQA
attention of `num_attention_heads` / `num_key_value_heads` heads (pages and
no state), and after every one a router over all `num_local_experts`, the
`num_experts_held` routed experts of `intermediate_size` this chip
holds, `num_experts_per_tok` a token, and a shared expert of
`shared_intermediate_size`; a tied head over the chip's slice of the
vocabulary. Everything is a function of the configuration file's keys and of
what the run observed (live rows, slots read, experts reached, tokens
prefilled).

The recurrence's counts are the WORK OF THE EQUATIONS, not of a program, and
are harness/ops_bytes_falcon_h1.py's own functions, by import: a head's
state `S [P, N]` is read and written once a token at decode, and a token's
update `S = a S + d x (outer) B`, `y = S C` is `5 P N` operations whatever
chunks, kernels or layouts compute it.
"""

from __future__ import annotations

# a mixer LAYER's counts are Falcon-H1's (the same operator, other sizes):
# its parameters, a row's state in a layer, the state pass and the scan
from harness.ops_bytes_falcon_h1 import (  # noqa: F401
    mixer_params, ssd_scan_cost, ssd_scan_floor_s, ssm_update_bytes,
    ssm_update_floor_s, state_bytes_per_row_layer,
)


def widths(cfg: dict) -> dict:
    D, H = cfg["hidden_size"], cfg["num_attention_heads"]
    Hs, P = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    G, N = cfg["mamba_n_groups"], cfg["mamba_d_state"]
    L = cfg["num_hidden_layers"]
    Lm = sum(t == "mamba" for t in cfg["layer_types"][:L])
    E = cfg["num_local_experts"]
    return dict(
        D=D, F=cfg["intermediate_size"], Fs=cfg["shared_intermediate_size"],
        V=cfg["vocab_size"], H=H, KV=cfg["num_key_value_heads"],
        hd=cfg.get("head_dim") or D // H, L=L, Lm=Lm, La=L - Lm, Hs=Hs, P=P,
        G=G, N=N, K=cfg["mamba_d_conv"], I=Hs * P, W=Hs * P + 2 * G * N, E=E,
        held=int(cfg.get("num_experts_held") or E),
        k=cfg["num_experts_per_tok"])


def attention_params(cfg: dict) -> int:
    """q, k, v, o: no biases, no norms."""
    w = widths(cfg)
    return 2 * w["D"] * w["H"] * w["hd"] + 2 * w["D"] * w["KV"] * w["hd"]


def expert_params(cfg: dict) -> int:
    """ONE routed expert's three kernels."""
    w = widths(cfg)
    return 3 * w["D"] * w["F"]


def beside_params(cfg: dict) -> int:
    """What every layer has beside its operator and its routed experts: the
    shared expert, the router and the two norms."""
    w = widths(cfg)
    return 3 * w["D"] * w["Fs"] + w["D"] * w["E"] + 2 * w["D"]


def layer_params(cfg: dict, kind: str, experts: float | None = None) -> float:
    """A layer of this chip: its operator, what stands beside it, and
    `experts` routed ones (default: the held)."""
    n = widths(cfg)["held"] if experts is None else experts
    own = mixer_params(cfg) if kind == "mamba" else attention_params(cfg)
    return own + beside_params(cfg) + n * expert_params(cfg)


def n_params(cfg: dict) -> float:
    """Every parameter this chip holds (the head is the tied embedding)."""
    w = widths(cfg)
    return (w["V"] * w["D"] + w["D"] + w["Lm"] * layer_params(cfg, "mamba")
            + w["La"] * layer_params(cfg, "attention"))


def kv_bytes_per_token_layer(cfg: dict, dtype_bytes: int = 2) -> int:
    """K and V of one token in one ATTENTION layer."""
    w = widths(cfg)
    return 2 * w["KV"] * w["hd"] * dtype_bytes


def kv_bytes_per_token(cfg: dict, dtype_bytes: int = 2) -> int:
    """Over the layers that keep pages: `serving/kv_bytes_per_token`."""
    return widths(cfg)["La"] * kv_bytes_per_token_layer(cfg, dtype_bytes)


def state_bytes_per_row(cfg: dict, dtype_bytes: int = 2) -> int:
    """Both leaves over every mixer layer: `serving/state_bytes_per_row`."""
    return widths(cfg)["Lm"] * sum(
        state_bytes_per_row_layer(cfg, dtype_bytes).values())


def decode_step_bytes(cfg: dict, *, rows: float, slots: float,
                      experts_hit: float, dtype_bytes: int = 2) -> dict:
    """Bytes one decode step MUST move, by part: the mixers' weights and the
    attention layers'; the shared expert, router and norms of every layer;
    the HELD experts some live row reached (`experts_hit` a layer: what the
    program counted), each once; the K and V slots inside the bounds
    (`slots`, summed over the live rows), an attention layer; the LIVE rows'
    state, both leaves, read and written once; the head, the final norm and
    the live rows' f32 logits. The embedding gather is left out. A floor."""
    w = widths(cfg)
    parts = {
        "mixer": w["Lm"] * mixer_params(cfg) * dtype_bytes,
        "attention": w["La"] * attention_params(cfg) * dtype_bytes,
        "shared_router_norms": w["L"] * beside_params(cfg) * dtype_bytes,
        "experts": w["L"] * experts_hit * expert_params(cfg) * dtype_bytes,
        "kv": w["La"] * slots * kv_bytes_per_token_layer(cfg, dtype_bytes),
        "state": 2 * rows * state_bytes_per_row(cfg, dtype_bytes),
        "head": (w["D"] * w["V"] + w["D"]) * dtype_bytes + rows * w["V"] * 4}
    return {**parts, "total": sum(parts.values())}


def held_experts_hit(cfg: dict, tokens: float) -> float:
    """Expected number of a layer's HELD experts that at least one of
    `tokens` tokens reaches, each choosing k of all E (taken as uniform):
    what to expect of a prefill piece's call; a decode step's few rows are
    counted by the program instead (`serving/held_experts_hit`)."""
    w = widths(cfg)
    return w["held"] * (1.0 - (1.0 - w["k"] / w["E"]) ** tokens)


def grouped_matmul_cost(cfg: dict, *, m: int, k: int, n: int,
                        tokens: float | None = None,
                        kernels: float | None = None,
                        dtype_bytes: int = 2) -> dict:
    """One call of the grouped matmul (`gmm`) in a chip's share
    (harness/ops_bytes_trinity.py's rule): of the sorted assignment rows
    `[m, k]` only those of held experts are in a group (held / E of them
    for a uniform router). `tokens`: how many of the call's m / top_k tokens
    were dispatched (default all); `kernels`: the held kernels `[k, n]`
    those reached where the run counted it, else a uniform router's
    expectation. Operations 2 x rows x k x n; bytes: those rows in and out,
    and each reached kernel once."""
    w = widths(cfg)
    tokens = max(m // w["k"], 1) if tokens is None else tokens
    rows = tokens * w["k"] * w["held"] / w["E"]
    if kernels is None:
        kernels = held_experts_hit(cfg, tokens)
    return {"flops": 2.0 * rows * k * n,
            "bytes": (rows * k + kernels * k * n + rows * n) * dtype_bytes}


def grouped_matmul_floor_s(cfg: dict, peaks: dict, **kw) -> float:
    """The larger of operations over the bf16 peak and bytes over the HBM
    bandwidth."""
    c = grouped_matmul_cost(cfg, **kw)
    return max(c["flops"] / peaks["bf16_flops_per_s"],
               c["bytes"] / peaks["hbm_bytes_per_s"])


def paged_read_bytes(cfg: dict, *, slots: float, dtype_bytes: int = 2) -> float:
    """One attention layer's paged decode read: K and V of the slots inside
    the live rows' bounds."""
    return slots * kv_bytes_per_token_layer(cfg, dtype_bytes)
