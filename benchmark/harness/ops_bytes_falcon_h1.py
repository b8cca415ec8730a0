"""Operations and bytes of Falcon-H1's layers as one pipeline stage holds
them, from shapes (docs/SSM.md): every layer a GQA attention of
`num_attention_heads` / `num_key_value_heads` heads of `head_dim`, BESIDE it
a Mamba-2 mixer (`mamba_n_heads` heads of `mamba_d_head`, B and C in
`mamba_n_groups` groups of `mamba_d_state`, `mamba_d_conv` taps with a bias,
a gated group norm), then a dense SwiGLU of `intermediate_size`; an untied
head. Everything is a function of the configuration file's keys and of what
the run observed (live rows, slots read, tokens prefilled).

The recurrence's counts are the WORK OF THE EQUATIONS, not of a program:
a head's state `S [P, N]` is read and written once a token at decode, and a
token's update `S = a S + d x (outer) B`, `y = S C` is `5 P N` operations
whatever chunks, kernels or layouts compute it. A program that touches the
state of rows nobody listens to, or pads a piece up to whole chunks, moves
more than this and reads lower for it.
"""

from __future__ import annotations


def widths(cfg: dict) -> dict:
    D, H = cfg["hidden_size"], cfg["num_attention_heads"]
    Hs, P = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    G, N = cfg["mamba_n_groups"], cfg["mamba_d_state"]
    return dict(
        D=D, F=cfg["intermediate_size"], V=cfg["vocab_size"], H=H,
        KV=cfg["num_key_value_heads"], hd=cfg.get("head_dim") or D // H,
        L=cfg["num_hidden_layers"], Hs=Hs, P=P, G=G, N=N,
        K=cfg["mamba_d_conv"], I=Hs * P, W=Hs * P + 2 * G * N,
        tied=bool(cfg.get("tie_word_embeddings", False)))


def attention_params(cfg: dict) -> int:
    """q, k, v, o: no biases, no norms."""
    w = widths(cfg)
    return 2 * w["D"] * w["H"] * w["hd"] + 2 * w["D"] * w["KV"] * w["hd"]


def mixer_params(cfg: dict) -> int:
    """in_proj [D, 2 I + 2 G N + H], out_proj [I, D], the taps [K, W] and
    their bias [W], A_log, D, dt_bias [H] each, the norm [I]."""
    w = widths(cfg)
    return (w["D"] * (w["I"] + w["W"] + w["Hs"]) + w["I"] * w["D"]
            + w["K"] * w["W"] + w["W"] + 3 * w["Hs"] + w["I"])


def mlp_params(cfg: dict) -> int:
    w = widths(cfg)
    return 3 * w["D"] * w["F"]


def layer_params(cfg: dict) -> int:
    """A layer: attention, mixer, MLP and its two norms."""
    return (attention_params(cfg) + mixer_params(cfg) + mlp_params(cfg)
            + 2 * widths(cfg)["D"])


def n_params(cfg: dict) -> int:
    w = widths(cfg)
    return (w["V"] * w["D"] + w["L"] * layer_params(cfg) + w["D"]
            + (0 if w["tied"] else w["D"] * w["V"]))


def kv_bytes_per_token_layer(cfg: dict, dtype_bytes: int = 2) -> int:
    """K and V of one token in one layer."""
    w = widths(cfg)
    return 2 * w["KV"] * w["hd"] * dtype_bytes


def state_bytes_per_row_layer(cfg: dict, dtype_bytes: int = 2) -> dict:
    """A row's state in one layer: the recurrence's `S [H, P, N]` in FLOAT32
    and the convolution's tail `[K - 1, W]` in the cache's type."""
    w = widths(cfg)
    return {"recurrent": w["Hs"] * w["P"] * w["N"] * 4,
            "tail": (w["K"] - 1) * w["W"] * dtype_bytes}


def state_bytes_per_row(cfg: dict, dtype_bytes: int = 2) -> int:
    """Both leaves over every layer: `serving/state_bytes_per_row`."""
    return widths(cfg)["L"] * sum(
        state_bytes_per_row_layer(cfg, dtype_bytes).values())


def decode_step_bytes(cfg: dict, *, rows: float, slots: float,
                      dtype_bytes: int = 2) -> dict:
    """Bytes one decode step MUST move, by part: every layer's weights; the
    K and V slots inside the bounds (`slots`, summed over the live rows: what
    the session counted), a layer; the LIVE rows' state, both leaves, read
    and written once; the head, the final norm and the live rows' f32
    logits. The embedding gather is left out. A floor."""
    w = widths(cfg)
    parts = {
        "attention": w["L"] * attention_params(cfg) * dtype_bytes,
        "mixer": w["L"] * mixer_params(cfg) * dtype_bytes,
        "mlp_norms": w["L"] * (mlp_params(cfg) + 2 * w["D"]) * dtype_bytes,
        "kv": w["L"] * slots * kv_bytes_per_token_layer(cfg, dtype_bytes),
        "state": 2 * rows * state_bytes_per_row(cfg, dtype_bytes),
        "head": (w["D"] * w["V"] + w["D"]) * dtype_bytes + rows * w["V"] * 4}
    return {**parts, "total": sum(parts.values())}


def ssm_update_bytes(cfg: dict, *, rows: float, dtype_bytes: int = 2) -> float:
    """One layer's decode pass over the state: the live rows' `S` read and
    written once, their tails likewise, and a token's operands in and out
    (`xs`, `B`, `C`, `dt`, `y`: float32 as the recurrence takes them)."""
    w = widths(cfg)
    leaf = state_bytes_per_row_layer(cfg, dtype_bytes)
    operands = (2 * w["I"] + 2 * w["G"] * w["N"] + w["Hs"]) * 4
    return rows * (2 * (leaf["recurrent"] + leaf["tail"]) + operands)


def ssm_update_floor_s(cfg: dict, peaks: dict, **kw) -> float:
    """Bytes over the HBM bandwidth: five operations an element of `S`
    against eight bytes moved is far under the ridge."""
    return ssm_update_bytes(cfg, **kw) / peaks["hbm_bytes_per_s"]


def ssd_scan_cost(cfg: dict, *, tokens: float, pieces: float) -> dict:
    """One layer's recurrence over `tokens` prefilled tokens in `pieces`
    forwards: `5 P N` operations a head a token (`a S`, `d x (outer) B`, the
    sum, `S C` as a multiply and an add); bytes: a token's operands in and
    out (float32) and a row's `S` read and written once a PIECE."""
    w = widths(cfg)
    flops = 5.0 * w["Hs"] * w["P"] * w["N"] * tokens
    operands = (2 * w["I"] + 2 * w["G"] * w["N"] + w["Hs"]) * 4
    state = 2 * state_bytes_per_row_layer(cfg)["recurrent"]
    return {"flops": flops, "bytes": tokens * operands + pieces * state}


def ssd_scan_floor_s(cfg: dict, peaks: dict, **kw) -> float:
    """The larger of operations over the bf16 peak (the matrix unit's: a
    float32 scan cannot reach it, and reads low for that) and bytes over the
    HBM bandwidth."""
    c = ssd_scan_cost(cfg, **kw)
    return max(c["flops"] / peaks["bf16_flops_per_s"],
               c["bytes"] / peaks["hbm_bytes_per_s"])
