"""Operations and bytes of Ouro's forwards (`model_type: ouro`, a looped
language model, docs/OURO.md), from shapes: a plain stack of `L` MHA + SwiGLU
layers with four norms each that every token passes `total_ut_steps` times,
the final norm after each pass, an untied head read once, and a cache of a
slot a pass a layer (`cache_layers = passes x L`). Everything is a function
of the configuration file's keys and of what the run observed (live rows,
slots read, tokens dispatched).
"""

from __future__ import annotations


def widths(cfg: dict) -> dict:
    D, H = cfg["hidden_size"], cfg["num_attention_heads"]
    return dict(
        D=D, F=cfg["intermediate_size"], V=cfg["vocab_size"], H=H,
        KV=cfg["num_key_value_heads"], hd=cfg.get("head_dim") or D // H,
        L=cfg["num_hidden_layers"], T=int(cfg.get("total_ut_steps") or 1),
        tied=bool(cfg.get("tie_word_embeddings")))


def layer_matmul_params(cfg: dict) -> int:
    """q, k, v, o and the three SwiGLU kernels of one layer."""
    w = widths(cfg)
    return (2 * w["D"] * w["H"] * w["hd"] + 2 * w["D"] * w["KV"] * w["hd"]
            + 3 * w["D"] * w["F"])


def layer_params(cfg: dict) -> int:
    """One layer: its kernels and its four norms."""
    return layer_matmul_params(cfg) + 4 * widths(cfg)["D"]


def stack_params(cfg: dict) -> int:
    """The shared block: every layer once (the passes share them)."""
    return widths(cfg)["L"] * layer_params(cfg)


def head_params(cfg: dict) -> int:
    w = widths(cfg)
    return w["D"] * w["V"]


def n_params(cfg: dict) -> int:
    """The whole tree: the stack, the embedding, the head (untied), the final
    norm and the exit gate (a `[D] -> 1` projection with bias)."""
    w = widths(cfg)
    return (stack_params(cfg) + w["V"] * w["D"]
            + (0 if w["tied"] else head_params(cfg)) + w["D"] + w["D"] + 1)


def cache_layers(cfg: dict) -> int:
    w = widths(cfg)
    return w["T"] * w["L"]


def kv_bytes_per_token_layer(cfg: dict, itemsize: int = 2) -> int:
    """K and V of one token in one cache layer."""
    w = widths(cfg)
    return 2 * w["KV"] * w["hd"] * itemsize


def kv_bytes_per_token(cfg: dict, itemsize: int = 2) -> int:
    """K and V of one token over every pass of every layer."""
    return cache_layers(cfg) * kv_bytes_per_token_layer(cfg, itemsize)


def decode_step_bytes(cfg: dict, rows: float, slots: float,
                      itemsize: int = 2) -> dict:
    """The bytes one decode step must move: the stack's weights ONCE A PASS,
    the final norm a pass, the head and its float32 logits once, the K and V
    of the `slots` slots the live rows hold over every cache layer, and the
    `rows` live rows' new K and V written to every cache layer."""
    w = widths(cfg)
    b = {"weights": w["T"] * (stack_params(cfg) + w["D"]) * itemsize,
         "head": head_params(cfg) * itemsize + rows * w["V"] * 4,
         "kv_read": slots * kv_bytes_per_token(cfg, itemsize),
         "kv_write": rows * kv_bytes_per_token(cfg, itemsize)}
    b["total"] = sum(b.values())
    return b


def decode_step_floor_s(cfg: dict, peaks: dict, rows: float,
                        slots: float) -> float:
    return decode_step_bytes(cfg, rows, slots)["total"] / peaks["hbm_bytes_per_s"]


def admission_flops(cfg: dict, tokens: float, forwards: float = 1.0) -> dict:
    """The operations of admission forwards that ran `tokens` tokens in all,
    `forwards` of them (so `tokens / forwards` a forward, the bucket as the
    device ran it, its pad slots too): every kernel of the stack a token A
    PASS, causal attention's QK and PV over the forward's own tokens (half
    the square) a pass a layer, and the head for each forward's LAST token."""
    w = widths(cfg)
    per = tokens / max(forwards, 1.0)
    f = {"matmuls": 2.0 * tokens * w["T"] * w["L"] * layer_matmul_params(cfg),
         "attention": w["T"] * w["L"] * forwards * 4.0 * w["H"] * w["hd"]
         * per * (per + 1) / 2.0,
         "head": forwards * 2.0 * head_params(cfg)}
    f["total"] = sum(f.values())
    return f


def admission_floor_s(cfg: dict, peaks: dict, tokens: float,
                      forwards: float = 1.0) -> float:
    """An admission at the bf16 peak, or at the bandwidth its one read of the
    weights a pass needs, whichever is longer."""
    w = widths(cfg)
    read = forwards * (w["T"] * stack_params(cfg) + head_params(cfg)) * 2
    return max(admission_flops(cfg, tokens, forwards)["total"]
               / peaks["bf16_flops_per_s"], read / peaks["hbm_bytes_per_s"])
