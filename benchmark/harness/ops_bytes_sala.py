"""Operations and bytes of MiniCPM-SALA's layers as one pipeline stage holds
them, from shapes (docs/SALA.md): `mixer_types` says which of two mixers a
layer has, a lightning linear attention (`lightning_nh` heads of
`lightning_head_dim`, a float32 state `[hd, hd]` a head and no pages) or a
sparse attention (`num_attention_heads` / `num_key_value_heads` heads of
`head_dim` whose queries read `topk` blocks of `block_size` slots once their
call holds `dense_len` keys, chosen by the scores of compressed keys, one
every `kernel_stride` slots), then a dense SwiGLU of `intermediate_size`; an
untied head. Everything is a function of the configuration file's keys and
of what the run observed (live rows, slots held and read, tokens prefilled).

The counts are the WORK OF THE EQUATIONS, not of a program: a lightning
head's state is read and written once a live row a step and a token's
update is `4 hd hd` operations; a selecting row's step reads `min(n, topk x
block_size)` slots of K and V and `n / kernel_stride` compressed keys, a
dense row's its `n` slots; a piece's sparse attention is the products of
each query with the slots of the blocks it CHOSE. A program that gathers
every page's compressed keys for rows that do not select, fetches whole
pages for a block, or attends over every key under a mask moves more than
this and reads lower for it."""

from __future__ import annotations

MIXERS = {"minicpm4": "sparse", "lightning-attn": "lightning"}


def widths(cfg: dict) -> dict:
    D, H = cfg["hidden_size"], cfg["num_attention_heads"]
    kinds = [MIXERS[m] for m in cfg["mixer_types"]]
    s = cfg["sparse_config"]
    return dict(
        D=D, F=cfg["intermediate_size"], V=cfg["vocab_size"], H=H,
        KV=cfg["num_key_value_heads"], hd=cfg.get("head_dim") or D // H,
        L=cfg["num_hidden_layers"], Hl=cfg["lightning_nh"],
        hl=cfg["lightning_head_dim"], Ll=kinds.count("lightning"),
        Ls=kinds.count("sparse"), stride=s["kernel_stride"],
        block=s["block_size"], topk=s["topk"], dense_len=s["dense_len"],
        tied=bool(cfg.get("tie_word_embeddings", False)))


def sparse_params(cfg: dict) -> int:
    """q, o and the gate `[D, H hd]`, k and v `[D, KV hd]`, two norms."""
    w = widths(cfg)
    return (3 * w["D"] * w["H"] * w["hd"] + 2 * w["D"] * w["KV"] * w["hd"]
            + 2 * w["hd"])


def lightning_params(cfg: dict) -> int:
    """q, k, v, z and o `[D, H hd]`, q/k norms `[hd]`, the output norm."""
    w = widths(cfg)
    I = w["Hl"] * w["hl"]
    return 5 * w["D"] * I + 2 * w["hl"] + I


def mlp_params(cfg: dict) -> int:
    w = widths(cfg)
    return 3 * w["D"] * w["F"]


def n_params(cfg: dict) -> int:
    w = widths(cfg)
    return (w["V"] * w["D"] + w["Ls"] * sparse_params(cfg)
            + w["Ll"] * lightning_params(cfg)
            + w["L"] * (mlp_params(cfg) + 2 * w["D"]) + w["D"]
            + (0 if w["tied"] else w["D"] * w["V"]))


def kv_bytes_per_token_layer(cfg: dict, dtype_bytes: int = 2) -> float:
    """K, V and the compressed keys (one every `kernel_stride` slots) of one
    token in one SPARSE layer."""
    w = widths(cfg)
    return w["KV"] * w["hd"] * dtype_bytes * (2 + 1 / w["stride"])


def kv_bytes_per_token(cfg: dict, dtype_bytes: int = 2) -> int:
    """Over the sparse layers: `serving/kv_bytes_per_token`."""
    return int(widths(cfg)["Ls"] * kv_bytes_per_token_layer(cfg, dtype_bytes))


def state_bytes_per_row_layer(cfg: dict) -> int:
    """A row's state in one lightning layer: `S [H, hd, hd]`, FLOAT32."""
    w = widths(cfg)
    return w["Hl"] * w["hl"] * w["hl"] * 4


def state_bytes_per_row(cfg: dict) -> int:
    """Over the lightning layers: `serving/state_bytes_per_row`."""
    return widths(cfg)["Ll"] * state_bytes_per_row_layer(cfg)


def sparse_step_bytes(cfg: dict, *, slots_read: float, slots_held: float,
                      dense_slots: float, dtype_bytes: int = 2) -> dict:
    """One sparse layer's decode reads, summed over the live rows: K and V
    of the slots the equations read (`slots_read` of the selecting rows,
    `dense_slots` of the others) and the selecting rows' compressed keys
    (`slots_held / kernel_stride`)."""
    w = widths(cfg)
    head = w["KV"] * w["hd"] * dtype_bytes
    return {"read": 2 * head * (slots_read + dense_slots),
            "select": head * slots_held / w["stride"]}


def decode_step_bytes(cfg: dict, *, rows: float, slots_read: float,
                      slots_held: float, dense_slots: float,
                      dtype_bytes: int = 2) -> dict:
    """Bytes one decode step MUST move, by part: every layer's weights; a
    sparse layer's chosen slots and compressed keys; the LIVE rows' state
    read and written once; the head, the final norm and the live rows' f32
    logits. The embedding gather is left out. A floor."""
    w = widths(cfg)
    sparse = sparse_step_bytes(cfg, slots_read=slots_read,
                               slots_held=slots_held, dense_slots=dense_slots,
                               dtype_bytes=dtype_bytes)
    parts = {
        "sparse": w["Ls"] * sparse_params(cfg) * dtype_bytes,
        "lightning": w["Ll"] * lightning_params(cfg) * dtype_bytes,
        "mlp_norms": w["L"] * (mlp_params(cfg) + 2 * w["D"]) * dtype_bytes,
        "kv": w["Ls"] * (sparse["read"] + sparse["select"]),
        "state": 2 * rows * state_bytes_per_row(cfg),
        "head": (w["D"] * w["V"] + w["D"]) * dtype_bytes + rows * w["V"] * 4}
    return {**parts, "total": sum(parts.values())}


def state_update_bytes(cfg: dict, *, rows: float) -> float:
    """One lightning layer's decode pass over the state: the live rows' `S`
    read and written once and a token's operands in and out (q, k, v, y:
    float32 as the recurrence takes them)."""
    w = widths(cfg)
    return rows * (2 * state_bytes_per_row_layer(cfg)
                   + 4 * w["Hl"] * w["hl"] * 4)


def state_update_floor_s(cfg: dict, peaks: dict, **kw) -> float:
    return state_update_bytes(cfg, **kw) / peaks["hbm_bytes_per_s"]


def linear_scan_cost(cfg: dict, *, tokens: float, pieces: float) -> dict:
    """One lightning layer's recurrence over `tokens` prefilled tokens in
    `pieces` forwards: `4 hd hd` operations a head a token (`lam S + k
    (outer) v` a multiply and an add an element, `q S` likewise); bytes: a
    token's operands in and out (float32) and a row's `S` read and written
    once a PIECE."""
    w = widths(cfg)
    flops = 4.0 * w["Hl"] * w["hl"] * w["hl"] * tokens
    operands = 4 * w["Hl"] * w["hl"] * 4
    return {"flops": flops, "bytes": tokens * operands
            + pieces * 2 * state_bytes_per_row_layer(cfg)}


def linear_scan_floor_s(cfg: dict, peaks: dict, **kw) -> float:
    """The larger of operations over the bf16 peak (the matrix unit's: a
    float32 scan cannot reach it, and reads low for that) and bytes over the
    HBM bandwidth."""
    c = linear_scan_cost(cfg, **kw)
    return max(c["flops"] / peaks["bf16_flops_per_s"],
               c["bytes"] / peaks["hbm_bytes_per_s"])


def select_floor_s(cfg: dict, peaks: dict, *, slots_held: float,
                   dtype_bytes: int = 2) -> float:
    """One sparse layer's decode selection over the selecting rows of one
    step: their compressed keys read once (`slots_held / kernel_stride`
    keys of KV x hd) against the bandwidth, or the score products (H x hd
    a key, a multiply and an add) against the bf16 peak, the larger."""
    w = widths(cfg)
    keys = slots_held / w["stride"]
    return max(keys * w["KV"] * w["hd"] * dtype_bytes / peaks["hbm_bytes_per_s"],
               2.0 * keys * w["H"] * w["hd"] / peaks["bf16_flops_per_s"])


def sparse_read_floor_s(cfg: dict, peaks: dict, *, slots: float,
                        dtype_bytes: int = 2) -> float:
    """One sparse layer's decode read of `slots` slots of K and V (the
    selecting rows' chosen ones and the dense rows' all) against the HBM
    bandwidth."""
    w = widths(cfg)
    return 2 * w["KV"] * w["hd"] * dtype_bytes * slots / peaks["hbm_bytes_per_s"]


def sparse_prefill_floor_s(cfg: dict, peaks: dict, *, query_slots: float
                           ) -> float:
    """One sparse layer's attention over prefilled queries: `query_slots`
    is the sum over the queries of the slots each READS by the equations
    (its chosen blocks', at most `topk x block_size`; every causal slot
    under `dense_len`); QK and PV are `4 H hd` operations a query a slot,
    against the bf16 peak."""
    w = widths(cfg)
    return 4.0 * w["H"] * w["hd"] * query_slots / peaks["bf16_flops_per_s"]


def prefill_query_slots(cfg: dict, prompt: int) -> float:
    """The slots the queries of a prompt of `prompt` tokens read in one
    sparse layer, by the equations: query t reads `t + 1` slots under
    `dense_len` (or while it has no more than `topk` blocks), else `topk x
    block_size` less the part of its own block that lies ahead of it."""
    w = widths(cfg)
    most = w["topk"] * w["block"]
    if prompt < w["dense_len"]:
        return prompt * (prompt + 1) / 2.0
    head = min(prompt, most)
    # past `most` slots a query reads topk blocks, its own to t only: on
    # average half a block less
    return head * (head + 1) / 2.0 + (prompt - head) * (most - w["block"] / 2.0)
