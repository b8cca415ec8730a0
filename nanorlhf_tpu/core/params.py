"""HF checkpoint → stacked JAX param tree.

The reference gets weights via `AutoModelForCausalLM.from_pretrained`
(`/root/reference/GRPO/grpo.py:218-224`). Here we map the HF Qwen2/Llama
state-dict layout (both families share it — Llama just drops the q/k/v
biases) onto our scan-friendly stacked tree (core/model.py): per-layer
tensors are stacked along a leading [L, ...] axis and torch `nn.Linear`
weights ([out, in]) are transposed to the x @ W layout ([in, out]).
SmallThinker (docs/SWA.md) names its router `block_sparse_moe.primary_router`
and its experts `block_sparse_moe.experts.{e}.{gate,up,down}` (`_expert_names`).
OLMoE adds (docs/MOE.md): `mlp.experts.{e}.{gate,up,down}_proj.weight` ↔
`layers.experts.{gate,up,down}_proj.kernel [L, E, in, out]`,
`mlp.gate.weight` ↔ `layers.router.kernel [L, D, E]`, and
`self_attn.{q,k}_norm.weight` ↔ `layers.{q,k}_norm`.

A.X-K1 (`model_type: axk1`, DeepSeek-V3's names; docs/MLA.md): the leading
`first_k_dense_replace` layers go to `dense_layers`, the rest to `layers`;
`self_attn.{q_a_proj,q_b_proj,kv_a_proj_with_mqa,kv_b_proj,o_proj}` ↔
`{q_a,q_b,kv_a,kv_b,o}_proj.kernel`, `self_attn.{q_a,kv_a}_layernorm`,
`mlp.gate.weight` ↔ `router.kernel`, `mlp.experts.{offset + e}.*` ↔ the
HELD experts' `experts.*.kernel [L, held, in, out]`, `mlp.shared_experts.*` ↔
`shared_expert.*`. The checkpoint stores rotary pairs interleaved; this tree
rotates halves, so the rotary columns of `q_b_proj` and `kv_a_proj` are
de-interleaved on load and re-interleaved on export (`_ROPE` below).

LFM2-MoE (`model_type: lfm2_moe`; docs/STATE.md; names assumed from the
family's modelling code): `operator_norm` / `ffn_norm` ↔ `input_layernorm` /
`post_attention_layernorm`, `self_attn.{q,k,v}_proj`, `self_attn.out_proj` ↔
`o_proj`, `self_attn.{q,k}_layernorm` ↔ `{q,k}_norm [hd]`, `conv.in_proj` ↔
`conv.in_proj.kernel [D, 3D]`, `conv.conv.weight [D, 1, K]` ↔
`conv.conv.kernel [K, D]`, `conv.out_proj`; `feed_forward.{w1,w3,w2}` ↔
`{gate,up,down}_proj` (dense layers, in `dense_layers`) and
`feed_forward.experts.{e}.{w1,w3,w2}`, `feed_forward.gate.weight` ↔
`router.kernel`, `feed_forward.expert_bias` ↔ `router.bias`;
`model.embedding_norm` ↔ `norm`. Each stack holds an attention layer's leaves
over ITS attention layers and `conv` over its conv layers (`_lfm2_*`).

Trinity (`model_type: afmoe`; docs/AFMOE.md; names assumed from the family's
modelling code): `self_attn.{q,k,v,o}_proj`, `self_attn.gate_proj` ↔ `g_proj`,
`self_attn.{q,k}_norm` ↔ `{q,k}_norm [hd]`; the four norms `input_layernorm`,
`post_attention_layernorm` ↔ `attn_branch_norm` (it norms the BRANCH here),
`pre_mlp_layernorm` ↔ `post_attention_layernorm` (this tree's name for the
MLP's input norm) and `post_mlp_layernorm` ↔ `mlp_branch_norm`;
`mlp.{gate,up,down}_proj` (the leading dense layers, in `dense_layers`),
`mlp.router.gate.weight` ↔ `router.kernel`, `mlp.expert_bias` ↔
`router.bias`, `mlp.shared_experts.*` ↔ `shared_expert.*` and
`mlp.experts.{offset + e}.*` ↔ the HELD experts (`_two_stack_*`, A.X-K1's
loader with this family's names).

Falcon-H1 (`model_type: falcon_h1`; docs/SSM.md; names assumed from the
family's modelling code): `input_layernorm`, `pre_ff_layernorm` ↔
`post_attention_layernorm` (this tree's name for the MLP's input norm),
`self_attn.{q,k,v,o}_proj`, `feed_forward.{gate,up,down}_proj`, and the mixer
under `ssm`: `mamba.in_proj [2 I + 2 G N + H, D]` ↔ `in_proj.kernel [D, 2 I +
2 G N]` and `dt_proj.kernel [D, H]` (its last H rows: core/model.py `_init_ssm`),
`mamba.conv1d.weight [W, 1, K]` ↔ `conv.kernel [K, W]`, `mamba.conv1d.bias` ↔
`conv.bias`, `mamba.{A_log, D, dt_bias}`, `mamba.norm.weight` ↔ `norm`,
`mamba.out_proj`; `model.final_layernorm` ↔ `norm` (`_falcon_h1_*`).

Weight fidelity (GQA head layout, tied embeddings, RoPE) is pinned by
tests/test_model_parity.py against the torch Qwen2 AND Llama
implementations.
"""

from __future__ import annotations

import json
import os

import jax.numpy as jnp
import numpy as np

from nanorlhf_tpu.core.config import ModelConfig

# bias presence is read off the state dict itself (Qwen2 q/k/v carry
# biases, Llama-family none — both map onto the same optional-bias tree)
_ATTENTION_KEYS = (
    ("q_proj", "self_attn.q_proj"),
    ("k_proj", "self_attn.k_proj"),
    ("v_proj", "self_attn.v_proj"),
    ("o_proj", "self_attn.o_proj"),
)
_MLP_KEYS = ("gate_proj", "up_proj", "down_proj")
_LINEAR_KEYS = _ATTENTION_KEYS + tuple((k, f"mlp.{k}") for k in _MLP_KEYS)
_NORM_KEYS = (
    ("input_layernorm", "input_layernorm"),
    ("post_attention_layernorm", "post_attention_layernorm"),
)
_QK_NORM_KEYS = (("q_norm", "self_attn.q_norm"), ("k_norm", "self_attn.k_norm"))
# A plain stack's branch norms (`config.branch_norms` outside Trinity's own
# table below) carry Ouro's names, the one family that has them there
# (docs/OURO.md): the second norm of each pair norms its BRANCH
_BRANCH_NORM_KEYS = (("attn_branch_norm", "input_layernorm_2"),
                     ("mlp_branch_norm", "post_attention_layernorm_2"))
_EXIT_GATE = "model.early_exit_gate"    # a looped model's (`loop_passes` > 1)


def _expert_names(config: ModelConfig):
    """(router, expert format) under `model.layers.{i}.`: OLMoE's
    `mlp.gate` / `mlp.experts.{e}.{gate,up,down}_proj`, SmallThinker's
    `block_sparse_moe.primary_router` / `block_sparse_moe.experts.{e}.
    {gate,up,down}`."""
    if config.model_type == "smallthinker":
        return ("block_sparse_moe.primary_router",
                lambda e, name: f"block_sparse_moe.experts.{e}."
                                f"{name.removesuffix('_proj')}")
    return "mlp.gate", lambda e, name: f"mlp.experts.{e}.{name}"


def _layer_keys(config: ModelConfig):
    """(linear, norm) name pairs of the layer tree this config builds: an
    expert model's MLP is not among the per-layer Linears."""
    linear = _ATTENTION_KEYS if config.num_experts else _LINEAR_KEYS
    # OLMoE's q/k norms (over the projection's width) and SDAR's (Qwen3-MoE's
    # names, a weight of `head_dim` a layer: assumed, docs/BLOCKDIFF.md)
    norm = _NORM_KEYS + (_QK_NORM_KEYS if config.qk_norm
                         or config.model_type == "sdar_moe" else ())
    if config.branch_norms:
        norm = norm + _BRANCH_NORM_KEYS
    return linear, norm


_MLA_LINEAR_KEYS = (
    ("q_a_proj", "self_attn.q_a_proj"),
    ("q_b_proj", "self_attn.q_b_proj"),
    ("kv_a_proj", "self_attn.kv_a_proj_with_mqa"),
    ("kv_b_proj", "self_attn.kv_b_proj"),
    ("o_proj", "self_attn.o_proj"),
)
_MLA_NORM_KEYS = _NORM_KEYS + (
    ("q_a_layernorm", "self_attn.q_a_layernorm"),
    ("kv_a_layernorm", "self_attn.kv_a_layernorm"),
)


def _rope_columns(config: ModelConfig, name: str, kernel, to_ours: bool):
    """`kernel` [in, out] of `q_b_proj` / `kv_a_proj` with its rotary output
    columns moved between the checkpoint's interleaved pairs
    (x0 y0 x1 y1 ..) and this tree's halves (x0 x1 .. y0 y1 ..); any other
    kernel comes back as it is."""
    dr = config.qk_rope_head_dim
    perm = np.concatenate([np.arange(0, dr, 2), np.arange(1, dr, 2)])
    if not to_ours:
        perm = np.argsort(perm)
    if name == "kv_a_proj":
        r = config.kv_lora_rank
        return np.concatenate([kernel[:, :r], kernel[:, r:][:, perm]], axis=1)
    if name == "q_b_proj":
        H, dn = config.num_attention_heads, config.qk_nope_head_dim
        k = kernel.reshape(kernel.shape[0], H, dn + dr)
        k = np.concatenate([k[..., :dn], k[..., dn:][..., perm]], axis=-1)
        return k.reshape(kernel.shape)
    return kernel


def _two_stacks(config: ModelConfig):
    """(tree name, HF layer indices) of the two stacks of a model whose
    leading layers have a dense MLP and whose attention leaves lie over
    every layer (A.X-K1, Trinity)."""
    Ld = config.num_dense_layers
    stacks = [("layers", range(Ld, config.num_hidden_layers))]
    return ([("dense_layers", range(Ld))] if Ld else []) + stacks


def _two_stack_names(config: ModelConfig):
    """(norm pairs, linear pairs, the router's HF name, its bias's | None)
    of such a model's family."""
    if config.kv_lora_rank:
        return _MLA_NORM_KEYS, _MLA_LINEAR_KEYS, "mlp.gate", None
    return _AFMOE_NORMS, _AFMOE_LINEAR, "mlp.router.gate", "mlp.expert_bias"


def _two_stack_params_from_sd(config: ModelConfig, sd: dict, cast) -> dict:
    norms, linears, router, bias = _two_stack_names(config)
    held = range(config.experts_offset,
                 config.experts_offset + config.num_held_experts)
    params = {}
    for stack, idx in _two_stacks(config):
        at = lambda i, name: sd[f"model.layers.{i}.{name}.weight"]  # noqa: E731
        tree = {ours: cast(np.stack([at(i, theirs) for i in idx]))
                for ours, theirs in norms}
        for ours, theirs in linears:
            tree[ours] = {"kernel": cast(np.stack([
                _rope_columns(config, ours, at(i, theirs).T, True)
                for i in idx]))}
        mlp = lambda prefix: {name: {"kernel": cast(np.stack(  # noqa: E731
            [at(i, f"{prefix}.{name}").T for i in idx]))} for name in _MLP_KEYS}
        if stack == "dense_layers" or not config.num_experts:
            tree.update(mlp("mlp"))
        else:
            tree["router"] = {"kernel": cast(np.stack(
                [at(i, router).T for i in idx]))}
            if bias:
                tree["router"]["bias"] = jnp.asarray(np.stack(
                    [sd[f"model.layers.{i}.{bias}"] for i in idx]), jnp.float32)
            tree["experts"] = {name: {"kernel": cast(np.stack([
                np.stack([at(i, f"mlp.experts.{e}.{name}").T for e in held])
                for i in idx]))} for name in _MLP_KEYS}
            if config.n_shared_experts:
                tree["shared_expert"] = mlp("mlp.shared_experts")
        params[stack] = tree
    return params


def _two_stack_sd_from_params(config: ModelConfig, params: dict, put) -> None:
    norms, linears, router, bias = _two_stack_names(config)
    for stack, idx in _two_stacks(config):
        tree = params[stack]
        for j, i in enumerate(idx):
            pre = f"model.layers.{i}."
            for ours, theirs in norms:
                put(f"{pre}{theirs}.weight", tree[ours][j])
            for ours, theirs in linears:
                put(f"{pre}{theirs}.weight", _rope_columns(
                    config, ours, np.asarray(tree[ours]["kernel"][j]), False).T)
            if "router" not in tree:
                for name in _MLP_KEYS:
                    put(f"{pre}mlp.{name}.weight", tree[name]["kernel"][j].T)
                continue
            put(f"{pre}{router}.weight", tree["router"]["kernel"][j].T)
            if bias:
                put(f"{pre}{bias}", tree["router"]["bias"][j])
            for name in _MLP_KEYS:
                kernel = tree["experts"][name]["kernel"][j]
                for e in range(kernel.shape[0]):
                    put(f"{pre}mlp.experts.{config.experts_offset + e}."
                        f"{name}.weight", kernel[e].T)
                if "shared_expert" in tree:
                    put(f"{pre}mlp.shared_experts.{name}.weight",
                        tree["shared_expert"][name]["kernel"][j].T)


_AFMOE_LINEAR = _ATTENTION_KEYS + (("g_proj", "self_attn.gate_proj"),)
_AFMOE_NORMS = (("input_layernorm", "input_layernorm"),
                ("attn_branch_norm", "post_attention_layernorm"),
                ("post_attention_layernorm", "pre_mlp_layernorm"),
                ("mlp_branch_norm", "post_mlp_layernorm")) + _QK_NORM_KEYS


_LFM2_MLP = (("gate_proj", "w1"), ("up_proj", "w3"), ("down_proj", "w2"))
_LFM2_ATTENTION = (("q_proj", "self_attn.q_proj"), ("k_proj", "self_attn.k_proj"),
                   ("v_proj", "self_attn.v_proj"), ("o_proj", "self_attn.out_proj"))
_LFM2_NORMS = (("input_layernorm", "operator_norm"),
               ("post_attention_layernorm", "ffn_norm"))
_LFM2_QK = (("q_norm", "self_attn.q_layernorm"), ("k_norm", "self_attn.k_layernorm"))


def _lfm2_stacks(config: ModelConfig):
    """[(tree name, first layer, layers)] of an LFM2-MoE model."""
    dense = config.num_dense_layers
    return ([("dense_layers", 0, dense)] if dense else []) + [
        ("layers", dense, config.num_hidden_layers - dense)]


def _lfm2_params_from_sd(config: ModelConfig, sd: dict, cast) -> dict:
    kinds = config.layer_kinds
    params = {}
    for tree_name, start, n in _lfm2_stacks(config):
        ids = range(start, start + n)
        conv_ids = [i for i in ids if kinds[i] == "conv"]
        attn_ids = [i for i in ids if kinds[i] != "conv"]
        w = lambda i, name: sd[f"model.layers.{i}.{name}.weight"]   # noqa: E731
        tree = {ours: cast(np.stack([w(i, theirs) for i in ids]))
                for ours, theirs in _LFM2_NORMS}
        if tree_name == "layers":
            tree["router"] = {"kernel": cast(np.stack(
                [w(i, "feed_forward.gate").T for i in ids]))}
            if config.use_expert_bias:
                tree["router"]["bias"] = jnp.asarray(np.stack(
                    [sd[f"model.layers.{i}.feed_forward.expert_bias"]
                     for i in ids]), jnp.float32)
            tree["experts"] = {ours: {"kernel": cast(np.stack([np.stack(
                [w(i, f"feed_forward.experts.{e}.{theirs}").T
                 for e in range(config.num_experts)]) for i in ids]))}
                for ours, theirs in _LFM2_MLP}
        else:
            for ours, theirs in _LFM2_MLP:
                tree[ours] = {"kernel": cast(np.stack(
                    [w(i, f"feed_forward.{theirs}").T for i in ids]))}
        if conv_ids:
            tree["conv"] = {
                "in_proj": {"kernel": cast(np.stack(
                    [w(i, "conv.in_proj").T for i in conv_ids]))},
                # torch's depthwise Conv1d weight [D, 1, K] -> [K, D]
                "conv": {"kernel": cast(np.stack(
                    [w(i, "conv.conv")[:, 0, :].T for i in conv_ids]))},
                "out_proj": {"kernel": cast(np.stack(
                    [w(i, "conv.out_proj").T for i in conv_ids]))}}
        if attn_ids:
            for ours, theirs in _LFM2_ATTENTION:
                tree[ours] = {"kernel": cast(np.stack(
                    [w(i, theirs).T for i in attn_ids]))}
            for ours, theirs in _LFM2_QK:
                tree[ours] = cast(np.stack([w(i, theirs) for i in attn_ids]))
        params[tree_name] = tree
    return params


def _lfm2_sd_from_params(config: ModelConfig, params: dict, put) -> None:
    kinds = config.layer_kinds
    for tree_name, start, n in _lfm2_stacks(config):
        tree = params[tree_name]
        at = {"conv": 0, "attn": 0}
        for j in range(n):
            pre = f"model.layers.{start + j}."
            for ours, theirs in _LFM2_NORMS:
                put(f"{pre}{theirs}.weight", tree[ours][j])
            if tree_name == "layers":
                put(f"{pre}feed_forward.gate.weight", tree["router"]["kernel"][j].T)
                if "bias" in tree["router"]:
                    put(f"{pre}feed_forward.expert_bias", tree["router"]["bias"][j])
                for ours, theirs in _LFM2_MLP:
                    kernel = tree["experts"][ours]["kernel"][j]
                    for e in range(kernel.shape[0]):
                        put(f"{pre}feed_forward.experts.{e}.{theirs}.weight",
                            kernel[e].T)
            else:
                for ours, theirs in _LFM2_MLP:
                    put(f"{pre}feed_forward.{theirs}.weight",
                        tree[ours]["kernel"][j].T)
            if kinds[start + j] == "conv":
                c, conv = at["conv"], tree["conv"]
                put(f"{pre}conv.in_proj.weight", conv["in_proj"]["kernel"][c].T)
                put(f"{pre}conv.conv.weight",
                    conv["conv"]["kernel"][c].T[:, None, :])
                put(f"{pre}conv.out_proj.weight", conv["out_proj"]["kernel"][c].T)
                at["conv"] += 1
            else:
                a = at["attn"]
                for ours, theirs in _LFM2_ATTENTION:
                    put(f"{pre}{theirs}.weight", tree[ours]["kernel"][a].T)
                for ours, theirs in _LFM2_QK:
                    put(f"{pre}{theirs}.weight", tree[ours][a])
                at["attn"] += 1


# MiniCPM-SALA (docs/SALA.md; the names are ASSUMED from the family's
# modelling code, the published checkpoint is not on this machine): a
# layer's mixer is `self_attn` whichever its kind; (ours, theirs) of the
# kernels and vectors of a sparse layer and of a lightning layer
_SALA_SPARSE = (("q_proj", "q_proj"), ("k_proj", "k_proj"), ("v_proj", "v_proj"),
                ("o_proj", "o_proj"), ("g_proj", "o_gate"))
_SALA_LIGHTNING = (("q_proj", "q_proj"), ("k_proj", "k_proj"),
                   ("v_proj", "v_proj"), ("o_proj", "o_proj"),
                   ("z_proj", "z_proj"))
_SALA_SPARSE_NORMS = (("q_norm", "q_norm"), ("k_norm", "k_norm"))
_SALA_LIGHTNING_NORMS = _SALA_SPARSE_NORMS + (("o_norm", "o_norm"),)
_SALA_SHARED = (("input_layernorm", "input_layernorm"),
                ("post_attention_layernorm", "post_attention_layernorm"))


def _sala_params_from_sd(config: ModelConfig, sd: dict, cast) -> dict:
    kinds = config.layer_kinds
    w = lambda i, name: sd[f"model.layers.{i}.{name}.weight"]       # noqa: E731
    ids = range(config.num_hidden_layers)
    tree = {ours: cast(np.stack([w(i, theirs) for i in ids]))
            for ours, theirs in _SALA_SHARED}
    for name in _MLP_KEYS:
        tree[name] = {"kernel": cast(np.stack(
            [w(i, f"mlp.{name}").T for i in ids]))}
    sparse = [i for i in ids if kinds[i] == "sparse"]
    light = [i for i in ids if kinds[i] == "lightning"]
    for ours, theirs in _SALA_SPARSE:
        tree[ours] = {"kernel": cast(np.stack(
            [w(i, f"self_attn.{theirs}").T for i in sparse]))}
    for ours, theirs in _SALA_SPARSE_NORMS:
        tree[ours] = cast(np.stack([w(i, f"self_attn.{theirs}")
                                    for i in sparse]))
    tree["lightning"] = {
        **{ours: {"kernel": cast(np.stack(
            [w(i, f"self_attn.{theirs}").T for i in light]))}
           for ours, theirs in _SALA_LIGHTNING},
        **{ours: cast(np.stack([w(i, f"self_attn.{theirs}") for i in light]))
           for ours, theirs in _SALA_LIGHTNING_NORMS}}
    return {"layers": tree}


def _sala_sd_from_params(config: ModelConfig, params: dict, put) -> None:
    kinds, tree = config.layer_kinds, params["layers"]
    at = {"sparse": 0, "lightning": 0}
    for i, kind in enumerate(kinds):
        pre = f"model.layers.{i}."
        for ours, theirs in _SALA_SHARED:
            put(f"{pre}{theirs}.weight", tree[ours][i])
        for name in _MLP_KEYS:
            put(f"{pre}mlp.{name}.weight", tree[name]["kernel"][i].T)
        a = at[kind]
        at[kind] += 1
        own = tree["lightning"] if kind == "lightning" else tree
        kernels, norms = ((_SALA_LIGHTNING, _SALA_LIGHTNING_NORMS)
                          if kind == "lightning"
                          else (_SALA_SPARSE, _SALA_SPARSE_NORMS))
        for ours, theirs in kernels:
            put(f"{pre}self_attn.{theirs}.weight", own[ours]["kernel"][a].T)
        for ours, theirs in norms:
            put(f"{pre}self_attn.{theirs}.weight", own[ours][a])


# Falcon-H1: (ours, theirs) of a layer's kernels outside its mixer, of its
# norms, and of the mixer's vectors
_FH1_KERNELS = (("q_proj", "self_attn.q_proj"), ("k_proj", "self_attn.k_proj"),
                ("v_proj", "self_attn.v_proj"), ("o_proj", "self_attn.o_proj"),
                ("gate_proj", "feed_forward.gate_proj"),
                ("up_proj", "feed_forward.up_proj"),
                ("down_proj", "feed_forward.down_proj"))
_FH1_NORMS = (("input_layernorm", "input_layernorm"),
              ("post_attention_layernorm", "pre_ff_layernorm"))
_FH1_VECTORS = (("A_log", "mamba.A_log"), ("D", "mamba.D"),
                ("dt_bias", "mamba.dt_bias"), ("norm", "mamba.norm.weight"))


def _falcon_h1_params_from_sd(config: ModelConfig, sd: dict, cast) -> dict:
    ids = range(config.num_hidden_layers)
    at = lambda i, name: sd[f"model.layers.{i}.{name}"]            # noqa: E731
    over = lambda name, f=lambda a: a: cast(np.stack(              # noqa: E731
        [f(at(i, name)) for i in ids]))
    T = lambda a: a.T                                               # noqa: E731
    layers = {ours: over(theirs + ".weight") for ours, theirs in _FH1_NORMS}
    for ours, theirs in _FH1_KERNELS:
        layers[ours] = {"kernel": over(theirs + ".weight", T)}
    layers["ssm"] = {
        "in_proj": {"kernel": over("mamba.in_proj.weight",
                                   lambda a: a[:-config.ssm_heads].T)},
        "dt_proj": {"kernel": over("mamba.in_proj.weight",
                                   lambda a: a[-config.ssm_heads:].T)},
        # torch's depthwise Conv1d weight [W, 1, K] -> [K, W]
        "conv": {"kernel": over("mamba.conv1d.weight", lambda a: a[:, 0, :].T),
                 "bias": over("mamba.conv1d.bias")},
        "out_proj": {"kernel": over("mamba.out_proj.weight", T)},
        **{ours: over(theirs) for ours, theirs in _FH1_VECTORS}}
    return {"layers": layers}


def _falcon_h1_sd_from_params(config: ModelConfig, params: dict, put) -> None:
    layers = params["layers"]
    ssm = layers["ssm"]
    for i in range(config.num_hidden_layers):
        pre = f"model.layers.{i}."
        for ours, theirs in _FH1_NORMS:
            put(f"{pre}{theirs}.weight", layers[ours][i])
        for ours, theirs in _FH1_KERNELS:
            put(f"{pre}{theirs}.weight", layers[ours]["kernel"][i].T)
        put(f"{pre}mamba.in_proj.weight", jnp.concatenate(
            [ssm["in_proj"]["kernel"][i].T, ssm["dt_proj"]["kernel"][i].T]))
        put(f"{pre}mamba.conv1d.weight", ssm["conv"]["kernel"][i].T[:, None, :])
        put(f"{pre}mamba.conv1d.bias", ssm["conv"]["bias"][i])
        put(f"{pre}mamba.out_proj.weight", ssm["out_proj"]["kernel"][i].T)
        for ours, theirs in _FH1_VECTORS:
            put(f"{pre}{theirs}", ssm[ours][i])


# Granite 4.0-H (docs/GRANITE_H.md; names assumed from the family's modelling
# code, `GraniteMoeHybrid` on Bamba's mixer: the published checkpoint is not
# on this machine): a layer holds `mamba.*` OR `self_attn.*`, then
# `block_sparse_moe.{router.layer, input_linear [E, 2 F, D] ([gate; up]),
# output_linear [E, D, F]}` and `shared_mlp.{input_linear [2 Fs, D],
# output_linear [D, Fs]}`. A chip's share reads and writes ITS experts' rows.
_GH_NORMS = (("input_layernorm", "input_layernorm"),
             ("post_attention_layernorm", "post_attention_layernorm"))
_GH_ATTENTION = (("q_proj", "self_attn.q_proj"), ("k_proj", "self_attn.k_proj"),
                 ("v_proj", "self_attn.v_proj"), ("o_proj", "self_attn.o_proj"))


def _granite_h_params_from_sd(config: ModelConfig, sd: dict, cast) -> dict:
    kinds = config.layer_kinds
    ids = range(config.num_hidden_layers)
    mixers = [i for i in ids if kinds[i] == "mamba"]
    attn = [i for i in ids if kinds[i] != "mamba"]
    at = lambda i, name: sd[f"model.layers.{i}.{name}"]             # noqa: E731
    over = lambda which, name, f=lambda a: a: cast(np.stack(        # noqa: E731
        [f(at(i, name)) for i in which]))
    T = lambda a: a.T                                               # noqa: E731
    F, Fs = config.expert_width, config.shared_expert_width
    n_held = config.num_held_experts
    # (a share's own export holds its experts' rows alone)
    own = lambda a: a if a.shape[0] == n_held else a[                # noqa: E731
        config.experts_offset:config.experts_offset + n_held]
    moe, shared = "block_sparse_moe.", "shared_mlp."
    tree = {ours: over(ids, theirs + ".weight") for ours, theirs in _GH_NORMS}
    tree["router"] = {"kernel": over(ids, moe + "router.layer.weight", T)}
    tree["experts"] = {
        "gate_proj": {"kernel": over(ids, moe + "input_linear.weight",
                                     lambda a: own(a)[:, :F].transpose(0, 2, 1))},
        "up_proj": {"kernel": over(ids, moe + "input_linear.weight",
                                   lambda a: own(a)[:, F:].transpose(0, 2, 1))},
        "down_proj": {"kernel": over(ids, moe + "output_linear.weight",
                                     lambda a: own(a).transpose(0, 2, 1))}}
    tree["shared_expert"] = {
        "gate_proj": {"kernel": over(ids, shared + "input_linear.weight",
                                     lambda a: a[:Fs].T)},
        "up_proj": {"kernel": over(ids, shared + "input_linear.weight",
                                   lambda a: a[Fs:].T)},
        "down_proj": {"kernel": over(ids, shared + "output_linear.weight", T)}}
    for ours, theirs in _GH_ATTENTION:
        tree[ours] = {"kernel": over(attn, theirs + ".weight", T)}
    tree["ssm"] = {
        "in_proj": {"kernel": over(mixers, "mamba.in_proj.weight",
                                   lambda a: a[:-config.ssm_heads].T)},
        "dt_proj": {"kernel": over(mixers, "mamba.in_proj.weight",
                                   lambda a: a[-config.ssm_heads:].T)},
        "conv": {"kernel": over(mixers, "mamba.conv1d.weight",
                                lambda a: a[:, 0, :].T),
                 "bias": over(mixers, "mamba.conv1d.bias")},
        "out_proj": {"kernel": over(mixers, "mamba.out_proj.weight", T)},
        **{ours: over(mixers, theirs) for ours, theirs in _FH1_VECTORS}}
    return {"layers": tree}


def _granite_h_sd_from_params(config: ModelConfig, params: dict, put) -> None:
    """(a chip's share writes `[held, ...]` stacks of ITS experts: no whole
    checkpoint, as `export_hf_checkpoint`'s config says)"""
    tree = params["layers"]
    ssm, place = tree["ssm"], {"mamba": 0, "attn": 0}
    for i, kind in enumerate(config.layer_kinds):
        pre = f"model.layers.{i}."
        for ours, theirs in _GH_NORMS:
            put(f"{pre}{theirs}.weight", tree[ours][i])
        put(f"{pre}block_sparse_moe.router.layer.weight",
            tree["router"]["kernel"][i].T)
        for stack, name in ((tree["experts"], "block_sparse_moe."),
                            (tree["shared_expert"], "shared_mlp.")):
            gate, up, down = (stack[k]["kernel"][i] for k in _MLP_KEYS)
            put(f"{pre}{name}input_linear.weight", jnp.concatenate(
                [jnp.swapaxes(gate, -1, -2), jnp.swapaxes(up, -1, -2)], -2))
            put(f"{pre}{name}output_linear.weight", jnp.swapaxes(down, -1, -2))
        if kind != "mamba":
            a = place["attn"]
            place["attn"] += 1
            for ours, theirs in _GH_ATTENTION:
                put(f"{pre}{theirs}.weight", tree[ours]["kernel"][a].T)
            continue
        m = place["mamba"]
        place["mamba"] += 1
        put(f"{pre}mamba.in_proj.weight", jnp.concatenate(
            [ssm["in_proj"]["kernel"][m].T, ssm["dt_proj"]["kernel"][m].T]))
        put(f"{pre}mamba.conv1d.weight", ssm["conv"]["kernel"][m].T[:, None, :])
        put(f"{pre}mamba.conv1d.bias", ssm["conv"]["bias"][m])
        put(f"{pre}mamba.out_proj.weight", ssm["out_proj"]["kernel"][m].T)
        for ours, theirs in _FH1_VECTORS:
            put(f"{pre}{theirs}", ssm[ours][m])


def _to_np(t) -> np.ndarray:
    """torch tensor / np array → np array (bf16-safe via float32 round-trip)."""
    if hasattr(t, "detach"):
        t = t.detach()
        if t.dtype.is_floating_point:
            t = t.float()
        t = t.cpu().numpy()
    return np.asarray(t)


def params_from_hf_state_dict(
    config: ModelConfig, state_dict: dict, dtype=jnp.bfloat16
) -> dict:
    """Convert an HF Qwen2ForCausalLM state dict (name → tensor) to our tree."""
    sd = {k: _to_np(v) for k, v in state_dict.items()}
    L = config.num_hidden_layers

    def cast(x):
        return jnp.asarray(x, dtype)

    if config.kv_lora_rank or config.model_type == "afmoe":
        params = _two_stack_params_from_sd(config, sd, cast)
        params.update(embed_tokens=cast(sd["model.embed_tokens.weight"]),
                      norm=cast(sd["model.norm.weight"]))
        if not config.tie_word_embeddings:
            params["lm_head"] = cast(sd["lm_head.weight"].T)
        return params
    if config.linear_layers:
        params = _sala_params_from_sd(config, sd, cast)
        params.update(embed_tokens=cast(sd["model.embed_tokens.weight"]),
                      norm=cast(sd["model.norm.weight"]))
        if not config.tie_word_embeddings:
            params["lm_head"] = cast(sd["lm_head.weight"].T)
        return params
    if config.mamba_layers:
        params = _granite_h_params_from_sd(config, sd, cast)
        params.update(embed_tokens=cast(sd["model.embed_tokens.weight"]),
                      norm=cast(sd["model.norm.weight"]))
        if not config.tie_word_embeddings:
            params["lm_head"] = cast(sd["lm_head.weight"].T)
        return params
    if config.ssm_layers:
        params = _falcon_h1_params_from_sd(config, sd, cast)
        params.update(embed_tokens=cast(sd["model.embed_tokens.weight"]),
                      norm=cast(sd["model.final_layernorm.weight"]))
        if not config.tie_word_embeddings:
            params["lm_head"] = cast(sd["lm_head.weight"].T)
        return params
    if config.conv_layers:
        params = _lfm2_params_from_sd(config, sd, cast)
        params.update(embed_tokens=cast(sd["model.embed_tokens.weight"]),
                      norm=cast(sd["model.embedding_norm.weight"]))
        if not config.tie_word_embeddings:
            params["lm_head"] = cast(sd["lm_head.weight"].T)
        return params
    linear_keys, norm_keys = _layer_keys(config)
    layers: dict = {
        ours: cast(np.stack(
            [sd[f"model.layers.{i}.{theirs}.weight"] for i in range(L)]))
        for ours, theirs in norm_keys
    }
    if config.num_experts:
        router, expert = _expert_names(config)
        layers["router"] = {"kernel": cast(np.stack(
            [sd[f"model.layers.{i}.{router}.weight"].T for i in range(L)]))}
        layers["experts"] = {
            name: {"kernel": cast(np.stack([
                np.stack([sd[f"model.layers.{i}.{expert(e, name)}.weight"].T
                          for e in range(config.num_experts)])
                for i in range(L)]))}
            for name in _MLP_KEYS
        }
    for ours, theirs in linear_keys:
        kernel = np.stack(
            [sd[f"model.layers.{i}.{theirs}.weight"].T for i in range(L)]
        )
        entry = {"kernel": cast(kernel)}
        if f"model.layers.0.{theirs}.bias" in sd:
            entry["bias"] = cast(
                np.stack([sd[f"model.layers.{i}.{theirs}.bias"] for i in range(L)])
            )
        layers[ours] = entry

    params = {
        "embed_tokens": cast(sd["model.embed_tokens.weight"]),
        "layers": layers,
        "norm": cast(sd["model.norm.weight"]),
    }
    if not config.tie_word_embeddings:
        # some HF checkpoints omit lm_head when tied; require it when untied
        params["lm_head"] = cast(sd["lm_head.weight"].T)
    if config.loop_passes > 1:      # `nn.Linear(hidden, 1)`, with bias
        params["early_exit_gate"] = {
            "kernel": cast(sd[f"{_EXIT_GATE}.weight"].T),
            "bias": cast(sd[f"{_EXIT_GATE}.bias"])}
    return params


def hf_state_dict_from_params(config: ModelConfig, params: dict,
                              dtype=jnp.float32) -> dict:
    """Inverse of `params_from_hf_state_dict`: stacked JAX tree → flat HF
    Qwen2/Llama state dict (torch [out, in] linear layout), cast per-tensor
    to `dtype` so a 7B export never holds a second full-precision copy.
    LoRA subtrees are NOT folded here — pass a `merge_lora`'d tree to export
    adapters into the base weights (`save_model` parity: the reference's
    trained output is a plain HF checkpoint, `GRPO/grpo_trainer.py:321-341`)."""
    L = config.num_hidden_layers
    sd: dict = {}

    def put(name, arr):
        sd[name] = jnp.asarray(arr, dtype)

    layers = params["layers"]
    linear_keys, norm_keys = _layer_keys(config)
    if config.linear_layers:
        _sala_sd_from_params(config, params, put)
        put("model.embed_tokens.weight", params["embed_tokens"])
        put("model.norm.weight", params["norm"])
        if not config.tie_word_embeddings:
            put("lm_head.weight", params["lm_head"].T)
        return sd
    if config.mamba_layers:
        _granite_h_sd_from_params(config, params, put)
        put("model.embed_tokens.weight", params["embed_tokens"])
        put("model.norm.weight", params["norm"])
        if not config.tie_word_embeddings:
            put("lm_head.weight", params["lm_head"].T)
        return sd
    if config.ssm_layers:
        _falcon_h1_sd_from_params(config, params, put)
        put("model.embed_tokens.weight", params["embed_tokens"])
        put("model.final_layernorm.weight", params["norm"])
        if not config.tie_word_embeddings:
            put("lm_head.weight", params["lm_head"].T)
        return sd
    if config.conv_layers:
        _lfm2_sd_from_params(config, params, put)
        put("model.embed_tokens.weight", params["embed_tokens"])
        put("model.embedding_norm.weight", params["norm"])
        if not config.tie_word_embeddings:
            put("lm_head.weight", params["lm_head"].T)
        return sd
    if config.kv_lora_rank or config.model_type == "afmoe":
        _two_stack_sd_from_params(config, params, put)
        L = 0       # the two stacks are written; the rest is shared
    for i in range(L):
        for ours, theirs in norm_keys:
            put(f"model.layers.{i}.{theirs}.weight", layers[ours][i])
        if config.num_experts:
            router, expert = _expert_names(config)
            put(f"model.layers.{i}.{router}.weight",
                layers["router"]["kernel"][i].T)
            for name in _MLP_KEYS:
                kernel = layers["experts"][name]["kernel"][i]
                for e in range(config.num_experts):
                    put(f"model.layers.{i}.{expert(e, name)}.weight",
                        kernel[e].T)
        for ours, theirs in linear_keys:
            put(f"model.layers.{i}.{theirs}.weight", layers[ours]["kernel"][i].T)
            if "bias" in layers[ours]:
                put(f"model.layers.{i}.{theirs}.bias", layers[ours]["bias"][i])
    put("model.embed_tokens.weight", params["embed_tokens"])
    put("model.norm.weight", params["norm"])
    if not config.tie_word_embeddings:
        put("lm_head.weight", params["lm_head"].T)
    if config.loop_passes > 1:
        put(f"{_EXIT_GATE}.weight", params["early_exit_gate"]["kernel"].T)
        put(f"{_EXIT_GATE}.bias", params["early_exit_gate"]["bias"])
    return sd


def export_hf_checkpoint(
    config: ModelConfig,
    params: dict,
    out_dir: str,
    lora_scale: float | None = None,
    dtype: str = "bfloat16",
    tokenizer=None,
    eos_token_id: int | None = None,
    bos_token_id: int | None = None,
    pad_token_id: int | None = None,
) -> str:
    """Write an HF-format checkpoint dir (config.json + model.safetensors)
    that `AutoModelForCausalLM.from_pretrained` (and this module's
    `load_hf_checkpoint`) accepts — the reference's `save_model` output
    contract. `lora_scale` folds a `params["lora"]` subtree into the base
    weights first (the reference merges adapters before saving/handoff,
    `GRPO/grpo_trainer.py:131-141,321-341`).

    The handoff is only usable if generation knows how to stop and tokenize:
    a `tokenizer` with `save_pretrained` is saved alongside the weights
    (the reference's save_model does the same), and eos/bos/pad ids — taken
    from the tokenizer when not given — go into config.json and
    generation_config.json so transformers/vLLM terminate correctly."""
    from safetensors.flax import save_file

    if lora_scale is not None and "lora" in params:
        from nanorlhf_tpu.core.lora import merge_lora

        params = merge_lora(params, lora_scale)
    params = {k: v for k, v in params.items() if k != "lora"}

    os.makedirs(out_dir, exist_ok=True)
    jdtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[dtype]
    sd = hf_state_dict_from_params(config, params, dtype=jdtype)
    save_file(sd, os.path.join(out_dir, "model.safetensors"))

    if tokenizer is not None:
        if eos_token_id is None:
            eos_token_id = getattr(tokenizer, "eos_token_id", None)
        if bos_token_id is None:
            bos_token_id = getattr(tokenizer, "bos_token_id", None)
        if pad_token_id is None:
            pad_token_id = getattr(tokenizer, "pad_token_id", None)
        if hasattr(tokenizer, "save_pretrained"):
            tokenizer.save_pretrained(out_dir)

    # echo the source family when the config carries one (from_hf_config /
    # load_hf_checkpoint set it; a Llama with attention_bias=True must not
    # round-trip to Qwen2), but only for the families this exporter can
    # faithfully emit (three with OLMoE) — an unknown slug (e.g. "mistral") echoed verbatim
    # would make transformers' AutoConfig apply that family's defaults
    # (sliding_window, ...) to keys we never write. Anything else falls
    # back to the attention_bias heuristic, as do random-init configs.
    family = config.model_type if config.model_type in (
        "qwen2", "llama", "olmoe", "axk1", "smallthinker", "lfm2_moe",
        "afmoe", "sdar_moe", "falcon_h1", "ouro", "granitemoehybrid") else (
        "qwen2" if config.attention_bias else "llama")
    arch = {"qwen2": "Qwen2ForCausalLM", "llama": "LlamaForCausalLM",
            "olmoe": "OlmoeForCausalLM", "axk1": "AXK1ForCausalLM",
            "smallthinker": "SmallThinkerForCausalLM",
            "lfm2_moe": "Lfm2MoeForCausalLM",
            "afmoe": "AfmoeForCausalLM",
            "sdar_moe": "SDARMoeForCausalLM",
            "falcon_h1": "FalconH1ForCausalLM",
            "ouro": "OuroForCausalLM",
            "granitemoehybrid": "GraniteMoeHybridForCausalLM"}[family]
    hf_config = {
        "architectures": [arch],
        "model_type": family,
        "vocab_size": config.vocab_size,
        "hidden_size": config.hidden_size,
        "intermediate_size": config.intermediate_size,
        "num_hidden_layers": config.num_hidden_layers,
        "num_attention_heads": config.num_attention_heads,
        "num_key_value_heads": config.num_key_value_heads,
        "head_dim": config.actual_head_dim,
        "rope_theta": config.rope_theta,
        "rms_norm_eps": config.rms_norm_eps,
        "tie_word_embeddings": config.tie_word_embeddings,
        "max_position_embeddings": config.max_position_embeddings,
        "attention_bias": config.attention_bias,
        "hidden_act": "silu",
        "torch_dtype": dtype,
    }
    if family == "axk1":
        del hf_config["head_dim"]
        hf_config.update(
            q_lora_rank=config.q_lora_rank, kv_lora_rank=config.kv_lora_rank,
            qk_nope_head_dim=config.qk_nope_head_dim,
            qk_rope_head_dim=config.qk_rope_head_dim,
            v_head_dim=config.v_head_dim,
            first_k_dense_replace=config.first_k_dense_replace,
            moe_intermediate_size=config.moe_intermediate_size,
            moe_layer_freq=1, n_routed_experts=config.num_experts,
            n_shared_experts=config.n_shared_experts,
            num_experts_per_tok=config.num_experts_per_tok,
            norm_topk_prob=config.norm_topk_prob,
            scoring_func=config.scoring_func, topk_method="none",
            routed_scaling_factor=config.routed_scaling_factor,
            rope_scaling=None if config.yarn is None else dict(
                zip(("factor", "original_max_position_embeddings",
                     "beta_fast", "beta_slow", "mscale", "mscale_all_dim"),
                    config.yarn), type="yarn"))
        if config.experts_held:     # a chip's share is no whole checkpoint
            hf_config.update(n_routed_experts_held=config.experts_held,
                             n_routed_experts_offset=config.experts_offset)
    elif family == "lfm2_moe":
        for key in ("head_dim", "rope_theta", "rms_norm_eps", "attention_bias",
                    "hidden_act"):
            del hf_config[key]
        hf_config.update(
            layer_types=list(config.layer_types),
            num_dense_layers=config.num_dense_layers,
            conv_L_cache=config.conv_L_cache, conv_bias=False,
            use_expert_bias=config.use_expert_bias,
            num_experts=config.num_experts,
            num_experts_per_tok=config.num_experts_per_tok,
            moe_intermediate_size=config.moe_intermediate_size,
            norm_topk_prob=config.norm_topk_prob,
            routed_scaling_factor=config.routed_scaling_factor,
            norm_eps=config.rms_norm_eps,
            rope_parameters={"rope_theta": config.rope_theta,
                             "rope_type": "default"})
    elif family == "ouro":
        hf_config.update(
            total_ut_steps=config.loop_passes,
            early_exit_threshold=1.0,   # (the one `_ouro_from_hf` takes)
            use_sliding_window=False, sliding_window=None, rope_scaling=None)
    elif family == "falcon_h1":
        hf_config.update(
            attn_layer_indices=None, rope_scaling=None,
            mamba_n_heads=config.ssm_heads, mamba_d_head=config.ssm_head_dim,
            mamba_d_ssm=config.ssm_inner, mamba_n_groups=config.ssm_groups,
            mamba_d_state=config.ssm_state, mamba_d_conv=config.ssm_conv,
            mamba_chunk_size=config.ssm_chunk, mamba_conv_bias=True,
            mamba_proj_bias=False, mamba_rms_norm=True,
            mamba_norm_before_gate=False, mlp_bias=False,
            projectors_bias=False,
            embedding_multiplier=config.embed_scale,
            attention_in_multiplier=config.attention_in_multiplier,
            key_multiplier=config.key_multiplier,
            attention_out_multiplier=config.attention_out_multiplier,
            ssm_in_multiplier=config.ssm_in_multiplier,
            ssm_multipliers=list(config.ssm_multipliers),
            ssm_out_multiplier=config.ssm_out_multiplier,
            mlp_multipliers=list(config.mlp_multipliers),
            lm_head_multiplier=config.lm_head_multiplier)
    elif family == "granitemoehybrid":
        del hf_config["head_dim"]
        hf_config.update(
            layer_types=["mamba" if k == "mamba" else "attention"
                         for k in config.layer_kinds],
            position_embedding_type="nope", normalization_function="rmsnorm",
            mamba_n_heads=config.ssm_heads, mamba_d_head=config.ssm_head_dim,
            mamba_n_groups=config.ssm_groups, mamba_d_state=config.ssm_state,
            mamba_d_conv=config.ssm_conv, mamba_chunk_size=config.ssm_chunk,
            mamba_expand=config.ssm_inner // config.hidden_size,
            mamba_conv_bias=True, mamba_proj_bias=False,
            num_local_experts=config.num_experts,
            num_experts_per_tok=config.num_experts_per_tok,
            shared_intermediate_size=config.shared_expert_width,
            embedding_multiplier=config.embed_scale,
            attention_multiplier=config.attention_multiplier,
            residual_multiplier=config.residual_multiplier,
            logits_scaling=1.0 / config.lm_head_multiplier,
            rope_scaling=None)
        if config.experts_held:     # a chip's share is no whole checkpoint
            hf_config.update(
                num_experts_held=config.experts_held,
                num_experts_offset=config.experts_offset)
    elif family == "afmoe":
        L = config.num_hidden_layers
        del hf_config["attention_bias"]
        hf_config.update(
            layer_types=["sliding_attention" if w else "full_attention"
                         for w in (config.sliding_window_layout or (0,) * L)],
            sliding_window=config.sliding_window,
            num_dense_layers=config.num_dense_layers,
            num_experts=config.num_experts,
            num_experts_per_tok=config.num_experts_per_tok,
            moe_intermediate_size=config.moe_intermediate_size,
            num_shared_experts=config.n_shared_experts,
            score_func=config.scoring_func, route_norm=config.norm_topk_prob,
            route_scale=config.routed_scaling_factor, mup_enabled=True,
            n_group=1, topk_group=1, num_expert_groups=1,
            num_limited_groups=1, rope_scaling=None)
        if config.experts_held:     # a chip's share is no whole checkpoint
            hf_config.update(num_experts_held=config.experts_held,
                             num_experts_offset=config.experts_offset)
    elif family == "smallthinker":
        L = config.num_hidden_layers
        for key in ("intermediate_size", "attention_bias", "hidden_act"):
            del hf_config[key]
        hf_config.update(
            moe_ffn_hidden_size=config.intermediate_size,
            moe_num_primary_experts=config.num_experts,
            moe_num_active_primary_experts=config.num_experts_per_tok,
            moe_primary_router_apply_softmax=True,
            norm_topk_prob=config.norm_topk_prob, rope_scaling=None,
            sliding_window_size=config.sliding_window,
            sliding_window_layout=list(config.sliding_window_layout
                                       or (0,) * L),
            rope_layout=list(config.rope_layout or (1,) * L))
    elif family == "sdar_moe":
        hf_config.update(
            num_experts=config.num_experts,
            num_experts_per_tok=config.num_experts_per_tok,
            moe_intermediate_size=config.moe_intermediate_size,
            norm_topk_prob=config.norm_topk_prob, decoder_sparse_step=1,
            mlp_only_layers=[], rope_scaling=None, sliding_window=None,
            use_sliding_window=False,
            # the generation procedure's, which the published file leaves
            # to the family's script (docs/BLOCKDIFF.md)
            block_length=config.block_length,
            mask_token_id=config.mask_token_id)
    elif config.num_experts:
        hf_config.update(num_experts=config.num_experts,
                         num_experts_per_tok=config.num_experts_per_tok,
                         norm_topk_prob=config.norm_topk_prob,
                         clip_qkv=None)
    gen_config = {"_from_model_config": True}
    for key, val in (("eos_token_id", eos_token_id),
                     ("bos_token_id", bos_token_id),
                     ("pad_token_id", pad_token_id)):
        if val is not None:
            hf_config[key] = int(val)
            gen_config[key] = int(val)
    with open(os.path.join(out_dir, "config.json"), "w") as f:
        json.dump(hf_config, f, indent=2)
    with open(os.path.join(out_dir, "generation_config.json"), "w") as f:
        json.dump(gen_config, f, indent=2)
    return out_dir


def load_hf_checkpoint(model_dir: str, dtype=jnp.bfloat16):
    """Load (ModelConfig, params) from an HF model directory on disk.

    Reads config.json + *.safetensors (or pytorch_model.bin fallback).
    Host-side, outside the compiled graph — like the reference's tokenizer/
    checkpoint IO.
    """
    with open(os.path.join(model_dir, "config.json")) as f:
        config = ModelConfig.from_hf_config(json.load(f))

    state_dict: dict = {}
    st_files = sorted(
        f for f in os.listdir(model_dir) if f.endswith(".safetensors")
    )
    if st_files:
        from safetensors import safe_open

        for fname in st_files:
            with safe_open(os.path.join(model_dir, fname), framework="np") as f:
                for k in f.keys():
                    state_dict[k] = f.get_tensor(k)
    else:
        import torch

        state_dict = torch.load(
            os.path.join(model_dir, "pytorch_model.bin"), map_location="cpu"
        )
    return config, params_from_hf_state_dict(config, state_dict, dtype)
