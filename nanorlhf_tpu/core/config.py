"""Model architecture config: the dense Qwen2/Llama block (GQA + SwiGLU),
OLMoE's sparse-expert block, A.X-K1's (DeepSeek-V3's) latent attention
with leading dense layers before shared-plus-routed sigmoid experts, or
SmallThinker's pattern of window layers with rotary embedding beside global
layers without it over ReLU-gated experts (docs/SWA.md), or LFM2's pattern of
gated short-convolution layers with a fixed-size state beside attention
layers, over experts chosen by bias-corrected sigmoid scores (docs/STATE.md),
or Trinity's (`afmoe`) gated attention over the same window pattern with four
norms a layer, a leading dense stack inside the pattern and a chip's share of
bias-selected sigmoid experts beside a shared one (docs/AFMOE.md), or SDAR's
(`sdar_moe`): Qwen3-MoE's layer under a BLOCK-causal mask, generated by
diffusion over blocks of `block_length` tokens (docs/BLOCKDIFF.md).

The reference loads policies with `AutoModelForCausalLM` (Qwen2.5 models,
`/root/reference/GRPO/grpo.py:218-224`); this dataclass captures the
architecture hyperparameters our JAX decoder needs. Presets mirror the HF
configs of the model sizes the reference trains (0.5B/1.5B/7B), the Llama
side of the same block, OLMoE-1B-7B (docs/MOE.md) and A.X-K1 (docs/MLA.md).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

# config.json keys that announce a sparse-expert MLP of some family
_EXPERT_KEYS = ("num_experts", "num_local_experts", "n_routed_experts",
                "num_experts_per_tok", "moe_intermediate_size",
                "n_shared_experts", "shared_expert_intermediate_size")


# SDAR's generation defaults (docs/BLOCKDIFF.md "assumed"): the Chat releases'
# block size, and the id of the `<|MASK|>` token the family adds to Qwen3's
# vocabulary. No key of the published config.json.
SDAR_BLOCK_LENGTH = 4
SDAR_MASK_TOKEN_ID = 151669


# the layer kinds `layer_types` names (every other entry is an attention
# layer, whose kind is its `(window, rotary)`)
NAMED_KINDS = ("conv", "hybrid", "mamba", "lightning", "sparse")
# MiniCPM-SALA's `mixer_types` under the tree's names
_SALA_MIXERS = {"minicpm4": "sparse", "lightning-attn": "lightning"}
_SALA_PUBLISHED = (
    "s" + "l" * 8 + "s" + "l" * 6 + "ss" + "l" * 4 + "s" + "l" * 6 + "sss")


# The kinds of model a config can be (`ModelConfig.traits`), in the order a
# refusal names them: the phrase it uses and the document it points to. A new
# kind of model adds one entry here, one line to `traits`, and its name to the
# rows of MECHANISMS that are built for it: every other row refuses it.
TRAITS = {
    "window": ("a model with window layers", "docs/SWA.md"),
    "sparse": ("a model with sparse-attention layers", "docs/SALA.md"),
    "conv": ("a model with conv layers", "docs/STATE.md"),
    "ssm": ("a model with state-space layers", "docs/SSM.md"),
    "linear": ("a model with linear-attention layers", "docs/SALA.md"),
    "latent": ("a model with latent attention", "docs/MLA.md"),
    "block": ("a model that generates by diffusion over blocks",
              "docs/BLOCKDIFF.md"),
    "loop": ("a looped model", "docs/OURO.md"),
}


def _takes(kinds: str, why: str) -> tuple:
    return frozenset(kinds.split()), why


# What each mechanism takes: the kinds it is built and tested for, and why it
# takes no other (docs/PAGED_CACHE.md "Which mechanism takes which kind of
# model" renders this table). An allow-list: `ModelConfig.require` refuses a
# kind a row does not list, so a kind nobody thought of is refused everywhere.
# A model of no kind (one stack of global attention layers, with experts or
# without) is taken by every row.
MECHANISMS = {
    "a rollout": _takes(
        "window sparse conv ssm linear latent loop",
        "every rollout path takes one token a row a step in order and keeps "
        "next-token log-probabilities; a model that generates otherwise is "
        "served through a serving-mode DecodeSession only"),
    "decode_step": _takes(
        "window sparse conv ssm linear latent loop",
        "it yields one token a row a forward, in order"),
    # the paged one-jit rollout's identity table, the rollout scheduler
    # (a session with per_row=False) and a session without a RadixCache
    "a page pool of one kind": _takes(
        "latent loop",
        "the identity table of the paged one-jit rollout and the rollout "
        "scheduler's batched bootstrap and device free list hand on pages "
        "of one pool: they keep no state that is not a page, and no page "
        "pool of two kinds (num_pages=(global, window), which the decode "
        "session builds). Such a model rolls out on the contiguous cache "
        "and decodes in a serving session only (per_row=True, pages handed "
        "out by the engine's RadixCache)"),
    "the serving session": _takes(
        "window sparse conv ssm linear latent block loop",
        "a kind it does not list may keep pages or a state a row that the "
        "session would not size, reset or hand on"),
    "a radix prefix hit": _takes(
        "latent loop",
        "the tree holds pages of the global kind only: no window pages, no "
        "snapshot of the conv state and no snapshot of the recurrent state "
        "at the prefix's end, and no block that has not committed; a "
        "session inserts no page of such a model, so none can occur"),
    "speculative decode": _takes(
        "latent",
        "a verify forward writes every candidate: a state is advanced past "
        "them all and a rejected draft needs it rolled back to the last "
        "accepted token (no such rollback is built), the verify reads have "
        "no window and no pass of a loop, and a block is no run of "
        "next-token drafts; a model with a pattern of layers decodes in a "
        "serving session only, without speculation"),
    # (a latent cache raises ValueError first: `model._latent_cache_shape`)
    "kv_cache_quant='int8'": _takes(
        "",
        "the int8 reads take one table of one kind of (k, v) cache, with no "
        "lower bound, no block read and no slot a pass"),
    "a rollout under a mesh": _takes(
        "window sparse conv ssm linear latent",
        "not built or tested for a stack every token passes several times"),
    "a mesh under a decode session": _takes(
        "window sparse latent",
        "a state's rows have no sharding rule and its updates no "
        "partitioned form, nor have the block read and the rows' block "
        "state, nor is a loop of passes built or tested under one"),
    "the sequence-parallel forward": _takes(
        "block",
        "ring attention exchanges whole per-head K and V blocks: it has no "
        "window, no latent, no chosen blocks that lie on any device, no "
        "state and no loop of passes"),
    "a LoRA adapter": _takes(
        "window sparse conv latent loop",
        "nothing trains the model here (a state-space or linear-attention "
        "mixer's scan has no backward, and its projections are no target; "
        "no RL objective is published for a policy that generates by "
        "blocks)"),
    "training": _takes(
        "window sparse latent loop",
        "training a model with conv, state-space or linear-attention layers "
        "is not built (the update's packed rows have no boundary the "
        "convolution stops at, its backward under the sparse trainer's "
        "packing is not written, nor the recurrent scans' at all, and a mesh "
        "has no rule for the state), and no RL objective is published for a "
        "policy that generates by blocks; the model is served"),
}


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    vocab_size: int = 151936
    hidden_size: int = 1536
    intermediate_size: int = 8960
    num_hidden_layers: int = 28
    num_attention_heads: int = 12
    num_key_value_heads: int = 2
    head_dim: Optional[int] = None  # defaults to hidden_size // num_attention_heads
    rope_theta: float = 1_000_000.0
    rms_norm_eps: float = 1e-6
    tie_word_embeddings: bool = True
    max_position_embeddings: int = 32768
    # Qwen2-family attention projections carry biases; Llama-family do not.
    # The decoder treats biases as optional, so this only steers random init
    # (HF loading is data-driven off the state dict).
    attention_bias: bool = True
    # HF family slug ("qwen2" | "llama"); carried through load → export so a
    # round-trip re-emits the source architecture instead of inferring it
    # from attention_bias (a Llama with attention_bias=True is valid, ADVICE
    # r3). None (random-init configs) falls back to the bias heuristic.
    model_type: Optional[str] = None
    # Sparse-expert MLP (ops/moe.py, docs/MOE.md). `num_experts == 0` is the
    # dense SwiGLU layer; otherwise every layer's MLP is a float32 softmax
    # router over `num_experts` experts of width `intermediate_size`, each
    # token reaching its top `num_experts_per_tok` (dropless), the weights
    # renormalised over the chosen ones only if `norm_topk_prob`.
    num_experts: int = 0
    num_experts_per_tok: int = 0
    norm_topk_prob: bool = False
    # RMSNorm of q and k over their WHOLE projection width, before the head
    # split and RoPE (OLMoE; `from_hf_config` sets it from the model type).
    qk_norm: bool = False
    # Latent attention (MLA, docs/MLA.md; `kv_lora_rank > 0` says the model
    # has it): queries through a `q_lora_rank` bottleneck, keys and values
    # through one `kv_lora_rank` latent a token plus a `qk_rope_head_dim`
    # rotary key all heads share, which is all the cache holds
    # (`latent_width`). Heads are `qk_nope_head_dim + qk_rope_head_dim` wide
    # for q and k and `v_head_dim` for v; `head_dim` is not used.
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # YaRN, DeepSeek-V3's formula (core/mla.py): (factor,
    # original_max_position_embeddings, beta_fast, beta_slow, mscale,
    # mscale_all_dim), or None for plain RoPE. A tuple: the config is a
    # static jit argument.
    yarn: Optional[tuple] = None
    # The A.X-K1 / DeepSeek-V3 expert layer (ops/moe.py): the first
    # `first_k_dense_replace` layers keep the dense SwiGLU of width
    # `intermediate_size`; every later layer routes over `num_experts`
    # (`n_routed_experts`: the router's width) experts of width
    # `moe_intermediate_size` with `scoring_func` scores, scales the
    # renormalised top-k weights by `routed_scaling_factor`, and adds
    # `n_shared_experts` shared experts (one SwiGLU of their summed width)
    # to every token.
    first_k_dense_replace: int = 0
    moe_intermediate_size: int = 0
    n_shared_experts: int = 0
    scoring_func: str = "softmax"  # softmax | sigmoid
    routed_scaling_factor: float = 1.0
    # The chip's share of an expert-parallel deployment: this program holds
    # experts [experts_offset, experts_offset + experts_held) of every expert
    # layer, routes over all `num_experts`, and adds its own experts' part
    # only (docs/MLA.md "the chip's share"). 0 held = all of them.
    experts_held: int = 0
    experts_offset: int = 0
    # Tokens an expert layer takes at a time; 0: all at once, but a chip's
    # share 1,024 (`core/model.py::_mlp`). Past it the layer goes in blocks
    # of at most this many, so a long row scored or prefilled beside a
    # served model's weights and page pool holds one block's dispatched rows
    # (tokens x experts a token x hidden, in, out and their float32
    # combine) and not the row's: about 0.12 GB a block at SmallThinker's
    # widths, where a 9,765-token row held 3.3 GB (compiled for a described
    # v5e, PR 34), as at A.X-K1's share. A configuration served on one chip
    # sets it; OLMoE, whose cell is a trainer with the chip to itself, does
    # not, and a mesh keeps its batch sharding (a block flattens the rows).
    expert_token_block: int = 0
    # The attention pattern (docs/SWA.md; empty layouts = every layer global
    # and rotated, every model but SmallThinker and afmoe). Layer `l` is a
    # WINDOW layer where `sliding_window_layout[l]`: query i sees key j iff
    # i - sliding_window < j <= i; it applies rotary embedding where
    # `rope_layout[l]` (a layer without it carries no positional signal).
    # Tuples: the config is a static jit argument. The layouts repeat with a
    # period that divides the depth (`attention_pattern`), which is what the
    # layer scan goes over.
    sliding_window: int = 0
    sliding_window_layout: tuple = ()
    rope_layout: tuple = ()
    # The experts' gate: silu(gate) * up (SwiGLU) or relu(gate) * up
    # (SmallThinker's "sparse ReGLU").
    expert_activation: str = "silu"  # silu | relu
    # Layers whose operator is not attention (docs/STATE.md; empty = every
    # layer attends). `layer_types[l]` is "conv" or "full_attention". A conv
    # layer is LFM2's gated short convolution: `[b | c | u] = h W_in`, `g = b
    # * u`, `y_t = c_t * sum_j w[j] g_(t-K+1+j)` over `conv_L_cache` = K
    # causal depthwise taps, `x += y W_out`. It keeps no keys or values: its
    # cache is the row's last K - 1 values of `g`, a STATE of fixed size
    # beside the attention layers' pages (`conv_layers`, `stack_pattern`).
    layer_types: tuple = ()
    conv_L_cache: int = 0
    # Falcon-H1's layer (docs/SSM.md): `layer_types[l] == "hybrid"` is a
    # Mamba-2 state-space mixer and an attention of `num_attention_heads`
    # SIDE BY SIDE, both reading the one normed state and both added into
    # the one residual, so the layer keeps pages AND a state. The mixer:
    # `ssm_heads` heads of `ssm_head_dim`, B and C in `ssm_groups` groups of
    # `ssm_state` (a recurrent state `[ssm_head_dim, ssm_state]` a head,
    # float32), a causal depthwise convolution of `ssm_conv` taps with a
    # bias over `[xs | B | C]`, a scan in chunks of `ssm_chunk` at prefill.
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_groups: int = 0
    ssm_state: int = 0
    ssm_conv: int = 0
    ssm_chunk: int = 128
    # Granite 4.0-H's layer (docs/GRANITE_H.md): `layer_types[l] == "mamba"`
    # is the same mixer ALONE in its layer (a state and no pages); the
    # layers beside it are global attention without rotary. Its multipliers,
    # the published keys: `attention_multiplier` is the softmax's scale IN
    # PLACE of 1 / sqrt(head_dim) (0.0: that default, which stages nothing),
    # `residual_multiplier` what every branch is multiplied by as it joins
    # the stream (0.0: none; `residual_scale`). `shared_intermediate_size`:
    # the shared expert's width where it is a number of its own and not
    # `n_shared_experts` routed widths (0: that product).
    attention_multiplier: float = 0.0
    residual_multiplier: float = 0.0
    shared_intermediate_size: int = 0
    # Falcon-H1's muP multipliers, the published keys under the tree's
    # names, applied where the published code applies them: on the
    # attention's input, on k, on its output; on the mixer's input, on the
    # five parts `[z | xs | B | C | dt]` of its input projection, on its
    # output; on the MLP's gate (before the SiLU) and down projection; on
    # the logits. (The embedding's is `embed_scale`.) 1.0 stages nothing.
    attention_in_multiplier: float = 1.0
    key_multiplier: float = 1.0
    attention_out_multiplier: float = 1.0
    ssm_in_multiplier: float = 1.0
    ssm_multipliers: tuple = (1.0, 1.0, 1.0, 1.0, 1.0)
    ssm_out_multiplier: float = 1.0
    mlp_multipliers: tuple = (1.0, 1.0)
    lm_head_multiplier: float = 1.0
    # MiniCPM-SALA's two mixers (docs/SALA.md). `layer_types[l] ==
    # "lightning"` is a LINEAR-attention layer (Lightning Attention-2):
    # `lightning_heads` heads of `lightning_head_dim`, per-head q/k norms and
    # rotary, a recurrent MATRIX state `S = lam S + k (outer) v` a head
    # (float32, `[head_dim, head_dim]`) and no pages, a per-head output norm
    # and a sigmoid gate; a piece's recurrence goes in chunks of `ssm_chunk`.
    # `layer_types[l] == "sparse"` is an InfLLM-V2 layer: a GQA attention of
    # `num_attention_heads / num_key_value_heads` without rotary whose
    # queries, once the call they belong to holds `sparse_dense_len` keys,
    # read `sparse_topk` blocks of `sparse_block_size` tokens only: the
    # first `sparse_init_blocks`, the `sparse_window_size / sparse_block_size`
    # that end at the query's own, and the best of the rest by the scores
    # of COMPRESSED keys (means over `sparse_kernel_size` keys every
    # `sparse_kernel_stride`, no weights), which its pages keep beside K and
    # V. The residual branches are scaled by `scale_depth / sqrt(depth)`,
    # `depth` the PUBLISHED one (`published_layers`; a cut's first layer is
    # the published layer `first_published_layer`, which the lightning
    # decays go by); 0.0 stages nothing.
    lightning_heads: int = 0
    lightning_head_dim: int = 0
    sparse_kernel_size: int = 32
    sparse_kernel_stride: int = 16
    sparse_block_size: int = 64
    sparse_topk: int = 64
    sparse_init_blocks: int = 1
    sparse_window_size: int = 2048
    sparse_dense_len: int = 8192
    scale_depth: float = 0.0
    published_layers: int = 0
    first_published_layer: int = 0
    # RMSNorm of q and k over each HEAD (a weight of `head_dim`), after the
    # head split and before RoPE (LFM2's q_layernorm / k_layernorm).
    qk_norm_per_head: bool = False
    # The router's selection is bias-corrected: the top k are taken of
    # `scores + expert_bias`, the weights of `scores` alone (LFM2's
    # `use_expert_bias`; the tree then has `router.bias [L, E]`, float32).
    use_expert_bias: bool = False
    # The epsilon under the sum when a bias-selected router renormalises its
    # chosen scores (LFM2: 1e-6; afmoe: 1e-20). Read under `use_expert_bias`.
    route_norm_eps: float = 1e-6
    # afmoe (docs/AFMOE.md). `attention_gate`: a fourth projection of the
    # normed state, `g_proj` [D, H * hd] beside `q_proj`, whose sigmoid
    # multiplies the attention's output element by element before `o_proj`.
    # `branch_norms`: each branch is normed AGAIN before it joins the stream
    # (`x + RMSNorm(attention)`, `x + RMSNorm(mlp)`: the tree's
    # `attn_branch_norm` / `mlp_branch_norm`, four norms a layer).
    # `embed_scale`: what the embedding's rows are multiplied by (muP:
    # sqrt(hidden_size)); 1.0 stages no operation.
    attention_gate: bool = False
    branch_norms: bool = False
    embed_scale: float = 1.0
    # A looped model (Ouro, docs/OURO.md): the whole stack of
    # `num_hidden_layers` layers is ONE shared block that every token passes
    # `loop_passes` times, the final norm after each pass (so the head does
    # not norm again), and pass `t` of layer `l` keeps keys and values of
    # its own: the cache holds `cache_layers` layers, index `t * L + l`.
    # Every token runs every pass: the published exit threshold of 1 (a
    # threshold below it, at which rows would leave a forward at different
    # passes, `from_hf_config` refuses by name). The tree carries the exit
    # gate's leaf (`early_exit_gate`) for the checkpoint; no forward reads it.
    # 1: every other model, whose programs hold no trace of the loop.
    loop_passes: int = 1
    # KV heads the cache keeps side by side in one row of lanes: 2 packs
    # heads of 64 into pages 128 lanes wide, which is what the paged kernels
    # read (core/model.py `_pack_heads`; docs/STATE.md). 1: a head a row.
    kv_head_pack: int = 1
    # Generation by diffusion over blocks (SDAR, docs/BLOCKDIFF.md). A
    # `block_length` B > 0 makes every mask BLOCK-causal (the query at
    # position i sees the key at position j iff j // B <= i // B, blocks
    # aligned to absolute positions) and the model generate a block at a
    # time: forwards over a block's B tokens, `mask_token_id` where one is
    # still masked (`core/model.block_forward`,
    # `sampler/paged/session._block_loop`). 0: a causal, autoregressive
    # model, every other one here.
    block_length: int = 0
    mask_token_id: int = -1
    # What the router reads: the MLP's own input (the post-attention normed
    # state), or the layer's PRE-attention normed state (SmallThinker:
    # "router placed before attention").
    router_input: str = "mlp"  # mlp | pre_attention
    # "int8": the sampler's KV cache stores int8 values + per-token-per-head
    # bf16 scales (absmax over head_dim). At long responses the cache read is
    # the dominant decode HBM stream (≈7.5 GB/step at 8k tokens, batch 32);
    # int8 + the 8x-sublane-replicated bf16 scale stream reads 144 B per
    # token/kv-head/side vs 256 B exact at hd=128 — a 1.78x reduction. The
    # Pallas decode kernel consumes int8 natively (scales fold into the
    # score row and the probability row, ops/decode_attention.py) and is
    # gated by the same attention_impl resolution as the exact kernel; the
    # XLA path dequantizes per step (correct, no bandwidth win).
    # Training/scoring paths never use a cache, so they are unaffected.
    kv_cache_quant: str = "none"  # none | int8
    # "xla": einsum attention fused by XLA everywhere.
    # "pallas": blockwise flash kernel (ops/attention.py) on self-attention
    #   paths + prefix-bounded decode kernel (ops/decode_attention.py).
    # "auto" (default): picks per call site (core/model.py's `use_*`): flash
    #   at padded T >= _FLASH_AUTO_MIN_T, the contiguous decode kernel at
    #   cache T_max >= _DECODE_AUTO_MIN_T (neither crossover has a record:
    #   ROADMAP S5), the paged in-place decode read and the grouped expert
    #   matmul on a TPU with no multi-device mesh at every size. Off-TPU
    #   backends always resolve to XLA (interpret-mode Pallas is a test
    #   vehicle, not an execution path).
    attention_impl: str = "auto"
    # Rematerialization policy for the training forward when gradient
    # checkpointing is on ("full" = jax.checkpoint default, save nothing and
    # recompute the whole layer in the backward; "dots" = save MXU matmul
    # outputs without batch dims — the projections' results survive to the
    # backward, trading HBM for roughly a third less recompute FLOPs). A
    # tuning knob, not a numerics one: gradients are identical either way.
    remat_policy: str = "full"  # full | dots
    # SPMD hints for the Pallas kernels. GSPMD has no partitioning rule for
    # a custom call: without these, a batch-sharded training/rollout step
    # ALL-GATHERS the kernel operands (q/k/v, the whole KV cache) onto every
    # device and replicates the output — silently, observed in compiled HLO.
    # When `spmd_mesh` is set, the kernel call sites wrap themselves in
    # shard_map over the batch dim (axes in `spmd_batch_axes` that are >1 in
    # the mesh) and, where head counts divide, the head dim over
    # `spmd_head_axis` — each device then runs the kernel on its own shard,
    # which is the whole point of the kernels. The trainer sets these from
    # its mesh automatically; None = single-device behavior (no wrap).
    # (Mesh is hashable, so this stays a valid static jit argument.)
    spmd_mesh: object = None            # jax.sharding.Mesh | None
    spmd_batch_axes: tuple = ()         # e.g. ("data", "fsdp")
    spmd_head_axis: Optional[str] = None  # e.g. "tensor"

    def __post_init__(self):
        if self.loop_passes < 1:
            raise ValueError(f"loop_passes={self.loop_passes}: at least 1")
        if self.loop_passes > 1 and (
                self.attention_pattern is not None or self.num_experts
                or self.kv_lora_rank or self.block_length):
            raise NotImplementedError(
                f"loop_passes={self.loop_passes} on a model with a layer "
                "pattern, experts, latent attention or block generation "
                f"({self.model_type}): the loop is built for a plain stack "
                "of one kind of layer (docs/OURO.md)")

    @property
    def cache_layers(self) -> int:
        """Layers of the KV cache: a slot a pass a layer (docs/OURO.md). The
        ONE place the product is taken."""
        return self.loop_passes * self.num_hidden_layers

    @property
    def traits(self) -> frozenset:
        """The kinds of model this config is (keys of TRAITS): what a
        mechanism must list to take it (`require`)."""
        kinds = set(self.layer_kinds)
        named = bool(kinds & set(NAMED_KINDS))
        is_ = {
            # (an attention layer without rotary makes a model a pattern
            # model as a window does, unless a named kind already has)
            "window": any(k not in NAMED_KINDS and (
                k[0] or (k != (False, True) and not named)) for k in kinds),
            "sparse": "sparse" in kinds,
            "conv": "conv" in kinds,
            "ssm": "hybrid" in kinds or "mamba" in kinds,
            "linear": "lightning" in kinds,
            "latent": self.kv_lora_rank > 0,
            "block": self.block_length > 0,
            "loop": self.loop_passes > 1,
        }
        return frozenset(t for t, has in is_.items() if has)

    def require(self, who: str, mechanism: str = "") -> None:
        """Raises NotImplementedError where `mechanism` (a row of
        MECHANISMS; `who` itself where it is one) does not take every kind
        this model is, naming `who`, the kinds and their documents."""
        takes, why = MECHANISMS[mechanism or who]
        lacking = self.traits - takes
        if lacking:
            phrases, docs = zip(*(TRAITS[t] for t in
                                  sorted(lacking, key=list(TRAITS).index)))
            raise NotImplementedError(
                f"{who} on {' and '.join(phrases)} ({self.model_type}): "
                f"{why} ({', '.join(dict.fromkeys(docs))})")

    @property
    def actual_head_dim(self) -> int:
        return self.head_dim or self.hidden_size // self.num_attention_heads

    @property
    def num_kv_groups(self) -> int:
        return self.num_attention_heads // self.num_key_value_heads

    @property
    def latent_width(self) -> int:
        """What an MLA cache holds a token a layer: `[c_kv | k_rope]`."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def num_dense_layers(self) -> int:
        """Leading layers with a dense MLP in a model whose other layers
        have experts (0 in every single-kind model)."""
        return min(self.first_k_dense_replace, self.num_hidden_layers) \
            if self.num_experts else 0

    @property
    def num_held_experts(self) -> int:
        return self.experts_held or self.num_experts

    @property
    def expert_width(self) -> int:
        return self.moe_intermediate_size or self.intermediate_size

    @property
    def layer_kinds(self) -> tuple:
        """Every layer's kind, in model order: `"conv"`, `"hybrid"`, `"mamba"`,
        `"lightning"` or `"sparse"` (`layer_types`), or an attention layer's
        `(window, rotary)`."""
        L = self.num_hidden_layers
        win = tuple(bool(w) for w in self.sliding_window_layout) or (False,) * L
        rot = tuple(bool(r) for r in self.rope_layout) or (True,) * L
        if self.sliding_window <= 0:
            win = (False,) * L
        kinds = tuple(zip(win, rot))
        if len(kinds) != L or len(self.layer_types) not in (0, L):
            raise ValueError(
                f"layer layouts of {len(kinds)} and {len(self.layer_types)} "
                f"entries for {L} layers")
        if self.layer_types:
            kinds = tuple(t if t in NAMED_KINDS else k
                          for t, k in zip(self.layer_types, kinds))
        return kinds

    def stack_pattern(self, start: int, n: int) -> tuple:
        """One period of the kinds of layers `[start, start + n)`, the
        shortest that tiles them: what the layer scan of that stack goes
        over (`core/model._run_layers`)."""
        kinds = self.layer_kinds[start:start + n]
        for p in range(1, n + 1):
            if n % p == 0 and kinds == kinds[:p] * (n // p):
                return kinds[:p]
        return kinds

    @property
    def attention_pattern(self):
        """One period of the layers' kinds, `("conv" | (window, rotary),
        ...)`, the shortest that tiles the stack of layers after the leading
        dense ones (every layer, in a model of one stack); None for a model
        without a pattern (every layer global attention with rotary), whose
        programs are the ones it always had."""
        if all(k == (False, True) for k in self.layer_kinds):
            return None
        dense = self.num_dense_layers
        return self.stack_pattern(dense, self.num_hidden_layers - dense)

    @property
    def conv_layers(self) -> int:
        """Layers that keep a state and no pages (0 in a model without)."""
        return sum(k == "conv" for k in self.layer_kinds)

    @property
    def ssm_layers(self) -> int:
        """Layers with a state-space mixer, which the state's two stacks are
        sized by (0 in a model without): beside an attention (`"hybrid"`: a
        state AND pages) or alone (`"mamba"`: `mamba_layers`)."""
        return sum(k in ("hybrid", "mamba") for k in self.layer_kinds)

    @property
    def mamba_layers(self) -> int:
        """Layers whose mixer stands ALONE: a state and no pages."""
        return sum(k == "mamba" for k in self.layer_kinds)

    @property
    def page_layers(self) -> int:
        """Layers that keep pages a pass, of either kind of pool: what
        `kv_bytes_per_token` counts."""
        return (self.num_hidden_layers - self.conv_layers
                - self.linear_layers - self.mamba_layers)

    @property
    def state_layers(self) -> int:
        """Layers that keep a state a row, of either form (docs/STATE.md):
        what a session sizes, donates, resets and reports a state by."""
        return self.conv_layers + self.ssm_layers + self.linear_layers

    @property
    def linear_layers(self) -> int:
        """Linear-attention layers, which keep a matrix state and no pages
        (0 in a model without; docs/SALA.md)."""
        return sum(k == "lightning" for k in self.layer_kinds)

    @property
    def sparse_layers(self) -> int:
        """Attention layers whose queries choose the blocks they read, and
        whose pages keep compressed keys beside K and V (docs/SALA.md)."""
        return sum(k == "sparse" for k in self.layer_kinds)

    @property
    def residual_scale(self) -> float:
        """What each branch is multiplied by as it joins the stream
        (MiniCPM's `scale_depth / sqrt(num_hidden_layers)`, of the PUBLISHED
        depth; Granite's `residual_multiplier`); 1.0, which stages nothing,
        for every other model."""
        if self.residual_multiplier:
            return self.residual_multiplier
        if not self.scale_depth:
            return 1.0
        depth = self.published_layers or self.num_hidden_layers
        return self.scale_depth / depth ** 0.5

    def lightning_log_decays(self, every_layer: bool = False):
        """`[linear layers, heads]` float32 numpy: `log lam` of every
        lightning layer in model order (docs/SALA.md): `lam[h] = exp(-s[h]
        f(l))`, `s[h] = 2^(-8 (h + 1) / H)`, `f(l) = 1 - l / (L - 1) + 1e-5`
        with `l` the PUBLISHED index of the layer and `L` the published
        depth (Lightning Attention-2's slopes, MiniMax-01's layer factor).
        Fixed, not learned: no leaf of the tree. `every_layer`: a row a
        layer of the model, whatever its kind (a layer looks its own up by
        its place)."""
        import numpy as np

        H = self.lightning_heads
        depth = self.published_layers or self.num_hidden_layers
        slopes = 2.0 ** (-8.0 * (np.arange(H) + 1) / H)
        at = [self.first_published_layer + i
              for i, k in enumerate(self.layer_kinds)
              if every_layer or k == "lightning"]
        factor = np.asarray([1.0 - l / max(depth - 1, 1) + 1e-5 for l in at])
        return (-slopes[None, :] * factor[:, None]).astype(np.float32)

    @property
    def query_multiplier(self) -> float:
        """What q is multiplied by so that the reads' own 1 / sqrt(head_dim)
        makes `attention_multiplier` of it; 1.0, which stages nothing,
        without one."""
        if not self.attention_multiplier:
            return 1.0
        return self.attention_multiplier * self.actual_head_dim ** 0.5

    @property
    def shared_expert_width(self) -> int:
        """The shared experts' one SwiGLU: a width of its own, or
        `n_shared_experts` routed widths."""
        return (self.shared_intermediate_size
                or self.n_shared_experts * self.expert_width)

    @property
    def ssm_inner(self) -> int:
        """The mixer's width, `ssm_heads * ssm_head_dim` (`mamba_d_ssm`)."""
        return self.ssm_heads * self.ssm_head_dim

    @property
    def ssm_conv_width(self) -> int:
        """Channels of the mixer's convolution, `[xs | B | C]`."""
        return self.ssm_inner + 2 * self.ssm_groups * self.ssm_state

    @property
    def window_layers(self) -> int:
        """Layers with a sliding window (0 without a pattern)."""
        return sum(k not in NAMED_KINDS and k[0] for k in self.layer_kinds)

    @property
    def block_generation(self) -> bool:
        """Tokens are generated a block at a time by unmasking, not one a
        forward in order (docs/BLOCKDIFF.md): a forward yields 0 to
        `block_length` tokens a row, and everything that assumes one token
        a row a step (the contiguous rollout, the trainers, speculation,
        `decode_step`) refuses the model by name (`require`)."""
        return self.block_length > 0

    @property
    def live_rows_dispatch(self) -> bool:
        """A session's decode step dispatches to the experts only the rows
        someone listens to (those not done), and counts the experts they
        reach (`ops/moe.moe_mlp` `live=`): every model with expert layers,
        whether it holds a share of them or all. A row nobody listens to
        otherwise reaches experts of its own, whose kernels the step then
        reads for nothing and whose count swings `tpot_p95_ms` (PR 31)."""
        return bool(self.num_experts)

    @classmethod
    def qwen2_tiny(cls, vocab_size: int = 512) -> "ModelConfig":
        """Test-size model: runs fast on the CPU test mesh."""
        return cls(
            vocab_size=vocab_size,
            hidden_size=64,
            intermediate_size=128,
            num_hidden_layers=2,
            num_attention_heads=4,
            num_key_value_heads=2,
            rope_theta=10_000.0,
            max_position_embeddings=1024,
        )

    @classmethod
    def qwen2_0_5b(cls) -> "ModelConfig":
        return cls(
            hidden_size=896,
            intermediate_size=4864,
            num_hidden_layers=24,
            num_attention_heads=14,
            num_key_value_heads=2,
            tie_word_embeddings=True,
        )

    @classmethod
    def qwen2_1_5b(cls) -> "ModelConfig":
        return cls()  # defaults are Qwen2.5-1.5B

    @classmethod
    def qwen2_7b(cls) -> "ModelConfig":
        return cls(
            hidden_size=3584,
            intermediate_size=18944,
            num_hidden_layers=28,
            num_attention_heads=28,
            num_key_value_heads=4,
            tie_word_embeddings=False,
            vocab_size=152064,  # Qwen2.5-7B pads its vocabulary further than the 1.5B
        )

    @classmethod
    def olmoe_1b_7b(cls) -> "ModelConfig":
        """allenai/OLMoE-1B-7B-0125-Instruct: 16 MHA layers, 64 experts of
        width 1024, 8 per token, QK-norm, no biases, untied head."""
        return cls(
            vocab_size=50304,
            hidden_size=2048,
            intermediate_size=1024,
            num_hidden_layers=16,
            num_attention_heads=16,
            num_key_value_heads=16,
            rope_theta=10_000.0,
            rms_norm_eps=1e-5,
            tie_word_embeddings=False,
            max_position_embeddings=4096,
            attention_bias=False,
            model_type="olmoe",
            num_experts=64,
            num_experts_per_tok=8,
            norm_topk_prob=False,
            qk_norm=True,
        )

    @classmethod
    def olmoe_tiny(cls, vocab_size: int = 512) -> "ModelConfig":
        """Test-size OLMoE: the same layer at 8 experts, 2 per token."""
        return dataclasses.replace(
            cls.olmoe_1b_7b(), vocab_size=vocab_size, hidden_size=64,
            intermediate_size=32, num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=4, max_position_embeddings=1024,
            num_experts=8, num_experts_per_tok=2)

    @classmethod
    def axk1(cls) -> "ModelConfig":
        """skt/A.X-K1: 61 MLA layers, the first dense (width 18,432), then
        192 sigmoid-routed experts of width 2,048, 8 per token, renormalised
        and scaled by 2.5, plus one shared expert; YaRN x32; untied head."""
        return cls(
            vocab_size=163840,
            hidden_size=7168,
            intermediate_size=18432,
            num_hidden_layers=61,
            num_attention_heads=64,
            num_key_value_heads=64,
            rope_theta=10_000.0,
            rms_norm_eps=1e-6,
            tie_word_embeddings=False,
            max_position_embeddings=131072,
            attention_bias=False,
            model_type="axk1",
            num_experts=192,
            num_experts_per_tok=8,
            norm_topk_prob=True,
            q_lora_rank=1536,
            kv_lora_rank=512,
            qk_nope_head_dim=128,
            qk_rope_head_dim=64,
            v_head_dim=128,
            yarn=(32.0, 4096, 32.0, 1.0, 1.0, 1.0),
            first_k_dense_replace=1,
            moe_intermediate_size=2048,
            n_shared_experts=1,
            scoring_func="sigmoid",
            routed_scaling_factor=2.5,
        )

    @classmethod
    def axk1_tiny(cls, vocab_size: int = 512, experts_held: int = 0,
                  experts_offset: int = 0) -> "ModelConfig":
        """Test-size A.X-K1: one dense layer and two expert layers, 16
        routed experts, 4 per token, one shared; optionally a chip's share."""
        return dataclasses.replace(
            cls.axk1(), vocab_size=vocab_size, hidden_size=64,
            intermediate_size=96, num_hidden_layers=3, num_attention_heads=4,
            num_key_value_heads=4, max_position_embeddings=1024,
            num_experts=16, num_experts_per_tok=4, q_lora_rank=24,
            kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
            v_head_dim=16, yarn=(4.0, 64, 32.0, 1.0, 1.0, 1.0),
            moe_intermediate_size=32, experts_held=experts_held,
            experts_offset=experts_offset)

    @classmethod
    def smallthinker_21b(cls) -> "ModelConfig":
        """PowerInfer/SmallThinker-21BA3B-Instruct: 52 GQA layers in periods
        of [global without rotary, window 4,096 with rotary x 3], every MLP
        64 ReLU-gated experts of width 768, 6 a token, routed from the
        pre-attention state; untied head (docs/SWA.md)."""
        return cls(
            vocab_size=151936,
            hidden_size=2560,
            intermediate_size=768,
            num_hidden_layers=52,
            num_attention_heads=28,
            num_key_value_heads=4,
            head_dim=128,
            rope_theta=1_500_000.0,
            rms_norm_eps=1e-6,
            tie_word_embeddings=False,
            max_position_embeddings=16384,
            attention_bias=False,
            model_type="smallthinker",
            num_experts=64,
            num_experts_per_tok=6,
            norm_topk_prob=True,
            sliding_window=4096,
            sliding_window_layout=(0, 1, 1, 1) * 13,
            rope_layout=(0, 1, 1, 1) * 13,
            expert_activation="relu",
            router_input="pre_attention",
            expert_token_block=4096,
        )

    @classmethod
    def smallthinker_tiny(cls, vocab_size: int = 512, window: int = 8,
                          layers: int = 4) -> "ModelConfig":
        """Test-size SmallThinker: the same period at 8 experts, 2 a token,
        and a window every test path crosses."""
        return dataclasses.replace(
            cls.smallthinker_21b(), vocab_size=vocab_size, hidden_size=64,
            intermediate_size=32, num_hidden_layers=layers,
            num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            max_position_embeddings=1024, num_experts=8,
            num_experts_per_tok=2, sliding_window=window,
            sliding_window_layout=((0, 1, 1, 1) * layers)[:layers],
            rope_layout=((0, 1, 1, 1) * layers)[:layers])

    @classmethod
    def lfm2_24b(cls) -> "ModelConfig":
        """LiquidAI/LFM2-24B-A2B: 40 layers in periods of [conv, conv,
        attention, conv], the first two with a dense SwiGLU of 11,776, the
        rest 64 experts of width 1,536, 4 a token, chosen by sigmoid scores
        plus a bias and weighted by the scores alone; 32 / 8 heads of 64
        with a per-head q/k norm; tied head (docs/STATE.md)."""
        return cls(
            vocab_size=65536,
            hidden_size=2048,
            intermediate_size=11776,
            num_hidden_layers=40,
            num_attention_heads=32,
            num_key_value_heads=8,
            rope_theta=1_000_000.0,
            rms_norm_eps=1e-5,
            tie_word_embeddings=True,
            max_position_embeddings=128000,
            attention_bias=False,
            model_type="lfm2_moe",
            num_experts=64,
            num_experts_per_tok=4,
            norm_topk_prob=True,
            first_k_dense_replace=2,
            moe_intermediate_size=1536,
            scoring_func="sigmoid",
            routed_scaling_factor=1.0,
            layer_types=("conv", "conv", "full_attention", "conv") * 10,
            conv_L_cache=3,
            qk_norm_per_head=True,
            use_expert_bias=True,
            kv_head_pack=2,
            expert_token_block=4096,
        )

    @classmethod
    def lfm2_tiny(cls, vocab_size: int = 512, layers: int = 10) -> "ModelConfig":
        """Test-size LFM2: the first `layers` of the same layout (two dense
        conv layers, then [attention, conv, conv, conv] periods of expert
        layers) at 8 experts, 2 a token, heads of 16 packed in pairs."""
        return dataclasses.replace(
            cls.lfm2_24b(), vocab_size=vocab_size, hidden_size=64,
            intermediate_size=96, num_hidden_layers=layers,
            num_attention_heads=4, num_key_value_heads=2,
            max_position_embeddings=1024, num_experts=8,
            num_experts_per_tok=2, moe_intermediate_size=32,
            layer_types=(("conv", "conv", "full_attention", "conv")
                         * layers)[:layers])

    @classmethod
    def falcon_h1_34b(cls) -> "ModelConfig":
        """tiiuae/Falcon-H1-34B-Instruct (docs/SSM.md): 72 layers, each a
        Mamba-2 mixer (32 heads of 128, 2 groups, state 256, 4 taps) beside
        an attention of 20 / 4 heads of 128, then a SwiGLU of 21,504; muP
        multipliers as published; vocabulary 261,120, untied."""
        return cls(
            vocab_size=261120, hidden_size=5120, intermediate_size=21504,
            num_hidden_layers=72, num_attention_heads=20,
            num_key_value_heads=4, head_dim=128, rope_theta=1e11,
            rms_norm_eps=1e-5, tie_word_embeddings=False,
            max_position_embeddings=262144, attention_bias=False,
            model_type="falcon_h1", layer_types=("hybrid",) * 72,
            ssm_heads=32, ssm_head_dim=128, ssm_groups=2, ssm_state=256,
            ssm_conv=4, ssm_chunk=128,
            embed_scale=5.656854249492381,
            attention_in_multiplier=1.0,
            key_multiplier=0.011048543456039804,
            attention_out_multiplier=0.0375,
            ssm_in_multiplier=0.25,
            ssm_multipliers=(0.3535533905932738, 0.25, 0.1767766952966369,
                             0.5, 0.3535533905932738),
            ssm_out_multiplier=0.08838834764831845,
            mlp_multipliers=(0.1767766952966369, 0.011160714285714284),
            lm_head_multiplier=0.0078125,
        )

    @classmethod
    def falcon_h1_tiny(cls, vocab_size: int = 512,
                       layers: int = 2) -> "ModelConfig":
        """Test-size Falcon-H1: the same layer at 4 / 2 heads of 16, a mixer
        of 4 heads of 16 in 2 groups of state 8, chunks of 4 tokens, and
        EVERY multiplier at a value of its own other than 1."""
        return dataclasses.replace(
            cls.falcon_h1_34b(), vocab_size=vocab_size, hidden_size=64,
            intermediate_size=96, num_hidden_layers=layers,
            num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            max_position_embeddings=1024, layer_types=("hybrid",) * layers,
            ssm_heads=4, ssm_head_dim=16, ssm_groups=2, ssm_state=8,
            ssm_chunk=4, rope_theta=1e4,
            embed_scale=1.7, attention_in_multiplier=0.9,
            key_multiplier=0.6, attention_out_multiplier=0.7,
            ssm_in_multiplier=0.8, ssm_multipliers=(0.9, 0.7, 0.6, 0.8, 1.3),
            ssm_out_multiplier=0.5, mlp_multipliers=(0.75, 0.4),
            lm_head_multiplier=0.3)

    @classmethod
    def granite_h_small(cls) -> "ModelConfig":
        """ibm-granite/granite-4.0-h-small (docs/GRANITE_H.md): 40 layers,
        nine Mamba-2 mixers ALONE in their layers (128 heads of 64, one group
        of state 128, 4 taps, chunks of 256) to every global attention layer
        (GQA 32 / 8 x 128, no rotary, scale 1 / 128), 72 experts of 768 ten
        a token and a shared expert of 1,536 after every one, tied
        vocabulary of 100,352."""
        period = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4
        return cls(
            vocab_size=100352, hidden_size=4096, intermediate_size=768,
            num_hidden_layers=40, num_attention_heads=32,
            num_key_value_heads=8, rope_theta=1e4,
            rms_norm_eps=1e-5, tie_word_embeddings=True,
            max_position_embeddings=131072, attention_bias=False,
            model_type="granitemoehybrid", layer_types=period * 4,
            rope_layout=(0,) * 40,
            ssm_heads=128, ssm_head_dim=64, ssm_groups=1, ssm_state=128,
            ssm_conv=4, ssm_chunk=256,
            num_experts=72, num_experts_per_tok=10, norm_topk_prob=True,
            n_shared_experts=1, shared_intermediate_size=1536,
            embed_scale=12.0, attention_multiplier=0.0078125,
            residual_multiplier=0.22, lm_head_multiplier=1 / 16,
            expert_token_block=1024,
        )

    @classmethod
    def granite_h_tiny(cls, vocab_size: int = 512,
                       experts_held: int = 0,
                       experts_offset: int = 0) -> "ModelConfig":
        """Test-size Granite 4.0-H: `[mamba, mamba, attention, mamba]` twice,
        4 / 2 heads of 16, a mixer of 4 heads of 16 with ONE group of state
        8 in chunks of 4, 8 experts of 32 three a token, a shared expert of
        48, and every multiplier at a value of its own that is neither 1 nor
        its published default."""
        kinds = ("mamba", "mamba", "attention", "mamba") * 2
        return dataclasses.replace(
            cls.granite_h_small(), vocab_size=vocab_size, hidden_size=64,
            intermediate_size=32, num_hidden_layers=8,
            num_attention_heads=4, num_key_value_heads=2,
            max_position_embeddings=1024, layer_types=kinds,
            rope_layout=(0,) * 8,
            ssm_heads=4, ssm_head_dim=16, ssm_groups=1, ssm_state=8,
            ssm_chunk=4, num_experts=8, num_experts_per_tok=3,
            shared_intermediate_size=48, embed_scale=3.0,
            attention_multiplier=0.2, residual_multiplier=0.6,
            lm_head_multiplier=0.25, experts_held=experts_held,
            experts_offset=experts_offset)

    @classmethod
    def minicpm_sala(cls) -> "ModelConfig":
        """openbmb/MiniCPM-SALA (docs/SALA.md): 32 layers, 8 sparse
        (InfLLM-V2: 32 / 2 heads of 128, no rotary, top 64 blocks of 64) and
        24 lightning (32 heads of 128, a matrix state), a SwiGLU of 16,384,
        muP scales (`scale_emb` 12, `scale_depth` 1.4, logits / 16);
        vocabulary 73,448, untied."""
        kinds = tuple("sparse" if c == "s" else "lightning"
                      for c in _SALA_PUBLISHED)
        return cls(
            vocab_size=73448, hidden_size=4096, intermediate_size=16384,
            num_hidden_layers=32, num_attention_heads=32,
            num_key_value_heads=2, head_dim=128, rope_theta=1e4,
            rms_norm_eps=1e-6, tie_word_embeddings=False,
            max_position_embeddings=524288, attention_bias=False,
            model_type="minicpm_sala", layer_types=kinds,
            rope_layout=tuple(int(k == "lightning") for k in kinds),
            qk_norm_per_head=True, attention_gate=True,
            lightning_heads=32, lightning_head_dim=128, ssm_chunk=128,
            embed_scale=12.0, scale_depth=1.4, published_layers=32,
            lm_head_multiplier=256 / 4096)

    @classmethod
    def minicpm_sala_tiny(cls, vocab_size: int = 512,
                          layers: int = 4) -> "ModelConfig":
        """Test-size MiniCPM-SALA: `[sparse, lightning, lightning, sparse]`
        repeated, 4 / 2 heads of 16, lightning 4 heads of 16 in chunks of 4,
        compressed keys over 8 tokens every 4, blocks of 16, the top 4 of
        them (the first, the 2 of a window of 32 and one free), dense under
        96 keys; every scale at a value other than 1."""
        kinds = (("sparse", "lightning", "lightning", "sparse")
                 * layers)[:layers]
        return dataclasses.replace(
            cls.minicpm_sala(), vocab_size=vocab_size, hidden_size=64,
            intermediate_size=96, num_hidden_layers=layers,
            num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            max_position_embeddings=1024, layer_types=kinds,
            rope_layout=tuple(int(k == "lightning") for k in kinds),
            lightning_heads=4, lightning_head_dim=16, ssm_chunk=4,
            sparse_kernel_size=8, sparse_kernel_stride=4,
            sparse_block_size=16, sparse_topk=4, sparse_init_blocks=1,
            sparse_window_size=32, sparse_dense_len=96,
            embed_scale=1.7, scale_depth=1.1, published_layers=2 * layers,
            first_published_layer=1, lm_head_multiplier=0.5)

    @classmethod
    def trinity_large(cls) -> "ModelConfig":
        """arcee-ai/Trinity-Large-Preview (`afmoe`): 60 GQA layers of 48 / 8
        heads of 128 in periods of [window 4,096 with rotary x 3, global
        without rotary], a gate on the attention's output, four norms a
        layer, the first 6 layers a dense SwiGLU of 12,288, the rest one
        shared expert plus 256 routed experts of 3,072, 4 a token, chosen by
        sigmoid scores plus a bias, renormalised and scaled by 2.448; the
        embedding scaled by sqrt(3,072); untied head (docs/AFMOE.md)."""
        return cls(
            vocab_size=200192,
            hidden_size=3072,
            intermediate_size=12288,
            num_hidden_layers=60,
            num_attention_heads=48,
            num_key_value_heads=8,
            head_dim=128,
            rope_theta=10_000.0,
            rms_norm_eps=1e-5,
            tie_word_embeddings=False,
            max_position_embeddings=262144,
            attention_bias=False,
            model_type="afmoe",
            num_experts=256,
            num_experts_per_tok=4,
            norm_topk_prob=True,
            first_k_dense_replace=6,
            moe_intermediate_size=3072,
            n_shared_experts=1,
            scoring_func="sigmoid",
            routed_scaling_factor=2.448,
            sliding_window=4096,
            sliding_window_layout=(1, 1, 1, 0) * 15,
            rope_layout=(1, 1, 1, 0) * 15,
            qk_norm_per_head=True,
            use_expert_bias=True,
            route_norm_eps=1e-20,
            attention_gate=True,
            branch_norms=True,
            embed_scale=3072 ** 0.5,
            expert_token_block=1024,
        )

    @classmethod
    def trinity_tiny(cls, vocab_size: int = 512, window: int = 8,
                     experts_held: int = 0,
                     experts_offset: int = 0) -> "ModelConfig":
        """Test-size Trinity: one dense layer (a window layer) and one period
        of expert layers [window x 3, global], 16 experts, 2 a token, one
        shared, heads of 16 and a window every test path crosses; optionally
        a chip's share of the experts."""
        return dataclasses.replace(
            cls.trinity_large(), vocab_size=vocab_size, hidden_size=64,
            intermediate_size=96, num_hidden_layers=5, num_attention_heads=4,
            num_key_value_heads=2, head_dim=16, max_position_embeddings=1024,
            num_experts=16, num_experts_per_tok=2, first_k_dense_replace=1,
            moe_intermediate_size=32, sliding_window=window,
            sliding_window_layout=(1, 1, 1, 1, 0), rope_layout=(1, 1, 1, 1, 0),
            embed_scale=8.0, experts_held=experts_held,
            experts_offset=experts_offset)

    @classmethod
    def sdar_30b_a3b(cls) -> "ModelConfig":
        """JetLM/SDAR-30B-A3B-Chat (`model_type: sdar_moe`): 48 layers of
        Qwen3-MoE's block (GQA 32 / 4 heads of 128, per-head q/k norms, 128
        softmax experts of width 768, 8 a token, renormalised), generated by
        diffusion over blocks of 4 (docs/BLOCKDIFF.md)."""
        return cls(
            vocab_size=151936,
            hidden_size=2048,
            intermediate_size=6144,
            num_hidden_layers=48,
            num_attention_heads=32,
            num_key_value_heads=4,
            head_dim=128,
            rope_theta=1_000_000.0,
            rms_norm_eps=1e-6,
            tie_word_embeddings=False,
            max_position_embeddings=32768,
            attention_bias=False,
            model_type="sdar_moe",
            num_experts=128,
            num_experts_per_tok=8,
            norm_topk_prob=True,
            moe_intermediate_size=768,
            qk_norm_per_head=True,
            expert_token_block=4096,
            block_length=SDAR_BLOCK_LENGTH,
            mask_token_id=SDAR_MASK_TOKEN_ID,
        )

    @classmethod
    def sdar_tiny(cls, vocab_size: int = 512,
                  block_length: int = 4) -> "ModelConfig":
        """Test-size SDAR: the same layer at 8 experts of 32, 2 a token,
        heads of 16, the mask token the vocabulary's last id."""
        return dataclasses.replace(
            cls.sdar_30b_a3b(), vocab_size=vocab_size, hidden_size=64,
            intermediate_size=96, num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, head_dim=16, max_position_embeddings=1024,
            num_experts=8, num_experts_per_tok=2, moe_intermediate_size=32,
            expert_token_block=0, block_length=block_length,
            mask_token_id=vocab_size - 1)

    @classmethod
    def ouro_2_6b(cls) -> "ModelConfig":
        """ByteDance/Ouro-2.6B (docs/OURO.md): 48 MHA layers of 16 heads of
        128 with four norms a layer, passed four times, untied head."""
        return cls(
            vocab_size=49152, hidden_size=2048, intermediate_size=5632,
            num_hidden_layers=48, num_attention_heads=16,
            num_key_value_heads=16, head_dim=128, rope_theta=1_000_000.0,
            rms_norm_eps=1e-6, tie_word_embeddings=False,
            max_position_embeddings=65536, attention_bias=False,
            model_type="ouro", branch_norms=True, loop_passes=4)

    @classmethod
    def ouro_tiny(cls, vocab_size: int = 512, layers: int = 2,
                  passes: int = 3) -> "ModelConfig":
        """Test-size Ouro: 2 layers passed 3 times (so that no count stands
        in for another), heads of 16."""
        return dataclasses.replace(
            cls.ouro_2_6b(), vocab_size=vocab_size, hidden_size=64,
            intermediate_size=96, num_hidden_layers=layers,
            num_attention_heads=4, num_key_value_heads=4, head_dim=16,
            max_position_embeddings=1024, loop_passes=passes)

    @classmethod
    def llama3_2_1b(cls) -> "ModelConfig":
        """Llama-3.2-1B geometry — the Llama side of the same decoder
        (no attention biases, untied-by-default in larger family members)."""
        return cls(
            vocab_size=128256,
            hidden_size=2048,
            intermediate_size=8192,
            num_hidden_layers=16,
            num_attention_heads=32,
            num_key_value_heads=8,
            head_dim=64,
            rope_theta=500_000.0,
            rms_norm_eps=1e-5,
            tie_word_embeddings=True,
            max_position_embeddings=131072,
            attention_bias=False,
            model_type="llama",
        )

    @classmethod
    def llama3_8b(cls) -> "ModelConfig":
        return cls(
            vocab_size=128256,
            hidden_size=4096,
            intermediate_size=14336,
            num_hidden_layers=32,
            num_attention_heads=32,
            num_key_value_heads=8,
            rope_theta=500_000.0,
            rms_norm_eps=1e-5,
            tie_word_embeddings=False,
            max_position_embeddings=131072,
            attention_bias=False,
            model_type="llama",
        )

    @classmethod
    def from_hf_config(cls, hf_config) -> "ModelConfig":
        """Build from a `transformers` Qwen2Config / LlamaConfig / OlmoeConfig
        (or dict), or from A.X-K1's `config.json` (`model_type: axk1`,
        DeepSeek-V3's key set; `_axk1_from_hf`), SmallThinker's, LFM2-MoE's,
        Trinity's (`afmoe`), Falcon-H1's (`falcon_h1`, docs/SSM.md), Granite
        4.0-H's (`granitemoehybrid`, docs/GRANITE_H.md) or SDAR's (`sdar_moe`: generation by diffusion
        over blocks, docs/BLOCKDIFF.md), each by a parser of its own. A config the
        decoder does not implement raises: expert keys under any other model
        type (each family routes, scales and shares in its own way), and a
        non-null `clip_qkv`."""
        get = (lambda k, d=None: getattr(hf_config, k, d)) if not isinstance(
            hf_config, dict
        ) else (lambda k, d=None: hf_config.get(k, d))
        # Qwen2 has no attention_bias knob (its q/k/v always carry biases);
        # Llama-family configs expose it (default False)
        model_type = str(get("model_type", "qwen2")).lower()
        attn_bias = get("attention_bias", "qwen" in model_type)
        olmoe = model_type == "olmoe"
        if model_type == "axk1":
            return cls._axk1_from_hf(get)
        if model_type == "smallthinker":
            return cls._smallthinker_from_hf(get)
        if model_type == "lfm2_moe":
            return cls._lfm2_from_hf(get)
        if model_type == "afmoe":
            return cls._afmoe_from_hf(get)
        if model_type == "falcon_h1":
            return cls._falcon_h1_from_hf(get)
        if model_type == "minicpm_sala":
            return cls._sala_from_hf(get)
        if model_type == "granitemoehybrid":
            return cls._granite_h_from_hf(get)
        if model_type == "sdar_moe":
            return cls._sdar_from_hf(get)
        if model_type == "ouro":
            return cls._ouro_from_hf(get)
        if int(get("total_ut_steps") or 1) > 1:
            raise ValueError(
                f"model_type={model_type!r} with total_ut_steps="
                f"{get('total_ut_steps')!r}: a stack that every token passes "
                "several times is built for model_type 'ouro' only "
                "(docs/OURO.md); one pass under this config would be "
                "another model under its name")
        # a window on a family this decoder builds with full attention only:
        # Qwen2's `use_sliding_window` (with `sliding_window`, `layer_types`
        # or `max_window_layers`), Mistral's bare `sliding_window`
        windowed = (get("use_sliding_window")
                    or "sliding_attention" in (get("layer_types") or ())
                    or (get("sliding_window") is not None
                        and get("use_sliding_window") is None))
        if windowed:
            raise ValueError(
                f"model_type={model_type!r} with a sliding window "
                f"(use_sliding_window={get('use_sliding_window')!r}, "
                f"sliding_window={get('sliding_window')!r}, layer_types="
                f"{'given' if get('layer_types') else None}): the window "
                "layers of docs/SWA.md are built for model_type "
                "'smallthinker' and 'afmoe' only; full attention under this "
                "config would be another model under its name")
        expert_keys = [k for k in _EXPERT_KEYS if get(k)]
        if expert_keys and not olmoe:
            raise ValueError(
                f"model_type={model_type!r} with expert keys {expert_keys}: "
                "the decoder implements OLMoE's sparse-expert layer "
                "(docs/MOE.md) and A.X-K1's (docs/MLA.md) only; building a "
                "dense model from this config would be another model under "
                "its name")
        if get("clip_qkv") is not None:
            raise ValueError(
                f"clip_qkv={get('clip_qkv')!r}: the decoder does not clip "
                "q/k/v (OLMoE-1B-7B-0125-Instruct publishes null)")
        return cls(
            vocab_size=get("vocab_size"),
            hidden_size=get("hidden_size"),
            intermediate_size=get("intermediate_size"),
            num_hidden_layers=get("num_hidden_layers"),
            num_attention_heads=get("num_attention_heads"),
            num_key_value_heads=get("num_key_value_heads"),
            head_dim=get("head_dim", None),
            rope_theta=get("rope_theta", 1_000_000.0),
            rms_norm_eps=get("rms_norm_eps", 1e-6),
            tie_word_embeddings=get("tie_word_embeddings", False),
            max_position_embeddings=get("max_position_embeddings", 32768),
            attention_bias=bool(attn_bias),
            model_type=model_type,
            num_experts=get("num_experts") or 0,    # olmoe only: see above
            num_experts_per_tok=get("num_experts_per_tok") or 0,
            norm_topk_prob=bool(get("norm_topk_prob", False)),
            qk_norm=olmoe,
        )

    @classmethod
    def _ouro_from_hf(cls, get) -> "ModelConfig":
        """Ouro's `config.json` (`model_type: ouro`, docs/OURO.md): Qwen2's
        key set with `total_ut_steps` (the passes) and `early_exit_threshold`
        (the exit gate's `q`). What the file has no key for (the four norms'
        places, the norm inside the loop, the gate, a cache slot a pass a
        layer) is the published modelling file's, listed in docs/OURO.md."""
        def refuse(key, why):
            raise ValueError(f"model_type='ouro' with {key}="
                             f"{get(key)!r}: {why} (docs/OURO.md)")

        if float(get("early_exit_threshold", 1.0)) < 1.0:
            refuse("early_exit_threshold",
                   "below 1 rows leave a forward at different passes, and "
                   "the session's chunk program runs every row through "
                   "every pass: what the skipped passes' cache slots then "
                   "hold is not built")
        if get("use_sliding_window") or "sliding_attention" in (
                get("layer_types") or ()):
            refuse("use_sliding_window", "the published model has no window")
        if get("rope_scaling") is not None:
            refuse("rope_scaling", "the published model has plain rotary")
        if str(get("hidden_act", "silu")) != "silu":
            refuse("hidden_act", "the MLP is SwiGLU")
        if get("attention_bias") or get("mlp_bias"):
            refuse("attention_bias", "the published layers carry no bias")
        expert_keys = [k for k in _EXPERT_KEYS if get(k)]
        if expert_keys:
            raise ValueError(f"model_type='ouro' with expert keys "
                             f"{expert_keys}: the model is dense")
        return cls(
            vocab_size=get("vocab_size"), hidden_size=get("hidden_size"),
            intermediate_size=get("intermediate_size"),
            num_hidden_layers=get("num_hidden_layers"),
            num_attention_heads=get("num_attention_heads"),
            num_key_value_heads=get("num_key_value_heads"),
            head_dim=get("head_dim", None),
            rope_theta=float(get("rope_theta", 1_000_000.0)),
            rms_norm_eps=get("rms_norm_eps", 1e-6),
            tie_word_embeddings=bool(get("tie_word_embeddings", False)),
            max_position_embeddings=get("max_position_embeddings", 65536),
            attention_bias=False, model_type="ouro", branch_norms=True,
            loop_passes=int(get("total_ut_steps") or 1))

    @classmethod
    def _sdar_from_hf(cls, get) -> "ModelConfig":
        """SDAR-MoE's published keys (`model_type: sdar_moe`,
        docs/BLOCKDIFF.md): Qwen3-MoE's key set. The generation procedure's
        `block_length` and `mask_token_id` are no keys of the published
        config.json (the family's `generate.py` takes them as arguments):
        read where a file states them, else the Chat releases' defaults
        (`SDAR_BLOCK_LENGTH`, `SDAR_MASK_TOKEN_ID`). What the decoder does
        not compute raises by name."""
        who = "model_type='sdar_moe'"
        if get("use_sliding_window") or get("sliding_window") is not None:
            raise ValueError(
                f"{who} with a sliding window (use_sliding_window="
                f"{get('use_sliding_window')!r}, sliding_window="
                f"{get('sliding_window')!r}): the block-causal mask is built "
                "for full attention only")
        if get("rope_scaling") is not None:
            raise ValueError(
                f"{who} with rope_scaling={get('rope_scaling')!r}: the "
                "family publishes plain rotary embedding (theta 1e6)")
        if get("mlp_only_layers"):
            raise ValueError(
                f"{who} with mlp_only_layers={get('mlp_only_layers')!r}: "
                "every layer is an expert layer here")
        if int(get("decoder_sparse_step", 1)) != 1:
            raise ValueError(
                f"{who} with decoder_sparse_step="
                f"{get('decoder_sparse_step')!r}: every layer is an expert "
                "layer here")
        if get("attention_bias"):
            raise ValueError(
                f"{who} with attention_bias=True: the family's q/k/v carry "
                "no bias, and per-head norms over a biased projection would "
                "be another model under its name")
        if str(get("hidden_act", "silu")) != "silu":
            raise ValueError(f"{who} with hidden_act={get('hidden_act')!r}")
        B = int(get("block_length", SDAR_BLOCK_LENGTH))
        mask_id = int(get("mask_token_id", SDAR_MASK_TOKEN_ID))
        V = int(get("vocab_size"))
        if B < 1 or not 0 <= mask_id < V:
            raise ValueError(
                f"{who} with block_length={B}, mask_token_id={mask_id}: the "
                f"block holds at least one token and the mask token is an "
                f"id of the vocabulary (0..{V - 1})")
        return cls(
            vocab_size=V,
            hidden_size=get("hidden_size"),
            intermediate_size=get("intermediate_size"),
            num_hidden_layers=get("num_hidden_layers"),
            num_attention_heads=get("num_attention_heads"),
            num_key_value_heads=get("num_key_value_heads"),
            head_dim=get("head_dim", None),
            rope_theta=float(get("rope_theta", 1_000_000.0)),
            rms_norm_eps=get("rms_norm_eps", 1e-6),
            tie_word_embeddings=bool(get("tie_word_embeddings", False)),
            max_position_embeddings=get("max_position_embeddings", 32768),
            attention_bias=False,
            model_type="sdar_moe",
            num_experts=int(get("num_experts")),
            num_experts_per_tok=int(get("num_experts_per_tok")),
            norm_topk_prob=bool(get("norm_topk_prob", True)),
            moe_intermediate_size=int(get("moe_intermediate_size")),
            qk_norm_per_head=True,
            # a served row's prefill piece or scored row dispatches in
            # blocks of tokens (a one-chip configuration: `expert_token_block`)
            expert_token_block=4096,
            block_length=B,
            mask_token_id=mask_id,
        )

    @classmethod
    def _smallthinker_from_hf(cls, get) -> "ModelConfig":
        """SmallThinker's published keys (docs/SWA.md). What the layer of
        docs/SWA.md does not compute raises: a sigmoid primary router,
        secondary experts, a `moe_layer_layout` with dense layers (the 4B
        sibling), rope scaling, attention biases, and layouts that give the
        model no global or no window layer."""
        L = int(get("num_hidden_layers"))
        if get("moe_primary_router_apply_softmax") is False:
            raise ValueError(
                "smallthinker: moe_primary_router_apply_softmax=False (a "
                "sigmoid primary router) is not implemented: docs/SWA.md")
        for key in ("moe_num_secondary_experts", "moe_secondary_expert_size",
                    "moe_num_active_secondary_experts"):
            if get(key):
                raise ValueError(
                    f"smallthinker: {key}={get(key)!r}: secondary experts "
                    "are not implemented (the 21B config.json has primary "
                    "keys only: docs/SWA.md)")
        layout = get("moe_layer_layout")
        if layout is not None and not all(layout):
            raise ValueError(
                "smallthinker: a moe_layer_layout with dense layers (the 4B "
                "sibling's) is not implemented: every layer has experts")
        if get("rope_scaling"):
            raise ValueError("smallthinker: rope_scaling="
                             f"{get('rope_scaling')!r} is not implemented")
        if get("attention_bias"):
            raise ValueError("smallthinker: attention biases are not "
                             "implemented (assumed none: docs/SWA.md)")
        window = int(get("sliding_window_size") or 0)
        win = tuple(int(bool(w)) for w in (get("sliding_window_layout")
                                           or (0,) * L))
        rot = tuple(int(bool(r)) for r in (get("rope_layout") or (1,) * L))
        if len(win) != L or len(rot) != L:
            raise ValueError(
                f"smallthinker: layouts of {len(win)} and {len(rot)} entries "
                f"for {L} layers")
        if any(win) and window <= 0:
            raise ValueError("smallthinker: window layers without a "
                             "sliding_window_size")
        if window and any(win) and all(win):
            raise ValueError(
                "smallthinker: a model of window layers only is not "
                "implemented: the page pool of two kinds keeps a global "
                "kind (docs/SWA.md)")
        return cls(
            vocab_size=get("vocab_size"),
            hidden_size=get("hidden_size"),
            intermediate_size=get("moe_ffn_hidden_size"),
            num_hidden_layers=L,
            num_attention_heads=get("num_attention_heads"),
            num_key_value_heads=get("num_key_value_heads"),
            head_dim=get("head_dim", None),
            rope_theta=float(get("rope_theta", 1_500_000.0)),
            rms_norm_eps=get("rms_norm_eps", 1e-6),
            tie_word_embeddings=bool(get("tie_word_embeddings", False)),
            max_position_embeddings=get("max_position_embeddings", 16384),
            attention_bias=False,
            model_type="smallthinker",
            num_experts=get("moe_num_primary_experts"),
            num_experts_per_tok=get("moe_num_active_primary_experts"),
            norm_topk_prob=bool(get("norm_topk_prob", True)),
            sliding_window=window if any(win) else 0,
            sliding_window_layout=win if any(win) else (),
            rope_layout=() if all(rot) else rot,
            expert_activation="relu",
            router_input="pre_attention",
            expert_token_block=4096,
        )

    @classmethod
    def _lfm2_from_hf(cls, get) -> "ModelConfig":
        """LFM2-MoE's published keys (docs/STATE.md). What the layers of
        docs/STATE.md do not compute raises: a convolution bias, a layer
        type other than `conv` and `full_attention`, a model without an
        attention layer (the session's page pool keeps that kind) and rope
        scaling."""
        L = int(get("num_hidden_layers"))
        types = tuple(str(t) for t in (get("layer_types") or ()))
        if len(types) != L:
            raise ValueError(
                f"lfm2_moe: layer_types of {len(types)} entries for {L} layers")
        other = sorted(set(types) - {"conv", "full_attention"})
        if other:
            raise ValueError(
                f"lfm2_moe: layer_types {other} are not implemented (only "
                "'conv' and 'full_attention': docs/STATE.md)")
        if "full_attention" not in types or "conv" not in types:
            raise ValueError(
                "lfm2_moe: a model of one layer kind only is not "
                "implemented: the cache spec keeps pages AND a state "
                "(docs/STATE.md)")
        if get("conv_bias"):
            raise ValueError("lfm2_moe: conv_bias=True is not implemented "
                             "(the published config has false)")
        K = int(get("conv_L_cache") or 0)
        if K < 2:
            raise ValueError(f"lfm2_moe: conv_L_cache={K} (a state of "
                             "K - 1 values needs K >= 2)")
        rope = get("rope_parameters") or {}
        kind = rope.get("rope_type", "default")
        if kind not in ("default", None) or get("rope_scaling"):
            raise ValueError(f"lfm2_moe: rope scaling ({kind!r}, "
                             f"{get('rope_scaling')!r}) is not implemented")
        dense = int(get("num_dense_layers") or 0)
        H, KV = int(get("num_attention_heads")), int(get("num_key_value_heads"))
        hd = get("head_dim") or int(get("hidden_size")) // H
        return cls(
            vocab_size=get("vocab_size"),
            hidden_size=get("hidden_size"),
            intermediate_size=get("intermediate_size"),
            num_hidden_layers=L,
            num_attention_heads=H,
            num_key_value_heads=KV,
            head_dim=get("head_dim", None),
            rope_theta=float(rope.get("rope_theta",
                                      get("rope_theta", 1_000_000.0))),
            rms_norm_eps=get("norm_eps", 1e-5),
            tie_word_embeddings=bool(get("tie_word_embeddings", True)),
            max_position_embeddings=get("max_position_embeddings", 128000),
            attention_bias=False,
            model_type="lfm2_moe",
            num_experts=int(get("num_experts")),
            num_experts_per_tok=int(get("num_experts_per_tok")),
            norm_topk_prob=bool(get("norm_topk_prob", True)),
            first_k_dense_replace=dense,
            moe_intermediate_size=int(get("moe_intermediate_size")),
            scoring_func="sigmoid",
            routed_scaling_factor=float(get("routed_scaling_factor", 1.0)),
            layer_types=types,
            conv_L_cache=K,
            qk_norm_per_head=True,
            use_expert_bias=bool(get("use_expert_bias", False)),
            # two heads of 64 (or narrower) a row of lanes: at 64 the pages
            # are the 128 lanes wide that the paged kernels read
            kv_head_pack=2 if (hd <= 64 and KV % 2 == 0) else 1,
            expert_token_block=4096,
        )

    @classmethod
    def _falcon_h1_from_hf(cls, get) -> "ModelConfig":
        """Falcon-H1's published keys (docs/SSM.md). What the layer of
        docs/SSM.md does not compute raises, by the key's name."""
        def refuse(key, why):
            raise ValueError(f"falcon_h1: {key}={get(key)!r} is not "
                             f"implemented ({why}; docs/SSM.md)")

        if get("mamba_rms_norm") is False:
            refuse("mamba_rms_norm", "the mixer's output goes through the "
                   "gated group norm")
        if get("mamba_norm_before_gate"):
            refuse("mamba_norm_before_gate", "the gate is applied before "
                   "the norm")
        if get("attn_layer_indices") is not None:
            refuse("attn_layer_indices", "every layer holds an attention "
                   "beside its mixer")
        for key in ("mamba_proj_bias", "projectors_bias", "mlp_bias",
                    "attention_bias"):
            if get(key):
                refuse(key, "the projections carry no bias")
        if get("mamba_conv_bias") is False:
            refuse("mamba_conv_bias", "the mixer's convolution has a bias")
        if get("rope_scaling"):
            refuse("rope_scaling", "plain rotary embedding only")
        if str(get("hidden_act", "silu")) != "silu":
            refuse("hidden_act", "SiLU in the mixer and the MLP")
        heads, head = int(get("mamba_n_heads")), int(get("mamba_d_head"))
        if int(get("mamba_d_ssm") or heads * head) != heads * head:
            refuse("mamba_d_ssm", f"mamba_n_heads * mamba_d_head = "
                   f"{heads * head}")
        groups = int(get("mamba_n_groups"))
        if heads % groups:
            refuse("mamba_n_groups", f"must divide mamba_n_heads = {heads}")
        if int(get("mamba_d_conv")) < 2:
            refuse("mamba_d_conv", "a tail of K - 1 values needs K >= 2")
        mults = {key: tuple(float(m) for m in get(key) or ())
                 for key in ("ssm_multipliers", "mlp_multipliers")}
        for key, n in (("ssm_multipliers", 5), ("mlp_multipliers", 2)):
            if len(mults[key]) != n:
                refuse(key, f"{n} numbers")
        L = int(get("num_hidden_layers"))
        return cls(
            vocab_size=get("vocab_size"),
            hidden_size=get("hidden_size"),
            intermediate_size=get("intermediate_size"),
            num_hidden_layers=L,
            num_attention_heads=int(get("num_attention_heads")),
            num_key_value_heads=int(get("num_key_value_heads")),
            head_dim=get("head_dim", None),
            rope_theta=float(get("rope_theta", 1e11)),
            rms_norm_eps=get("rms_norm_eps", 1e-5),
            tie_word_embeddings=bool(get("tie_word_embeddings", False)),
            max_position_embeddings=get("max_position_embeddings", 262144),
            attention_bias=False,
            model_type="falcon_h1",
            layer_types=("hybrid",) * L,
            ssm_heads=heads, ssm_head_dim=head, ssm_groups=groups,
            ssm_state=int(get("mamba_d_state")),
            ssm_conv=int(get("mamba_d_conv")),
            ssm_chunk=int(get("mamba_chunk_size") or 128),
            embed_scale=float(get("embedding_multiplier", 1.0)),
            attention_in_multiplier=float(get("attention_in_multiplier", 1.0)),
            key_multiplier=float(get("key_multiplier", 1.0)),
            attention_out_multiplier=float(
                get("attention_out_multiplier", 1.0)),
            ssm_in_multiplier=float(get("ssm_in_multiplier", 1.0)),
            ssm_multipliers=mults["ssm_multipliers"],
            ssm_out_multiplier=float(get("ssm_out_multiplier", 1.0)),
            mlp_multipliers=mults["mlp_multipliers"],
            lm_head_multiplier=float(get("lm_head_multiplier", 1.0)),
        )

    @classmethod
    def _granite_h_from_hf(cls, get) -> "ModelConfig":
        """Granite 4.0-H's published keys (`model_type: granitemoehybrid`,
        docs/GRANITE_H.md), and the chip's share where the file states one
        (`num_experts_held` / `num_experts_offset`). What the
        layers of docs/GRANITE_H.md do not compute raises, by the key's
        name."""
        def refuse(key, why):
            raise ValueError(f"granitemoehybrid: {key}={get(key)!r} is not "
                             f"implemented ({why}; docs/GRANITE_H.md)")

        if str(get("position_embedding_type", "nope")) != "nope":
            refuse("position_embedding_type", "the attention layers carry "
                   "no position: only 'nope'")
        if str(get("normalization_function", "rmsnorm")) != "rmsnorm":
            refuse("normalization_function", "every norm is an RMSNorm")
        for key in ("attention_bias", "mamba_proj_bias"):
            if get(key):
                refuse(key, "the projections carry no bias")
        if get("mamba_conv_bias") is False:
            refuse("mamba_conv_bias", "the mixer's convolution has a bias")
        if get("rope_scaling"):
            refuse("rope_scaling", "no rotary embedding at all")
        if str(get("hidden_act", "silu")) != "silu":
            refuse("hidden_act", "SiLU in the mixer and the experts")
        L = int(get("num_hidden_layers"))
        types = tuple(get("layer_types") or ())
        if len(types) != L or set(types) - {"mamba", "attention"}:
            refuse("layer_types", f"{L} entries of 'mamba' or 'attention'")
        heads, head = int(get("mamba_n_heads")), int(get("mamba_d_head"))
        hidden = int(get("hidden_size"))
        if float(get("mamba_expand") or 0) * hidden != heads * head:
            refuse("mamba_expand", f"mamba_n_heads * mamba_d_head = "
                   f"{heads * head} over hidden_size = {hidden}")
        groups = int(get("mamba_n_groups"))
        if groups < 1 or heads % groups:
            refuse("mamba_n_groups", f"must divide mamba_n_heads = {heads}")
        if int(get("mamba_d_conv")) < 2:
            refuse("mamba_d_conv", "a tail of K - 1 values needs K >= 2")
        E = int(get("num_local_experts"))
        held, offset = cls._chip_share(get, "granitemoehybrid", E,
                                       "num_experts")
        return cls(
            vocab_size=get("vocab_size"),
            hidden_size=hidden,
            intermediate_size=int(get("intermediate_size")),
            num_hidden_layers=L,
            num_attention_heads=int(get("num_attention_heads")),
            num_key_value_heads=int(get("num_key_value_heads")),
            head_dim=get("head_dim", None),
            rope_theta=float(get("rope_theta", 1e4)),
            rms_norm_eps=get("rms_norm_eps", 1e-5),
            tie_word_embeddings=bool(get("tie_word_embeddings", True)),
            max_position_embeddings=get("max_position_embeddings", 131072),
            attention_bias=False,
            model_type="granitemoehybrid",
            layer_types=types,
            rope_layout=(0,) * L,
            ssm_heads=heads, ssm_head_dim=head, ssm_groups=groups,
            ssm_state=int(get("mamba_d_state")),
            ssm_conv=int(get("mamba_d_conv")),
            ssm_chunk=int(get("mamba_chunk_size") or 256),
            num_experts=E,
            num_experts_per_tok=int(get("num_experts_per_tok")),
            norm_topk_prob=True,    # softmax over the chosen logits
            n_shared_experts=1 if get("shared_intermediate_size") else 0,
            shared_intermediate_size=int(
                get("shared_intermediate_size") or 0),
            experts_held=held, experts_offset=offset,
            embed_scale=float(get("embedding_multiplier", 1.0)),
            attention_multiplier=float(get("attention_multiplier") or 0.0),
            residual_multiplier=float(get("residual_multiplier") or 0.0),
            lm_head_multiplier=1.0 / float(get("logits_scaling", 1.0)),
            expert_token_block=1024,
        )

    @classmethod
    def _sala_from_hf(cls, get) -> "ModelConfig":
        """MiniCPM-SALA's published keys (docs/SALA.md) plus the sizes the
        published config does not carry (`sparse_config`, MiniCPM4.1's; a
        cut's `published_layers` / `first_published_layer`). What the
        layers of docs/SALA.md do not compute raises, by the key's name."""
        def refuse(key, why):
            raise ValueError(f"minicpm_sala: {key}={get(key)!r} is not "
                             f"implemented ({why}; docs/SALA.md)")

        if get("attn_use_rope"):
            refuse("attn_use_rope", "the sparse layers carry no rotary")
        if get("lightning_use_rope") is False:
            refuse("lightning_use_rope", "the lightning layers rotate q and k")
        if get("qk_norm") is False:
            refuse("qk_norm", "q and k are normed a head in both mixers")
        for key in ("use_output_gate", "attn_use_output_gate"):
            if get(key) is False:
                refuse(key, "both mixers' outputs go through a sigmoid gate")
        if get("use_output_norm") is False:
            refuse("use_output_norm", "the lightning output is normed a head")
        if get("attention_bias"):
            refuse("attention_bias", "the projections carry no bias")
        heads = int(get("lightning_nh"))
        if int(get("lightning_nkv") or heads) != heads:
            refuse("lightning_nkv", f"lightning_nh = {heads}: a state a head")
        if str(get("lightning_scale", "1/sqrt(d)")) != "1/sqrt(d)":
            refuse("lightning_scale", "the output is divided by sqrt(head)")
        if get("rope_scaling"):
            refuse("rope_scaling", "plain rotary embedding only")
        if str(get("hidden_act", "silu")) != "silu":
            refuse("hidden_act", "SiLU in the MLP")
        mixers = tuple(get("mixer_types") or ())
        L = int(get("num_hidden_layers"))
        if len(mixers) != L or any(m not in _SALA_MIXERS for m in mixers):
            refuse("mixer_types", f"{L} entries, each one of "
                   f"{sorted(_SALA_MIXERS)}")
        kinds = tuple(_SALA_MIXERS[m] for m in mixers)
        sparse = dict(get("sparse_config") or {})
        sizes = {f"sparse_{key}": int(sparse[key]) for key in (
            "kernel_size", "kernel_stride", "block_size", "topk",
            "init_blocks", "window_size", "dense_len") if key in sparse}
        made = dataclasses.replace(cls.minicpm_sala(), **sizes)
        block, stride = made.sparse_block_size, made.sparse_kernel_stride
        if (block % stride or made.sparse_kernel_size != 2 * stride
                or made.sparse_window_size % block):
            refuse("sparse_config", "a compressed key covers two strides, "
                   "a block whole strides and the window whole blocks")
        hidden = int(get("hidden_size"))
        return dataclasses.replace(
            made,
            vocab_size=get("vocab_size"), hidden_size=hidden,
            intermediate_size=get("intermediate_size"),
            num_hidden_layers=L,
            num_attention_heads=int(get("num_attention_heads")),
            num_key_value_heads=int(get("num_key_value_heads")),
            head_dim=get("head_dim", None),
            rope_theta=float(get("rope_theta", 1e4)),
            rms_norm_eps=get("rms_norm_eps", 1e-6),
            tie_word_embeddings=bool(get("tie_word_embeddings", False)),
            max_position_embeddings=get("max_position_embeddings", 524288),
            layer_types=kinds,
            rope_layout=tuple(int(k == "lightning") for k in kinds),
            lightning_heads=heads,
            lightning_head_dim=int(get("lightning_head_dim")),
            ssm_chunk=int(get("lightning_chunk") or 128),
            embed_scale=float(get("scale_emb", 1.0)),
            scale_depth=float(get("scale_depth", 0.0)),
            published_layers=int(get("published_layers") or L),
            first_published_layer=int(get("first_published_layer") or 0),
            lm_head_multiplier=float(get("dim_model_base", hidden)) / hidden)

    @staticmethod
    def _chip_share(get, who: str, experts: int, key: str) -> tuple:
        """`(held, offset)`: the chip's share of the routed experts where the
        file states one (`<key>_held` / `<key>_offset`; a published
        config.json has neither: all are held)."""
        held, offset = (int(get(key + "_held") or 0),
                        int(get(key + "_offset") or 0))
        if held and not 0 <= offset <= experts - held:
            raise ValueError(f"{who}: experts [{offset}, {offset + held}) "
                             f"are not among the router's {experts}")
        return held, offset

    @classmethod
    def _afmoe_from_hf(cls, get) -> "ModelConfig":
        """Trinity's published keys (`model_type: afmoe`, docs/AFMOE.md), and
        the chip's share where the file states one (`num_experts_held` /
        `num_experts_offset`). What the layers of docs/AFMOE.md do not
        compute raises, by name."""
        L = int(get("num_hidden_layers"))
        for key in ("n_group", "topk_group", "num_expert_groups",
                    "num_limited_groups"):
            if get(key) not in (1, None):
                raise ValueError(
                    f"afmoe: {key}={get(key)!r}: group-limited selection is "
                    "not implemented (the top k are taken over all experts: "
                    "docs/AFMOE.md)")
        refuse = {
            "score_func": ("sigmoid",),
            "hidden_act": ("silu", None),
            "rope_scaling": (None,),
            "attention_bias": (False, None),
            # without muP the embedding's scale, and whatever else the
            # family's code then changes, is not stated anywhere we can read
            "mup_enabled": (True,),
        }
        for key, allowed in refuse.items():
            if get(key) not in allowed:
                raise ValueError(
                    f"afmoe: {key}={get(key)!r} is not implemented (only "
                    f"{allowed[0]!r}: docs/AFMOE.md)")
        types = tuple(str(t) for t in (get("layer_types") or ()))
        if len(types) != L:
            raise ValueError(
                f"afmoe: layer_types of {len(types)} entries for {L} layers")
        other = sorted(set(types) - {"sliding_attention", "full_attention"})
        if other:
            raise ValueError(
                f"afmoe: layer_types {other} are not implemented (only "
                "'sliding_attention' and 'full_attention': docs/AFMOE.md)")
        win = tuple(int(t == "sliding_attention") for t in types)
        window = int(get("sliding_window") or 0)
        if any(win) and window <= 0:
            raise ValueError("afmoe: sliding_attention layers without a "
                             "sliding_window")
        if all(win):
            raise ValueError(
                "afmoe: a model of window layers only is not implemented: "
                "the page pool of two kinds keeps a global kind "
                "(docs/SWA.md)")
        E = int(get("num_experts"))
        held, offset = cls._chip_share(get, "afmoe", E, "num_experts")
        D = int(get("hidden_size"))
        return cls(
            vocab_size=get("vocab_size"),
            hidden_size=D,
            intermediate_size=get("intermediate_size"),
            num_hidden_layers=L,
            num_attention_heads=get("num_attention_heads"),
            num_key_value_heads=get("num_key_value_heads"),
            head_dim=get("head_dim", None),
            rope_theta=float(get("rope_theta", 10_000.0)),
            rms_norm_eps=get("rms_norm_eps", 1e-5),
            tie_word_embeddings=bool(get("tie_word_embeddings", False)),
            max_position_embeddings=get("max_position_embeddings", 262144),
            attention_bias=False,
            model_type="afmoe",
            num_experts=E,
            num_experts_per_tok=int(get("num_experts_per_tok")),
            norm_topk_prob=bool(get("route_norm", True)),
            first_k_dense_replace=int(get("num_dense_layers") or 0),
            moe_intermediate_size=int(get("moe_intermediate_size")),
            n_shared_experts=int(get("num_shared_experts") or 0),
            scoring_func="sigmoid",
            routed_scaling_factor=float(get("route_scale", 1.0)),
            experts_held=held,
            experts_offset=offset,
            sliding_window=window if any(win) else 0,
            sliding_window_layout=win if any(win) else (),
            # rotary on the window layers only: a global layer carries no
            # positional signal (assumed: docs/AFMOE.md)
            rope_layout=win,
            qk_norm_per_head=True,
            use_expert_bias=True,
            route_norm_eps=1e-20,
            attention_gate=True,
            branch_norms=True,
            embed_scale=float(D) ** 0.5,
            expert_token_block=1024,
        )

    @classmethod
    def _axk1_from_hf(cls, get) -> "ModelConfig":
        """A.X-K1's keys, and the chip's share where the file states one
        (`n_routed_experts_held` / `n_routed_experts_offset`; a published
        config.json has neither: all experts are held). Values the layer of
        docs/MLA.md does not compute raise."""
        refuse = {
            "topk_method": ("none", None),      # no groups, no correction bias
            "moe_layer_freq": (1, None),        # every later layer has experts
            "hidden_act": ("silu", None),
            "attention_bias": (False, None),
        }
        for key, allowed in refuse.items():
            if get(key) not in allowed:
                raise ValueError(
                    f"axk1: {key}={get(key)!r} is not implemented (only "
                    f"{allowed[0]!r}: docs/MLA.md)")
        scaling = get("rope_scaling")
        yarn = None
        if scaling:
            kind = scaling.get("type", scaling.get("rope_type"))
            if kind != "yarn":
                raise ValueError(f"axk1: rope_scaling type {kind!r} is not "
                                 "implemented (only 'yarn')")
            yarn = (float(scaling["factor"]),
                    int(scaling["original_max_position_embeddings"]),
                    float(scaling.get("beta_fast", 32)),
                    float(scaling.get("beta_slow", 1)),
                    float(scaling.get("mscale", 1)),
                    float(scaling.get("mscale_all_dim", 0)))
        scoring = str(get("scoring_func", "softmax"))
        if scoring not in ("softmax", "sigmoid"):
            raise ValueError(f"axk1: scoring_func={scoring!r}")
        E = int(get("n_routed_experts"))
        held, offset = cls._chip_share(get, "axk1", E, "n_routed_experts")
        return cls(
            vocab_size=get("vocab_size"),
            hidden_size=get("hidden_size"),
            intermediate_size=get("intermediate_size"),
            num_hidden_layers=get("num_hidden_layers"),
            num_attention_heads=get("num_attention_heads"),
            num_key_value_heads=get("num_key_value_heads"),
            rope_theta=get("rope_theta", 10_000.0),
            rms_norm_eps=get("rms_norm_eps", 1e-6),
            tie_word_embeddings=bool(get("tie_word_embeddings", False)),
            max_position_embeddings=get("max_position_embeddings", 131072),
            attention_bias=False,
            model_type="axk1",
            num_experts=E,
            num_experts_per_tok=get("num_experts_per_tok"),
            norm_topk_prob=bool(get("norm_topk_prob", False)),
            q_lora_rank=get("q_lora_rank"),
            kv_lora_rank=get("kv_lora_rank"),
            qk_nope_head_dim=get("qk_nope_head_dim"),
            qk_rope_head_dim=get("qk_rope_head_dim"),
            v_head_dim=get("v_head_dim"),
            yarn=yarn,
            first_k_dense_replace=get("first_k_dense_replace", 0),
            moe_intermediate_size=get("moe_intermediate_size"),
            n_shared_experts=get("n_shared_experts") or 0,
            scoring_func=scoring,
            routed_scaling_factor=float(get("routed_scaling_factor", 1.0)),
            experts_held=held,
            experts_offset=offset,
        )
