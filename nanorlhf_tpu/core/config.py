"""Model architecture config: the dense Qwen2/Llama block (GQA + SwiGLU),
OLMoE's sparse-expert block, A.X-K1's (DeepSeek-V3's) latent attention
with leading dense layers before shared-plus-routed sigmoid experts, or
SmallThinker's pattern of window layers with rotary embedding beside global
layers without it over ReLU-gated experts (docs/SWA.md), or LFM2's pattern of
gated short-convolution layers with a fixed-size state beside attention
layers, over experts chosen by bias-corrected sigmoid scores (docs/STATE.md),
or Trinity's (`afmoe`) gated attention over the same window pattern with four
norms a layer, a leading dense stack inside the pattern and a chip's share of
bias-selected sigmoid experts beside a shared one (docs/AFMOE.md).

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
    # KV heads the cache keeps side by side in one row of lanes: 2 packs
    # heads of 64 into pages 128 lanes wide, which is what the paged kernels
    # read (core/model.py `_pack_heads`; docs/STATE.md). 1: a head a row.
    kv_head_pack: int = 1
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
        """Every layer's kind, in model order: `"conv"` (`layer_types`), or
        an attention layer's `(window, rotary)`."""
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
            kinds = tuple("conv" if t == "conv" else k
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
    def window_layers(self) -> int:
        """Layers with a sliding window (0 without a pattern)."""
        return sum(k != "conv" and k[0] for k in self.layer_kinds)

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
        DeepSeek-V3's key set; `_axk1_from_hf`), SmallThinker's, LFM2-MoE's
        or Trinity's (`afmoe`), each by a parser of its own. A config the
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
