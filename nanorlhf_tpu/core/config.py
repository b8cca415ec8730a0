"""Model architecture config: the dense Qwen2/Llama block (GQA + SwiGLU),
OLMoE's sparse-expert block, or A.X-K1's (DeepSeek-V3's) latent attention
with leading dense layers before shared-plus-routed sigmoid experts.

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
        DeepSeek-V3's key set; `_axk1_from_hf`). A config the decoder does not
        implement raises: expert keys under any other model type (each family
        routes, scales and shares in its own way), and a non-null `clip_qkv`."""
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
        held, offset = (int(get("n_routed_experts_held") or 0),
                        int(get("n_routed_experts_offset") or 0))
        if held and not 0 <= offset <= E - held:
            raise ValueError(f"axk1: experts [{offset}, {offset + held}) "
                             f"are not among the router's {E}")
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
