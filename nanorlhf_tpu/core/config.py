"""Model architecture config: one decoder layer kind per model, either the
dense Qwen2/Llama block (GQA + SwiGLU) or OLMoE's sparse-expert block.

The reference loads policies with `AutoModelForCausalLM` (Qwen2.5 models,
`/root/reference/GRPO/grpo.py:218-224`); this dataclass captures the
architecture hyperparameters our JAX decoder needs. Presets mirror the HF
configs of the model sizes the reference trains (0.5B/1.5B/7B), the Llama
side of the same block, and OLMoE-1B-7B (docs/MOE.md).
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
        (or dict). A config the decoder does not implement raises: expert keys
        under any model type but `olmoe` (shared experts, dense leading
        layers and the rest are other layers than ops/moe.py's), and a
        non-null `clip_qkv`."""
        get = (lambda k, d=None: getattr(hf_config, k, d)) if not isinstance(
            hf_config, dict
        ) else (lambda k, d=None: hf_config.get(k, d))
        # Qwen2 has no attention_bias knob (its q/k/v always carry biases);
        # Llama-family configs expose it (default False)
        model_type = str(get("model_type", "qwen2")).lower()
        attn_bias = get("attention_bias", "qwen" in model_type)
        olmoe = model_type == "olmoe"
        expert_keys = [k for k in _EXPERT_KEYS if get(k)]
        if expert_keys and not olmoe:
            raise ValueError(
                f"model_type={model_type!r} with expert keys {expert_keys}: "
                "the decoder implements OLMoE's sparse-expert layer only "
                "(docs/MOE.md); building a dense model from this config "
                "would be another model under its name")
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
