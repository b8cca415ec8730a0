"""LoRA adapters applied in-graph — no merge/disk round-trip, ever.

The reference wraps the policy with PEFT (r=64, alpha=16, all seven
projections; embed/lm_head fully trained via `modules_to_save`)
(`/root/reference/GRPO/grpo.py:86-99,226-243`) and must merge the adapter
into a full checkpoint on disk every update so vLLM can load it
(`/root/reference/GRPO/grpo_trainer.py:131-141`). Here the adapter is just an
extra `params["lora"]` subtree that the decoder applies inline during
training, scoring *and* sampling — weight freshness is automatic because
there is only one tree.

Layout mirrors the stacked layer tree: `lora["layers"][proj] = {"a": [L, in, r],
"b": [L, r, out]}`; contribution `(x @ A) @ B * (alpha / r)`, B zero-init so
step 0 is exactly the base model.

On a sparse-expert model (`ModelConfig.num_experts > 0`) the adapter sits on
the four attention projections only (`lora_targets`): the router and the
expert kernels are frozen, as is usual for expert models (an adapter per
expert would be 64 x the parameters for an eighth of the tokens each), and
the model has no `gate_proj` / `up_proj` / `down_proj` leaf to adapt. The
model decides, not a user option; `merge_lora`, `trainable_mask` and the
export therefore need no expert case.

On a latent-attention model (A.X-K1, core/mla.py) the adapter sits on MLA's
five projections (`MLA_TARGETS`) in both of the model's stacks
(`lora["dense_layers"]`, `lora["layers"]`); the leading dense MLP, the
router, the routed and the shared experts are frozen. Again the model
decides.

On Trinity (`afmoe`, docs/AFMOE.md) the adapter sits on the attention
projections and the gate's (`g_proj`) in both stacks; the leading dense MLP,
the router, the routed and the shared experts are frozen.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from nanorlhf_tpu.core.config import ModelConfig

ATTENTION_TARGETS = ("q_proj", "k_proj", "v_proj", "o_proj")
ALL_TARGETS = ATTENTION_TARGETS + ("gate_proj", "up_proj", "down_proj")
MLA_TARGETS = ("q_a_proj", "q_b_proj", "kv_a_proj", "kv_b_proj", "o_proj")


@dataclasses.dataclass(frozen=True)
class LoraConfig:
    r: int = 64
    alpha: int = 16
    # default matches `lora_target_modules` (`GRPO/grpo.py:94`)
    targets: tuple[str, ...] = ALL_TARGETS
    # fully-trained extras, as `modules_to_save` (`GRPO/grpo.py:95`)
    train_embed: bool = True
    train_lm_head: bool = True

    @property
    def scale(self) -> float:
        return self.alpha / self.r


def _proj_dims(config: ModelConfig, name: str) -> tuple[int, int]:
    D, F = config.hidden_size, config.intermediate_size
    H, KV = config.num_attention_heads, config.num_key_value_heads
    if config.kv_lora_rank:
        dq, r = config.q_lora_rank, config.kv_lora_rank
        dn, dr, dv = (config.qk_nope_head_dim, config.qk_rope_head_dim,
                      config.v_head_dim)
        return {
            "q_a_proj": (D, dq),
            "q_b_proj": (dq, H * (dn + dr)),
            "kv_a_proj": (D, r + dr),
            "kv_b_proj": (r, H * (dn + dv)),
            "o_proj": (H * dv, D),
        }[name]
    hd = config.actual_head_dim
    return {
        "q_proj": (D, H * hd),
        "k_proj": (D, KV * hd),
        "v_proj": (D, KV * hd),
        "o_proj": (H * hd, D),
        "g_proj": (D, H * hd),
        "gate_proj": (D, F),
        "up_proj": (D, F),
        "down_proj": (F, D),
    }[name]


def lora_targets(config: ModelConfig, lora: LoraConfig) -> tuple[str, ...]:
    """The projections of `lora.targets` this model adapts: all of them on a
    dense model, the attention ones on an expert model, MLA's five on a
    latent-attention model (which has none of the dense model's names)."""
    if config.kv_lora_rank:
        return MLA_TARGETS
    if config.num_experts:
        kept = tuple(t for t in lora.targets if t in ATTENTION_TARGETS)
        # the attention gate's projection goes with the attention's
        return kept + (("g_proj",) if kept and config.attention_gate else ())
    return tuple(lora.targets)


def init_lora_params(
    config: ModelConfig, lora: LoraConfig, key: jax.Array, dtype=jnp.bfloat16
) -> dict:
    """A ~ N(0, 1/r) (kaiming-ish), B = 0 → adapter starts as identity."""
    config.require("a LoRA adapter")
    if config.kv_lora_rank or (config.num_dense_layers
                               and not config.conv_layers):
        # a model of two stacks whose attention leaves lie over every layer
        names = lora_targets(config, lora)
        Ld = config.num_dense_layers
        stacks = {"layers": config.num_hidden_layers - Ld}
        if Ld:
            stacks["dense_layers"] = Ld
        out = {}
        for i, (stack, L) in enumerate(sorted(stacks.items())):
            keys = jax.random.split(jax.random.fold_in(key, i), len(names))
            out[stack] = {}
            for k, name in zip(keys, names):
                d_in, d_out = _proj_dims(config, name)
                out[stack][name] = {
                    "a": (jax.random.normal(k, (L, d_in, lora.r), jnp.float32)
                          / jnp.sqrt(lora.r)).astype(dtype),
                    "b": jnp.zeros((L, lora.r, d_out), dtype),
                }
        return out
    L = config.num_hidden_layers
    keys = jax.random.split(key, len(lora.targets))
    targets = lora_targets(config, lora)
    layers = {}
    for k, name in zip(keys, lora.targets):
        if name not in targets:
            continue
        d_in, d_out = _proj_dims(config, name)
        layers[name] = {
            "a": (jax.random.normal(k, (L, d_in, lora.r), jnp.float32) / jnp.sqrt(lora.r)).astype(dtype),
            "b": jnp.zeros((L, lora.r, d_out), dtype),
        }
    return {"layers": layers}


def merge_lora(params: dict, lora_scale: float) -> dict:
    """Fold the adapter into the base kernels (checkpoint export only —
    runtime never needs this)."""
    if "lora" not in params:
        return params
    merged = dict(params)
    for stack, lora_layers in params["lora"].items():   # "layers" (+ A.X-K1's)
        new_layers = dict(params[stack])
        for name, ab in lora_layers.items():
            delta = jnp.einsum("lir,lro->lio", ab["a"].astype(jnp.float32), ab["b"].astype(jnp.float32))
            entry = dict(new_layers[name])
            entry["kernel"] = (
                entry["kernel"].astype(jnp.float32) + lora_scale * delta
            ).astype(entry["kernel"].dtype)
            new_layers[name] = entry
        merged[stack] = new_layers
    del merged["lora"]
    return merged


def trainable_mask(params: dict, lora: LoraConfig | None) -> dict:
    """Boolean pytree: which leaves the optimizer updates.

    Full fine-tuning (lora=None): everything True. LoRA: adapter leaves plus
    (optionally) embed_tokens / lm_head — PEFT `modules_to_save` parity.
    """
    if lora is None:
        return jax.tree.map(lambda _: True, params)

    def mask(path, leaf):
        keys = tuple(p.key if hasattr(p, "key") else str(p) for p in path)
        if keys and keys[0] == "lora":
            return True
        if keys and keys[0] == "embed_tokens":
            return lora.train_embed
        if keys and keys[0] == "lm_head":
            return lora.train_lm_head
        return False

    return jax.tree_util.tree_map_with_path(mask, params)
