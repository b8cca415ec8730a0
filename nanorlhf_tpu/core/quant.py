"""Weight-only int8 quantization for the rollout path.

Decode is HBM-bandwidth-bound: every step re-reads the full weight set, and
at LLM sizes the seven layer projections are ~85% of those bytes. Storing
them as int8 with per-output-channel scales halves that traffic (the MXU
consumes the int8 blocks straight from VMEM; XLA fuses the upcast into the
matmul operand pipeline, so no bf16 copy lands in HBM).

Placement in the RL loop (`RLConfig.rollout_quant="int8"`):
- generation samples from the quantized base + EXACT bf16 LoRA/embed/norm
  (adapters ride on top in-graph, so policy updates reach the sampler
  immediately — same freshness story as the bf16 path);
- the scoring pass and the update always run the exact bf16 weights. With
  the default recomputed-old-logprobs scoring, the quantization mismatch
  enters the gradient as a small unmeasured off-policy bias that the
  PPO-clip TOLERATES (the same way it tolerates `rollout_ahead`'s
  one-update staleness — the reference leans on the same tolerance,
  `REINFORCE/reinforce_trainer.py:637`). To have the ratio MEASURE and
  importance-correct the quantized behavior distribution, enable
  `sampler_logprob_capture=True`: the captured logprobs then come from the
  quantized policy that actually sampled, which is the correct π_behavior.

Under LoRA the base projections are FROZEN, so quantization happens once at
trainer construction; under full fine-tuning the trainer re-quantizes after
each update (a jitted elementwise pass, negligible next to the update).

Per-output-channel symmetric scheme: y[o] = Σ_i x[i]·w[i,o] with
w[i,o] ≈ q[i,o]·s[o] gives y ≈ (x @ q)·s — one multiply per output element,
fused into the matmul epilogue.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# the stacked-kernel projections of core/model.py's layer tree
QUANT_PROJS = (
    "q_proj", "k_proj", "v_proj", "o_proj",
    "gate_proj", "up_proj", "down_proj",
)


def quantize_kernel(w: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """[..., in, out] -> (int8 [..., in, out], f32 scale [..., 1, out])."""
    wf = w.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(wf), axis=-2, keepdims=True)
    scale = jnp.maximum(absmax / 127.0, 1e-12)
    q = jnp.clip(jnp.round(wf / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_kernel(q: jnp.ndarray, scale: jnp.ndarray,
                      dtype=jnp.bfloat16) -> jnp.ndarray:
    return (q.astype(jnp.float32) * scale).astype(dtype)


@jax.jit
@jax.named_scope("sync")
def quantize_layers(layers: dict) -> dict:
    """Replace each projection's `kernel` with (`kernel_q`, `kernel_scale`).

    Non-kernel leaves (biases, layernorms) pass through by reference.
    """
    out = {}
    for name, entry in layers.items():
        if isinstance(entry, dict) and name in QUANT_PROJS:
            e = dict(entry)
            q, scale = quantize_kernel(e.pop("kernel"))
            e["kernel_q"] = q
            e["kernel_scale"] = scale
            out[name] = e
        else:
            out[name] = entry
    return out


def rollout_view(params: dict, quant_layers: dict) -> dict:
    """Splice the quantized layer tree into the LIVE param tree: embeddings,
    norms and LoRA adapters stay the caller's (fresh, trainable) arrays —
    only the frozen projection kernels are swapped for int8."""
    return {**params, "layers": quant_layers}
