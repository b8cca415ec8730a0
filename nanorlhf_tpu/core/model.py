"""One decoder as pure functions over stacked-layer pytrees: the Qwen2/Llama
block (GQA + SwiGLU), OLMoE's (QK-norm + a sparse-expert MLP, ops/moe.py),
A.X-K1's (latent attention, core/mla.py; a leading dense stack, then shared
plus routed experts), SmallThinker's and Trinity's (an attention PATTERN:
window layers beside global ones, docs/SWA.md, docs/AFMOE.md) and LFM2's (a
pattern with a kind that is no attention at all: a gated short convolution
whose cache is a STATE of fixed size a row, docs/STATE.md) and SDAR's
(Qwen3-MoE's layer under a BLOCK-causal mask, generated a block at a time:
`block_forward`, docs/BLOCKDIFF.md), chosen at trace
time from the `ModelConfig` and the tree. Every forward is the same few boxes:

    _embed
      -> _run_layers            the ONE function that scans layers
           (a looped model, docs/OURO.md: `loop_passes` times over all of
           the following, the final norm closing each pass)
           for each stack       (`_layer_stacks`: `dense_layers`, `layers`)
             lax.scan over the periods of its pattern (`stack_pattern`;
             a model without a pattern: one kind, a layer a trip)
               _layer_body      one layer of one static kind
                 norm
                 attention      `_attention`: project (`attn.qkv`)
                                / write (`_cache_write`)
                                / dispatch (`attention_form` names the
                                  read's form, `_attention_read` calls it)
                                / out (`attn.out`);
                                or `mla.mla_attention`, `_conv_operator`
                 norm
                 MLP            `_mlp`: SwiGLU or the sparse experts
      -> _logits (`head`)

What differs by KIND OF CACHE (the global layers' pages, the window layers',
the conv layers' state; one group for a model without a pattern) travels as
one record a group, `KindView`: the kind's mask, its decode or verify bounds,
its table with the page size and the step's write plan, the state's rows and
context. `_kind_views` makes a call's tuple of them from what `prefill` /
`decode_step` / `decode_verify` / `_hidden_from_inputs` know, and a layer
takes its kind's (`_kind_group`). The cache itself is groups of stacks in the
scans' carry. How a layer gets its weights (by index into the whole stacks,
or as the scan's xs) is `leaves_in_place`'s rule, and `LayerLeaves` says
which it was.

TPU-first design choices (vs the reference's HF `AutoModelForCausalLM`,
`/root/reference/GRPO/grpo.py:218-224`):

- **Stacked layers + `lax.scan`**: all per-layer weights are stacked along a
  leading [L, ...] axis and the decoder runs one traced layer body L times.
  One compilation regardless of depth; XLA pipelines the scan body.
- **Pure pytrees**: params are a nested dict of jnp arrays — the same tree is
  sharded once over the mesh and shared by rollout, logprob scoring and the
  train step (this kills the reference's CPU↔GPU offload + disk→vLLM handoff,
  `GRPO/grpo_trainer.py:122-166,475-476`).
- **bf16 params, f32 softmax/norms**: matmuls hit the MXU in bf16; softmax,
  RMSNorm statistics and rotary tables run in f32 for stability.
- **GQA without materializing repeated KV**: queries are reshaped to
  [B, KV, G, T, hd] and contracted against unrepeated KV heads.
- **The KV cache is the layer scan's carry**: every cached forward (prefill,
  decode step, speculative verify; contiguous and paged, exact and int8)
  scans over (layer weights, layer index) with the STACKED cache in the
  carry. A layer writes its new tokens into the stack at `(layer, ...)`, an
  update the size of what is new, and reads its slab back from the updated
  stack, so the cache that leaves the scan is the buffer that entered it:
  a decode loop that carries the cache copies nothing (with the cache as
  the scan's xs/ys, XLA copied both stacks whole in every decode step and
  wrote a slab back per layer; PERF.md, PR 26).

The padding-robust entrypoint `padded_forward_logits` reproduces the contract
of the reference's shared `forward()` helper (`GRPO/grpo_trainer.py:90-120`):
mask = (ids != pad), positions = cumsum(mask)-mask, padded ids zeroed.

Weight layout: all projection matrices are stored [in, out] (x @ W), i.e. the
transpose of torch `nn.Linear.weight`; the HF loader transposes on load.
"""

from __future__ import annotations

import contextlib
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from nanorlhf_tpu.core.config import ModelConfig

NEG_INF = -2.0**30  # large-but-finite mask value; -inf breaks softmax rows that are fully masked


# ---------------------------------------------------------------------------
# Parameter construction
# ---------------------------------------------------------------------------

def init_params(config: ModelConfig, key: jax.Array, dtype=jnp.bfloat16) -> dict:
    """Random-init a full parameter tree (tests / from-scratch training)."""
    if config.kv_lora_rank:
        from nanorlhf_tpu.core import mla

        return mla.init_params(config, key, dtype)
    if (config.state_layers or config.num_dense_layers
            or config.n_shared_experts or config.experts_held):
        return _init_stacked_model_params(config, key, dtype)
    hd = config.actual_head_dim
    D, F, V = config.hidden_size, config.intermediate_size, config.vocab_size
    H, KV, L = config.num_attention_heads, config.num_key_value_heads, config.num_hidden_layers
    if config.num_experts:      # (`moe_intermediate_size`, where a model has one)
        F = config.expert_width

    keys = iter(jax.random.split(key, 16))

    def dense(k, shape, scale=None):
        scale = scale if scale is not None else (1.0 / jnp.sqrt(shape[0]))
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(dtype)

    def stacked(k, shape, scale=None):
        return dense(k, (L,) + shape, scale)

    def qkv(k, shape):
        entry = {"kernel": stacked(k, shape)}
        if config.attention_bias:  # Qwen2 yes, Llama no (core/config.py)
            entry["bias"] = jnp.zeros((L, shape[-1]), dtype)
        return entry

    # (built in the order the dense tree always was: the same jitted program)
    embed_tokens = dense(next(keys), (V, D), scale=0.02)
    # the MLP's three kernels are [L, D, F] dense and [L, E, D, F] per expert
    # (docs/MOE.md), drawn from the same three keys
    E = config.num_experts
    mlp = lambda shape: {"kernel": stacked(next(keys), ((E,) if E else ()) + shape)}  # noqa: E731
    layers = {
        "input_layernorm": jnp.ones((L, D), dtype),
        "q_proj": qkv(next(keys), (D, H * hd)),
        "k_proj": qkv(next(keys), (D, KV * hd)),
        "v_proj": qkv(next(keys), (D, KV * hd)),
        "o_proj": {"kernel": stacked(next(keys), (H * hd, D))},
        "post_attention_layernorm": jnp.ones((L, D), dtype),
    }
    mlp_kernels = {"gate_proj": mlp((D, F)), "up_proj": mlp((D, F)),
                   "down_proj": mlp((F, D))}
    if E:
        layers["experts"] = mlp_kernels
    else:
        layers.update(mlp_kernels)
    params = {"embed_tokens": embed_tokens, "layers": layers,
              "norm": jnp.ones((D,), dtype)}
    if not config.tie_word_embeddings:
        params["lm_head"] = dense(next(keys), (D, V), scale=0.02)
    # leaves only OLMoE has draw from keys no dense model has reached
    if E:
        params["layers"]["router"] = {"kernel": stacked(
            next(keys), (D, E), scale=1.0 / jnp.sqrt(D))}
    if config.qk_norm:
        params["layers"]["q_norm"] = jnp.ones((L, H * hd), dtype)
        params["layers"]["k_norm"] = jnp.ones((L, KV * hd), dtype)
    if config.qk_norm_per_head:     # over each head's `hd` (`_attention`)
        params["layers"]["q_norm"] = jnp.ones((L, hd), dtype)
        params["layers"]["k_norm"] = jnp.ones((L, hd), dtype)
    if config.branch_norms:     # (a plain stack with four norms a layer: Ouro)
        params["layers"]["attn_branch_norm"] = jnp.ones((L, D), dtype)
        params["layers"]["mlp_branch_norm"] = jnp.ones((L, D), dtype)
    if config.loop_passes > 1:  # a looped model's exit gate (docs/OURO.md)
        params["early_exit_gate"] = {
            "kernel": dense(next(keys), (D, 1)),
            "bias": jnp.zeros((1,), dtype)}
    return params


# an attention layer's own leaves; in a model with conv layers they are
# stacked over the attention layers of their stack only, as `conv` is over
# its conv layers (`_run_layers`)
_ATTENTION_LEAVES = ("q_proj", "k_proj", "v_proj", "o_proj", "q_norm",
                     "k_norm", "g_proj")


def _init_stacked_model_params(config: ModelConfig, key, dtype) -> dict:
    """The tree of a GQA model with a dense stack before its expert stack, a
    shared expert or a chip's share of the experts (LFM2, docs/STATE.md;
    Trinity, docs/AFMOE.md): `dense_layers` (the leading layers with a dense
    SwiGLU) and `layers` (the expert layers), each with the leaves every
    layer has stacked over its layers (`input_layernorm`, the operator's
    norm; `post_attention_layernorm`, the MLP's; the MLP), the attention
    layers' projections and per-head q/k norms stacked over ITS attention
    layers, and `conv` over its conv layers: `in_proj.kernel [n, D, 3D]`
    (`[b | c | u]`), `conv.kernel [n, K, D]` (the depthwise taps, oldest
    first), `out_proj.kernel [n, D, D]`. The router carries `bias [n, E]`
    (float32) where the selection is bias-corrected. The experts are the
    HELD ones, `[n, held, ...]`, under a router of every expert's width;
    `shared_expert` is one SwiGLU of the shared experts' summed width;
    `g_proj` the attention gate's projection (`config.attention_gate`);
    `attn_branch_norm` / `mlp_branch_norm` the norms on the two branches
    (`config.branch_norms`)."""
    hd = config.actual_head_dim
    D, V = config.hidden_size, config.vocab_size
    H, KV, K = (config.num_attention_heads, config.num_key_value_heads,
                config.conv_L_cache)
    E, dense = config.num_experts, config.num_dense_layers
    keys = iter(jax.random.split(key, 40))

    def normal(shape, scale):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * scale).astype(dtype)

    def stack(start, n, width, experts):
        kinds = config.layer_kinds[start:start + n]
        nc = sum(k == "conv" for k in kinds)
        nl = sum(k == "lightning" for k in kinds)
        nm = sum(k == "mamba" for k in kinds)   # (a mixer alone: no attention)
        na = n - nc - nl - nm
        ns = nm + sum(k == "hybrid" for k in kinds)  # (an attention layer too)
        fan = lambda *shape: normal(shape, 1.0 / jnp.sqrt(shape[-2]))  # noqa: E731
        swiglu = lambda lead, width: {  # noqa: E731
            "gate_proj": {"kernel": fan(*lead, D, width)},
            "up_proj": {"kernel": fan(*lead, D, width)},
            "down_proj": {"kernel": fan(*lead, width, D)}}
        tree = {"input_layernorm": jnp.ones((n, D), dtype),
                "post_attention_layernorm": jnp.ones((n, D), dtype)}
        if config.branch_norms:
            tree["attn_branch_norm"] = jnp.ones((n, D), dtype)
            tree["mlp_branch_norm"] = jnp.ones((n, D), dtype)
        if experts:
            tree["experts"] = swiglu((n, config.num_held_experts), width)
            tree["router"] = {"kernel": fan(n, D, experts)}
            if config.use_expert_bias:
                tree["router"]["bias"] = jnp.zeros((n, experts), jnp.float32)
            if config.n_shared_experts:
                tree["shared_expert"] = swiglu(
                    (n,), config.shared_expert_width)
        else:
            tree.update(swiglu((n,), width))
        if nc:
            tree["conv"] = {
                "in_proj": {"kernel": fan(nc, D, 3 * D)},
                "conv": {"kernel": normal((nc, K, D), 1.0 / jnp.sqrt(K))},
                "out_proj": {"kernel": fan(nc, D, D)}}
        if ns:
            tree["ssm"] = _init_ssm(config, ns, fan, normal, next(keys), dtype)
        if nl:
            from nanorlhf_tpu.core import sala

            tree["lightning"] = sala.init_lightning(config, nl, fan, dtype)
        if na:
            tree.update({
                "q_proj": {"kernel": fan(na, D, H * hd)},
                "k_proj": {"kernel": fan(na, D, KV * hd)},
                "v_proj": {"kernel": fan(na, D, KV * hd)},
                "o_proj": {"kernel": fan(na, H * hd, D)}})
            if config.qk_norm_per_head:
                tree["q_norm"] = jnp.ones((na, hd), dtype)
                tree["k_norm"] = jnp.ones((na, hd), dtype)
            if config.attention_gate:
                tree["g_proj"] = {"kernel": fan(na, D, H * hd)}
        return tree

    params = {"embed_tokens": normal((V, D), 0.02),
              "norm": jnp.ones((D,), dtype)}
    L = config.num_hidden_layers
    if dense:
        params["dense_layers"] = stack(0, dense, config.intermediate_size, 0)
    params["layers"] = stack(dense, L - dense, config.expert_width, E)
    if not config.tie_word_embeddings:
        params["lm_head"] = normal((D, V), 0.02)
    return params


def _init_ssm(config: ModelConfig, n: int, fan, normal, key, dtype) -> dict:
    """The state-space mixers of `n` layers (docs/SSM.md): `in_proj.kernel
    [n, D, 2 I + 2 G N]` (`[z | xs | B | C]`) and `dt_proj.kernel [n, D, H]`
    (the published input projection's last H columns, a leaf of their own:
    2 I + 2 G N is whole 128-lane tiles and with the H columns behind them
    it is not, and the chip's compiler then stored the stack
    contraction-minor and relaid all of it, 473 MB at the published widths,
    at the start of every decode chunk; compiled for a described v5e, PR
    49), `conv.kernel [n, K, I + 2 G N]` (depthwise taps, oldest first) and
    `conv.bias`, `A_log`, `D`, `dt_bias` `[n, H]`, `norm [n, I]`,
    `out_proj.kernel [n, I, D]`.
    `A_log` and `dt_bias` are drawn as Mamba-2 draws them: `A` uniform in
    [1, 16], `softplus(dt_bias)` log-uniform in [1e-3, 1e-1], so a head
    forgets within one token or within a thousand."""
    D, H, K = config.hidden_size, config.ssm_heads, config.ssm_conv
    I, W = config.ssm_inner, config.ssm_conv_width
    k_a, k_dt = jax.random.split(key)
    step = jnp.exp(jax.random.uniform(
        k_dt, (n, H), jnp.float32, jnp.log(1e-3), jnp.log(1e-1)))
    return {
        "in_proj": {"kernel": fan(n, D, I + W)},
        "dt_proj": {"kernel": fan(n, D, H)},
        "conv": {"kernel": normal((n, K, W), 1.0 / jnp.sqrt(K)),
                 "bias": jnp.zeros((n, W), dtype)},
        "A_log": jnp.log(jax.random.uniform(
            k_a, (n, H), jnp.float32, 1.0, 16.0)).astype(dtype),
        "D": jnp.ones((n, H), dtype),
        # (softplus's inverse)
        "dt_bias": (step + jnp.log(-jnp.expm1(-step))).astype(dtype),
        "norm": jnp.ones((n, I), dtype),
        "out_proj": {"kernel": fan(n, I, D)}}


# ---------------------------------------------------------------------------
# Building blocks
# ---------------------------------------------------------------------------

def rms_norm(x: jnp.ndarray, weight: jnp.ndarray, eps: float) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    normed = xf * jax.lax.rsqrt(var + eps)
    return (normed * weight.astype(jnp.float32)).astype(x.dtype)


def rope_tables(positions: jnp.ndarray, head_dim: int, theta: float):
    """cos/sin tables [B, T, hd] for the given absolute positions (f32)."""
    inv_freq = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    angles = positions[..., None].astype(jnp.float32) * inv_freq  # [B, T, hd/2]
    angles = jnp.concatenate([angles, angles], axis=-1)  # HF rotate_half layout
    return jnp.cos(angles), jnp.sin(angles)


def _rope(config: ModelConfig, positions: jnp.ndarray):
    """The model's cos/sin tables: plain RoPE over the head, or MLA's YaRN
    tables over the rotated part of it (core/mla.py)."""
    if config.kv_lora_rank:
        from nanorlhf_tpu.core import mla

        return mla.rope_tables(config, positions)
    return rope_tables(positions, config.actual_head_dim, config.rope_theta)


def apply_rope(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray) -> jnp.ndarray:
    """x: [B, H, T, hd]; cos/sin: [B, T, hd] (HF rotate-half convention)."""
    cos = cos[:, None, :, :]
    sin = sin[:, None, :, :]
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    rotated = jnp.concatenate([-x2, x1], axis=-1)
    xf = x.astype(jnp.float32)
    rf = rotated.astype(jnp.float32)
    return (xf * cos + rf * sin).astype(x.dtype)


# "auto" thresholds of the CONTIGUOUS cache. Neither crossover has a record:
# the sweeps this comment used to cite (pallas-512 flash tying XLA at T=256
# and winning from 512; the decode kernel paying off from 2,048 slots) are
# in no ledger line and no PERF.md entry (ROADMAP S5). What the records do
# back (PERF.md sections 5 and 6): under 2,048 slots XLA's decode attention
# fuses both cache reads into the QK and PV matmuls and streams the slab at
# ~725 GB/s (PR 26, T_max 768). It masks, it does not bound: it reads every
# slot of the extent it is handed, filled or not. `decode_step` hands it the
# whole cache unless its caller names a static `extent`; the one-jit rollout
# does (`decode_read_extents`, PR 33: a multiple of 128 slots that no row's
# write has passed yet), so the crossover S5 is to measure is against a read
# of the filled slots rounded up to a block plus the rows' left pads, not of
# all T_max slots. The PAGED single-token read has no threshold
# (`use_paged_decode_kernel`).
_FLASH_AUTO_MIN_T = 512
_DECODE_AUTO_MIN_T = 2048
# The XLA decode read's extents: whole blocks of this many slots, and at most
# this many extents a loop: each is one more traced and compiled decode step
# (~0.5-1 s of a warm set-up each at Qwen2.5-1.5B and OLMoE widths), and a
# fourth bought nothing on the v5e (PERF.md section 6, PR 33: at 256 + 512
# slots, 384/512/640/768 against 512/640/768 moved a rollout by 0.1-0.3 %,
# because the softmax over 384 slots takes as long as over 768)
_DECODE_READ_BLOCK = 128
_DECODE_READ_EXTENTS = 3


def use_flash(impl: str, seq_len: int) -> bool:
    """Resolve the train/prefill self-attention impl for a padded length."""
    if impl == "pallas":
        return True
    return (impl == "auto" and seq_len >= _FLASH_AUTO_MIN_T
            and jax.default_backend() == "tpu")


def use_decode_kernel(impl: str, cache_len: int) -> bool:
    """Resolve the single-token decode-attention impl for a cache size."""
    if impl == "pallas":
        return True
    return (impl == "auto" and cache_len >= _DECODE_AUTO_MIN_T
            and jax.default_backend() == "tpu")


def decode_read_extents(config: ModelConfig, first_slot: int, last_slot: int,
                        cache_len: int) -> tuple:
    """Static extents for `decode_step` over a decode loop whose write slot
    runs from `first_slot` up to `last_slot`, every row's at once, in a
    contiguous cache of `cache_len` slots: ascending, the last one
    `cache_len`, the others multiples of `_DECODE_READ_BLOCK` that the write
    slot reaches, thinned evenly to `_DECODE_READ_EXTENTS` in all. A step
    may read the first extent that lies above its write slot: no row holds
    a key at or beyond it. `(cache_len,)`, today's one program, wherever
    the read does not go by the mask's width: the Pallas read bounds itself
    by `[start, filled)`, the int8 cache and the latent cache have reads of
    their own."""
    if (config.kv_lora_rank or config.kv_cache_quant == "int8"
            or use_decode_kernel(config.attention_impl, cache_len)):
        return (cache_len,)
    block, most = _DECODE_READ_BLOCK, _DECODE_READ_EXTENTS
    cuts = [*range((first_slot // block + 1) * block, last_slot + 1, block),
            cache_len]
    return tuple(sorted({cuts[-(-len(cuts) * i // most) - 1]
                         for i in range(1, most + 1)}))


def use_q8_decode_kernel(impl: str) -> bool:
    """int8-cache decode routing. Unlike the exact case there is no length
    threshold: the only alternative is the dequantize-everything fallback,
    which re-materializes the full cache per layer per step and is strictly
    worse than both the q8 kernel and the unquantized path — so on TPU every
    non-"xla" impl takes the kernel at any cache length ("xla" stays the
    operator escape hatch; "pallas" also exercises it in interpret mode)."""
    return impl == "pallas" or (impl != "xla" and jax.default_backend() == "tpu")


def use_expert_kernel(config: ModelConfig) -> bool:
    """Resolve the sparse-expert MLP's grouped matmul (ops/moe.py): the
    megablox Pallas kernel for `"pallas"`, and for `"auto"` on a TPU; the
    plain `ragged_dot` for `"xla"`, off the TPU, and under a multi-device
    mesh, where GSPMD partitions XLA's own op and refuses a Mosaic kernel
    (as `trainer.fused_logprob_impl` does for the fused logprob)."""
    if config.spmd_mesh is not None:
        return False
    impl = config.attention_impl
    return impl == "pallas" or (impl == "auto"
                                and jax.default_backend() == "tpu")


def use_paged_decode_kernel(config: ModelConfig) -> bool:
    """Resolve the PAGED single-token decode read: the kernel that reads the
    rows' pages from the stacked pool in place
    (ops/decode_attention.paged_decode_attention), under `use_expert_kernel`'s
    rule and for its reason (a Mosaic kernel, which GSPMD will not
    partition), at every cache width: the alternative, `_paged_view`, gathers
    and transposes every row's every page per layer per step whatever is
    live (36 % of the serving cell's device time, PERF.md PR 28), so there
    is no width at which it wins. `"xla"`, off the TPU and under a mesh the
    gathered view stays: the plain form, and the kernel's oracle. The int8
    cache has its own kernel (`use_q8_decode_kernel`). An MLA model reads
    its latent pools through XLA on the gathered view: the kernel is a GQA
    read of equal-width K and V pages and does not compute the absorbed
    form (handed the one-array pool as K and as V it refused the 576-wide
    page: "Slice shape along dimension 4 must be aligned to tiling (128)",
    compiled for a v5e, PR 31). A pattern model's T > 1 paged read (a
    prefill piece, a suffix forward) goes by the same rule: the flash kernel
    over the pages in place (ops/paged_prefill_attention) where this is
    true, `_attend_paged_blocks` where it is not (`attention_form`)."""
    if config.kv_lora_rank:
        return False
    return config.kv_cache_quant != "int8" and use_expert_kernel(config)


def decode_loop_page_size(config: ModelConfig) -> int:
    """The page size the one-jit rollout gives its OWN KV cache
    (`sampler.generate_tokens` where its caller names none): pages of one
    read block (`_DECODE_READ_BLOCK` slots) under the dense identity table
    wherever a decode step then reads each row's own slots `[start, filled)`
    in place (`attention_form` -> `"paged_decode"`), 0 = the contiguous cache
    and its static extents (`decode_read_extents`) wherever it does not. The
    two layouts of that private loop carry hold the same values and emit the
    same tokens, so the choice is the program's, from what it can observe:

    - `use_paged_decode_kernel`: the backend behind `attention_impl` (a TPU
      under `"auto"`, or `"pallas"`), no mesh, a (k, v) cache that is not
      int8 and not latent: elsewhere a paged read is the gathered view, which
      moves every page of every row and loses to the extents;
    - one kind of layer: what the monolithic paged loop runs
      (`sampler.compose_check`: no window pattern, no state, no blocks);
    - pages of whole 128-lane rows, the kernels' own geometry
      (`_paged_row_kernel_takes`; the read's "`hd` must be a multiple of 128
      when compiled"): Qwen2.5-0.5B's heads of 64 stay contiguous.

    On the v5e the in-place read wins at every step of a 512-token response
    of 64 rows at both measured geometries (`tools/bench_paged_read.py
    rollout.*`, PERF.md section 6, PR 52; µs a layer, in place against
    XLA's masked read of the 512 / 640 / 768-slot extent that step reads):
    Qwen2.5-1.5B's 2 KV heads 46 / 72 / 83 against 64 / 75 / 86, OLMoE's 16
    heads 254 / 431 / 520 against 372 / 455 / 545; in their cells the read
    fell 1.92 -> 1.67 and 1.83 -> 1.53 ms a step. So the rule has no term
    for the pool's geometry: none was found at which the extents win."""
    KV, hd = _cache_heads(config)
    # (a page of 128 slots is whole tiles of any cache type's sublanes:
    # bfloat16 stands for whichever the parameters bring)
    leaf = jax.ShapeDtypeStruct((1, 1, KV, _DECODE_READ_BLOCK, hd),
                                jnp.bfloat16)
    one_kind = (config.attention_pattern is None and not config.state_layers
                and not config.block_generation)
    return _DECODE_READ_BLOCK if (
        one_kind and use_paged_decode_kernel(config)
        and _paged_row_kernel_takes((leaf, leaf), _DECODE_READ_BLOCK)) else 0


def _kernel_spmd(config: ModelConfig, H: int, KV: int):
    """(mesh, batch_axes, head_axis|None) for wrapping a Pallas kernel in
    shard_map, or None when no multi-device hint applies (single device, or
    nothing in the config's axes actually spans >1 device)."""
    mesh = config.spmd_mesh
    if mesh is None:
        return None
    batch = tuple(
        a for a in config.spmd_batch_axes if mesh.shape.get(a, 1) > 1
    )
    head = config.spmd_head_axis
    hsz = mesh.shape.get(head, 1) if head else 1
    if hsz <= 1 or H % hsz or KV % hsz:
        head = None  # uneven heads: replicate them (still fixes the batch)
    if not batch and head is None:
        return None
    return mesh, (batch or None), head


def _spmd_call(spmd, fn, args, head_dims):
    """Run `fn(*args)` under shard_map: batch dim 0 sharded over the batch
    axes, the head dim (per-arg index in `head_dims`, None = no head dim)
    over the head axis. Output shards like the first argument. Without this
    GSPMD must treat the inner pallas_call as an opaque custom call and
    all-gathers every operand (see ModelConfig.spmd_mesh)."""
    from jax.sharding import PartitionSpec as P

    mesh, batch, head = spmd

    def spec(x, hdim):
        s = [None] * x.ndim
        s[0] = batch
        if head is not None and hdim is not None:
            s[hdim] = head
        return P(*s)

    in_specs = tuple(spec(x, h) for x, h in zip(args, head_dims))
    out_specs = spec(args[0], head_dims[0])
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)(*args)


def _flash_attention(q, k, v, mask, spmd=None):
    """The flash kernel (ops/attention.py) over the tokens at hand, q
    [B, H, T, hd] against k, v [B, KV, T, hd], for a `mask` [B, 1, T, T]
    that factors as causal(T, T) & key_valid[B, T]: the kernel rebuilds the
    causal part and keeps only the key-validity row (`attention_form` names
    this form where that holds). `spmd` (from `_kernel_spmd`) shard_maps the
    kernel so a sharded batch stays sharded."""
    from nanorlhf_tpu.ops.attention import flash_attention

    # key-validity = the mask's last query row (causal there is all-True)
    key_valid = mask[:, 0, -1, :]
    if spmd is not None:
        return _spmd_call(
            spmd, lambda q, k, v, kv: flash_attention(q, k, v, kv, causal=True),
            (q, k, v, key_valid), (1, 1, 1, None),
        )
    return flash_attention(q, k, v, key_valid, causal=True)


def gqa_attention(
    q: jnp.ndarray,       # [B, H, Tq, hd]
    k: jnp.ndarray,       # [B, KV, Tk, hd]
    v: jnp.ndarray,       # [B, KV, Tk, hd]
    mask: jnp.ndarray,    # [B, 1, Tq, Tk] bool, True = attend
) -> jnp.ndarray:
    """Masked attention in XLA, any mask, without materializing repeated
    KV: the plain form of every read."""
    B, H, Tq, hd = q.shape
    KV = k.shape[1]
    G = H // KV
    qg = q.reshape(B, KV, G, Tq, hd)
    scores = jnp.einsum("bkgqh,bkth->bkgqt", qg, k).astype(jnp.float32)
    scores = scores / jnp.sqrt(jnp.float32(hd))
    scores = jnp.where(mask[:, :, None, :, :], scores, NEG_INF)  # [B,1,1,Tq,Tk] broadcast
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgqt,bkth->bkgqh", probs, v)
    return out.reshape(B, H, Tq, hd)


# ---------------------------------------------------------------------------
# Layer body (scanned)
# ---------------------------------------------------------------------------

def _proj(h, layer_params, lora_layer, name, lora_scale):
    """x @ W (+ bias) (+ LoRA (x@A)@B · scale) — LoRA applied in-graph so
    sampling/scoring/training all see fresh adapter weights (core/lora.py).

    Weight-only int8 form (`kernel_q` + per-output-channel `kernel_scale`,
    core/quant.py): the upcast feeds the matmul directly (int8 stays the HBM
    resident form) and the scale folds into the epilogue."""
    p = layer_params[name]
    if "kernel_q" in p:
        y = h @ p["kernel_q"].astype(h.dtype)
        y = (y.astype(jnp.float32) * p["kernel_scale"]).astype(h.dtype)
    else:
        y = h @ p["kernel"]
    if "bias" in p:
        y = y + p["bias"]
    if lora_layer is not None and name in lora_layer:
        ab = lora_layer[name]
        y = y + ((h @ ab["a"]) @ ab["b"]) * lora_scale
    return y


def _cache_update(stack, new, layer, idx):
    """Write `new` [B, KV, T, hd] into the STACKED cache [L, B, KV, T_max,
    hd] at `(layer, :, :, idx, :)`, in place: the update operand is the size
    of what is new, never a layer slab. A scalar `idx` is the shared-slot
    decode/prefill path; a per-row [B] `idx` (speculative verify — accepted
    rows advance at different rates) vmaps the update over the batch axis of
    the stack.

    On the TPU the single-token write of the decode loop goes through a view
    that splits the sequence axis into (T_max / rows, rows), `rows` being the
    sublanes one tile of this dtype holds: a bitcast in the tiled layout, the
    same bytes written. Written against the 5-D shape, the v5e compiler
    moves the V stack's sequence axis outside the batch (a layout that suits
    a one-token write) and then pays a slab slice and a relayout of V in
    every layer of every step (9.99 ms a step against 8.17, PERF.md PR 26);
    with the view both stacks keep their layout and both reads fuse into the
    attention matmuls. XLA:CPU instead copies the stack around the reshape,
    so elsewhere the write stays 5-D."""
    if getattr(idx, "ndim", 0) == 1:
        return jax.vmap(
            lambda c, n, i: jax.lax.dynamic_update_slice(
                c, n[None], (layer, 0, i, 0)),
            in_axes=(1, 0, 0), out_axes=1,
        )(stack, new, idx)
    L, B, KV, T_max, hd = stack.shape
    rows = 32 // stack.dtype.itemsize
    if (new.shape[2] == 1 and T_max % rows == 0
            and jax.default_backend() == "tpu"):
        tiled = jax.lax.dynamic_update_slice(
            stack.reshape(L, B, KV, T_max // rows, rows, hd),
            new[None, :, :, :, None, :],
            (layer, 0, 0, idx // rows, idx % rows, 0))
        return tiled.reshape(stack.shape)
    return jax.lax.dynamic_update_slice(stack, new[None], (layer, 0, 0, idx, 0))


def _scale_update(stack, new, layer, idx):
    """Same for the int8 cache's sublane-expanded scales [L, B, KV, 8, T_max]
    (sequence on the LAST axis)."""
    if getattr(idx, "ndim", 0) == 1:
        return jax.vmap(
            lambda c, n, i: jax.lax.dynamic_update_slice(
                c, n[None], (layer, 0, 0, i)),
            in_axes=(1, 0, 0), out_axes=1,
        )(stack, new, idx)
    return jax.lax.dynamic_update_slice(stack, new[None], (layer, 0, 0, 0, idx))


# --------------------------------------------------------------------------- #
# paged KV cache (ISSUE 10): a global page pool + per-row block table replaces
# the per-row [T_max] slab — see docs/PAGED_CACHE.md and sampler/paged/
# --------------------------------------------------------------------------- #

def _paged_slots(cache_index, B, T):
    """Logical cache slots [B, T] for a write of T tokens starting at
    `cache_index` (scalar shared slot, or per-row [B] — speculative verify
    and the continuous-batching scheduler advance rows at different rates)."""
    idx = jnp.asarray(cache_index, jnp.int32)
    if idx.ndim == 0:
        idx = jnp.broadcast_to(idx, (B,))
    return idx[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]


def _paged_pages(pool, table, slots, page_size):
    """Resolve logical slots [B, T] to (physical page, offset) pairs of the
    stacked pool [L, num_pages, ...].
    Out-of-table slots and sentinel table entries both map to page
    `num_pages`, which `mode="drop"` scatters discard — a row past its page
    budget (or with released pages) can never corrupt a live page."""
    num_pages, nb = pool.shape[1], table.shape[1]
    lb = slots // page_size
    page = jnp.where(
        lb < nb,
        jnp.take_along_axis(table, jnp.clip(lb, 0, nb - 1), axis=1),
        num_pages,
    )
    return page, slots % page_size


def _paged_row_scatter(pool, new, layer, table, cache_index, page_size):
    """The plain form of the paged write, and the oracle of the other two:
    one scatter of `B x T x KV` rows of `[hd]`, each to its `(page, head,
    offset)`. The TPU's compiler runs it over a `[rows, hd]` view of the
    whole leaf, a row at a time (~100-240 ns each whatever a row holds:
    PERF.md section 6, PR 41)."""
    B, KV, T, hd = new.shape
    page, off = _paged_pages(pool, table, _paged_slots(cache_index, B, T),
                             page_size)
    heads = jnp.arange(KV, dtype=jnp.int32)[None, None, :]
    return pool.at[layer, page[:, :, None], heads, off[:, :, None], :].set(
        new.transpose(0, 2, 1, 3), mode="drop")


# Rows of `hd` (tokens x KV heads) from which a write SHORTER than a page
# goes by page all the same: two pages read and written back for those rows
# spared. One row's write into a 1.5 GB leaf on a v5e, a K and V pair a
# layer, row scatter / by page in microseconds (my chip run, PR 41,
# `tools/bench_paged_write.py`, `chiprun_out/w1`): KV 2: T 2 6.9 / 10.7, T 8
# 8.4 / 10.5, T 16 11.8 / 10.8, T 32 18.3 / 10.1, T 64 34.3 / 10.2, T 128
# 54.8 / 10.7; KV 4: T 16 17.4 / 11.6, T 64 50.7 / 12.0; one head of 512: T
# 64 20.6 / 12.2. The page write is flat at 10-12 us under a page of tokens
# and the scatter ~0.25 us a row over ~6: they cross at about 32 rows.
_PAGE_WRITE_MIN_ROWS = 32


def _touched_blocks(T: int, page_size: int) -> int:
    """Logical blocks that T consecutive slots can span, from any start."""
    return (T + page_size - 2) // page_size + 1


def _paged_page_write(pool, new, layer, table, cache_index, page_size):
    """The paged write by PAGE: a row's T slots are consecutive from its
    `cache_index`, so they lie in `n = _touched_blocks(T, P)` logical blocks,
    the first and the last in part (a served prompt is left-padded: its first
    piece starts at slot `Tp - len`, not at a page's first). Those n pages of
    the layer are read `[B, n, KV, P, hd]`, `new` is laid over them shifted by
    `cache_index % P` (ONE dynamic slice of `new` padded at both ends: the
    slots are consecutive), slots that are not among the T keep what the page
    held, and the pages go back in one scatter of `B x n` windows `[KV, P,
    hd]`, which the pool stores contiguously. What the row scatter dropped is
    dropped: a block past the table or a sentinel entry resolves to page
    `num_pages` (`_paged_pages`), block by block as ever.

    PRECONDITION: the n pages a row touches in one call are distinct, and
    its own. The global table's are by allocation; a window ring's because
    `pages.ring_blocks` sizes it `window + longest write` slots and two
    pages more (tests/test_paged_cache_write.py pins that); a page shared
    through the radix tree is never written (`radix.copy_page` gives a
    straddling row its own)."""
    B, KV, T, hd = new.shape
    P, n = page_size, _touched_blocks(T, page_size)
    first = jnp.asarray(cache_index, jnp.int32)
    # the first slot of each touched block, [B, n], resolved as any slot is
    page, _ = _paged_pages(
        pool, table, _paged_slots(first // P * P, B, 1)
        + P * jnp.arange(n, dtype=jnp.int32)[None], P)
    old = pool[layer, jnp.minimum(page, pool.shape[1] - 1)]  # [B, n, KV, P, hd]
    # slot j of the n pages is token j - shift: `new` with P slots before it
    # and n P - T after, read from P - shift on
    padded = jnp.pad(new, ((0, 0), (0, 0), (P, n * P - T), (0, 0)))
    window = lambda a, s: jax.lax.dynamic_slice_in_dim(      # noqa: E731
        a, P - s, n * P, axis=-2)
    shift = first % P
    laid = (window(padded, shift) if first.ndim == 0
            else jax.vmap(window)(padded, shift))           # [B, KV, n P, hd]
    j = jnp.arange(n * P, dtype=jnp.int32)[None] - jnp.broadcast_to(
        shift, (B,))[:, None]
    mine = ((j >= 0) & (j < T)).reshape(B, n, 1, P, 1)
    laid = laid.reshape(B, KV, n, P, hd).transpose(0, 2, 1, 3, 4)
    return pool.at[layer, page].set(jnp.where(mine, laid, old), mode="drop")


# `KindView.write_plan` of a decode step whose table is the dense identity
# table and whose rows all stand at one slot (the one-jit rollout's)
IDENTITY_SLOT = "identity_slot"


def _identity_slot_write(pool, new, layer, slot):
    """A decode step's write under the dense identity table
    (`pages.full_table`: row r's block j is page `r nb + j`) with every row
    at the one scalar `slot`, as the one-jit rollout's are: every row writes
    the same `(block, offset)`, so the write is ONE `dynamic_update_slice`
    through the `[L, B, nb, KV, P, hd]` view of the pool, the contiguous
    cache's write (`_cache_update`) and its bytes, where the live-row kernel
    moves a row's whole tile group there and back (64 live rows: PERF.md
    section 6, PR 52). The page splits into tiles of sublanes as the
    contiguous cache's sequence does, for `_cache_update`'s reason. `new`
    [B, KV, 1, hd]; bit-identical to `_paged_row_scatter`."""
    L, N, KV, P, hd = pool.shape
    B = new.shape[0]
    sub = 32 // pool.dtype.itemsize
    tiles = (P // sub, sub) if P % sub == 0 else (1, P)
    off = slot % P
    view = jax.lax.dynamic_update_slice(
        pool.reshape(L, B, N // B, KV, *tiles, hd),
        new[None, :, None, :, :, None, :],
        (layer, 0, slot // P, 0, off // tiles[1], off % tiles[1], 0))
    return view.reshape(pool.shape)


def _page_write_takes(pool, T: int, page_size: int, table_blocks: int) -> bool:
    """Whether a write of T tokens into `pool` goes by page
    (`_paged_cache_update`)."""
    return ((T >= page_size or T * pool.shape[2] >= _PAGE_WRITE_MIN_ROWS)
            and pool.dtype != jnp.int8
            and _touched_blocks(T, page_size) <= table_blocks)


def _paged_cache_update(pool, new, layer, table, cache_index, page_size):
    """Write `new` [B, KV, T, hd] through the block table into layer `layer`
    of the stacked page pool [L, num_pages, KV, page_size, hd], in place, in
    the unit the pool stores contiguously where that is cheaper than a row
    of `hd` at a time, chosen by what this call can see (docs/PAGED_CACHE.md
    "The write"):

    - a page of tokens or more (a prefill piece, a long suffix bucket), or
      `_PAGE_WRITE_MIN_ROWS` rows of `hd` (a short bucket): by page
      (`_paged_page_write`), where the table is wide enough to hold the
      pages of one write apart;
    - everything else, and the int8 pool (whose scales go row by row beside
      it, `_paged_scale_update`): `_paged_row_scatter`.

    Every form leaves the pool bit-identical to the row scatter's."""
    if _page_write_takes(pool, new.shape[2], page_size, table.shape[1]):
        return _paged_page_write(pool, new, layer, table, cache_index,
                                 page_size)
    return _paged_row_scatter(pool, new, layer, table, cache_index, page_size)


def _paged_scale_update(pool, new, layer, table, cache_index, page_size):
    """Same for the int8 scale pool [L, num_pages, KV, 8, page_size]
    (offset on the LAST axis); `new` is [B, KV, 8, T]."""
    B, KV, e, T = new.shape
    page, off = _paged_pages(pool, table, _paged_slots(cache_index, B, T),
                             page_size)
    heads = jnp.arange(KV, dtype=jnp.int32)[None, None, :, None]
    eight = jnp.arange(e, dtype=jnp.int32)[None, None, None, :]
    return pool.at[layer, page[:, :, None, None], heads, eight,
                   off[:, :, None, None]].set(
        new.transpose(0, 3, 1, 2), mode="drop")


def _cache_write(stacks, news, layer, view):
    """Write one layer's new tokens into the stacked cache arrays, each at
    `(layer, ...)`, from slot `view.index` on: `stacks`/`news` are (k, v)
    exact, or (k_q, k_s, v_q, v_s) int8, whose odd members are scale arrays
    (sequence on the last axis). A `view` with a table routes the write
    through it; one with a `write_plan` is a decode step's on a TPU
    (`_kind_views`): a plan a new slot of the row (one, or a block
    forward's `block_length`), the live rows' slot i through
    ops/paged_cache_write, K and V in one call, each in turn; under the
    identity table the plan is `IDENTITY_SLOT`, one slice a leaf
    (`_identity_slot_write`). Returns the updated stacks. Under the scope
    `attn.write`."""
    out = []
    with jax.named_scope("attn.write"):
        if view.write_plan is IDENTITY_SLOT:
            return tuple(
                _identity_slot_write(stack, new, layer, view.index)
                for stack, new in zip(stacks, news))
        if view.write_plan is not None:
            from nanorlhf_tpu.ops.paged_cache_write import paged_row_write

            for i, plan in enumerate(view.write_plan):
                stacks = tuple(paged_row_write(
                    *stacks, news[0][:, :, i], news[1][:, :, i], layer, plan))
            return stacks
        for i, (stack, new) in enumerate(zip(stacks, news)):
            is_scale = view.cache == "int8" and i % 2 == 1
            if view.table is not None:
                update = (_paged_scale_update if is_scale
                          else _paged_cache_update)
                out.append(update(stack, new, layer, view.table, view.index,
                                  view.page_size))
            else:
                update = _scale_update if is_scale else _cache_update
                out.append(update(stack, new, layer, view.index))
    return tuple(out)


def _paged_row_kernel_takes(stacks, page_size: int) -> bool:
    """Whether ops/paged_cache_write takes a decode step's write into these
    stacks: a (k, v) pool of whole 128-lane rows and whole tiles a page (not
    the int8 pool, not a page of 4 or 8 slots)."""
    from nanorlhf_tpu.ops.paged_cache_write import sublanes

    return (stacks[0].dtype == stacks[1].dtype
            and stacks[0].dtype != jnp.int8
            and stacks[0].shape[-1] % 128 == 0
            and page_size % sublanes(stacks[0].dtype) == 0)


def paged_write_forms(config: ModelConfig, caches, page_size: int,
                      longest_write: int, table_blocks: int) -> tuple:
    """`(by page, live rows)`, 0 or 1 each: whether a forward of
    `longest_write` tokens (a prefill piece, a whole prompt) writes the paged
    cache `caches` by page, and whether a decode step's write is the
    live-row kernel: what `_paged_cache_update` and `_kind_views` decide
    from the same shapes when the programs are traced
    (`DecodeSession.kv_write_by_page`, `kv_write_live_rows`)."""
    group = caches[0] if config.attention_pattern is not None else caches
    return (int(_page_write_takes(group[0], longest_write, page_size,
                                  table_blocks)),
            int(use_paged_decode_kernel(config)
                and _paged_row_kernel_takes(group, page_size)))


def _layer_slab(stack, layer, width=None):
    """Layer `layer`'s slab of a stacked cache array: the one read a layer
    makes of the stack, after its write. `width`: its first `width` slots
    only, as ONE dynamic slice of the stack (a static slice of the whole
    slab is not folded into it: the v5e compiler then sets the slab down
    in memory first, compiled for a described v5e, PR 33)."""
    if width is None:
        return jax.lax.dynamic_index_in_dim(stack, layer, 0, keepdims=False)
    _, B, KV, _, hd = stack.shape
    return jax.lax.dynamic_slice(
        stack, (layer, 0, 0, 0, 0), (1, B, KV, width, hd))[0]


def _paged_view(pool, layer, table, width):
    """Gather a row-contiguous [B, KV, width, hd] view of layer `layer` from
    the stacked pool [L, num_pages, KV, P, hd] — the plain form of the paged
    read (`"xla"`, off the TPU, under a mesh; the in-place kernel's oracle)
    and the T > 1 reads' under the kernel threshold. It moves every table
    entry of every row, whatever is live, so the TPU's decode step does not
    take it (`use_paged_decode_kernel`). The gather reads the rows' pages
    straight from the stack: no layer slab is sliced out first. Sentinel
    entries clamp to page num_pages-1; the garbage they surface sits in slots
    the attention mask already excludes, and NEG_INF masking zeroes its
    contribution exactly, so this view is bit-identical to the contiguous
    cache under the same mask."""
    num_pages = pool.shape[1]
    g = pool[layer, jnp.minimum(table, num_pages - 1)]   # [B, nb, KV, P, hd]
    B, nb, KV, P, hd = g.shape
    return g.transpose(0, 2, 1, 3, 4).reshape(B, KV, nb * P, hd)[:, :, :width, :]


def _paged_scale_view(pool, layer, table, width):
    """[L, num_pages, KV, 8, P] scale pool → [B, KV, 8, width] view."""
    num_pages = pool.shape[1]
    g = pool[layer, jnp.minimum(table, num_pages - 1)]   # [B, nb, KV, 8, P]
    B, nb, KV, e, P = g.shape
    return g.transpose(0, 2, 3, 1, 4).reshape(B, KV, e, nb * P)[..., :width]


# A chip's share dispatches N x k assignment rows of which it computes its
# experts' part (held / E of them): past this many tokens the layer goes in
# blocks, so a 4k-token scoring row at A.X-K1's widths holds 0.12 GB of
# dispatched rows at a time instead of 0.5 GB in, 0.5 GB out and a float32
# [N, k, D] beside a served model's weights and pool. A model that holds
# every expert says for itself where its blocks end
# (`ModelConfig.expert_token_block`).
_SHARE_TOKEN_BLOCK = 1024


def _in_token_blocks(fn, h, block: int, *more):
    """`fn(x [n, D]) -> (y [n, D], moe aux)` over `h`'s tokens in equal
    blocks of at most `block` (the layer is per token, so this is the same
    layer); zero rows pad the last block and are cut off again. `more`:
    further per-token inputs `fn` takes after `x`, blocked alike."""
    lead, D = h.shape[:-1], h.shape[-1]
    N = h.size // D
    n = -(-N // block)
    size = -(-N // n)
    blocks = lambda a: jnp.pad(     # noqa: E731
        a.reshape(N, -1), ((0, n * size - N), (0, 0))).reshape(n, size, -1)
    x = blocks(h)
    if more:
        out, aux = jax.lax.map(lambda xs: fn(*xs),
                               (x,) + tuple(blocks(a) for a in more))
    else:
        out, aux = jax.lax.map(fn, x)
    cut = lambda a: a.reshape((n * size,) + a.shape[2:])[:N]  # noqa: E731
    experts = cut(aux["experts"])
    return cut(out).reshape(h.shape), {
        "experts": experts.reshape(lead + experts.shape[1:]),
        "entropy": cut(aux["entropy"]).reshape(lead),
        "dropped": jnp.sum(aux["dropped"]),
        # of the real tokens: the padding rows' assignments do not count
        **({"absent": jnp.sum(cut(aux["absent_by_token"]))}
           if "absent" in aux else {}),
        **({"bias_changed": cut(aux["bias_changed"]).reshape(lead)}
           if "bias_changed" in aux else {}),
    }


def _times(x, multiplier: float):
    """`x * multiplier` in x's type (a muP multiplier of the published
    config, core/config.py); 1.0 stages no operation."""
    return x if multiplier == 1.0 else x * jnp.asarray(multiplier, x.dtype)


def _swiglu(h, layer_params, lora_layer, lora_scale, multipliers=(1.0, 1.0)):
    """`multipliers`: on the gate before its SiLU, on the down projection's
    result (Falcon-H1's `mlp_multipliers`)."""
    gate = _times(_proj(h, layer_params, lora_layer, "gate_proj", lora_scale),
                  multipliers[0])
    up = _proj(h, layer_params, lora_layer, "up_proj", lora_scale)
    return _times(_proj(
        jax.nn.silu(gate.astype(jnp.float32)).astype(h.dtype) * up,
        layer_params, lora_layer, "down_proj", lora_scale,
    ), multipliers[1])


def _mlp(config: ModelConfig, h, layer_params, lora_layer, lora_scale,
         expert_stack=None, layer=None, live=None, router_h=None):
    """The layer's MLP on normed hidden states, chosen at trace time from
    the layer's own tree: the sparse-expert MLP of ops/moe.py where the layer
    has a router (its router and experts carry no adapter, core/lora.py),
    else the dense SwiGLU (every layer of a dense model; A.X-K1's leading
    stack). Per token, so every caller (uncached, contiguous, paged,
    int8-KV, verify, ring attention, shared prefill) goes through it
    untouched. `expert_stack` is the experts' subtree of EVERY layer of the
    stack, addressed in place at `layer` instead of sliced (`_expert_xs`;
    ops/moe.py says why). `live` [B] marks the decode step's rows someone
    listens to (an expert layer dispatches those only: `moe_mlp`). Returns
    `(out, aux)`; `aux` is the router's per-token record (`moe_mlp`), None
    for the dense layer. `router_h`: what the router reads where that is not
    `h` (SmallThinker: the pre-attention normed state)."""
    if "router" in layer_params:
        from nanorlhf_tpu.ops.moe import moe_mlp

        experts = expert_stack or layer_params["experts"]
        routing = {}    # OLMoE's uncached and prefill programs pass none
        # (a decode step's one token a row, or a block forward's block)
        listening = live is not None and (
            h.shape[1] == 1 or config.block_generation)
        if config.experts_held or listening:
            # a chip's share of the routed experts; a decode step that
            # knows its listeners holds "all of them" the same way
            routing["held"] = (config.num_held_experts, config.experts_offset)
        if listening:   # the rows without a request are not dispatched
            routing["live"] = jnp.broadcast_to(live[:, None], h.shape[:2])
        if config.expert_activation != "silu":
            routing["activation"] = config.expert_activation
        if config.scoring_func != "softmax" or config.routed_scaling_factor != 1.0:
            routing.update(scoring=config.scoring_func,
                           routed_scale=config.routed_scaling_factor)
        if config.use_expert_bias:      # selects with it, weighs without
            routing.update(select_bias=layer_params["router"]["bias"],
                           norm_eps=config.route_norm_eps)
        def routed(x, router_h=None):
            return moe_mlp(
                x, layer_params["router"]["kernel"],
                experts["gate_proj"]["kernel"], experts["up_proj"]["kernel"],
                experts["down_proj"]["kernel"], config.num_experts_per_tok,
                config.norm_topk_prob,
                layer=layer if expert_stack is not None else None,
                kernel=use_expert_kernel(config), router_h=router_h, **routing)

        more = () if router_h is None else (router_h,)
        block = config.expert_token_block or (
            _SHARE_TOKEN_BLOCK if config.experts_held else 0)
        if block and h.size // h.shape[-1] > block:
            out, aux = _in_token_blocks(routed, h, block, *more)
        else:
            out, aux = routed(h, *more)
        if "shared_expert" in layer_params:
            with jax.named_scope("moe.shared"):
                out = out + _swiglu(h, layer_params["shared_expert"], None,
                                    lora_scale)
        return out, aux
    return _swiglu(h, layer_params, lora_layer, lora_scale,
                   config.mlp_multipliers), None


class KindView(NamedTuple):
    """What ONE cache group of ONE call hands the layers of its kind
    (`_kind_views` makes a call's tuple of them, one a group): everything
    that differs by kind of cache, and the call's slot and listeners beside
    it. A model without a pattern has one group."""
    mask: jnp.ndarray           # [B, 1, Tq, Tk] bool, True = attend: the
                                # kind's own (a window layer's holds its window)
    cache: str | None = None    # what the group's cache is: "exact" (k, v),
                                # "int8" (k_q, k_s, v_q, v_s), "latent"
                                # (core/mla.py), "state" (`_conv_operator`);
                                # None: the call has no cache
    index: object = 0           # the cache slot of the call's first token:
                                # scalar, or per-row [B]
    decode: object = None       # a decode step's `(start, filled)` [B] each,
                                # or its `PagedDecodePlan` (the in-place read)
    verify: tuple | None = None  # `(first, fill)` [B] each: T candidate or
                                # chunk tokens a row from its slot `fill` on
    table: object = None        # block table [B, nb] int32 (paged), or the
                                # state group's rows [B, 1]
    page_size: int = 0
    write_plan: object = None   # a decode step's `PagedWritePlan`s on a TPU,
                                # or `IDENTITY_SLOT`
    conv_ctx: tuple | None = None   # the state group's `(valid, fresh)`
                                # (a sparse layer's group: `valid` beside
                                # its span)
    live: object = None         # [B] bool: the rows someone listens to
    span: tuple | None = None   # a sparse layer's `(start, keys)` [B] each:
                                # the slot of the row's position 0 and the
                                # keys of the call its tokens belong to
                                # (core/sala.py)


class LayerLeaves(NamedTuple):
    """One layer's weights as `_run_layers` hands them to `_layer_body`."""
    tree: dict                  # the layer's own leaves
    lora: dict | None           # its adapters' (core/lora.py)
    experts: dict | None        # the expert kernels of EVERY layer of its
                                # stack, addressed in place (`_expert_xs`)
    at: object                  # its place in its stack: its experts' index
    in_place: bool = False      # provenance: `tree`'s leaves are size-one
                                # slices of the whole stacks at a traced
                                # index (`leaves_in_place`), not a scan's xs


def _layer_body(config: ModelConfig, x, leaves: LayerLeaves, layer, kind,
                view: KindView, cache, cos, sin, lora_scale=1.0, attn_fn=None,
                state=None):
    """One decoder layer: norm, the operator of its `kind` with its residual
    (attention: `_attention`; MLA: core/mla.py; `"conv"`: `_conv_operator`),
    norm, MLP. If `cache` is not None, operate incrementally.

    `kind=(window, rotary)` or `"conv"` is the layer's static kind
    (`config.stack_pattern`); `view` its kind's record of this call and
    `cache` its kind's group of STACKED cache arrays (init_kv_cache /
    init_paged_kv_cache: (k, v) each [L, B, KV, T_max, hd], the four int8
    arrays, MLA's latent, the conv layers' state) or None; `layer` is this
    layer's index into them. The layer writes its new tokens into the stack
    at `(layer, ...)`, an update the size of what is new, and reads its slab
    back out of the UPDATED stack, so the stack can ride the layer scan's
    carry and the decode loop's carry as one buffer (`_run_layers`); the
    returned cache is the whole group again.
    `attn_fn(q, k, v)`, when given, replaces the attention contraction (used
    by the sequence-parallel path to route through ring attention): every
    other op stays this single implementation.

    A `"hybrid"` layer (docs/SSM.md) is an attention AND a state-space mixer
    on the one normed state, both added into the one residual: it takes its
    pages as `view` / `cache` like any attention layer and the state group's
    pair beside them as `state = (view, cache | None)`, and its new cache is
    the pair `(pages, state)`. A `"mamba"` layer is that mixer alone
    (docs/GRANITE_H.md): its `view` / `cache` are the state group's.

    Returns (x_out, new_cache_or_None, mlp_aux_or_None)."""
    layer_params = leaves.tree
    with jax.named_scope("norm"):
        h = rms_norm(x, layer_params["input_layernorm"], config.rms_norm_eps)
    with jax.named_scope("attn"):
        if kind == "hybrid":
            mixed, new_state = _ssm_operator(
                config, h, layer_params["ssm"], state[1], layer, state[0])
            x, new_cache = _attention(
                config, x, h, leaves, layer, (False, True), view, cache, cos,
                sin, lora_scale, attn_fn)
            x, new_cache = x + mixed, (new_cache, new_state)
        elif kind == "mamba":   # the mixer alone (docs/GRANITE_H.md)
            mixed, new_cache = _ssm_operator(
                config, h, layer_params["ssm"], cache, layer, view)
            x = x + _times(mixed, config.residual_scale)
        elif kind == "conv":
            x, new_cache = _conv_operator(
                config, x, h, layer_params["conv"], cache, layer, view)
        elif kind == "lightning":
            from nanorlhf_tpu.core import sala

            x, new_cache = sala.lightning_operator(
                config, x, h, layer_params["lightning"], cache, layer, view,
                cos, sin, leaves.at)
        elif config.kv_lora_rank:
            from nanorlhf_tpu.core.mla import mla_attention

            out, new_cache = mla_attention(
                config, h, layer_params, leaves.lora, lora_scale, cos, sin,
                view, cache, layer)
            x = x + out
        else:
            x, new_cache = _attention(
                config, x, h, leaves, layer, kind, view, cache, cos, sin,
                lora_scale, attn_fn)

    router_h = h if config.router_input == "pre_attention" else None
    with jax.named_scope("norm"):
        h = rms_norm(x, layer_params["post_attention_layernorm"],
                     config.rms_norm_eps)
    with jax.named_scope("mlp"):
        ff, aux = _mlp(config, h, layer_params, leaves.lora, lora_scale,
                       leaves.experts, leaves.at, view.live, router_h)
        if config.branch_norms:     # afmoe: the branch is normed again
            with jax.named_scope("norm"):
                ff = rms_norm(ff, layer_params["mlp_branch_norm"],
                              config.rms_norm_eps)
        x = x + _times(ff, config.residual_scale)
    return x, new_cache, aux


def _attention(config, x, h, leaves, layer, kind, view, cache, cos, sin,
               lora_scale, attn_fn):
    """A layer's attention on the normed state `h`, with its residual:
    `(x + attention, the updated cache stacks | None)`: project, write,
    dispatch, out, each under its scope (utils/profiling.py `DEVICE_SCOPES`):
    the projections and rotary (`attn.qkv`; the gate's projection too, where
    the model has one), the new tokens' write into the cache (`attn.write`,
    `_cache_write`), the contraction (`_attention_read`, under `attn.read`;
    a pattern model's under `attn.global` / `attn.window`, which hold its
    write too) and the output projection (`attn.out`); afmoe's gate, `out *
    sigmoid(g)`, is a fifth between the last two (`attn.gate`), and its
    branch norm closes `attn.out`.

    Where the layer took its kernels at a traced index of the whole stacks
    (`leaves.in_place`) the projections' results are fenced from the head
    split: the chip's compiler carries a reshape `[B, T, H, hd]` and its
    transpose back INTO the q, k and v kernels' layout (it wants them
    contraction-minor, `[H, hd, D]`), so it relaid the whole stacks once a
    call and had to make each layer's relaid slice before it could prefetch
    it, made, dropped and made again (`.remat`): ~0.5 ms of a 5.3 ms decode
    step at SmallThinker's widths (PERF.md PR 44; docs/SWA.md). Behind the
    fence a projection is a plain matmul over the stack where it lies, as
    `o_proj`'s always was. A scanned slice of xs is a layer's own buffer
    already and is not fenced."""
    hd = config.actual_head_dim
    H, KV = config.num_attention_heads, config.num_key_value_heads
    B, T, D = x.shape
    layer_params, lora_layer = leaves.tree, leaves.lora
    # a sparse layer (docs/SALA.md): global, without rotary, and its cache
    # group holds its compressed keys as a third leaf
    sparse = kind == "sparse"
    window, rotary = (False, False) if sparse else kind
    spmd = _kernel_spmd(config, H, KV)
    with jax.named_scope("attn.qkv"):
        h = _times(h, config.attention_in_multiplier)
        q = _times(_proj(h, layer_params, lora_layer, "q_proj", lora_scale),
                   config.query_multiplier)
        k = _times(_proj(h, layer_params, lora_layer, "k_proj", lora_scale),
                   config.key_multiplier)
        v = _proj(h, layer_params, lora_layer, "v_proj", lora_scale)
        gate = (_proj(h, layer_params, lora_layer, "g_proj", lora_scale)
                if config.attention_gate else None)
        # (a looped model fences too: with heads x head_dim = hidden the
        # chip's compiler relaid the three [48, 2048, 2048] stacks whole at
        # every call, a decode chunk's included, 1.2 GB of temporaries;
        # compiled for a described v5e, PR 55)
        if leaves.in_place or config.loop_passes > 1:
            q, k, v, gate = jax.lax.optimization_barrier((q, k, v, gate))
        if config.qk_norm:
            # OLMoE: over the whole projection width, before the head split
            q = rms_norm(q, layer_params["q_norm"], config.rms_norm_eps)
            k = rms_norm(k, layer_params["k_norm"], config.rms_norm_eps)
        q = q.reshape(B, T, H, hd).transpose(0, 2, 1, 3)
        k = k.reshape(B, T, KV, hd).transpose(0, 2, 1, 3)
        v = v.reshape(B, T, KV, hd).transpose(0, 2, 1, 3)
        if config.qk_norm_per_head:     # LFM2: over each head, before RoPE
            q = rms_norm(q, layer_params["q_norm"], config.rms_norm_eps)
            k = rms_norm(k, layer_params["k_norm"], config.rms_norm_eps)

        if rotary:      # a NoPE layer carries no position
            q = apply_rope(q, cos, sin)
            k = apply_rope(k, cos, sin)
        if config.kv_head_pack > 1:
            q, k, v = _pack_heads(q, k, v, config.kv_head_pack)

    # a pattern model's scope names the layer's kind and holds its write
    # (harness/attn_trace.py reads a decode read by it); a model of one
    # kind writes beside `attn.read`
    patterned = config.attention_pattern is not None
    window = config.sliding_window if window else 0
    with contextlib.ExitStack() as scopes:
        # (a sparse layer names its own steps, as a model of one kind does:
        # `attn.write`, `attn.compress`, `attn.select`, `attn.read`)
        if patterned and not sparse:
            scopes.enter_context(jax.named_scope(
                "attn.window" if window else "attn.global"))
        new_cache = compressed = None
        if sparse and cache is not None:
            *cache, compressed = cache
        if cache is not None and attn_fn is None:
            news = (k, v)
            if view.cache == "int8":    # see init_kv_cache
                with jax.named_scope("attn.write"):
                    news = _quantize_kv(k) + _quantize_kv(v)
            new_cache = _cache_write(tuple(cache), news, layer, view)
        if compressed is not None:
            from nanorlhf_tpu.core import sala

            with jax.named_scope("attn.compress"):
                compressed = sala.compress_write(
                    config, compressed, new_cache[0], layer, view, T)
        if not patterned:
            scopes.enter_context(jax.named_scope("attn.read"))
        if attn_fn is not None:
            out = attn_fn(q, k, v)
        elif view.cache == "int8":
            out = _int8_attention_read(config, q, k, v, view, new_cache,
                                       layer, spmd)
        elif sparse:
            from nanorlhf_tpu.core import sala

            @jax.named_scope("attn.read")
            def dense_read():   # a call none of whose rows selects
                return _attention_read(config, q, k, v, view, new_cache,
                                       layer, window, spmd)

            out = sala.sparse_read(config, q, k, v, view, new_cache,
                                   compressed, layer, dense_read)
            if new_cache is not None:
                new_cache = new_cache + (compressed,)
        else:
            out = _attention_read(config, q, k, v, view, new_cache, layer,
                                  window, spmd)

    def merged(out):    # [B, H, T, hd] as the cache's heads -> [B, T, H hd]
        if config.kv_head_pack > 1:
            out = _unpack_heads(out, KV, config.kv_head_pack)
        return out.transpose(0, 2, 1, 3).reshape(B, T, H * hd)

    if gate is not None:    # afmoe: per element, before the output projection
        with jax.named_scope("attn.gate"):
            out = merged(out) * jax.nn.sigmoid(
                gate.astype(jnp.float32)).astype(out.dtype)
    with jax.named_scope("attn.out"):
        if gate is None:
            out = merged(out)
        out = _times(_proj(out, layer_params, lora_layer, "o_proj",
                           lora_scale), config.attention_out_multiplier)
        if config.branch_norms:     # afmoe: the branch is normed again
            with jax.named_scope("norm"):
                out = rms_norm(out, layer_params["attn_branch_norm"],
                               config.rms_norm_eps)
        return x + _times(out, config.residual_scale), new_cache


def _pack_heads(q, k, v, pack: int):
    """Heads narrower than a row of lanes, `pack` KV heads side by side:
    k, v [B, KV, T, hd] -> [B, KV / pack, T, pack * hd], which is what the
    cache then holds (pages 128 lanes wide for heads of 64: the layout every
    paged kernel reads, and no lane of a page is padding); q [B, H, T, hd] ->
    [B, H, T, pack * hd], each query head's values in ITS KV head's lanes and
    zeros in the others', so `q' . k'` is `q . k` of its own head and the
    read is a GQA read of KV / pack heads with `pack` times the group. The
    values come back in the same lanes (`_unpack_heads`). q is scaled by
    sqrt(pack): every read divides by the square root of the width it sees."""
    B, KV, T, hd = k.shape
    H = q.shape[1]
    G = H // KV
    lanes = lambda a: a.reshape(B, KV // pack, pack, T, hd).transpose(  # noqa: E731
        0, 1, 3, 2, 4).reshape(B, KV // pack, T, pack * hd)
    own = (jnp.arange(H) // G) % pack                           # [H]
    sel = own[:, None] == jnp.arange(pack)[None, :]             # [H, pack]
    q = q * jnp.asarray(pack ** 0.5, q.dtype)
    q = jnp.where(sel[None, :, None, :, None], q[:, :, :, None, :], 0)
    return q.reshape(B, H, -1, pack * hd), lanes(k), lanes(v)


def _unpack_heads(out, KV: int, pack: int):
    """A packed read's output [B, H, T, pack * hd] -> [B, H, T, hd]: each
    query head keeps the lanes of its own KV head (`_pack_heads`)."""
    B, H, T, W = out.shape
    hd = W // pack
    own = (jnp.arange(H) // (H // KV)) % pack
    out = out.reshape(B, H, T, pack, hd)
    return jnp.take_along_axis(
        out, own[None, :, None, None, None], axis=3)[:, :, :, 0, :]


def _tail_read(stack, layer, row, B, fresh):
    """A call's B rows of a state leaf `[layers, K - 1, rows, W]` at `layer`,
    from row `row` on, as `[B, K - 1, W]`; a `fresh` row's as zeros."""
    _, K1, _, W = stack.shape
    past = jax.lax.dynamic_slice(stack, (layer, 0, row, 0), (1, K1, B, W))[0]
    past = past.transpose(1, 0, 2)
    if fresh is not None:
        past = jnp.where(fresh[:, None, None], 0, past)
    return past


def _tail_write(stack, seq, T, valid, layer, row):
    """The leaf with the call's rows set to the last K - 1 values of `seq`
    `[B, K - 1 + T, W]` (what stood before the call's T tokens, then they):
    with `valid` [B, T], the K - 1 values up to the row's LAST real token."""
    K1 = seq.shape[1] - T
    if valid is None:
        tail = seq[:, T:]
    else:
        last = jnp.where(
            valid.any(axis=1),
            T - 1 - jnp.argmax(valid[:, ::-1], axis=1), -1)
        at = last[:, None] + 1 + jnp.arange(K1)[None, :]
        tail = jnp.take_along_axis(seq, at[:, :, None], axis=1)
    return jax.lax.dynamic_update_slice(
        stack, tail.transpose(1, 0, 2)[None].astype(stack.dtype),
        (layer, 0, row, 0))


def _conv_operator(config, x, h, conv, state_group, layer, view):
    """A conv layer's operator on the normed state `h`, with its residual
    (docs/STATE.md): `[b | c | u] = h W_in`, `g = b * u`, `y_t = c_t *
    sum_j w[j] * g_(t-K+1+j)`, `x + y W_out`. `(x, the updated state group |
    None)`.

    The layer's cache is `state_group = (state,)`, `[conv layers, K - 1,
    rows, D]`: a row's last K - 1 values of `g`, oldest first, which stand
    before this call's `g` in the convolution (rows before D, so that a
    decode step's `[rows, D]` operands are the state's own tiles: with the
    rows outside the K - 1 values the chip's compiler relaid the whole state
    at both ends of every decode chunk, compiled for a described v5e, PR
    38). The call's B rows are rows `[r, r + B)` of it, `r =
    state_rows[0, 0]` (`state_rows` [B, 1] int32 is the state kind's
    "table": an admission's one row, a decode chunk's every row; None: rows
    `[0, B)`, the contiguous cache). `ctx = (valid, fresh)`:
    `valid` [B, T] bool marks the real tokens (a pad's `g` is 0, and the
    state that leaves is the one after the row's LAST real token: left pads
    before a prompt, a bucket's pads after a suffix, a decode step's rows
    nobody listens to, which leave it as it was); `fresh` [B] bool marks the
    rows that start here, whose incoming state counts as zeros whatever the
    row's last occupant left. Without a cache the row starts from zeros.
    Float32 products and sums over the taps, as the attention's softmax."""
    B, T, D = x.shape
    K = config.conv_L_cache
    state_rows, ctx = view.table, view.conv_ctx
    valid, fresh = ctx if ctx is not None else (None, None)
    with jax.named_scope("attn.conv"):
        with jax.named_scope("attn.conv.in"):
            b, c, u = jnp.split(h @ conv["in_proj"]["kernel"], 3, axis=-1)
            g = b * u
            if valid is not None:
                g = jnp.where(valid[..., None], g, 0)
        with jax.named_scope("attn.conv.mix"):
            if state_group is None:
                past = jnp.zeros((B, K - 1, D), g.dtype)
            else:
                (state,) = state_group
                row = 0 if state_rows is None else state_rows[0, 0]
                past = _tail_read(state, layer, row, B, fresh)
            seq = jnp.concatenate([past.astype(g.dtype), g], axis=1)
            taps = conv["conv"]["kernel"].astype(jnp.float32)       # [K, D]
            mixed = sum(taps[j] * seq[:, j:j + T].astype(jnp.float32)
                        for j in range(K))
            y = (c.astype(jnp.float32) * mixed).astype(x.dtype)
        new_group = None
        if state_group is not None:
            with jax.named_scope("attn.write"):
                new_group = (_tail_write(state, seq, T, valid, layer, row),)
        with jax.named_scope("attn.conv.out"):
            return x + y @ conv["out_proj"]["kernel"], new_group


def _gated_norm(config: ModelConfig, y, z, weight):
    """The mixer's output `y` [B, T, I] (float32) times `silu(z)`, THEN an
    RMSNorm over each group's I / G channels, times `weight` (the gate
    before the norm, as published; benchmark/tools/mamba_control.py lays the
    other order in here to show that the comparison refuses it)."""
    f32 = jnp.float32
    B, T, I = y.shape
    y = y * jax.nn.silu(z.astype(f32))
    y = y.reshape(B, T, config.ssm_groups, I // config.ssm_groups)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True)
                          + config.rms_norm_eps)
    return y.reshape(B, T, I) * weight.astype(f32)


def _ssm_operator(config, h, ssm, state_group, layer, view):
    """A layer's state-space mixer on the normed state `h` (docs/SSM.md;
    Mamba-2 with Falcon-H1's multipliers, every one 1.0 for a model without
    them): `(the mixer's branch [B, T, D], the updated state group | None)`;
    the caller adds it into the residual, beside the attention's (a
    `"hybrid"` layer) or alone (a `"mamba"` layer, docs/GRANITE_H.md).

    `[z | xs | B | C | dt] = ((h ssm_in) W_in) * mup` (`W_in`'s last H
    columns are the leaf `dt_proj`: `_init_ssm`); `[xs | B | C]` through
    a causal depthwise convolution of K taps with a bias and a SiLU; the
    selective recurrence over `xs` (ops/ssm.py: `ssd_scan` for a piece,
    `ssm_update` for one token a row, which with a cache is
    `ssm_update_in_place`: one pass over the rows someone listens to, where
    `S` lies in the stack); `+ D xs`; times `silu(z)`; RMSNorm over each
    group's channels; `(y W_out) ssm_out`.

    The layer's state is `state_group = (tail, S)`: `tail` `[layers, K - 1,
    rows, I + 2 G N]`, a row's last K - 1 inputs of the convolution, oldest
    first (`_conv_operator`'s layout and rules), and `S` `[layers, rows, H,
    P, N]` FLOAT32, the recurrence's state. `view.table` names the rows and
    `view.conv_ctx = (valid, fresh)` means for both what it means for a conv
    layer's state: a token not `valid` (a left pad, a bucket's pad, a row
    nobody listens to) enters the convolution as 0 and has `dt = 0`, so it
    neither decays nor feeds `S` (a step does not visit its row at all, and
    its `y` is 0), and both leaves hold what they held after the row's LAST
    real token; a `fresh` row starts from zeros. Without a cache the row
    starts from zeros."""
    from nanorlhf_tpu.ops import ssm as ops

    B, T, _ = h.shape
    H, P, G, N = (config.ssm_heads, config.ssm_head_dim, config.ssm_groups,
                  config.ssm_state)
    K, I, W = config.ssm_conv, config.ssm_inner, config.ssm_conv_width
    f32 = jnp.float32
    state_rows, ctx = view.table, view.conv_ctx
    valid, fresh = ctx if ctx is not None else (None, None)
    with jax.named_scope("attn.ssm"):
        with jax.named_scope("attn.ssm.in"):
            h = _times(h, config.ssm_in_multiplier)
            p = h @ ssm["in_proj"]["kernel"]
            m = config.ssm_multipliers
            if any(part != 1.0 for part in m[:4]):
                p = p * jnp.concatenate([
                    jnp.full((width,), part, p.dtype) for width, part in
                    zip((I, I, G * N, G * N), m)])
            z, xbc = jnp.split(p, (I,), axis=-1)
            dt = _times(h @ ssm["dt_proj"]["kernel"], m[4])
            if valid is not None:
                xbc = jnp.where(valid[..., None], xbc, 0)
        with jax.named_scope("attn.ssm.conv"):
            if state_group is None:
                past = jnp.zeros((B, K - 1, W), xbc.dtype)
                before = jnp.zeros((B, H, P, N), f32)
            else:
                tail_stack, s_stack = state_group
                row = 0 if state_rows is None else state_rows[0, 0]
                past = _tail_read(tail_stack, layer, row, B, fresh)
                if T > 1:   # (a cached step passes over `S` where it lies)
                    # a row's state is cut out of the stack seen as `[L,
                    # rows, H P, N]` (the same tiles), so the layout the
                    # scan wants of `[H, P, N]` is the slice's to take. Cut
                    # out of `[L, rows, H, P, N]`, a piece of ONE chunk at
                    # H 128 / P 64 / N 128 had the chip's compiler relay
                    # the WHOLE stack heads-inside and back, two copies of
                    # 1.8 GB in every admission's closing forward (compiled
                    # for a described v5e and traced on the chip, PR 59;
                    # fences did not hold it: a slice takes its operand's
                    # layout)
                    before = jax.lax.dynamic_slice(
                        s_stack.reshape(s_stack.shape[:2] + (H * P, N)),
                        (layer, row, 0, 0), (1, B, H * P, N)
                    ).reshape(B, H, P, N)
                    if fresh is not None:
                        before = jnp.where(fresh[:, None, None, None], 0,
                                           before)
            seq = jnp.concatenate([past.astype(xbc.dtype), xbc], axis=1)
            taps = ssm["conv"]["kernel"].astype(f32)                # [K, W]
            mixed = jax.nn.silu(
                sum(taps[j] * seq[:, j:j + T].astype(f32) for j in range(K))
                + ssm["conv"]["bias"].astype(f32))
            xs, Bm, Cm = jnp.split(mixed, (I, I + G * N), axis=-1)
            xs = xs.reshape(B, T, H, P)
            Bm, Cm = Bm.reshape(B, T, G, N), Cm.reshape(B, T, G, N)
            dt = jax.nn.softplus(dt.astype(f32) + ssm["dt_bias"].astype(f32))
            if valid is not None:
                dt = jnp.where(valid[..., None], dt, 0)
            A = -jnp.exp(ssm["A_log"].astype(f32))
        if T == 1:
            with jax.named_scope("attn.ssm.update"):
                step = (xs[:, 0], dt[:, 0], A, Bm[:, 0], Cm[:, 0])
                if state_group is None:
                    y, _ = ops.ssm_update(*step, before)
                else:   # one pass over the live rows' `S`, in the stack
                    y, s_stack = ops.ssm_update_in_place(
                        s_stack, layer, row,
                        None if valid is None else valid[:, 0], fresh, *step)
                y = y[:, None]
        else:
            with jax.named_scope("attn.ssm.scan"):
                y, after = ops.ssd_scan(xs, dt, A, Bm, Cm, before,
                                        config.ssm_chunk)
        with jax.named_scope("attn.ssm.gate"):
            y = y + ssm["D"].astype(f32)[:, None] * xs
            y = _gated_norm(config, y.reshape(B, T, I), z,
                            ssm["norm"]).astype(h.dtype)
        new_group = None
        if state_group is not None:
            with jax.named_scope("attn.write"):
                new_group = (
                    _tail_write(tail_stack, seq, T, valid, layer, row),
                    s_stack if T == 1 else jax.lax.dynamic_update_slice(
                        s_stack.reshape(s_stack.shape[:2] + (H * P, N)),
                        after.reshape(1, B, H * P, N).astype(s_stack.dtype),
                        (layer, row, 0, 0)).reshape(s_stack.shape))
        with jax.named_scope("attn.ssm.out"):
            return _times(y @ ssm["out_proj"]["kernel"],
                          config.ssm_out_multiplier), new_group


def _int8_attention_read(config, q, k, v, view, new_cache, layer, spmd):
    """The attention contraction over the int8 cache `(k_q, k_scales, v_q,
    v_scales)` (init_kv_cache), which already holds this call's tokens: the
    q8 decode kernels where they apply, the tokens at hand for a prefill,
    else the dequantized view. Only a model of one kind has this cache
    (`_pattern_caches`, `_latent_cache_shape`)."""
    T = q.shape[2]
    mask, table = view.mask, view.table
    kq_c, ks_c, vq_c, vs_c = (_layer_slab(c, layer) for c in new_cache)

    def _q8_views(width):
        """Row-contiguous dequantized cache views (paged gathers through
        the table; contiguous passes the slabs through)."""
        if table is not None:
            kq_p, ks_p, vq_p, vs_p = new_cache
            return (
                _dequantize_kv(
                    _paged_view(kq_p, layer, table, width),
                    _paged_scale_view(ks_p, layer, table, width),
                    q.dtype),
                _dequantize_kv(
                    _paged_view(vq_p, layer, table, width),
                    _paged_scale_view(vs_p, layer, table, width),
                    q.dtype),
            )
        return (_dequantize_kv(kq_c, ks_c, q.dtype),
                _dequantize_kv(vq_c, vs_c, q.dtype))

    if view.verify is not None:
        # speculative verify over the int8 cache: dequantize and run the
        # general masked path: correct everywhere, no bandwidth win (the q8
        # k-query kernel is future work; single-token decode keeps the q8
        # kernel either way)
        return gqa_attention(q, *_q8_views(mask.shape[-1]), mask)
    if attention_form(config, T, cached=False) == "flash":
        # a prefill needs the tokens at hand only, by the uncached rule
        return _flash_attention(q, k, v, mask[..., :T], spmd)
    if T > 1:
        return gqa_attention(q, k, v, mask[..., :T])
    if (view.decode is not None
            and use_q8_decode_kernel(config.attention_impl)):
        # decode reads the cache: the q8 kernel consumes int8 + scales
        # natively, the whole point of the quantized cache.
        # attention_impl="xla" stays a working escape hatch (dequant
        # fallback below: correct, no bandwidth win)
        start, filled = view.decode
        if table is not None:
            from nanorlhf_tpu.ops.decode_attention import (
                paged_decode_attention_q8,
            )

            return paged_decode_attention_q8(
                q[:, :, 0, :], kq_c, ks_c, vq_c, vs_c, table, start, filled,
            )[:, :, None, :]
        from nanorlhf_tpu.ops.decode_attention import decode_attention_q8

        q8_args = (q[:, :, 0, :], kq_c, ks_c, vq_c, vs_c, start, filled)
        if spmd is not None:
            return _spmd_call(spmd, decode_attention_q8, q8_args,
                              (1, 1, 1, 1, 1, None, None))[:, :, None, :]
        return decode_attention_q8(*q8_args)[:, :, None, :]
    # correctness fallback (CPU tests): dequantize and reuse the exact path:
    # no bandwidth win off-TPU, none needed
    return gqa_attention(q, *_q8_views(mask.shape[-1]), mask)


def attention_form(config: ModelConfig, T: int, *, cached: bool,
                   paged: bool = False, decode: bool = False,
                   verify: bool = False, window: int = 0, mesh: bool = False,
                   cache_len: int = 0) -> str:
    """The form a layer's bf16 attention read takes (`_attention_read` calls
    it): a pure function of what the call can see. `T` queries a row;
    `cached`: there is a cache, `paged` or contiguous, of `cache_len` logical
    slots; which bounds came (`decode`: a decode step's, `verify`: a
    candidate or chunk forward's); `window`: the layer's window (0: none);
    `mesh`: a multi-device hint applies (`_kernel_spmd`); and of the
    configuration `attention_impl`, the backend behind it (`use_flash`,
    `use_decode_kernel`, `use_paged_decode_kernel`) and whether the model
    has a layer pattern. The rules, in order. Four of them tell a pattern
    model from one of a single kind: nobody decided those, two functions
    drifted (ROADMAP, model layer debts (a) and (b)), and each stays until a
    `perf_opt` issue measures it."""
    patterned = config.attention_pattern is not None
    impl = config.attention_impl
    # the flash kernel rebuilds causal x key-valid from the tokens at hand
    # and has no block bound for a window: inside its window a window layer
    # is a causal one, past it the kernel does not apply
    flash = (T > 1 and not (window and T > window) and use_flash(impl, T))
    if config.block_generation:
        # a BLOCK-causal model (docs/BLOCKDIFF.md): neither flash kernel
        # takes a block length (both rebuild causal from the tokens at
        # hand), so every read of T > 1 tokens is XLA's, under the call's
        # own mask: in blocks of queries uncached, the walk of the key
        # blocks over pages. A block forward (`decode` bounds with T > 1:
        # the block's queries all see ONE key set, the committed prefix and
        # the block's own slots) rides the in-place paged decode kernel on
        # a TPU, its queries on the kernel's group axis
        if not cached:
            return "query_blocks"
        if decode and T > 1:
            return ("paged_block" if paged and use_paged_decode_kernel(config)
                    else "view")
        if verify and paged:
            return "paged_walk"
        return "view"
    if not cached:
        if flash:
            return "flash"
        # XLA over the tokens at hand. (1) a pattern model's goes in blocks
        # of queries once the scores pass `_PATTERN_SCORE_BYTES` (a served
        # row's thousands of tokens); a model of one kind scores whole
        return "query_blocks" if patterned else "local"
    if verify:
        # T candidate or chunk tokens against the cache they just joined
        if patterned:
            # (2) the T > 1 paged read over the pages in place: the flash
            # kernel on a TPU, XLA's walk of the key blocks elsewhere; a
            # contiguous cache is read whole under the mask
            if not paged:
                return "view"
            return ("paged_flash" if use_paged_decode_kernel(config)
                    else "paged_walk")
        # (2) a model of one kind: the k-query prefix-bounded kernel over
        # the layer's slab from the contiguous kernel's threshold on
        if use_decode_kernel(impl, cache_len):
            return "paged_verify_slab" if paged else "verify_slab"
        return "view"
    if T > 1:
        # a prefill from slot 0: the tokens at hand are all there is
        if flash:
            return "flash"
        # (3) XLA: a pattern model over the tokens at hand, a model of one
        # kind over the cache it just wrote, under the mask
        return "local" if patterned else "view"
    if decode and paged and use_paged_decode_kernel(config):
        # the rows' live pages read from the stacks in place
        return "paged_decode"
    if (decode and not paged and use_decode_kernel(impl, cache_len)
            and not (patterned and mesh)):
        # the prefix-bounded kernel reads the filled range, not the masked
        # T_max square. (4) under a mesh a model of one kind wraps it in
        # shard_map; a pattern model's refuses the mesh
        return "decode"
    # the plain form, and every kernel's oracle: the cache as a
    # row-contiguous view (a paged one gathered), masked
    return "view"


def _attention_read(config, q, k, v, view, new_cache, layer, window, spmd):
    """The bf16 attention contraction of one layer, `out [B, H, T, hd]`, in
    the form `attention_form` names: over `new_cache`, the kind's cache
    stacks that already hold this call's tokens (`_attention` wrote them),
    or over `k` and `v` alone where there is no cache or a prefill needs no
    more than the tokens at hand. The reads that go by bounds (the decode
    kernels, the paged T > 1 reads) get the kind's own from `view`; its
    mask carries the window per query on every path."""
    T = q.shape[2]
    mask, table = view.mask, view.table
    cached, paged = new_cache is not None, table is not None
    # the logical cache length (the kernels' threshold, the gathered view):
    # on the paged layout the mask's width, not the pool's shape
    cache_len = (mask.shape[-1] if paged or not cached
                 else new_cache[0].shape[3])
    form = attention_form(
        config, T, cached=cached, paged=paged, decode=view.decode is not None,
        verify=view.verify is not None, window=window, mesh=spmd is not None,
        cache_len=cache_len)
    slabs = functools.cache(
        lambda: tuple(_layer_slab(c, layer) for c in new_cache))
    if cached and config.attention_pattern is None:
        # (a model of one kind has always staged its layer's slabs here,
        # read or not: dead in a decode loop's text, which is the compile
        # cache's key, and nothing once compiled)
        slabs()

    def views(width):
        if paged:
            return tuple(_paged_view(c, layer, table, width)
                         for c in new_cache)
        if width < cache_len:       # `decode_step(extent=)`
            return tuple(_layer_slab(c, layer, width) for c in new_cache)
        return slabs()

    def on_mesh(kernel, args, head_dims):
        if spmd is not None:
            return _spmd_call(spmd, kernel, args, head_dims)
        return kernel(*args)

    def at_hand():      # the mask over the call's own tokens
        return mask[..., :T] if cached else mask

    if form == "flash":
        return _flash_attention(q, k, v, at_hand(), spmd)
    if form == "local":
        return gqa_attention(q, k, v, at_hand())
    if form == "query_blocks":
        return _gqa_attention_in_query_blocks(q, k, v, mask)
    if form == "paged_flash":
        # under a scope of its own (harness/attn_trace.py takes a custom
        # call named after `attn.global` / `attn.window` for a DECODE read)
        from nanorlhf_tpu.ops.paged_prefill_attention import (
            paged_prefill_attention,
        )

        with jax.named_scope("attn.paged_flash"):
            return paged_prefill_attention(
                q, *new_cache, layer, table, *view.verify, window)
    if form == "paged_walk":
        first, fill = view.verify
        return _attend_paged_blocks(new_cache, layer, table, view.page_size,
                                    mask, first, fill + (T - 1), q)
    if form == "paged_verify_slab":
        from nanorlhf_tpu.ops.decode_attention import (
            paged_decode_verify_attention,
        )

        return paged_decode_verify_attention(q, *slabs(), table, *view.verify)
    if form == "verify_slab":
        from nanorlhf_tpu.ops.decode_attention import decode_verify_attention

        return on_mesh(decode_verify_attention, (q, *slabs(), *view.verify),
                       (1, 1, 1, None, None))
    if form == "paged_block":
        # the block's T queries see one key set: a KV head's G x T query
        # rows go as that head's group through the decode kernel, over the
        # rows' live pages in place (`view.decode`: the forward's work list)
        from nanorlhf_tpu.ops.decode_attention import paged_decode_attention

        B, H, _, hd = q.shape
        KV = new_cache[0].shape[2]
        with jax.named_scope("attn.block"):
            out = paged_decode_attention(
                q.reshape(B, KV, H // KV, T, hd).reshape(B, H * T, hd),
                *new_cache, layer, view.decode)
        return out.reshape(B, H, T, hd)
    if form == "paged_decode":
        # `view.decode` is the step's work list (`_kind_views` made it
        # under the same rule)
        from nanorlhf_tpu.ops.decode_attention import paged_decode_attention

        return paged_decode_attention(
            q[:, :, 0, :], *new_cache, layer, view.decode)[:, :, None, :]
    if form == "decode":
        from nanorlhf_tpu.ops.decode_attention import decode_attention

        return on_mesh(decode_attention,
                       (q[:, :, 0, :], *slabs(), *view.decode),
                       (1, 1, 1, None, None))[:, :, None, :]
    # (the block read's plain form carries the block read's scope)
    block_read = config.block_generation and view.decode is not None and T > 1
    with (jax.named_scope("attn.block") if block_read
          else contextlib.nullcontext()):
        return gqa_attention(q, *views(mask.shape[-1]), mask)


# pages a key block of the pattern model's T > 1 paged read holds (1,024 keys
# at pages of 128, as core/mla.py's)
_PAGED_BLOCK_PAGES = 8


def _attend_paged_blocks(pools, layer, table, page_size, mask, first, last, q):
    """GQA attention of T > 1 queries over a paged cache a block of
    `_PAGED_BLOCK_PAGES` pages at a time, over the blocks that hold slots
    `[min(first), max(last)]` only, with a float32 online softmax across
    them (`mla._attend_paged`'s walk, for per-head K and V). A chunked
    prefill's queries need the slots from the chunk's first key (a window
    layer's: `window` before it) to its last, not the row's whole table: at
    16,384 slots the gathered view's scores alone are 1.9 GB a layer, a
    block's 0.12. The plain form of that read (`"xla"`, off the TPU, under a
    mesh) and the oracle of its kernel, ops/paged_prefill_attention, which a
    TPU takes (`use_paged_decode_kernel`). `mask` [B, 1, T, width] is the
    kind's own (the window per query); q [B, H, T, hd]; first/last [B]
    int32. Returns [B, H, T, hd]."""
    B, H, T, hd = q.shape
    KV = pools[0].shape[2]
    nb, width = table.shape[1], mask.shape[-1]
    bp = _PAGED_BLOCK_PAGES
    K = bp * page_size
    n_blocks = -(-nb // bp)
    table = jnp.pad(table, ((0, 0), (0, n_blocks * bp - nb)),
                    constant_values=pools[0].shape[1])
    mask = jnp.pad(mask, ((0, 0),) * 3 + ((0, max(n_blocks * K - width, 0)),))
    lo = jnp.clip(jnp.min(first) // K, 0, n_blocks)
    hi = jnp.clip(jnp.max(last) // K + 1, 0, n_blocks)
    qg = q.reshape(B, KV, H // KV, T, hd)
    scale = 1.0 / jnp.sqrt(jnp.float32(hd))

    def block(kb, carry):
        m_i, l_i, acc = carry
        pages = jax.lax.dynamic_slice_in_dim(table, kb * bp, bp, axis=1)
        kd = _paged_view(pools[0], layer, pages, K)
        vd = _paged_view(pools[1], layer, pages, K)
        s = jnp.einsum("bkgqh,bkth->bkgqt", qg, kd,
                       preferred_element_type=jnp.float32) * scale
        valid = jax.lax.dynamic_slice_in_dim(mask, kb * K, K, axis=3)
        s = jnp.where(valid[:, :, None], s, NEG_INF)
        m_new = jnp.maximum(m_i, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_i - m_new)
        p = jnp.exp(s - m_new)
        l_new = alpha * l_i + jnp.sum(p, axis=-1, keepdims=True)
        pv = jnp.einsum("bkgqt,bkth->bkgqh", p.astype(vd.dtype), vd,
                        preferred_element_type=jnp.float32)
        return m_new, l_new, acc * alpha + pv

    lead = (B, KV, H // KV, T)
    init = (jnp.full(lead + (1,), NEG_INF, jnp.float32),
            jnp.zeros(lead + (1,), jnp.float32),
            jnp.zeros(lead + (hd,), jnp.float32))
    _, l, acc = jax.lax.fori_loop(lo, hi, block, init)
    return (acc / jnp.maximum(l, 1e-30)).reshape(B, H, T, hd).astype(q.dtype)


# the most an XLA score array of the pattern model's uncached read may hold
_PATTERN_SCORE_BYTES = 512 << 20


def _gqa_attention_in_query_blocks(q, k, v, mask):
    """`gqa_attention` (XLA) over blocks of queries once the float32 scores
    of all of them pass `_PATTERN_SCORE_BYTES`: a window layer past its
    window, and every layer under `"xla"`, at the thousands of tokens a
    served row has (at 9,728 tokens one layer's scores are 10.6 GB). Each
    block sees every key under its own rows of the mask: the same sums."""
    B, H, T, hd = q.shape
    S = k.shape[2]
    if B * H * T * S * 4 <= _PATTERN_SCORE_BYTES:
        return gqa_attention(q, k, v, mask)
    fit = max(_PATTERN_SCORE_BYTES // (B * H * S * 4), 8)
    bq = 1 << (fit.bit_length() - 1)
    n = -(-T // bq)
    pad = ((0, 0), (0, 0), (0, n * bq - T), (0, 0))   # see nothing, cut off
    qs = jnp.moveaxis(jnp.pad(q, pad).reshape(B, H, n, bq, hd), 2, 0)
    ms = jnp.moveaxis(jnp.pad(mask, pad).reshape(B, 1, n, bq, S), 2, 0)
    out = jax.lax.map(lambda a: gqa_attention(a[0], k, v, a[1]), (qs, ms))
    return jnp.moveaxis(out, 0, 2).reshape(B, H, n * bq, hd)[:, :, :T]


def _expert_xs(layers: dict, in_place: bool):
    """`(scanned layer tree, expert stack | None)`. In place, an expert
    model's expert kernels stay OUT of the scanned xs and every layer
    addresses the whole stack at its own index (`_mlp`): a scanned slice
    would be copied out of the stack for the grouped matmul's custom call
    (ops/moe.py). A dense model has no experts and nothing changes."""
    if not in_place or "experts" not in layers:
        return layers, None
    layer_xs = dict(layers)
    return layer_xs, layer_xs.pop("experts")


def _layer_stacks(params: dict) -> list:
    """`[(stacked layer tree, its adapter tree | None, index of its first
    layer, layers)]` in model order: `layers` alone for every model of one
    stack; `dense_layers` before it for a model whose leading layers have
    another MLP than the rest (A.X-K1, LFM2, Trinity), so another tree to
    scan."""
    lora = params.get("lora", {})
    stacks, start = [], 0
    for name in ("dense_layers", "layers"):
        if name in params:
            n = params[name]["input_layernorm"].shape[0]
            stacks.append((params[name], lora.get(name), start, n))
            start += n
    return stacks


def _rematerialized(config: ModelConfig, body):
    """The scanned layer body under `jax.checkpoint`, by `remat_policy`."""
    if config.remat_policy == "dots":
        # keep MXU matmul outputs (no batch dims = the weight projections,
        # not attention scores) for the backward
        return jax.checkpoint(
            body,
            policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    if config.remat_policy == "full":
        return jax.checkpoint(body)
    raise ValueError(
        f"remat_policy={config.remat_policy!r}: must be 'full' or 'dots'")


def _at(stacks, layer):
    """Every leaf of stacked trees at one (traced) index of its leading
    axis: a size-one dynamic slice of the stack where it lies."""
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, layer, keepdims=False),
        stacks)


def _kind_group(kind) -> int:
    """The cache group of a layer kind: 0 the global attention layers' pages
    (every layer of a model without a pattern), 1 the window layers', 2 the
    conv layers' state. A hybrid layer's attention keeps pages of group 0;
    its mixer's state lies in group 2 at the same index (every layer of such
    a model is hybrid: core/config.py). A mixer alone in its layer
    (`"mamba"`) keeps a state and no pages, like a conv layer."""
    if kind in ("hybrid", "sparse"):
        return 0
    return 2 if kind in ("conv", "lightning", "mamba") else int(kind[0])


def leaves_in_place(config: ModelConfig, cached: bool,
                    layer_transform=None) -> bool:
    """How a layer of `_run_layers` gets its leaves, from what the call can
    see: True, by index into the WHOLE stacks (`_at`: a size-one dynamic
    slice where the stack lies); False, as the scan's xs.

    - the CACHED forward of a pattern model (a session's decode chunk,
      prefill piece, suffix and admission forwards, `generate()`) indexes.
      Each such slice has one user, the layer's matmul, and the compiler
      reads the stack there. A period's slice `[p, ...]` of scanned xs has
      `p` users, fuses into none of them and is set down: at SmallThinker's
      widths the q and o kernels of four layers, 73 MB each, copied every
      period of every decode step (0.585 of a 5.65 ms step, PERF.md PR 43).
      Such a layer's attention keeps its head split off the kernels
      (`_attention`, from `LayerLeaves.in_place`). The rule turns on the
      model's having a pattern, not on the period's length: a pattern
      model's stack of period one (Trinity's leading dense stack) indexes
      too, as it has since PR 43, and its programs are the ones measured;
    - the UNCACHED forward (scoring, training, `remat`) scans the stacked
      tree, a pattern model's `[n, ...]` as `[n / p, p, ...]`: the backward
      of an index into a closed-over stack carries a gradient the size of
      the stack through the scan, where a scanned slice's is a slice. A
      call with a `layer_transform` (the FSDP hook: a scanned slice enters
      as a shard) scans too, and so does a model of one kind, whose scanned
      slice has one user."""
    return (config.attention_pattern is not None and cached
            and layer_transform is None)


def _run_layers(config, params, x, cos, sin, views, kv_caches=None,
                lora_scale=1.0, remat=False, attn_fn=None,
                layer_transform=None, cached_aux=False):
    """The decoder's layers, the one function that scans them: for each
    stack of stacked layer params (`_layer_stacks`) ONE `lax.scan` over the
    periods of its pattern (`config.stack_pattern`), its body the period's
    layers in order through `_layer_body`, each of its own static kind,
    `(window, rotary)` or `"conv"`. A model without a pattern goes through
    the same scan a layer a trip, but not yet AS a pattern of period one:
    see the last paragraph.

    `views` is the call's `KindView` a cache group (`_kind_views`) and a
    layer takes its kind's (`_kind_group`); the CACHE is groups of stacks
    `((k, v) of the global layers, (k, v) of the window layers[, (state,)
    of the conv layers])` for every model (`kv_caches` of a model without a
    pattern is its one group, wrapped here and unwrapped on the way out),
    all in the scans' CARRY, not their xs/ys: every layer writes its new
    tokens into the one stacked buffer at its own index, the count of its
    kind's layers before it, and reads its slab from it, so the cache that
    leaves the scan is the buffer that entered it and a decode loop that
    carries the cache needs no copy of it.

    In a model with conv layers the leaves only one kind has
    (`_ATTENTION_LEAVES`, `conv`) are stacked over that kind's layers, and a
    layer's place among them is that kind's count a period times the period
    plus its rank. How a layer gets its leaves: `leaves_in_place`.

    `remat=True` wraps the body in jax.checkpoint: the training path's
    activation rematerialization (capability parity with the reference's
    `gradient_checkpointing=True`, `/root/reference/GRPO/grpo.py:134`, but
    trading FLOPs for HBM the XLA way).

    `layer_transform(layer_params, lora_layer) -> (layer_params, lora_layer)`
    runs inside the scan body before the layer math: the FSDP hook: scanned
    param slices enter as shards and are all-gathered one layer at a time.

    A LOOPED model (`config.loop_passes` > 1, docs/OURO.md) passes the whole
    of this `loop_passes` times: an outer `lax.scan` over the passes
    around the layer scan, `x` and the caches in ITS carry too, a layer's
    cache index `pass * L + i` (a slot a pass a layer), and the model's final
    norm after each pass (`_close_pass`: the head then norms no more,
    `_final_norm`). With one pass none of it is staged: every other model's
    program is what it was.

    Returns `(x, the updated caches | None, aux)`; `aux` is the expert
    stack's stacked router record (`_mlp`), from the uncached forward always
    and from a cached one where `cached_aux` asks for it (else None).

    A model of one kind keeps the programs it always had, and the fork
    survives in here as `plain`, asked at TEN sites below for four things:
    its cache is one group, wrapped and unwrapped (2 sites); its period has
    no axis of its own, the scan's slice IS the layer and its aux needs no
    restacking (4); its layer index is the scan's own xs, over every stack
    (3); and the differentiated forward under `ragged_dot` scans the
    experts like every other weight (1). Scanning it as `[n, 1, ...]` with
    the pattern's index rule would delete all but the first; it changes
    every such program's text, so it takes the compiled comparison and
    paired chip runs (ROADMAP D5 (b'))."""
    cached = kv_caches is not None
    plain = config.attention_pattern is None
    caches = None
    if cached:
        caches = (tuple(kv_caches),) if plain else tuple(kv_caches)
    in_place = leaves_in_place(config, cached, layer_transform)
    # (the leaves of the kind that keeps a state and no pages)
    own = ("lightning" if config.linear_layers
           else "ssm" if config.mamba_layers else "conv")
    split = (config.conv_layers + config.linear_layers
             + config.mamba_layers) > 0

    def one_pass(x, caches, offset=None):
        """Every stack's scan, once; `offset`: what a looped model's pass
        adds to a layer's cache index."""
        before = [0, 0, 0]      # layers of each group in the stacks so far
        aux = None
        for stack in _layer_stacks(params):
            x, caches, stack_aux = one_stack(x, caches, before, offset, *stack)
            aux = aux if stack_aux is None else stack_aux
        return x, caches, aux

    def one_stack(x, caches, before, offset, tree, lora, start, count):
        """One stack's scan: `(x, caches, its stacked aux | None)`; `before`
        (the layers of each cache group in the stacks so far) moves on."""
        pattern = config.stack_pattern(start, count)
        p = len(pattern)
        n = count // p
        groups = [_kind_group(kind) for kind in pattern]
        per_period = [groups.count(g) for g in range(3)]
        rank = [groups[:j].count(groups[j]) for j in range(p)]
        # the expert kernels stay out of the xs: a period's slice of them is
        # p layers' experts copied a scan step (2.3 GB at SmallThinker's
        # widths, compiled for a described v5e, PR 34: the plain scoring
        # path beside a served model). `ragged_dot` addresses the stack in
        # place as the kernel does; its backward then transposes the whole
        # stack a kernel, which full fine-tuning of a pattern model at real
        # widths would have to repair (under LoRA the experts are frozen),
        # and which a model of one kind avoids by scanning them
        layer_xs, expert_stack = _expert_xs(
            tree, in_place=cached or not plain or use_expert_kernel(config))
        # a layer's leaves lie in up to four stacked trees: what every layer
        # has, its adapters, and in a model with conv layers what only the
        # attention layers and only the conv layers have, each stacked over
        # ITS layers, `per` of them a period
        trees = [layer_xs, lora, None, None]
        per = (p, p, per_period[0] + per_period[1], per_period[2])
        if split:
            shared = dict(layer_xs)
            trees[3] = {own: shared.pop(own, None)}
            trees[2] = {name: shared.pop(name) for name in _ATTENTION_LEAVES
                        if name in shared}
            trees[0] = shared
        if in_place:
            xs = [None] * 4
        elif plain:
            xs = trees
        else:
            xs = [jax.tree.map(lambda a, k=k: a.reshape((n, k) + a.shape[1:]),
                               t) for t, k in zip(trees, per)]
        index = jnp.arange(n, dtype=jnp.int32)
        if plain and not cached and expert_stack is None:
            index = None        # nothing of such a layer is addressed by it
        elif plain and cached and start:
            index = index + start       # over every layer: the cache's
        if offset is not None and index is not None:
            index = index + offset      # a looped model's slot a pass a layer
        first = tuple(before)

        def body(carry, inp):
            y, caches = carry
            shared_xs, lora_xs, i, attn_xs, conv_xs = inp
            period = (shared_xs, lora_xs, attn_xs, conv_xs)

            def leaves(which, k):
                """Tree `which` at the period's layer `k` of `per[which]`:
                out of the whole stacks, or of the scanned period."""
                if in_place:
                    return _at(trees[which], i * per[which] + k)
                if plain:
                    return period[which]
                return jax.tree.map(lambda a: a[k], period[which])

            auxes = []
            for j, kind in enumerate(pattern):
                g = groups[j]
                layer_params, lora_layer = leaves(0, j), leaves(1, j)
                if split:
                    # its place among the period's layers of its own leaves
                    at = rank[j] if g == 2 else sum(
                        q != 2 for q in groups[:j])
                    layer_params = {**layer_params,
                                    **leaves(3 if g == 2 else 2, at)}
                if layer_transform is not None:
                    layer_params, lora_layer = layer_transform(layer_params,
                                                               lora_layer)
                # the layer's index into its group's cache stacks, and its
                # place in its own stack, where its experts lie
                if plain:
                    layer = 0 if i is None else i
                    place = layer - start if cached and start else layer
                else:
                    layer = i * per_period[g] + rank[j]
                    if first[g]:    # (no `+ 0` in a one-stack model's program)
                        layer = layer + first[g]
                    place = i * p + j
                hybrid = kind == "hybrid"   # pages and a state at once
                y, cache, layer_aux = _layer_body(
                    config, y, LayerLeaves(layer_params, lora_layer,
                                           expert_stack, place, in_place),
                    layer, kind, views[g], caches[g] if cached else None,
                    cos, sin, lora_scale, attn_fn,
                    (views[2], caches[2] if cached else None)
                    if hybrid else None)
                if cached:
                    new = {g: cache[0], 2: cache[1]} if hybrid else {g: cache}
                    caches = tuple(new.get(k, c)
                                   for k, c in enumerate(caches))
                auxes.append(layer_aux)
            if cached and not cached_aux:
                return (y, caches), None
            return (y, caches), (auxes[0] if plain else jax.tree.map(
                lambda *a: jnp.stack(a), *auxes))

        if remat and not cached:
            body = _rematerialized(config, body)
        (x, caches), stack_aux = jax.lax.scan(
            body, (x, caches), (*xs[:2], index, *xs[2:]))
        if stack_aux is not None and not plain:     # [n / p, p, ...] -> [n, ...]
            stack_aux = jax.tree.map(
                lambda a: a.reshape((n * p,) + a.shape[2:]), stack_aux)
        for g in range(3):
            before[g] += n * per_period[g]
        return x, caches, stack_aux

    if config.loop_passes == 1:
        x, caches, aux = one_pass(x, caches)
    else:
        def pass_body(carry, t):
            y, c, _ = one_pass(*carry, _pass_cache_offset(config, t))
            return (_close_pass(config, params, y), c), None

        (x, caches), aux = jax.lax.scan(     # (no experts: `aux` is None)
            pass_body, (x, caches),
            jnp.arange(config.loop_passes, dtype=jnp.int32))
    if cached and plain:
        (caches,) = caches
    return x, caches, aux


def _pass_cache_offset(config: ModelConfig, t):
    """What pass `t` (traced, from 0) of a looped model adds to a layer's
    cache index: a slot a pass a layer, pass `t` of layer `l` at `t * L + l`
    (docs/OURO.md; with 0 the passes would share a slot a layer, the paper's
    "last-step reuse", which tests and benchmark/tools/loop_control.py lay
    in here to show that the comparison refuses it)."""
    return t * config.num_hidden_layers


def _close_pass(config: ModelConfig, params: dict, y):
    """The final norm, closing a pass of a looped model."""
    with jax.named_scope("norm"):
        return rms_norm(y, params["norm"], config.rms_norm_eps)


def _kind_views(config: ModelConfig, mask, q_slot, *, kv_caches=None, index=0,
                decode=None, verify=None, page_table=None, page_size=0,
                live=None, conv_ctx=None, write_at=None,
                identity_table=False, span=None) -> tuple:
    """The call's `KindView` a cache group, from what its entrypoint knows:
    one for a model without a pattern, else `(global, window[, state])`.

    `mask` [B, 1, Tq, Tk] is the call's; the window layers' also holds key
    slot j from query slot i unless i - window < j. `q_slot()` gives [B | 1,
    Tq], the cache slot (or sequence index) of each query (a function, so
    that a model without a window stages no op for it); keys count from 0
    along the mask's last axis. `decode=(start, filled)` / `verify=(first,
    fill)` are the bounds of a read by slots; the window layers' start no
    earlier than `window` - 1 slots before the FIRST query's slot (a decode
    step's `filled` is one past its query, a verify's `fill` is its first
    query). `page_table` is a model of one kind's [B, nb] table, a pattern
    model's `(global, window[, state rows])`, one a kind of cache: the
    window layers' is [B, nb] like the other, the ring of the row's window
    pages laid out over its logical blocks (sampler/paged/pages.py
    `RingPages.table`), so every read and write addresses it as any table;
    the state kind's entry is the rows themselves (`_conv_operator`).
    `kv_caches` says what each group's cache is and, for a decode step over
    pages under `use_paged_decode_kernel`, sizes the step's plans, made once
    here for every layer: the in-place read's work list a kind (its table,
    its pool, its bound) and the live-row write's where the pool's shapes
    take it (`_paged_row_kernel_takes`; elsewhere the row scatter), a plan
    a slot of `write_at` (`(index,)`, or a block forward's `block_length`
    slots from `index` on). `identity_table`: the table is the dense
    identity table and `index` one slot for every row (the one-jit
    rollout), where that write is `IDENTITY_SLOT` and needs no plan.
    `conv_ctx`: a thunk of `_conv_ctx(...)`, called last (the operations
    stand in the program in the order the entrypoints always staged them:
    the decode bounds, the masks, the verify bounds, the plans, the state's
    context). `span`: a thunk of a sparse layer's `(start, keys)`
    (`KindView.span`), called for a model with such layers only, whose decode
    step's read makes its own work list a layer (core/sala.py)."""
    plain = config.attention_pattern is None
    kinds = 1 if plain else 2
    window = 0 if plain else config.sliding_window
    tables, rows = [None] * kinds, None
    if page_table is not None and plain:
        tables = [page_table]
    elif page_table is not None:
        if (not isinstance(page_table, (tuple, list))
                or len(page_table) != kinds + bool(config.state_layers)):
            raise ValueError(
                "a model with a layer pattern takes page_table=(global table, "
                "window table[, state rows]), one a kind of cache (docs/SWA.md, "
                "docs/STATE.md)")
        tables = list(page_table[:2])
        rows = page_table[2] if config.state_layers else None
    groups = [None] * kinds
    if kv_caches is not None:
        groups = [kv_caches] if plain else list(kv_caches[:2])

    def near(bounds, first_query_past):
        first, bound = bounds
        lo = jnp.maximum(first, bound + first_query_past - window)
        return lo.astype(first.dtype), bound

    decodes, masks, verifies = [decode] * kinds, [mask] * kinds, [verify] * kinds
    if window and decode is not None:
        decodes[1] = near(decode, 0)
    if window:
        k_slot = jnp.arange(mask.shape[-1], dtype=jnp.int32)
        masks[1] = mask & (k_slot[None, None, None, :] > (
            q_slot().astype(jnp.int32)[:, None, :, None] - window))
    if window and verify is not None:
        verifies[1] = near(verify, 1)
    if decode is not None and config.kv_lora_rank and live is not None:
        # an MLA model's paged read walks the key blocks these bounds span
        # (core/mla.py): a row nobody listens to asks for none
        decodes = [(jnp.where(live, decode[0], mask.shape[-1]),
                    jnp.where(live, decode[1], 0))]
    plans = [None] * kinds
    if (decode is not None and page_table is not None
            and use_paged_decode_kernel(config)):
        from nanorlhf_tpu.ops.decode_attention import (
            paged_decode_plan, paged_pages_per_item,
        )
        from nanorlhf_tpu.ops.paged_cache_write import paged_write_plan

        if not config.sparse_layers:
            decodes = [paged_decode_plan(
                table, first, decode[1], page_size=page_size,
                num_pages=group[0].shape[1],
                pages_per_item=paged_pages_per_item(group[0]), live=live)
                for table, group, (first, _) in zip(tables, groups, decodes)]
        plans = [None if not _paged_row_kernel_takes(group, page_size)
                 else IDENTITY_SLOT if identity_table
                 else tuple(paged_write_plan(
                     table, at, page_size=page_size,
                     num_pages=group[0].shape[1], live=live)
                     for at in write_at or (index,))
                 for table, group in zip(tables, groups)]
    ctx = None if conv_ctx is None else conv_ctx().get("conv_ctx")

    def form(group):
        if group is None:
            return None
        if config.kv_lora_rank:
            return "latent"
        return "int8" if group[0].dtype == jnp.int8 else "exact"

    views = tuple(
        KindView(mask=m, cache=form(group), index=index, decode=d, verify=v,
                 table=table, page_size=page_size, write_plan=plan, live=live)
        for m, group, d, v, table, plan
        in zip(masks, groups, decodes, verifies, tables, plans))
    if config.sparse_layers:
        views = (views[0]._replace(span=span(), conv_ctx=ctx),) + views[1:]
    if config.state_layers:
        views += (KindView(
            mask=None, cache=None if kv_caches is None else "state",
            index=index, table=rows, conv_ctx=ctx, live=live),)
    return views


def unembedding(config: ModelConfig, params: dict):
    """`(weight, transposed)` for the fused hidden→logprob op
    (ops/fused_logprob.py): `(lm_head [D, V], False)`, or
    `(embed_tokens [V, D], True)` when tied. The tied leaf is handed over
    UNtransposed on purpose — the op contracts on the shared D axis either
    way, dW accumulates straight into `embed_tokens`, and its Pallas kernel
    reads vocab-row blocks; an `embed.T` view feeding a Pallas custom call
    would make XLA stage the full [D, V] transposed copy (custom-call
    operands are physical buffers; only XLA dots fold transposes)."""
    if config.tie_word_embeddings:
        return params["embed_tokens"], True
    return params["lm_head"], False


def unembedding_weight(config: ModelConfig, params: dict) -> jnp.ndarray:
    """The [D, V] unembedding matrix: `lm_head`, or `embed_tokens`ᵀ when
    tied. Under jit the transpose fuses into the consuming XLA matmul (dot
    dimension numbers), so no transposed copy materializes — and gradients
    flow back through the transpose to `embed_tokens` unchanged. That
    folding does NOT hold for Pallas custom calls: anything feeding
    ops/fused_logprob.py should use `unembedding()` + `transposed=` and
    skip the view entirely."""
    if config.tie_word_embeddings:
        return params["embed_tokens"].T
    return params["lm_head"]


def _final_norm(config: ModelConfig, params: dict, x: jnp.ndarray):
    """The model's final norm, where the layers' runner has not applied it:
    a looped model norms after every pass, the last one too (`_run_layers`),
    and its head takes that state as it is (docs/OURO.md)."""
    if config.loop_passes > 1:
        return x
    return rms_norm(x, params["norm"], config.rms_norm_eps)


def _logits(config: ModelConfig, params: dict, x: jnp.ndarray) -> jnp.ndarray:
    with jax.named_scope("head"):
        x = _final_norm(config, params, x)
        return _times(x @ unembedding_weight(config, params),
                      config.lm_head_multiplier)


def _embed(config: ModelConfig, params: dict, ids: jnp.ndarray) -> jnp.ndarray:
    with jax.named_scope("embed"):
        x = params["embed_tokens"][ids].astype(params["embed_tokens"].dtype)
        if config.embed_scale != 1.0:   # afmoe (muP): x sqrt(hidden_size)
            x = x * jnp.asarray(config.embed_scale, x.dtype)
        return x


# ---------------------------------------------------------------------------
# Public entrypoints
# ---------------------------------------------------------------------------

def model_forward(
    params: dict,
    config: ModelConfig,
    input_ids: jnp.ndarray,       # [B, T]
    attention_mask: jnp.ndarray,  # [B, T] bool/int, True = real token
    position_ids: jnp.ndarray,    # [B, T]
    lora_scale: float = 1.0,
    remat: bool = False,
) -> jnp.ndarray:
    """Full-sequence forward (training / logprob pass). Returns logits [B, T, V]."""
    x = _hidden_from_inputs(params, config, input_ids, attention_mask,
                            position_ids, lora_scale, remat)
    return _logits(config, params, x)


def _hidden_from_inputs(params, config, input_ids, attention_mask, position_ids,
                        lora_scale, remat, attn_fn=None, layer_transform=None,
                        router_stats=False, context_length=None):
    """embed → rope → causal+padding mask → scanned layers. The one copy of
    this recipe; every forward entrypoint goes through it.

    `attn_fn` overrides the attention contraction (sequence-parallel ring
    path); the local causal mask is then unused — the override builds its own
    mask from global positions.

    `router_stats=True` (expert models) returns `(x, stats)`: the per-row
    sums of ops/moe.py's `router_stats` over the real tokens, from this very
    forward (the scan emits each layer's router record; a few reductions,
    no second forward).

    `context_length`: the first so many slots are a prompt that was taken in
    as ONE call and every later token a decode step of its own. Only a
    sparse layer reads it (core/sala.py: a call's keys decide whether its
    queries select); None: the row is one call.
    """
    if attn_fn is not None:
        config.require("the sequence-parallel forward")
    attention_mask = attention_mask.astype(bool)
    x = _embed(config, params, input_ids)
    T = input_ids.shape[1]
    cos, sin = _rope(config, position_ids)
    if config.block_generation:
        mask = (_block_causal(config, position_ids)
                & attention_mask[:, None, None, :])
    else:
        causal = jnp.tril(jnp.ones((T, T), bool))
        mask = causal[None, None, :, :] & attention_mask[:, None, None, :]
    views = _kind_views(config, mask, lambda: jnp.arange(T)[None, :],
                        conv_ctx=lambda: _conv_ctx(config, attention_mask),
                        span=lambda: _mask_span(attention_mask,
                                                context_length))
    x, _, aux = _run_layers(config, params, x, cos, sin, views,
                            lora_scale=lora_scale, remat=remat, attn_fn=attn_fn,
                            layer_transform=layer_transform)
    if router_stats:
        from nanorlhf_tpu.ops.moe import router_stats as reduce_stats

        return x, reduce_stats(aux, attention_mask, config.num_experts)
    return x


def _mask_span(attention_mask, context_length=None) -> tuple:
    """`KindView.span` of rows whose real tokens are `attention_mask`'s: the
    first one's slot, and how many there are; with `context_length`, the
    keys of each QUERY's call [B, T]: the prompt's real tokens for a query
    among the first `context_length` slots, what the row holds up to itself
    for a later one (a decode step's)."""
    start = jnp.argmax(attention_mask, axis=1).astype(jnp.int32)
    if context_length is None:
        return start, jnp.sum(attention_mask, axis=1, dtype=jnp.int32)
    slot = jnp.arange(attention_mask.shape[1], dtype=jnp.int32)[None, :]
    prompt = jnp.sum(attention_mask[:, :context_length], axis=1,
                     dtype=jnp.int32)[:, None]
    return start, jnp.where(slot < context_length, prompt,
                            slot - start[:, None] + 1)


def _block_causal(config: ModelConfig, positions, key_positions=None):
    """The block-causal mask of a model that generates by blocks
    (docs/BLOCKDIFF.md), `[B, 1, Tq, Tk]` bool: the query at position i
    sees the key at position j iff `j // block <= i // block`; blocks are
    aligned to ABSOLUTE positions, wherever a left-padded row's tokens lie.
    `positions` [B, Tq] are the queries' (and, without `key_positions`
    [B, Tk], the keys')."""
    B_ = config.block_length
    keys = positions if key_positions is None else key_positions
    return ((keys // B_)[:, None, None, :] <= (positions // B_)[:, None, :, None])


def _conv_ctx(config: ModelConfig, valid=None, fresh=None) -> dict:
    """`{"conv_ctx": (valid, fresh)}`, what a call knows of its tokens for
    the state group's `KindView` (`_kind_views`) of a model with conv layers,
    and nothing for every other model. What a call has to COMPUTE comes as a
    thunk (`fresh` always does) and is computed for a model with conv layers
    only: an operation nobody reads still stands in the program of a loop's
    body, and every other model's programs stay the ones they were."""
    if not config.state_layers:
        return {}
    return {"conv_ctx": tuple(v() if callable(v) else v for v in (valid, fresh))}


def _padded_hidden(
    params: dict,
    config: ModelConfig,
    query_responses: jnp.ndarray,
    pad_token_id: int,
    lora_scale: float = 1.0,
    remat: bool = False,
    router_stats: bool = False,
    context_length: int | None = None,
) -> jnp.ndarray:
    """Shared padding recipe → pre-final-norm hidden states [B, T, D].

    attention_mask = (ids != pad); position_ids = cumsum(mask) - mask; padded
    ids replaced with 0 (`/root/reference/GRPO/grpo_trainer.py:90-120`). The
    single source of truth for both the policy logit pass and the value/RM
    score pass — their padding numerics must never drift apart.
    """
    input_ids, attention_mask, position_ids = padding_inputs(
        query_responses, pad_token_id
    )
    return _hidden_from_inputs(
        params, config, input_ids, attention_mask, position_ids, lora_scale,
        remat, router_stats=router_stats,
        # (the prompt was one call, each response token a step of its own)
        context_length=context_length)


def padding_inputs(query_responses: jnp.ndarray, pad_token_id: int):
    """(input_ids, attention_mask, position_ids) from padded token ids — the
    single copy of the reference's padding recipe, shared by every scorer
    (incl. the sequence-parallel paths in parallel/sp.py)."""
    attention_mask = query_responses != pad_token_id
    position_ids = jnp.cumsum(attention_mask, axis=1) - attention_mask.astype(jnp.int32)
    input_ids = jnp.where(attention_mask, query_responses, 0)
    return input_ids, attention_mask, position_ids


def padded_forward_logits(
    params: dict,
    config: ModelConfig,
    query_responses: jnp.ndarray,
    pad_token_id: int,
    lora_scale: float = 1.0,
    remat: bool = False,
    response_context_length: int | None = None,
    router_stats: bool = False,
) -> jnp.ndarray:
    """Padding-robust forward: the reference's shared `forward()` contract.

    `response_context_length=ctx` returns next-token logits for the response
    positions only — hidden states are sliced `[ctx-1:-1]` BEFORE the vocab
    projection, so the lm_head never runs over prompt positions (the
    reference slices logits after computing all of them,
    `GRPO/grpo_trainer.py:546`; at 152k vocab the discarded prompt logits
    are the single largest wasted tensor in the update pass). The shift-by-
    one next-token convention lives here, in one place. `router_stats` as in
    `padded_forward_hidden`: `(logits, stats)`.
    """
    x = _padded_hidden(params, config, query_responses, pad_token_id, lora_scale,
                       remat, router_stats=router_stats,
                       context_length=response_context_length)
    stats = None
    if router_stats:
        x, stats = x
    if response_context_length is not None:
        x = x[:, response_context_length - 1 : -1]
    logits = _logits(config, params, x)
    return (logits, stats) if router_stats else logits


def padded_forward_hidden(
    params: dict,
    config: ModelConfig,
    query_responses: jnp.ndarray,
    pad_token_id: int,
    lora_scale: float = 1.0,
    remat: bool = False,
    response_context_length: int | None = None,
    router_stats: bool = False,
) -> jnp.ndarray:
    """`padded_forward_logits` minus the vocab projection: FINAL-NORMED
    hidden states [B, T', D] — the input the fused hidden→logprob op
    (ops/fused_logprob.py) consumes together with `unembedding_weight`.

    `padded_forward_logits(p, c, qr, ...) ==
    padded_forward_hidden(p, c, qr, ...) @ unembedding_weight(c, p)` exactly:
    the response slice happens at the same point (before the head; the final
    RMSNorm is positionwise, so slicing before or after it is equivalent),
    and the shift-by-one next-token convention stays in one place.

    `router_stats=True` (expert models only) returns `(hidden, stats)`, the
    router's per-row sums over the real tokens (`_hidden_from_inputs`).
    """
    x = _padded_hidden(params, config, query_responses, pad_token_id, lora_scale,
                       remat, router_stats=router_stats)
    stats = None
    if router_stats:
        x, stats = x
    if response_context_length is not None:
        x = x[:, response_context_length - 1 : -1]
    with jax.named_scope("head"):   # its matmul is `fused_logprob`'s
        x = _final_norm(config, params, x)
    return (x, stats) if router_stats else x


def init_score_head(config: ModelConfig, key: jax.Array, num_labels: int = 1,
                    dtype=jnp.bfloat16) -> jnp.ndarray:
    """Score head [D, num_labels] — a value/reward model is the decoder with
    this head instead of lm_head (HF `AutoModelForSequenceClassification(
    num_labels=1)`, `/root/reference/PPO/ppo.py:280-287`)."""
    scale = 1.0 / jnp.sqrt(jnp.float32(config.hidden_size))
    return (jax.random.normal(key, (config.hidden_size, num_labels), jnp.float32) * scale).astype(dtype)


def score_forward(
    params: dict,
    config: ModelConfig,
    query_responses: jnp.ndarray,
    pad_token_id: int,
    lora_scale: float = 1.0,
    remat: bool = False,
) -> jnp.ndarray:
    """Per-position scores [B, T, num_labels] from a tree carrying "score".

    Same padding recipe as padded_forward_logits (shared `_padded_hidden`);
    hidden states are final-normed before the head (matching
    Qwen2ForSequenceClassification). Used for the PPO value pass
    (`PPO/ppo_trainer.py:630-634,732`) and RM-based rewards.
    """
    x = _padded_hidden(params, config, query_responses, pad_token_id, lora_scale, remat)
    with jax.named_scope("head"):
        x = _final_norm(config, params, x)
        return (x.astype(jnp.float32) @ params["score"].astype(jnp.float32))


def _latent_cache_shape(config: ModelConfig, rows: int, slots: int) -> tuple:
    """An MLA model's contiguous cache (core/mla.py): ONE array, `[c_kv |
    k_rope]` a token a layer under a single "head", `[L, B, 1, T_max, W]`,
    addressed by layer, row and slot like the K/V arrays (the paged cache is
    two lane-aligned leaves of the same bytes: `mla.paged_cache_shapes`).
    It has no int8 form: the latent is already 1/36 of A.X-K1's per-head K
    and V, and absmax-per-token scales over a 576-wide mix of a normed
    latent and a rotary key are not the per-head scheme's numerics."""
    if config.kv_cache_quant == "int8":
        raise ValueError(
            "kv_cache_quant='int8' on a latent (MLA) cache is not "
            "implemented: the cache is one latent a token, not per-head K "
            "and V (docs/MLA.md)")
    return (config.num_hidden_layers, rows, 1, slots, config.latent_width)


def _int8_cache(config: ModelConfig) -> bool:
    """Whether the (k, v) cache being built stores int8 values and scales:
    where its spec is chosen (a latent cache has raised before,
    `_latent_cache_shape`)."""
    if config.kv_cache_quant != "int8":
        return False
    config.require("kv_cache_quant='int8'")
    return True


def _pattern_caches(config: ModelConfig) -> tuple:
    """(global layers, window layers) of a pattern model: the depths of its
    two groups of cache stacks."""
    return (config.page_layers - config.window_layers, config.window_layers)


def _state_group(config: ModelConfig, rows: int, dtype) -> tuple:
    """The state group of a cache, in one of its two forms (docs/STATE.md);
    nothing for a model without a state. Conv layers: `((state,),)`, `[conv
    layers, K - 1, rows, D]`, a row's last K - 1 values of `g` a layer,
    oldest first (`_conv_operator`). Layers with a state-space mixer
    (`config.ssm_layers`: beside an attention or alone): `((tail, S),)`, the
    convolution's tail `[layers, K - 1, rows, I + 2 G N]` likewise and the
    recurrence's state `[layers, rows, H, P, N]` in FLOAT32 whatever the
    cache's type (`_ssm_operator`; docs/SSM.md). Not a page: its size does
    not grow with the row, no table addresses it, and a row's is at the
    row's own index."""
    if config.linear_layers:    # (docs/SALA.md: a matrix a head, no tail)
        H, hd = config.lightning_heads, config.lightning_head_dim
        return ((jnp.zeros((config.linear_layers, rows, H, hd, hd),
                           jnp.float32),),)
    if config.ssm_layers:
        L = config.ssm_layers
        return ((jnp.zeros((L, config.ssm_conv - 1, rows,
                            config.ssm_conv_width), dtype),
                 jnp.zeros((L, rows, config.ssm_heads, config.ssm_head_dim,
                            config.ssm_state), jnp.float32)),)
    if not config.conv_layers:
        return ()
    return ((jnp.zeros((config.conv_layers, config.conv_L_cache - 1, rows,
                        config.hidden_size), dtype),),)


def _with_compressed(config: ModelConfig, groups: tuple, slots: int,
                     dtype, paged: bool = False) -> tuple:
    """A pattern model's page groups, the global one with a third leaf where
    its layers are sparse (docs/SALA.md): the compressed keys, `slots //
    sparse_kernel_stride` a row (contiguous: laid out as K is) or a page
    (`paged`: a page's heads and entries on one axis;
    `sala.compress_write`)."""
    if not config.sparse_layers:
        return groups
    stride = config.sparse_kernel_stride
    if slots % stride:
        raise ValueError(
            f"a cache of {slots} slots a row or page does not hold whole "
            f"strides of {stride} compressed keys (sparse_kernel_stride)")
    L, lead, KV, _, hd = groups[0][0].shape
    shape = ((L, lead, KV * (slots // stride), hd) if paged
             else (L, lead, KV, slots // stride, hd))
    return ((*groups[0], jnp.zeros(shape, dtype)),) + groups[1:]


def _cache_heads(config: ModelConfig) -> tuple:
    """(KV heads, head width) as the cache holds them (`_pack_heads`)."""
    pack = config.kv_head_pack
    return config.num_key_value_heads // pack, config.actual_head_dim * pack


def init_kv_cache(
    config: ModelConfig, batch: int, max_len: int, dtype=jnp.bfloat16
) -> tuple[jnp.ndarray, ...]:
    """Stacked KV cache.

    Exact: (k, v), each [L, B, KV, max_len, hd]; an MLA model: (latent,)
    (`_latent_cache_shape`).
    kv_cache_quant="int8": (k_q, k_s, v_q, v_s) — int8 values plus bf16
    per-token-per-head scales carried SUBLANE-EXPANDED as [L, B, KV, 8,
    max_len]: the decode kernel's (1, 1, 8, block_k) scale blocks are
    Mosaic-legal because the 8 SPANS its array dimension (the
    equal-to-the-dim clause; bf16's native sublane tile is 16, so the
    divisibility clause alone would not cover it), with the sequence on
    the lane axis — same recipe as the flash kernel's mask
    (ops/attention.py).
    """
    if config.kv_lora_rank:
        return (jnp.zeros(_latent_cache_shape(config, batch, max_len), dtype),)
    int8 = _int8_cache(config)
    if config.attention_pattern is not None:
        # both groups whole: correct by mask, no slot saved (the paged pool
        # is where a window layer keeps a window's pages only)
        KV, hd = _cache_heads(config)
        groups = tuple(
            tuple(jnp.zeros((n, batch, KV, max_len, hd), dtype) for _ in "kv")
            for n in _pattern_caches(config))
        return _with_compressed(config, groups, max_len, dtype) + \
            _state_group(config, batch, dtype)
    shape = (
        config.cache_layers,    # (a looped model: a slot a pass a layer)
        batch,
        config.num_key_value_heads,
        max_len,
        config.actual_head_dim,
    )
    if int8:
        sshape = shape[:3] + (8, max_len)
        return (
            jnp.zeros(shape, jnp.int8), jnp.ones(sshape, jnp.bfloat16),
            jnp.zeros(shape, jnp.int8), jnp.ones(sshape, jnp.bfloat16),
        )
    return jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)


def init_paged_kv_cache(
    config: ModelConfig, num_pages: int, page_size: int, dtype=jnp.bfloat16,
    state_rows: int = 0,
) -> tuple[jnp.ndarray, ...]:
    """Paged KV cache: a global page pool shared by every row, addressed
    through a per-row block table (sampler/paged/pages.py).

    Exact: (k, v), each [L, num_pages, KV, page_size, hd]; an MLA model:
    (c_kv, k_rope) pools (`mla.paged_cache_shapes`).
    kv_cache_quant="int8": (k_q, k_s, v_q, v_s) with scale pools
    [L, num_pages, KV, 8, page_size] — the sublane-expanded layout of
    `init_kv_cache`, per page instead of per row.

    Same tuple arity and leading layer axis as the contiguous cache, so
    `_run_layers` carries it through the layer scan the same way (the stack
    is the scan's carry; a layer scatters its new tokens in at
    `(layer, page, head, offset)` and gathers its rows' pages back out); the
    block table is NOT part of the cache tuple (it is shared across layers
    and rides as a separate argument).

    A pattern model's is groups of such stacks, `num_pages = (global,
    window)`, and with conv layers a third group that is no pool: the state
    of `state_rows` rows (`_state_group`).
    """
    if config.kv_lora_rank:
        from nanorlhf_tpu.core import mla

        _latent_cache_shape(config, num_pages, page_size)    # what raises
        return tuple(jnp.zeros(shape, dtype) for shape in
                     mla.paged_cache_shapes(config, num_pages, page_size))
    int8 = _int8_cache(config)
    if config.attention_pattern is not None:
        # a pool a kind, `num_pages = (global pages, window pages)`: the
        # window layers' holds a window's pages a row, not a row's budget
        if isinstance(num_pages, int):
            config.require("one page pool (num_pages an int)",
                           "a page pool of one kind")
        if config.state_layers and state_rows <= 0:
            raise ValueError(
                "a model with conv, state-space or linear-attention layers "
                f"({config.model_type}) keeps a state a row beside its "
                "pages: init_paged_kv_cache(..., state_rows=rows) "
                "(docs/STATE.md)")
        KV, hd = _cache_heads(config)
        groups = tuple(
            tuple(jnp.zeros((n, pages, KV, page_size, hd), dtype)
                  for _ in "kv")
            for n, pages in zip(_pattern_caches(config), num_pages))
        return _with_compressed(config, groups, page_size, dtype, True) + \
            _state_group(config, state_rows, dtype)
    shape = (
        config.cache_layers,
        num_pages,
        config.num_key_value_heads,
        page_size,
        config.actual_head_dim,
    )
    if int8:
        sshape = shape[:3] + (8, page_size)
        return (
            jnp.zeros(shape, jnp.int8), jnp.ones(sshape, jnp.bfloat16),
            jnp.zeros(shape, jnp.int8), jnp.ones(sshape, jnp.bfloat16),
        )
    return jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)


def _quantize_kv(x: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """[B, KV, T, hd] -> (int8 [B, KV, T, hd], bf16 scales [B, KV, 8, T]).

    Scales are STORED bf16 (the sublane-replicated layout already costs 8x,
    so dtype is where the scale stream's bandwidth goes) and quantization
    divides by the bf16-ROUNDED scale, keeping dequantization exact with
    respect to what the cache actually holds.
    """
    B, KV, T, hd = x.shape
    xf = x.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(xf), axis=-1)                 # [B, KV, T]
    scale = jnp.maximum(absmax / 127.0, 1e-8)
    scale = scale.astype(jnp.bfloat16)
    q = jnp.clip(
        jnp.round(xf / scale[..., None].astype(jnp.float32)), -127, 127
    ).astype(jnp.int8)
    scale8 = jnp.broadcast_to(scale[:, :, None, :], (B, KV, 8, T))
    return q, scale8


def _dequantize_kv(q: jnp.ndarray, scale8: jnp.ndarray, dtype) -> jnp.ndarray:
    """Inverse of _quantize_kv (XLA fallback path)."""
    return (
        q.astype(jnp.float32)
        * scale8[:, :, 0, :, None].astype(jnp.float32)
    ).astype(dtype)


def prefill(
    params: dict,
    config: ModelConfig,
    input_ids: jnp.ndarray,       # [B, T_prompt]
    attention_mask: jnp.ndarray,  # [B, T_prompt]
    kv_caches: tuple[jnp.ndarray, jnp.ndarray],  # from init_kv_cache, T_max >= T_prompt
    lora_scale: float = 1.0,
    page_table=None,              # [B, nb] int32 (paged layout; see init_paged_kv_cache)
    page_size: int = 0,
    logical_len: int = 0,         # paged: the logical cache width T_max (mask
                                  # width must match the contiguous run
                                  # bit-for-bit, so it cannot be inferred
                                  # from the pool shape)
):
    """Prompt ingestion: fills the KV cache, returns (last-position logits, caches).

    Prompts are assumed *left-padded* to a common length (sampler contract), so
    the last position is the last prompt token for every row.
    """
    B, T = input_ids.shape
    if page_table is not None:
        T_max = logical_len if logical_len else (
            jax.tree.leaves(page_table)[0].shape[1] * page_size)
    else:
        T_max = jax.tree.leaves(kv_caches)[0].shape[3]
    attention_mask = attention_mask.astype(bool)
    position_ids = jnp.cumsum(attention_mask, axis=1) - attention_mask.astype(jnp.int32)
    x = _embed(config, params, jnp.where(attention_mask, input_ids, 0))
    cos, sin = _rope(config, position_ids)
    if config.block_generation:
        mask = (_block_causal(config, position_ids)
                & attention_mask[:, None, None, :])
    else:
        causal = jnp.tril(jnp.ones((T, T), bool))
        # queries attend over cache positions [0, T); the rest of T_max is masked
        mask = (causal[None, None, :, :] & attention_mask[:, None, None, :])
    mask_full = jnp.zeros((B, 1, T, T_max), bool).at[:, :, :, :T].set(mask)
    views = _kind_views(
        config, mask_full, lambda: jnp.arange(T)[None, :], kv_caches=kv_caches,
        page_table=page_table, page_size=page_size,
        # (a prompt starts its rows: whatever state they held is not theirs)
        conv_ctx=lambda: _conv_ctx(config, attention_mask,
                                   lambda: jnp.ones((B,), bool)),
        span=lambda: _mask_span(attention_mask))
    x, new_caches, _ = _run_layers(config, params, x, cos, sin, views,
                                   kv_caches, lora_scale)
    logits = _logits(config, params, x[:, -1:, :])[:, 0, :]
    return logits, new_caches


def decode_step(
    params: dict,
    config: ModelConfig,
    token: jnp.ndarray,           # [B] current token
    position: jnp.ndarray,        # [B] its absolute position id
    cache_index,                  # slot to write KV into: scalar, or per-row
                                  # [B] (continuous-batching rows advance at
                                  # different rates)
    key_mask: jnp.ndarray,        # [B, T_max] bool: which cache slots are valid (incl. this one)
    kv_caches: tuple[jnp.ndarray, jnp.ndarray],
    lora_scale: float = 1.0,
    page_table=None,              # [B, nb] int32 (paged layout)
    page_size: int = 0,
    live=None,                    # [B] bool: rows whose logits the caller
                                  # uses (None: all). The paged in-place read
                                  # skips the others and an expert layer
                                  # dispatches them to no expert
    count_experts: bool = False,  # a model with expert layers: also
                                  # return the held experts its rows reach
                                  # (the live ones), summed over the layers
    extent: int | None = None,    # static: no row has a valid slot at or
                                  # beyond it, so the XLA read of a
                                  # contiguous cache stops there (None: the
                                  # whole cache; `decode_read_extents`)
    want_logits: bool = True,     # False: the final hidden state [B, D] as
                                  # the head (`_logits`) would take it, for a
                                  # caller that scores some of the rows
    identity_table: bool = False,  # static: `page_table` is the dense
                                  # identity table (`pages.full_table`) and
                                  # `cache_index` a scalar, every row's
):
    """One autoregressive decode step. Returns (logits [B, V], new caches),
    and with `count_experts` a third, [] int32: `moe_mlp`'s `reached`, summed
    over the layers."""
    config.require("decode_step")
    B = token.shape[0]
    if extent is not None and extent < key_mask.shape[1]:
        # the mask's width is what the XLA read goes by (`_attention_read`); the
        # cache write below addresses the full stack as ever
        key_mask = key_mask[:, :extent]
    x = _embed(config, params, token)[:, None, :]
    cos, sin = _rope(config, position[:, None])
    mask = key_mask[:, None, None, :]  # [B, 1, 1, T_max]
    # valid cache slots form the contiguous range [start, cache_index+1):
    # left-pad offset up to the slot just written (sampler sets it True before
    # the call) — the bounds the prefix-reading Pallas decode kernel needs
    start = jnp.argmax(key_mask, axis=1).astype(jnp.int32)
    filled = jnp.broadcast_to(
        jnp.asarray(cache_index, jnp.int32) + 1, (B,))
    views = _kind_views(
        config, mask, lambda: (filled - 1)[:, None], kv_caches=kv_caches,
        index=cache_index, decode=(start, filled), page_table=page_table,
        page_size=page_size, identity_table=identity_table,
        # (an expert layer dispatches the rows someone listens to only, and
        # the in-place paged read and write skip the others)
        live=live,
        # (a row nobody listens to leaves its state as it was: it may be a
        # chunked admission between two of its pieces)
        conv_ctx=lambda: _conv_ctx(
            config, lambda: None if live is None else live[:, None]),
        # (a decode step's call is the row as it stands)
        span=lambda: (start, filled - start))
    x, new_caches, aux = _run_layers(
        config, params, x, cos, sin, views, kv_caches, lora_scale,
        # (the experts its rows reach, where the caller asks)
        cached_aux=count_experts)
    logits = (_logits(config, params, x) if want_logits else x)[:, 0, :]
    if count_experts:
        return logits, new_caches, jnp.sum(aux["reached"])
    return logits, new_caches


# The positions `decode_verify(logits_at=)` hands the head for the one a row
# that is sampled: a sublane's worth that ends at it. A one-row product is no
# matmul to the TPU's compiler: it multiplies in bf16 on the vector unit and
# sums the rounded products, 1.6e-3 of the logits' scale from the row the
# whole bucket's matmul gave. Eight rows are that matmul, bit for bit, at the
# same weight stream (3.73 against 3.61 ms at Falcon-H1's 5,120 x 261,120
# head, 14.79 over 1,024 positions: PERF.md section 6, PR 56).
_HEAD_ROWS = 8


def decode_verify(
    params: dict,
    config: ModelConfig,
    tokens: jnp.ndarray,          # [B, Tq] candidates: last accepted + k drafts
    positions: jnp.ndarray,       # [B, Tq] their absolute position ids
    fill: jnp.ndarray,            # [B] cache slot of tokens[:, 0] (per-row!)
    key_mask: jnp.ndarray,        # [B, T_max] valid slots BEFORE this call
                                  # (excludes the candidate slots)
    kv_caches: tuple[jnp.ndarray, ...],
    lora_scale: float = 1.0,
    page_table=None,              # [B, nb] int32 (paged layout)
    page_size: int = 0,
    want_logits: bool = True,
    token_valid=None,             # [B, Tq] bool: the real candidates (None:
                                  # all). Only a model with conv layers
                                  # reads it: its state stops at a row's
                                  # last real token (`_conv_operator`)
    call_keys=None,               # [B] int32: the keys of the call these
                                  # tokens are a piece of (a prompt's whole
                                  # length; None: what the row holds after
                                  # them). Only a sparse layer reads it
                                  # (core/sala.py: `sparse_dense_len`)
    logits_at=None,               # [B] int32: the ONE candidate a row whose
                                  # logits the caller samples (None: all Tq).
                                  # The head then runs over `_HEAD_ROWS`
                                  # positions that end at it, not over Tq
):
    """Batched k-token verification for speculative decode
    (sampler/speculative.py): one small-T causal forward over Tq = k+1
    candidate tokens against the cache — the prefill attention recipe at
    decode granularity, so the dominant per-step weight stream is amortized
    over every candidate. Candidate KV is written at per-row slots
    [fill, fill+Tq) (accepted rows advance at different rates, hence the
    [B]-shaped slot index); query i attends to `key_mask` plus candidates
    0..i. Rejected candidates leave garbage KV in slots the caller never
    marks valid — the next verify overwrites them. On the paged layout a
    candidate write may straddle two pages; the generic table-routed scatter
    handles that, and writes past the row's page budget drop (those
    candidates are beyond `max_tokens` and are truncated before emission —
    docs/PAGED_CACHE.md walks the bound). Returns
    (logits [B, Tq, V], new caches): logits[:, i] is the next-token
    distribution after consuming candidates 0..i, equal to a chain of
    `decode_step` calls over the same tokens to float32 roundoff (test-pinned
    on the CPU mesh at 1e-5 of the logits' scale; not bit for bit: the T = 1
    and the T = k+1 forwards are two compiled programs).

    `want_logits=False` skips the lm_head matmul and returns
    (None, new caches) — the chunked-prefill path (sampler/paged/session.py)
    runs every non-final prompt chunk purely for its KV writes, and at LLM
    vocabularies the unread [B, Tq, V] projection would dominate the chunk.
    `logits_at` [B] returns (logits [B, V], new caches), row b's those of
    candidate `logits_at[b]`: an admission's closing forward
    (serving/radix.py::suffix_logits) samples one position of its bucket, so
    the head is a weight stream over `_HEAD_ROWS` positions a row and no
    [B, Tq, V] product.
    """
    B, Tq = tokens.shape
    # the logical width is the key_mask width — equal to the slab's T_max on
    # the contiguous layout, and the only meaningful width on the paged one
    T_max = key_mask.shape[1]
    key_mask = key_mask.astype(bool)
    x = _embed(config, params, tokens)
    cos, sin = _rope(config, positions)
    slot = jnp.arange(T_max)[None, None, :]                  # [1, 1, T_max]
    qi = jnp.arange(Tq)[None, :, None]                       # [1, Tq, 1]
    if config.block_generation:
        # block-causal inside the candidates (a prompt's piece or suffix,
        # docs/BLOCKDIFF.md): query i sees the candidates up to the END of
        # its own block, by absolute position
        bl = config.block_length
        ends = (positions // bl + 1) * bl - positions[:, :1]     # [B, Tq]
        cand = ((slot >= fill[:, None, None])
                & (slot < fill[:, None, None]
                   + jnp.minimum(ends, Tq)[:, :, None]))
    else:
        cand = ((slot >= fill[:, None, None])
                & (slot <= fill[:, None, None] + qi))
    mask = (key_mask[:, None, :] | cand)[:, None, :, :]      # [B, 1, Tq, T_max]
    # first valid slot; a row with no valid prefix (a cold serving admission
    # starts at its first real token) begins at its own candidates
    start = jnp.where(key_mask.any(axis=1), jnp.argmax(key_mask, axis=1),
                      fill).astype(jnp.int32)
    fill = fill.astype(jnp.int32)
    views = _kind_views(
        config, mask, lambda: fill[:, None] + jnp.arange(Tq)[None, :],
        kv_caches=kv_caches, index=fill, verify=(start, fill),
        page_table=page_table, page_size=page_size,
        # a row with no valid slot before its candidates starts here
        conv_ctx=lambda: _conv_ctx(config, token_valid,
                                   lambda: ~key_mask.any(axis=1)),
        span=lambda: (start, (fill + Tq - start) if call_keys is None
                      else call_keys.astype(jnp.int32)))
    x, new_caches, _ = _run_layers(config, params, x, cos, sin, views,
                                   kv_caches, lora_scale)
    if not want_logits:
        return None, new_caches
    if logits_at is not None:
        # before the head: its norm, weight and multiplier see the kept rows
        n = min(_HEAD_ROWS, Tq)
        at = logits_at.astype(jnp.int32)
        first = jnp.clip(at - (n - 1), 0, Tq - n)
        x = jax.vmap(lambda row, i: jax.lax.dynamic_slice_in_dim(row, i, n))(
            x, first)
        logits = _logits(config, params, x)                  # [B, n, V]
        return jax.vmap(lambda row, i: jax.lax.dynamic_index_in_dim(
            row, i, keepdims=False))(logits, at - first), new_caches
    return _logits(config, params, x), new_caches


def block_forward(
    params: dict,
    config: ModelConfig,
    tokens: jnp.ndarray,          # [B, Tb] the block: its tokens unmasked so
                                  # far, `mask_token_id` elsewhere
    positions: jnp.ndarray,       # [B, Tb] their absolute position ids
    fill: jnp.ndarray,            # [B] cache slot of tokens[:, 0] (per-row)
    key_mask: jnp.ndarray,        # [B, T_max] the COMMITTED slots (the
                                  # prompt's whole blocks and every block
                                  # committed since: one range a row)
    kv_caches: tuple[jnp.ndarray, ...],
    lora_scale: float = 1.0,
    page_table=None,              # [B, nb] int32 (paged layout)
    page_size: int = 0,
    live=None,                    # [B] bool: rows whose logits the caller
                                  # uses (None: all)
    count_experts: bool = False,
    want_logits: bool = True,     # False: the final hidden state
                                  # [B, Tb, D] as the head would take it
):
    """One forward of a model that generates by blocks (docs/BLOCKDIFF.md)
    over ONE block a row, `decode_verify`'s successor for it: the block's
    Tb = `block_length` tokens are written at slots `[fill, fill + Tb)` and
    every query sees the committed slots (`key_mask`) plus ALL Tb slots of
    its block, so the queries of a row share one key set. The write is
    PROVISIONAL: the caller leaves `key_mask` alone after a denoise forward
    (the next forward of the block overwrites the same slots with the
    tokens as they then stand) and marks the Tb slots valid only after the
    COMMIT forward, run on the fully unmasked block. Rows stand at different
    steps of different blocks; a row not `live` reads nothing, reaches no
    expert and its logits are discarded. Returns `(logits [B, Tb, V], new
    caches)`: position i's logits predict position i's OWN token (no
    shift); with `count_experts` also the held experts the live rows
    reached, summed over the layers."""
    if not config.block_generation:
        raise ValueError("block_forward is the forward of a model that "
                         "generates by blocks (config.block_length > 0)")
    B, Tb = tokens.shape
    T_max = key_mask.shape[1]
    key_mask = key_mask.astype(bool)
    fill = fill.astype(jnp.int32)
    x = _embed(config, params, tokens)
    cos, sin = _rope(config, positions)
    slot = jnp.arange(T_max)[None, :]
    block = (slot >= fill[:, None]) & (slot < fill[:, None] + Tb)
    mask = jnp.broadcast_to((key_mask | block)[:, None, None, :],
                            (B, 1, Tb, T_max))
    # one range a row: from its first committed slot (its own block's first,
    # where nothing is committed yet) to the block's last
    start = jnp.where(key_mask.any(axis=1), jnp.argmax(key_mask, axis=1),
                      fill).astype(jnp.int32)
    views = _kind_views(
        config, mask, lambda: fill[:, None] + jnp.arange(Tb)[None, :],
        kv_caches=kv_caches, index=fill, decode=(start, fill + Tb),
        page_table=page_table, page_size=page_size, live=live,
        # the block's write on a TPU: the live rows' slot i, for each of
        # the block's Tb slots, through the decode step's live-row kernel
        # (the row scatter elsewhere: at 4 tokens x 4 heads a row it is
        # what `_paged_cache_update` picks, and on the chip it ran under no
        # scope at 0.87 ms a forward, PERF.md PR 46)
        write_at=tuple(fill + i for i in range(Tb)))
    x, new_caches, aux = _run_layers(
        config, params, x, cos, sin, views, kv_caches, lora_scale,
        cached_aux=count_experts)
    logits = _logits(config, params, x) if want_logits else x
    if count_experts:
        return logits, new_caches, jnp.sum(aux["reached"])
    return logits, new_caches
