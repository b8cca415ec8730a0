"""chip_smoke.py — the quickest proof that the system still starts on the chip.

    python chip_smoke.py              # one chip:   kernels, train, serve
    python chip_smoke.py --multichip  # four chips: sharded GRPO vs one device
    python chip_smoke.py --olmoe      # one chip:   the sparse-expert layer
    python chip_smoke.py --axk1       # one chip:   latent attention + a chip's share
    python chip_smoke.py --falcon-h1  # one chip:   a state-space mixer beside attention
    python chip_smoke.py --sala       # one chip:   lightning and sparse-attention layers

One process. It pins no platform: the first thing it does after `import jax`
is read `jax.devices()`, print what it found, and exit non-zero unless that is
a TPU. Every phase raises on failure and nothing catches it, so the exit code
is 0 only when every phase passed. Each phase prints one JSON object; the last
line of a passing run is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

Phases of the default run, at the published widths AND depth of Qwen2.5-1.5B
(28 layers, vocab 151936; bf16 weights random from the seed — the launcher's
offline mode, no checkpoint and no network):

- kernels: every Pallas kernel `auto` can select, compiled on the chip and
  compared with the XLA reference that sits next to it in `nanorlhf_tpu/ops/`.
- train:   the GRPO launcher's own path (`entrypoints.grpo.build_config()` →
  `entrypoints.common.run(cfg)`) for a few updates with only sizes overridden,
  then: finite loss / grad-norm / KL, step-1 KL ≈ 0, no compile after the
  first update, and the first batch's policy logprobs from the `auto` path
  (Pallas flash + Pallas fused logprob) against the same batch scored with XLA
  attention and the lax scan — each measured against a float32 reference,
  which is what says how far apart two bf16 paths may be.
- serve:   the trained parameters behind `ServingEngine` + `ServingGateway`
  on a loopback port: eight `POST /generate` requests, a radix hit on a shared
  prefix, `/metrics` that parses, and one greedy answer against `generate()`.

`--multichip` runs none of those: GRPO on `MeshConfig(data=1, fsdp=2,
tensor=2)` over four chips, then the placement, memory-balance, HLO and
sharded-vs-one-device logprob checks of `phase_multichip`.

`--olmoe` runs none of those either: OLMoE-1B-7B at its published widths and
the benchmark cell's depth (`benchmark/configs/olmoe-1b-7b.json`, through
`ModelConfig.from_hf_config`), bf16 weights from the seed with a non-zero
adapter, against `benchmark/harness/reference_olmoe.py` in float32 at
`highest`: scoring logprobs of a seeded sample, then prefill + decode steps
through the contiguous cache (teacher-forced) and through the paged
`DecodeSession` (greedy), each held to what the plain bf16 forward itself
loses against float32, measured in the run (`phase_olmoe`).

`--axk1` likewise runs A.X-K1 alone, as one chip of sixteen holds it
(`benchmark/configs/axk1-ep16.json`: published widths, 1 + 6 layers, 12 of
192 experts, an eighth of the vocabulary; 9.7 GB of bf16 weights), against
`benchmark/harness/reference_axk1.py`: the scoring forward, then a prefill
of a thousand tokens (the expanded form) and teacher-forced single-token
steps through the contiguous latent cache (the absorbed form), each held to
what the plain bf16 forward loses against float32 (`phase_axk1`). The paged
session at these widths is the benchmark cell `serve-axk1-docqa` itself.

`--falcon-h1` runs Falcon-H1 alone, as one pipeline stage holds it
(`benchmark/configs/falcon-h1-34b-l5.json`: published widths, 5 layers, the
whole vocabulary; 9.65 GB of bf16 weights under the file's `assumed.init`),
against `benchmark/harness/reference_falcon_h1.py`: the scoring forward (the
scan in chunks over the whole row), then a prefill in TWO pieces whose second
takes both state leaves over and teacher-forced single-token steps through
the contiguous cache (`phase_falcon_h1`). The paged session at these widths
is the benchmark cell `serve-falcon-h1-assist` itself.

`--sala` runs MiniCPM-SALA alone, as one pipeline stage holds it
(`benchmark/configs/minicpm-sala-l8.json`: published widths, 8 layers, the
whole vocabulary; 5.64 GB of bf16 weights under the file's `assumed.init`),
against `benchmark/harness/reference_sala.py`: rows of which ONE is past
`dense_len` (so that both branches of the sparse layer run), the scoring
forward, then a prefill in two pieces whose second takes the lightning state
and the compressed keys over and teacher-forced single-token steps, each a
selection and a pass over the state, through the contiguous cache
(`phase_sala`). The paged session at these widths is the benchmark cell
`serve-sala-docchat` itself.

Sizes live in `Sizes`; a rehearsal on the CPU imports this module and passes
smaller ones (tests and scratch scripts steer, the program grows no option).
jax is imported inside `main()`, not at module level: the reward side spawns
children that re-import `__main__`, and none of them may touch the chip.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import re
import shutil
import sys
import threading
import time
import urllib.request

ROOT = os.path.dirname(os.path.abspath(__file__))

KERNEL_REL_TOL = 0.02        # bf16 inputs, f32 accumulation (max|a-b| / max|b|)
# "bf16 tolerance" for logprobs of the whole model is measured, not guessed:
# two bf16 paths are each compared with a float32 reference, and the one under
# test may be this much further from it than the plain one (mean, max).
# Random 28-layer weights put either bf16 path ~0.15 nats/token from float32.
BF16_SLACK = (1.5, 2.0)
KL_STEP1_TOL = 1e-2          # k3 KL per token, policy == reference at step 1
PEAK_BALANCE = 1.5           # max/min peak bytes across the four chips


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What the smoke overrides in the launcher's config, and the shapes of
    the other phases. Defaults are the chip run."""
    # train / multichip: sizes only — model, LoRA r=64, sample_n=4, KL, lr and
    # the monolithic contiguous rollout stay as entrypoints/grpo.py has them
    model: str = "Qwen/Qwen2.5-1.5B-Instruct"  # not on disk → qwen2_1_5b()
    response_length: int = 512     # prompt+response crosses _FLASH_AUTO_MIN_T
    per_device_batch: int = 2      # x grad_accum x mini_batches = 8 prompts
    grad_accum: int = 2
    mini_batches: int = 2
    updates: int = 3
    multichip_updates: int = 2
    # kernels
    flash_bt: tuple = (2, 1024)    # 2x2 blocks of 512: cross-block carry
    decode_bt: tuple = (8, 2048)   # _DECODE_AUTO_MIN_T: where auto picks it
    verify_queries: int = 4        # spec_k=3 candidates + the accepted token
    fused_rows: int = 1024
    # serve (and the paged kernel twins)
    page_size: int = 128           # the q8 scale block needs 128 lanes
    prompt_len: int = 128
    max_new_tokens: int = 64
    rows: int = 8
    shared_prefix: int = 100
    seed: int = 0
    # olmoe: the benchmark's configuration file, or a tiny preset to rehearse
    olmoe_config: str = "benchmark/configs/olmoe-1b-7b.json"
    olmoe_rows: int = 8            # 256 greedy tokens: a maximum over 64 swings
    olmoe_prompt: int = 736        # prefill; + olmoe_decode = 768, the cell's row
    olmoe_decode: int = 32
    olmoe_context: int = 256       # scoring: responses start here
    olmoe_last: int = 128          # logits compared on the last positions
    # axk1: the benchmark's configuration file, or a tiny one to rehearse
    axk1_config: str = "benchmark/configs/axk1-ep16.json"
    axk1_rows: int = 2
    axk1_prompt: int = 1000        # prefill: past one token block of the share
    axk1_decode: int = 24          # absorbed single-token steps
    axk1_last: int = 64            # logits compared on the last positions
    # falcon-h1: the benchmark's configuration file, or a tiny one to rehearse
    fh1_config: str = "benchmark/configs/falcon-h1-34b-l5.json"
    fh1_rows: int = 2
    fh1_prompt: int = 1100         # prefill: a piece of 1,024 and one of 76
    fh1_piece: int = 1024
    fh1_decode: int = 24           # single-token passes over the state
    fh1_last: int = 64             # logits compared on the last positions
    sala_config: str = "benchmark/configs/minicpm-sala-l8.json"
    sala_rows: int = 2
    sala_prompt: int = 8300        # a row of it past dense_len (8,192), the
                                   # other row left-padded to a quarter of it
    sala_piece: int = 8192         # prefill: a piece of 8,192 and one of 108
    sala_decode: int = 16          # single-token steps: a selection each


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


class Meter:
    """Wall seconds, programs that reached the backend and the seconds that
    took (telemetry/mfu.RecompileCounter: compiled, or loaded from the
    persistent cache), cache requests/hits and peak device bytes since a mark."""

    def __init__(self):
        import jax

        from nanorlhf_tpu.telemetry.mfu import recompile_counter

        self._compiles = recompile_counter()
        self._cache = {"/jax/compilation_cache/compile_requests_use_cache": 0,
                       "/jax/compilation_cache/cache_hits": 0}
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, name: str, **kw) -> None:
        if name in self._cache:
            self._cache[name] += 1

    def mark(self) -> tuple:
        return (time.perf_counter(), self._compiles.count,
                self._compiles.seconds, *self._cache.values())

    def since(self, mark: tuple) -> dict:
        from nanorlhf_tpu.trainer.trainer import device_peak_bytes

        now = self.mark()
        return {
            "seconds": round(now[0] - mark[0], 2),
            "compiles": now[1] - mark[1],
            "compile_seconds": round(now[2] - mark[2], 2),
            "cache_requests": now[3] - mark[3],
            "cache_hits": now[4] - mark[4],
            "peak_bytes": int(device_peak_bytes()),
        }


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)


class Phase:
    """One phase's expectations. A failed one is remembered, the phase's JSON
    line is still printed with its numbers, and `finish` raises after."""

    def __init__(self, name: str, meter: Meter):
        self.name, self.meter, self.t0, self.failed = name, meter, meter.mark(), []

    def expect(self, ok: bool, what: str) -> None:
        if not ok:
            self.failed.append(what)

    def finish(self, **fields) -> None:
        emit(self.name, **fields, **self.meter.since(self.t0),
             failed=self.failed)
        check(not self.failed, f"{self.name}: " + "; ".join(self.failed))


def response_scorer(trainer, ctx: int, **mcfg_changes):
    """Jitted `(params, query_responses) -> response logprobs` through the
    trainer's own scoring function, with `mcfg_changes` applied to its model
    config; `attention_impl="xla"` also means the lax logprob scan — the
    plain path."""
    import jax

    from nanorlhf_tpu.trainer.trainer import fused_response_logprobs

    mcfg = dataclasses.replace(trainer.mcfg, **mcfg_changes)
    cfg = trainer.cfg
    if mcfg.attention_impl == "xla":
        cfg = dataclasses.replace(cfg, fused_logprob_impl="lax")
    pad, scale = trainer.tokenizer.pad_token_id, trainer.lora_scale
    return jax.jit(lambda p, x: fused_response_logprobs(
        p, mcfg, x, x[:, ctx:], pad, ctx, cfg, lora_scale=scale))


def float32(params):
    import jax
    import jax.numpy as jnp

    return jax.tree.map(lambda x: x.astype(jnp.float32), params)


def score_float32(trainer, params, qr, ctx: int):
    """The reference for logprobs: float32 weights and activations, the plain
    path, matmuls at highest precision, on whatever device holds `params`."""
    import jax
    import numpy as np

    with jax.default_matmul_precision("highest"):
        return np.asarray(response_scorer(
            trainer, ctx, attention_impl="xla", spmd_mesh=None)(
                float32(params), qr))


def bf16_agreement(phase: Phase, tested, plain, float32, real, names) -> dict:
    """`tested` and `plain` are the same logprobs from two bf16 paths; each is
    compared with the float32 reference over the `real` (non-pad) tokens."""
    import numpy as np

    def stat(x):
        return {"mean_abs": round(float(x.mean()), 5),
                "max_abs": round(float(x.max()), 4)}

    e_t, e_p = np.abs(tested - float32)[real], np.abs(plain - float32)[real]
    phase.expect(
        e_t.mean() <= BF16_SLACK[0] * e_p.mean() + 1e-3
        and e_t.max() <= BF16_SLACK[1] * e_p.max() + 1e-2,
        f"{names[0]} is further from float32 (mean {e_t.mean():.4f}, max "
        f"{e_t.max():.3f}) than bf16 explains: {names[1]} is at mean "
        f"{e_p.mean():.4f}, max {e_p.max():.3f}")
    return {f"{names[0]}_vs_float32": stat(e_t),
            f"{names[1]}_vs_float32": stat(e_p),
            f"{names[0]}_vs_{names[1]}": stat(np.abs(tested - plain)[real]),
            "tokens": int(real.sum())}


# --------------------------------------------------------------------------- #
# kernels
# --------------------------------------------------------------------------- #


def phase_kernels(sz: Sizes, mcfg, meter: Meter) -> None:
    """Each Pallas kernel of the main path against its XLA reference, at the
    model's head geometry. `auto` reaches the contiguous decode kernels only
    from cache length 2048 and the q8/verify twins only under options the
    launcher leaves off, so this phase is what runs them on the chip (the
    paged in-place read is also what the serve phase decodes through)."""
    import jax
    import jax.numpy as jnp

    from nanorlhf_tpu.core.model import _quantize_kv
    from nanorlhf_tpu.ops import attention as att
    from nanorlhf_tpu.ops import decode_attention as dec
    from nanorlhf_tpu.ops import fused_logprob, fused_logprob_reference

    phase = Phase("kernels", meter)
    interpreted = att._interpret_default()
    check(interpreted == (jax.default_backend() != "tpu"),
          "kernels must run compiled on a TPU (NANORLHF_PALLAS_INTERPRET set?)")
    H, KV = mcfg.num_attention_heads, mcfg.num_key_value_heads
    hd, D, V = mcfg.actual_head_dim, mcfg.hidden_size, mcfg.vocab_size
    f32 = jnp.float32
    keys = iter(jax.random.split(jax.random.PRNGKey(sz.seed), 16))

    def normal(shape, dtype=jnp.bfloat16):
        return jax.random.normal(next(keys), shape, dtype)

    def rel(a, b) -> float:
        a, b = a.astype(f32), b.astype(f32)
        return float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(b)) + 1e-6))

    errs = {}

    # flash forward + backward: one row with masked keys at the tail
    B, T = sz.flash_bt
    q, k, v = normal((B, H, T, hd)), normal((B, KV, T, hd)), normal((B, KV, T, hd))
    lens = jnp.asarray([T] + [T - T // 10] * (B - 1))
    valid = jnp.arange(T)[None, :] < lens[:, None]
    errs["flash_fwd"] = rel(att.flash_attention(q, k, v, valid),
                            att.reference_attention(q, k, v, valid))

    def attn_grads(fn):
        return jax.jit(jax.grad(
            lambda q, k, v: (fn(q, k, v, valid).astype(f32) ** 2).sum(),
            argnums=(0, 1, 2)))(q, k, v)

    errs["flash_bwd"] = max(map(rel, attn_grads(att.flash_attention),
                                attn_grads(att.reference_attention)))
    del q, k, v

    # decode / q8 / verify over a contiguous cache: per-row left-pad offsets
    # (block-aligned and not) and fill levels, so the index-map clamp works
    B, T = sz.decode_bt
    qd, kc, vc = normal((B, H, hd)), normal((B, KV, T, hd)), normal((B, KV, T, hd))
    start = (jnp.arange(B, dtype=jnp.int32) * 37) % (T // 2)
    filled = T - (jnp.arange(B, dtype=jnp.int32) * 17) % (T // 4)
    errs["decode"] = rel(dec.decode_attention(qd, kc, vc, start, filled),
                         dec.reference_decode_attention(qd, kc, vc, start, filled))
    kq, ks = _quantize_kv(kc)
    vq, vs = _quantize_kv(vc)
    errs["decode_q8"] = rel(
        dec.decode_attention_q8(qd, kq, ks, vq, vs, start, filled),
        dec.reference_decode_attention_q8(qd, kq, ks, vq, vs, start, filled))
    Tq = sz.verify_queries
    qv, fill = normal((B, H, Tq, hd)), filled - Tq
    errs["verify"] = rel(
        dec.decode_verify_attention(qv, kc, vc, start, fill),
        dec.reference_decode_verify_attention(qv, kc, vc, start, fill))

    # the paged twins: the same caches cut into pages scattered over a pool
    P = sz.page_size
    nb = T // P
    N = B * nb + 3
    page_ids = jax.random.permutation(next(keys), N)[: B * nb].astype(jnp.int32)
    table = page_ids.reshape(B, nb)

    def pool(c):        # [B, KV, T, hd] -> [N, KV, P, hd]
        pages = c.reshape(B, KV, nb, P, hd).transpose(0, 2, 1, 3, 4)
        return jnp.zeros((N, KV, P, hd), c.dtype).at[page_ids].set(
            pages.reshape(B * nb, KV, P, hd))

    def scale_pool(s):  # [B, KV, 8, T] -> [N, KV, 8, P]
        pages = s.reshape(B, KV, 8, nb, P).transpose(0, 3, 1, 2, 4)
        return jnp.ones((N, KV, 8, P), s.dtype).at[page_ids].set(
            pages.reshape(B * nb, KV, 8, P))

    kp, vp = pool(kc), pool(vc)
    # the in-place read takes whole stacks: the pools as the middle layer of
    # three, every third row dead (it reads zero and is left out)
    live = jnp.arange(B) % 3 != 2
    stack = [jnp.stack([jnp.zeros_like(p_), p_, jnp.ones_like(p_)])
             for p_ in (kp, vp)]
    plan = dec.paged_decode_plan(
        table, start, filled, page_size=P, num_pages=N,
        pages_per_item=dec.paged_pages_per_item(stack[0]), live=live)
    in_place = jax.jit(dec.paged_decode_attention)(qd, *stack, jnp.int32(1), plan)
    check(not bool(jnp.any(in_place[~live])), "a dead row did not read zero")
    errs["paged_decode"] = rel(
        in_place[live],
        dec.reference_paged_decode_attention(qd, kp, vp, table, start,
                                             filled)[live])
    del stack
    errs["paged_verify"] = rel(
        dec.paged_decode_verify_attention(qv, kp, vp, table, start, fill),
        dec.reference_paged_decode_verify_attention(qv, kp, vp, table, start,
                                                    fill))
    q8 = (pool(kq), scale_pool(ks), pool(vq), scale_pool(vs))
    errs["paged_decode_q8"] = rel(
        dec.paged_decode_attention_q8(qd, *q8, table, start, filled),
        dec.reference_paged_decode_attention_q8(qd, *q8, table, start, filled))
    del kc, vc, kp, vp, q8, kq, ks, vq, vs

    # fused hidden->logprob, tied layout: the [V, D] embedding leaf itself
    R, temp = sz.fused_rows, 0.9
    h = normal((R, D))
    w = (normal((V, D), f32) * 0.05).astype(jnp.bfloat16)
    labels = jax.random.randint(next(keys), (R,), 0, V)

    def fused(h_, w_):
        return fused_logprob(h_, w_, labels, temp, impl="pallas",
                             with_entropy=True, transposed=True)

    def oracle(h_, w_):  # full f32 logits
        return fused_logprob_reference(h_.astype(f32), w_.astype(f32),
                                       labels, temp, with_entropy=True,
                                       transposed=True)

    errs["fused_fwd"] = max(map(rel, jax.jit(fused)(h, w), jax.jit(oracle)(h, w)))

    def head_grads(fn):
        return jax.jit(jax.grad(lambda h_, w_: (fn(h_, w_)[0] ** 2).sum(),
                                argnums=(0, 1)))(h, w)

    errs["fused_bwd"] = max(map(rel, head_grads(fused), head_grads(oracle)))

    bad = {k: e for k, e in errs.items() if not e < KERNEL_REL_TOL}
    phase.expect(not bad, f"kernel mismatch vs XLA reference: {bad}")
    phase.finish(interpreted=interpreted,
                 geometry={"q_heads": H, "kv_heads": KV, "head_dim": hd,
                           "hidden": D, "vocab": V, "page": P},
                 rel_err={k: round(e, 5) for k, e in errs.items()},
                 tol=KERNEL_REL_TOL)


# --------------------------------------------------------------------------- #
# train (the launcher's path), shared with --multichip
# --------------------------------------------------------------------------- #


def smoke_config(sz: Sizes, out_dir: str, mesh=None, updates=None):
    """`entrypoints.grpo.build_config()` with sizes overridden — and the
    prompt corpus named as the launcher's own offline fallback, so that no
    download is even attempted on a machine without a network."""
    from nanorlhf_tpu.entrypoints.grpo import build_config

    updates = sz.updates if updates is None else updates
    cfg = build_config()
    cfg.sft_model_path = sz.model
    cfg.train_dataset_name = "synthetic:512"
    cfg.response_length = sz.response_length
    world = 1
    if mesh is not None:
        cfg.mesh = mesh
        world = mesh.data * mesh.fsdp   # the axes that shard the batch
    cfg.per_device_train_batch_size = sz.per_device_batch // world
    cfg.gradient_accumulation_steps = sz.grad_accum
    cfg.num_mini_batches = sz.mini_batches
    prompts = sz.per_device_batch * sz.grad_accum * sz.mini_batches
    cfg.total_episodes = updates * prompts
    cfg.save_steps = updates        # one checkpoint, after the last update
    cfg.output_dir = out_dir
    shutil.rmtree(out_dir, ignore_errors=True)  # metrics.jsonl appends
    return cfg


def run_launcher(cfg, before_training=None):
    """`entrypoints.common.run(cfg)`; returns (state, trainer, metric rows).
    `post_build` is the launcher's hook that runs before training — used
    here only to keep a handle on the trainer `run` builds."""
    from nanorlhf_tpu.entrypoints.common import run

    held = {}

    def post_build(trainer, dataset, reward_func):
        held["trainer"] = trainer
        if before_training is not None:
            before_training(trainer)

    state = run(cfg, post_build=post_build)
    with open(os.path.join(cfg.output_dir, "metrics.jsonl")) as f:
        rows = [json.loads(line) for line in f]
    return state, held["trainer"], [r for r in rows if "episode" in r]


def check_updates(phase: Phase, rows: list, updates: int, state: dict,
                  prompts: int) -> dict:
    import math

    check(state["global_step"] == updates and len(rows) == updates,
          f"wanted {updates} updates, got global_step="
          f"{state['global_step']} and {len(rows)} metric rows")
    keys = {"loss": "loss/policy_avg_new", "grad_norm": "policy/grad_norm_new",
            "kl": "objective/kl_old", "kl_rollout": "objective/kl_rollout_old"}
    series = {name: [r[k] for r in rows] for name, k in keys.items()}
    for name, vals in series.items():
        phase.expect(all(math.isfinite(x) for x in vals),
                     f"{name} not finite: {vals}")
    # a group whose samples all score alike has zero advantage, so a single
    # update may see no gradient; all of them seeing none trains nothing
    phase.expect(any(g > 0 for g in series["grad_norm"]),
                 f"no update saw a gradient: {series['grad_norm']}")
    # LoRA B starts at zero, so at step 1 the policy IS the reference
    phase.expect(abs(series["kl"][0]) < KL_STEP1_TOL,
                 f"step-1 KL {series['kl'][0]} not ~0")
    compiles = [int(r["perf/recompiles"]) for r in rows]
    phase.expect(all(b == a for a, b in zip(compiles, compiles[1:])),
                 f"backend compiles after the first update: {compiles}")
    return {**{k: [round(x, 6) for x in v] for k, v in series.items()},
            "compiles_cumulative": compiles,
            "seconds_per_update": [round(r["sec_per_episode"] * prompts, 2)
                                   for r in rows],
            "phase_seconds_last": {
                k[5:-2]: round(v, 2) for k, v in rows[-1].items()
                if k.startswith("time/") and k.endswith("_s")}}


def auto_choices(trainer, context_length: int) -> dict:
    """Which implementation each `auto` resolves to at this run's shapes —
    asked of the same functions the model asks."""
    from nanorlhf_tpu.core.model import use_decode_kernel, use_flash
    from nanorlhf_tpu.ops.fused_logprob import _resolve_impl
    from nanorlhf_tpu.trainer.trainer import fused_logprob_impl

    cfg, impl = trainer.cfg, trainer.mcfg.attention_impl
    total = context_length + cfg.response_length
    pick = lambda pallas: "pallas" if pallas else "xla"  # noqa: E731
    return {
        "prefill_attention": pick(use_flash(impl, context_length)),
        "decode_attention": pick(use_decode_kernel(impl, total)),
        "score_update_attention": pick(use_flash(impl, total)),
        "fused_logprob": _resolve_impl(
            fused_logprob_impl(cfg, trainer.mcfg), False),
    }


def release_training_state(trainer) -> None:
    """What follows training needs the policy only: give the reference copy
    and the optimizer state back to the device."""
    import gc

    trainer.ref_params = trainer.opt_state = None
    gc.collect()


def phase_train(sz: Sizes, meter: Meter, out_dir: str):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from nanorlhf_tpu.rewards.math_grader import is_correct

    phase = Phase("train", meter)
    cfg = smoke_config(sz, out_dir)
    first = {}

    def keep_first_batch(trainer):
        score = trainer._score_chunk_fn()

        def spy(params, ref_params, qr, ctx):
            if not first:
                first.update(qr=np.asarray(qr), ctx=ctx)
            return score(params, ref_params, qr, ctx)

        trainer._score_fn_cached = spy

    state, trainer, rows = run_launcher(cfg, keep_first_batch)
    series = check_updates(phase, rows, sz.updates, state, cfg.batch_size)

    # a grader child forked from the (threaded) process that holds the chip
    # must come back; "1/2" vs "0.5" is not a string match, so it does fork
    t_grade = time.perf_counter()
    phase.expect(is_correct("1/2", "0.5", timeout=10.0, use_subprocess=True),
                 "math-grader child did not return True for 1/2 == 0.5")
    t_grade = time.perf_counter() - t_grade

    # numerics against the plain path: the first rollout batch, scored by the
    # trainer's own policy scorer (`auto`: Pallas flash + Pallas fused logprob
    # on a TPU), by XLA attention + the lax scan, and in float32. Done after
    # training, on the trained policy: the float32 copy needs the HBM that
    # the reference and the optimizer held until now.
    release_training_state(trainer)
    qr, ctx = jnp.asarray(first["qr"]), first["ctx"]
    score_auto = trainer._policy_score_fn()
    custom_calls = score_auto.lower(trainer.params, qr, ctx).as_text().count(
        "tpu_custom_call")
    choices = auto_choices(trainer, ctx)
    if jax.default_backend() == "tpu":  # else the two bf16 paths are one path
        phase.expect(choices["score_update_attention"] == "pallas"
                     and choices["fused_logprob"] == "pallas"
                     and custom_calls > 0,
                     f"auto did not route scoring through Pallas: {choices}")
    numerics = bf16_agreement(
        phase, np.asarray(score_auto(trainer.params, qr, ctx)),
        np.asarray(response_scorer(trainer, ctx, attention_impl="xla")(
            trainer.params, qr)),
        score_float32(trainer, trainer.params, qr, ctx),
        first["qr"][:, ctx:] != trainer.tokenizer.pad_token_id,
        ("auto", "xla_lax"))

    mcfg = trainer.mcfg
    phase.finish(model=cfg.sft_model_path, layers=mcfg.num_hidden_layers,
                 hidden=mcfg.hidden_size, vocab=mcfg.vocab_size,
                 lora_r=cfg.lora_r, prompts=cfg.batch_size,
                 sample_n=cfg.sample_n, context_length=ctx,
                 response_length=cfg.response_length,
                 updates=state["global_step"], save_steps=cfg.save_steps,
                 auto=choices, score_custom_calls=custom_calls,
                 logprobs=numerics, grader_child_seconds=round(t_grade, 3),
                 **series)
    return trainer, numerics["xla_lax_vs_float32"]["mean_abs"]


# --------------------------------------------------------------------------- #
# serve
# --------------------------------------------------------------------------- #


def phase_serve(sz: Sizes, trainer, bf16_error: float, meter: Meter) -> None:
    """`bf16_error`: the mean distance of the plain bf16 scorer's logprobs
    from float32, as the train phase just measured it on this model."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from nanorlhf_tpu.core.model import padded_forward_logits, use_paged_decode_kernel
    from nanorlhf_tpu.sampler import SamplingParams, generate
    from nanorlhf_tpu.serving.engine import ServingEngine
    from nanorlhf_tpu.serving.gateway import ServingGateway
    from nanorlhf_tpu.telemetry.exporter import validate_prometheus_text
    from nanorlhf_tpu.telemetry.hist import LatencyHub

    phase = Phase("serve", meter)
    params, mcfg, tok = trainer.params, trainer.mcfg, trainer.tokenizer
    lora_scale = trainer.lora_scale
    eos, pad = tok.eos_token_id, tok.pad_token_id
    rng = np.random.default_rng(sz.seed)

    def prompt(n):
        return rng.integers(3, tok.vocab_size, n).tolist()

    Tp, new = sz.prompt_len, sz.max_new_tokens
    prefix = prompt(sz.shared_prefix)
    sampled = {"temperature": 0.9, "top_p": 0.95}
    requests = [
        {"tokens": prefix + prompt(Tp - len(prefix)), "greedy": True},
        {"tokens": prefix + prompt(Tp - len(prefix)), **sampled},
        {"tokens": prompt(Tp), "greedy": True, "stream": True},
        {"tokens": prompt(Tp // 2), **sampled, "stream": True},
        {"tokens": prompt(Tp), **sampled},
        {"tokens": prompt(Tp // 2), **sampled, "max_tokens": new // 2},
        {"tokens": prompt(Tp // 4), "greedy": True, "max_tokens": new // 2},
        {"tokens": prompt(Tp), "temperature": 0.7, "top_p": 0.9},
    ]

    engine = ServingEngine(
        params, mcfg, eos_token_id=eos, pad_token_id=pad,
        page_size=sz.page_size, prompt_len=Tp, max_new_tokens=new,
        rows=sz.rows, latency=LatencyHub(enabled=True),
        lora_scale=lora_scale, seed=sz.seed)
    gateway = ServingGateway(engine, port=-1)
    base = f"http://127.0.0.1:{gateway.port}"
    paged_kernel = use_paged_decode_kernel(mcfg)
    pool_donated = engine.session.pool_donated

    def get(path):
        return urllib.request.urlopen(base + path, timeout=60).read().decode()

    def post(spec) -> list:
        """One /generate call; returns its tokens, having checked the count."""
        body = urllib.request.urlopen(urllib.request.Request(
            base + "/generate", data=json.dumps(spec).encode(),
            headers={"Content-Type": "application/json"}), timeout=900).read()
        if spec.get("stream"):
            lines = [json.loads(ln) for ln in body.decode().splitlines()]
            toks = [ln["token"] for ln in lines[:-1]]
            check(lines[-1] == {"done": True, "n": len(toks)},
                  f"stream ended with {lines[-1]} after {len(toks)} tokens")
        else:
            toks = json.loads(body)["tokens"]
        budget = spec.get("max_tokens", new)
        check(1 <= len(toks) <= budget
              and (len(toks) == budget or toks[-1] == eos),
              f"{len(toks)} tokens for a budget of {budget}")
        return toks

    try:
        # the first request alone (it pays the compiles, and its prompt must
        # be in the radix tree before its prefix twin arrives), then seven
        # at once over the engine's eight rows
        answers = {0: post(requests[0])}
        failures = []

        def worker(i):
            try:
                answers[i] = post(requests[i])
            except Exception as e:  # re-raised on the main thread below
                failures.append((i, e))

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(1, len(requests))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if failures:
            raise failures[0][1]

        status = json.loads(get("/statusz"))
        radix = status["prefix_cache"]
        phase.expect(radix["hit_tokens"] >= sz.shared_prefix - 1,
                     f"no radix hit on the shared prefix: {radix}")
        phase.expect(status["counters"]["completed"] == len(requests)
                     and status["counters"]["shed"] == 0,
                     f"requests not all completed: {status['counters']}")
        metrics = get("/metrics")
        problems = validate_prometheus_text(metrics)
        phase.expect(not problems and "nanorlhf_serving_completed" in metrics,
                     f"/metrics does not parse: {problems[:3]}")
        phase.expect(get("/healthz") == "ok\n", "/healthz is not ok")
        # on a chip every session program consumes the page pool it is given
        # (a CPU rehearsal copies: utils/donation.py)
        phase.expect(pool_donated == int(jax.default_backend() != "cpu"),
                     f"serving/pool_donated is {pool_donated}")
    finally:
        gateway.close()
        engine.close()

    # greedy through the gateway == greedy through generate(), same prompt
    ids = jnp.asarray([requests[0]["tokens"]], jnp.int32)
    want = np.asarray(generate(
        params, mcfg, ids, ids != pad, jax.random.PRNGKey(sz.seed),
        SamplingParams(greedy=True, max_tokens=new), eos_token_id=eos,
        pad_token_id=pad, lora_scale=lora_scale))[0].tolist()
    if eos in want:
        want = want[: want.index(eos) + 1]
    got = answers[0]
    agreement = {"match": "exact"}
    if got != want:
        # The paged session and the contiguous loop are two compiled programs
        # and round bf16 differently, so argmax may flip — but only between
        # tokens that the float32 model itself puts within bf16's reach of
        # its top. Anything wider is a fault (a wrong cache, a wrong mask).
        t = next((i for i, (a, b) in enumerate(zip(got, want)) if a != b), None)
        check(t is not None, f"gateway {len(got)} vs generate {len(want)} tokens")
        del engine, gateway
        seq = jnp.asarray([requests[0]["tokens"] + got[:t]], jnp.int32)
        mcfg_xla = dataclasses.replace(mcfg, attention_impl="xla")
        with jax.default_matmul_precision("highest"):
            logits = np.asarray(jax.jit(lambda p, x: padded_forward_logits(
                p, mcfg_xla, x, pad, lora_scale=lora_scale)[0, -1])(
                    float32(params), seq))
        reach = 4 * bf16_error
        gaps = {"gateway_below_float32_top": float(logits.max() - logits[got[t]]),
                "generate_below_float32_top": float(logits.max() - logits[want[t]])}
        agreement = {"match": "near-tie", "position": t,
                     "bf16_reach": round(reach, 4),
                     **{k: round(v, 4) for k, v in gaps.items()}}
        phase.expect(max(gaps.values()) <= reach,
                     f"gateway and generate() diverge at token {t} by more "
                     f"than bf16 explains: {agreement}")

    phase.finish(
        requests=len(requests), rows=sz.rows, page_size=sz.page_size,
        prompt_len=Tp, max_new_tokens=new,
        tokens_returned=sum(len(a) for a in answers.values()),
        radix={k: radix[k] for k in ("hit_tokens", "cow_splits", "nodes",
                                     "shared_pages_acquired")},
        auto={"decode_attention": "pallas-paged-in-place" if paged_kernel
              else "xla-gathered-view",
              "page_pool": "donated" if pool_donated else "copied"},
        greedy_vs_generate=agreement)


# --------------------------------------------------------------------------- #
# --multichip
# --------------------------------------------------------------------------- #


def phase_multichip(sz: Sizes, meter: Meter, out_dir: str) -> None:
    """GRPO on MeshConfig(data=1, fsdp=2, tensor=2), then what has only ever
    been pinned on virtual CPU devices: no device holds a whole sharded leaf,
    the devices' peak memory is balanced, the compiled update keeps the
    Pallas call on per-device shards with no gather of q/k/v around it, and
    the sharded scorer agrees with the same parameters on one device."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from nanorlhf_tpu.parallel import MeshConfig, make_mesh, shard_params

    phase = Phase("multichip", meter)
    mesh_cfg = MeshConfig(data=1, fsdp=2, tensor=2)
    cfg = smoke_config(sz, out_dir, mesh=mesh_cfg, updates=sz.multichip_updates)
    update_args = []

    def keep_update_shapes(trainer):
        """Record the abstract arguments of the first jitted update, so its
        compiled HLO can be read back afterwards."""
        update = trainer._update_fn

        def spy(trainable, frozen, opt_state, minibatch, ctx):
            if not update_args:
                abstract = jax.tree.map(
                    lambda x: jax.ShapeDtypeStruct(
                        x.shape, x.dtype,
                        sharding=x.sharding if x.committed else None),
                    (trainable, frozen, opt_state, minibatch))
                update_args.extend([update, *abstract, ctx])
            return update(trainable, frozen, opt_state, minibatch, ctx)

        trainer._update_fn = spy

    state, trainer, rows = run_launcher(cfg, keep_update_shapes)
    series = check_updates(phase, rows, sz.multichip_updates, state,
                           cfg.batch_size)
    devices = list(trainer.mesh.devices.flat)

    # 1. placement: every leaf the rules shard is really in pieces
    n_sharded = 0
    for path, leaf in jax.tree_util.tree_leaves_with_path(
            {"policy": trainer.params, "ref": trainer.ref_params}):
        if leaf.sharding.is_fully_replicated:
            continue
        n_sharded += 1
        whole = [s.device.id for s in leaf.addressable_shards
                 if s.data.size == leaf.size]
        phase.expect(not whole, f"{jax.tree_util.keystr(path)} "
                     f"{leaf.sharding} sits whole on devices {whole}")
    phase.expect(n_sharded > 0, "no parameter leaf is sharded")

    # 2. memory: process-lifetime peaks, read before anything below puts a
    # whole copy on one device
    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
             for d in devices]
    if jax.default_backend() == "tpu":
        phase.expect(min(peaks) > 0 and max(peaks) <= PEAK_BALANCE * min(peaks),
                     f"unbalanced device memory, peak bytes {peaks}")

    # 3. the compiled update: every Pallas call works on one device's shard
    # (rows over fsdp, heads over tensor — core/model._spmd_call), and nothing
    # all-gathers q/k/v to its global shape on the way in or out
    update, *abstract, ctx = update_args
    hlo = update.lower(*abstract, ctx).compile().as_text()
    mcfg = trainer.mcfg
    H, KV, hd = (mcfg.num_attention_heads, mcfg.num_key_value_heads,
                 mcfg.actual_head_dim)
    rows = cfg.micro_batch_size
    qkv = re.compile(rf"\[(\d+),(\d+),\d+,{hd}\]")  # [rows, heads, T, hd]
    gathers = [ln for ln in hlo.splitlines() if "all-gather" in ln]
    kernel_calls = [ln for ln in hlo.splitlines() if "tpu_custom_call" in ln]
    local = {(rows // mesh_cfg.fsdp, h // mesh_cfg.tensor) for h in (H, KV)}
    operands = {(int(r), int(h)) for ln in kernel_calls
                for r, h in qkv.findall(ln)}
    phase.expect(operands <= local, "a Pallas call outside shard_map: operand "
                 f"(rows, heads) {sorted(operands - local)}, per device "
                 f"{sorted(local)}")
    whole = [ln.strip()[:160] for ln in gathers
             if {(int(r), int(h)) for r, h in qkv.findall(ln)}
             & {(rows, H), (rows, KV)}]
    phase.expect(not whole,
                 f"q/k/v all-gathered to its global shape: {whole[:3]}")
    if jax.default_backend() == "tpu":
        phase.expect(kernel_calls, "no Pallas call in the compiled update")
    gathered = re.findall(r"= (\w+\[[\d,]*\])[^=]* all-gather", hlo)

    # 4. sharded vs one device vs float32, one fixed seeded batch, trained
    # parameters; the whole copies land on the first device only now
    release_training_state(trainer)
    qr = np.random.default_rng(sz.seed).integers(
        3, trainer.tokenizer.vocab_size,
        (cfg.batch_size, ctx + cfg.response_length)).astype(np.int32)
    lp_sharded = np.asarray(
        trainer._policy_score_fn()(trainer.params, jnp.asarray(qr), ctx))
    one = make_mesh(MeshConfig(1, 1, 1), devices=devices[:1])
    params_one = shard_params(trainer.params, one)
    qr_one = jax.device_put(qr, devices[0])
    lp_one = np.asarray(response_scorer(trainer, ctx, spmd_mesh=None)(
        params_one, qr_one))
    numerics = bf16_agreement(
        phase, lp_sharded, lp_one, score_float32(trainer, params_one, qr_one, ctx),
        np.ones(lp_one.shape, bool), ("sharded", "one_device"))

    phase.finish(
        mesh={"data": 1, "fsdp": 2, "tensor": 2}, model=cfg.sft_model_path,
        layers=mcfg.num_hidden_layers, prompts=cfg.batch_size,
        sample_n=cfg.sample_n, response_length=cfg.response_length,
        updates=state["global_step"], auto=auto_choices(trainer, ctx),
        sharded_leaves=n_sharded, peak_bytes_per_device=peaks,
        update_hlo={"pallas_calls": len(kernel_calls),
                    "pallas_operand_rows_heads": sorted(operands),
                    "all_gathers": len(gathers),
                    "all_gather_shapes": sorted(
                        set(gathered), key=gathered.count, reverse=True)[:8]},
        logprobs=numerics, **series)


# --------------------------------------------------------------------------- #
# the sparse-expert layer
# --------------------------------------------------------------------------- #


def phase_olmoe(sz: Sizes, meter: Meter) -> None:
    """OLMoE through the normal path against the plain float32 reference,
    under `bf16_agreement`'s rule on logits or logprobs: the path under test
    may be BF16_SLACK times further from float32 than the plain bf16 forward
    (XLA attention, `ragged_dot`, no cache), both measured here on the same
    positions."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    sys.path.insert(0, os.path.join(ROOT, "benchmark"))
    from harness import agreement, reference_olmoe

    from nanorlhf_tpu.core import (ModelConfig, decode_step, init_kv_cache,
                                   init_params, padded_forward_logits, prefill)
    from nanorlhf_tpu.core.lora import LoraConfig, init_lora_params
    from nanorlhf_tpu.entrypoints.grpo import build_config
    from nanorlhf_tpu.sampler.paged.session import DecodeSession
    from nanorlhf_tpu.trainer.trainer import fused_response_logprobs

    phase = Phase("olmoe", meter)
    with open(os.path.join(ROOT, sz.olmoe_config)) as f:
        file = json.load(f)
    mcfg = ModelConfig.from_hf_config(file)
    plain_mcfg = dataclasses.replace(mcfg, attention_impl="xla")
    check(mcfg.num_experts == file["num_experts"] and mcfg.qk_norm,
          "from_hf_config dropped the expert keys")
    dtype = jnp.bfloat16 if jax.default_backend() == "tpu" else jnp.float32
    key = jax.random.PRNGKey(sz.seed)
    lora_cfg = LoraConfig(r=64, alpha=16)
    params = jax.jit(lambda k: init_params(mcfg, k, dtype))(key)
    lora = init_lora_params(mcfg, lora_cfg, jax.random.fold_in(key, 1), dtype)
    for i, name in enumerate(sorted(lora["layers"])):   # B is zero at birth
        b = lora["layers"][name]["b"]
        lora["layers"][name]["b"] = (0.02 * jax.random.normal(
            jax.random.fold_in(key, 10 + i), b.shape, jnp.float32)).astype(dtype)
    params["lora"] = lora
    scale, pad = lora_cfg.scale, 0
    P, n_new, T = sz.olmoe_prompt, sz.olmoe_decode, sz.olmoe_prompt + sz.olmoe_decode
    last, ctx = sz.olmoe_last, sz.olmoe_context
    ids = np.array(jax.random.randint(jax.random.fold_in(key, 2),
                                      (sz.olmoe_rows, T), 3, mcfg.vocab_size))
    ids[0, : P // 8] = pad                                 # one left-padded row
    ids = jnp.asarray(ids, jnp.int32)
    real = ids != pad

    def reference_logits(x, mask, n):
        with jax.default_matmul_precision("highest"):
            return np.asarray(jax.jit(lambda p, x, m: reference_olmoe.logits(
                p, file, x, pad, scale, last=n, mask=m))(params, x, mask))

    def within(tested, plain, ref, what):
        return bf16_agreement(phase, tested, plain, ref,
                              np.ones(np.shape(ref), bool), (what, "plain"))

    # ---- scoring logprobs: auto, plain, float32 -------------------------
    cfg = build_config()
    plain_cfg = dataclasses.replace(cfg, fused_logprob_impl="lax")
    score = lambda m, c: np.asarray(jax.jit(lambda p, x: fused_response_logprobs(  # noqa: E731
        p, m, x, x[:, ctx:], pad, ctx, c, lora_scale=scale))(params, ids))
    with jax.default_matmul_precision("highest"):
        ref_lp = np.asarray(jax.jit(lambda p, x: reference_olmoe.response_logprobs(
            p, file, x, ctx, pad, cfg.temperature, scale))(params, ids))
    resp = np.asarray(real)[:, ctx:]
    scoring = within(score(mcfg, cfg)[resp], score(plain_mcfg, plain_cfg)[resp],
                     ref_lp[resp], "auto_scoring")

    # ---- the plain bf16 forward and the reference on the last positions --
    ref_last = reference_logits(ids, real, last)            # [B, last, V]
    plain_last = np.asarray(jax.jit(lambda p, x: padded_forward_logits(
        p, plain_mcfg, x, pad, scale))(params, ids)[:, -last:], np.float32)
    steps = slice(last - n_new - 1, last)    # positions P-1 .. T-1: n_new + 1

    # ---- prefill + teacher-forced decode through the contiguous cache ----
    caches = init_kv_cache(mcfg, sz.olmoe_rows, T, dtype)
    lg, caches = jax.jit(lambda p, x, m, c: prefill(p, mcfg, x, m, c, scale))(
        params, ids[:, :P], real[:, :P], caches)
    step = jax.jit(lambda p, tok, pos, t, km, c: decode_step(
        p, mcfg, tok, pos, t, km, c, scale))
    got, n_real = [np.asarray(lg, np.float32)], real[:, :P].sum(axis=1)
    key_mask = jnp.zeros((sz.olmoe_rows, T), bool).at[:, :P].set(real[:, :P])
    for t in range(P, T):
        key_mask = key_mask.at[:, t].set(True)
        lg, caches = step(params, ids[:, t], n_real + (t - P), jnp.int32(t),
                          key_mask, caches)
        got.append(np.asarray(lg, np.float32))
    del caches
    contiguous = within(np.stack(got, axis=1), plain_last[:, steps],
                        ref_last[:, steps], "contiguous_cache")

    # ---- the paged session, greedy, its own tokens ------------------------
    sess = DecodeSession(
        params, mcfg, rows=sz.olmoe_rows, prompt_len=P, max_tokens=n_new,
        page_size=sz.page_size, eos_token_id=mcfg.vocab_size + 1,
        pad_token_id=pad, key=jax.random.fold_in(key, 3), greedy=True,
        capture_logprobs=True, lora_scale=scale, sync_every=8)
    sess.bootstrap(ids[:, :P], real[:, :P])
    for _ in range(n_new):
        if sess.step()[0].all():
            break
    out, captured = np.asarray(sess.state[1]), np.asarray(sess.state[2])
    del sess
    served = jnp.concatenate([ids[:, :P], jnp.asarray(out)], axis=1)
    served_real = jnp.concatenate([real[:, :P], jnp.ones_like(out, bool)], axis=1)
    ref_s = reference_logits(served, served_real, n_new + 1)[:, :-1]
    plain_s = np.asarray(jax.jit(lambda p, x: padded_forward_logits(
        p, plain_mcfg, x, pad, scale))(params, served)[:, -n_new - 1:-1],
        np.float32)

    def chosen_logprob(lg):
        lp = lg - lg.max(-1, keepdims=True)
        lp = lp - np.log(np.exp(lp).sum(-1, keepdims=True))
        return np.take_along_axis(lp, out[..., None], axis=-1)[..., 0]

    paged = within(captured, chosen_logprob(plain_s), chosen_logprob(ref_s),
                   "paged_session")
    # a greedy token may sit below the reference's top by what the plain
    # path's own argmax does (the benchmark's rule for served tokens)
    V = ref_s.shape[-1]
    follows, greedy = agreement.follows_greedy(
        ref_s.reshape(-1, V), out.reshape(-1), plain_s.reshape(-1, V))
    phase.expect(follows, f"greedy tokens sit further under the reference's "
                          f"top than the plain path's: {greedy}")
    phase.finish(config=sz.olmoe_config, layers=mcfg.num_hidden_layers,
                 hidden=mcfg.hidden_size, experts=mcfg.num_experts,
                 per_token=mcfg.num_experts_per_tok, vocab=mcfg.vocab_size,
                 dtype=str(jnp.dtype(dtype)), rows=sz.olmoe_rows, tokens=T,
                 prefill=P, decode_steps=n_new, scoring=scoring,
                 contiguous=contiguous, paged=paged, greedy=greedy)


# --------------------------------------------------------------------------- #
# latent attention, a chip's share of the experts
# --------------------------------------------------------------------------- #


def phase_axk1(sz: Sizes, meter: Meter) -> None:
    """A.X-K1 through the normal path against the plain float32 reference,
    under `bf16_agreement`'s rule on logits: the path under test may be
    BF16_SLACK times further from float32 than the plain bf16 forward (XLA
    `ragged_dot`, no cache, expanded attention), both measured here on the
    same positions. The scoring forward takes the grouped-matmul kernel; the
    contiguous cache takes the expanded form at prefill and the absorbed
    form at every step after it."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    sys.path.insert(0, os.path.join(ROOT, "benchmark"))
    from harness import reference_axk1

    from nanorlhf_tpu.core import (ModelConfig, decode_step, init_kv_cache,
                                   init_params, padded_forward_logits, prefill)

    phase = Phase("axk1", meter)
    with open(os.path.join(ROOT, sz.axk1_config)) as f:
        file = json.load(f)
    mcfg = ModelConfig.from_hf_config(file)
    plain_mcfg = dataclasses.replace(mcfg, attention_impl="xla")
    check(mcfg.kv_lora_rank == file["kv_lora_rank"]
          and mcfg.num_experts == file["n_routed_experts"]
          and mcfg.num_dense_layers == file["first_k_dense_replace"],
          "from_hf_config dropped the latent or the expert keys")
    dtype = jnp.bfloat16 if jax.default_backend() == "tpu" else jnp.float32
    key = jax.random.PRNGKey(sz.seed)
    params = jax.jit(lambda k: init_params(mcfg, k, dtype))(key)
    pad = 0
    P, n_new = sz.axk1_prompt, sz.axk1_decode
    T, last = P + n_new, sz.axk1_last
    ids = np.array(jax.random.randint(jax.random.fold_in(key, 2),
                                      (sz.axk1_rows, T), 3, mcfg.vocab_size))
    ids[0, : P // 8] = pad                                 # one left-padded row
    ids = jnp.asarray(ids, jnp.int32)
    real = ids != pad

    def within(tested, plain, ref, what):
        return bf16_agreement(phase, tested, plain, ref,
                              np.ones(np.shape(ref), bool), (what, "plain"))

    with jax.default_matmul_precision("highest"):
        ref_last = np.asarray(jax.jit(lambda p, x, m: reference_axk1.logits(
            p, file, x, pad, last=last, mask=m))(params, ids, real))
    forward = lambda m: np.asarray(jax.jit(lambda p, x: padded_forward_logits(  # noqa: E731
        p, m, x, pad)[:, -last:])(params, ids), np.float32)
    plain_last = forward(plain_mcfg)
    scoring = within(forward(mcfg), plain_last, ref_last, "auto_scoring")

    # ---- prefill + teacher-forced decode through the contiguous cache ----
    steps = slice(last - n_new - 1, last)    # positions P-1 .. T-1: n_new + 1
    caches = init_kv_cache(mcfg, sz.axk1_rows, T, dtype)
    latent_bytes = sum(c.nbytes for c in caches) // (sz.axk1_rows * T)
    phase.expect(latent_bytes == mcfg.num_hidden_layers * mcfg.latent_width
                 * jnp.dtype(dtype).itemsize,
                 f"the cache holds {latent_bytes} B a token, not the latent")
    lg, caches = jax.jit(lambda p, x, m, c: prefill(p, mcfg, x, m, c))(
        params, ids[:, :P], real[:, :P], caches)
    step = jax.jit(lambda p, tok, pos, t, km, c: decode_step(
        p, mcfg, tok, pos, t, km, c))
    got, n_real = [np.asarray(lg, np.float32)], real[:, :P].sum(axis=1)
    key_mask = jnp.zeros((sz.axk1_rows, T), bool).at[:, :P].set(real[:, :P])
    for t in range(P, T):
        key_mask = key_mask.at[:, t].set(True)
        lg, caches = step(params, ids[:, t], n_real + (t - P), jnp.int32(t),
                          key_mask, caches)
        got.append(np.asarray(lg, np.float32))
    del caches
    contiguous = within(np.stack(got, axis=1), plain_last[:, steps],
                        ref_last[:, steps], "contiguous_cache")
    phase.finish(config=sz.axk1_config, layers=mcfg.num_hidden_layers,
                 hidden=mcfg.hidden_size, experts_held=mcfg.num_held_experts,
                 router_width=mcfg.num_experts, vocab=mcfg.vocab_size,
                 latent_bytes_per_token=int(latent_bytes),
                 dtype=str(jnp.dtype(dtype)), rows=sz.axk1_rows, tokens=T,
                 prefill=P, decode_steps=n_new, scoring=scoring,
                 contiguous=contiguous)


# --------------------------------------------------------------------------- #
# a state-space mixer beside attention in every layer
# --------------------------------------------------------------------------- #


def phase_falcon_h1(sz: Sizes, meter: Meter) -> None:
    """Falcon-H1 through the normal path against the plain float32
    reference, under `bf16_agreement`'s rule on logits: the path under test
    may be BF16_SLACK times further from float32 than the plain bf16 forward
    (XLA attention, no cache), both measured here on the same positions. The
    contiguous cache takes the prompt in two pieces (`prefill`, then a
    `decode_verify` that takes both state leaves over) and then a pass over
    the state a step."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    sys.path.insert(0, os.path.join(ROOT, "benchmark"))
    from drivers import serve_ssm_ref
    from harness import reference_falcon_h1

    from nanorlhf_tpu.core import (ModelConfig, decode_step, init_kv_cache,
                                   init_params, padded_forward_logits, prefill)
    from nanorlhf_tpu.core.model import decode_verify

    phase = Phase("falcon_h1", meter)
    with open(os.path.join(ROOT, sz.fh1_config)) as f:
        file = json.load(f)
    mcfg = ModelConfig.from_hf_config(file)
    plain_mcfg = dataclasses.replace(mcfg, attention_impl="xla")
    check(mcfg.ssm_layers == file["num_hidden_layers"]
          and mcfg.ssm_state == file["mamba_d_state"]
          and mcfg.ssm_multipliers == tuple(file["ssm_multipliers"]),
          "from_hf_config dropped the mixer or its multipliers")
    dtype = jnp.bfloat16 if jax.default_backend() == "tpu" else jnp.float32
    key = jax.random.PRNGKey(sz.seed)
    params = serve_ssm_ref.spread(
        jax.jit(lambda k: init_params(mcfg, k, dtype))(key),
        file["assumed"].get("init"), sz.seed)
    pad = 0
    P, piece, n_new = sz.fh1_prompt, sz.fh1_piece, sz.fh1_decode
    T, last, rows = P + n_new, sz.fh1_last, sz.fh1_rows
    ids = np.array(jax.random.randint(jax.random.fold_in(key, 2), (rows, T), 3,
                                      mcfg.vocab_size))
    ids[0, : piece // 8] = pad                             # one left-padded row
    ids = jnp.asarray(ids, jnp.int32)
    real = ids != pad

    def within(tested, plain, ref, what):
        return bf16_agreement(phase, tested, plain, ref,
                              np.ones(np.shape(ref), bool), (what, "plain"))

    with jax.default_matmul_precision("highest"):
        ref_last = np.asarray(jax.jit(lambda p, x, m: reference_falcon_h1.logits(
            p, file, x, pad, last=last, mask=m))(params, ids, real))
    forward = lambda m: np.asarray(jax.jit(lambda p, x: padded_forward_logits(  # noqa: E731
        p, m, x, pad)[:, -last:])(params, ids), np.float32)
    plain_last = forward(plain_mcfg)
    scoring = within(forward(mcfg), plain_last, ref_last, "auto_scoring")

    # ---- two pieces, then teacher-forced decode, the contiguous cache ----
    steps = slice(last - n_new - 1, last)    # positions P-1 .. T-1: n_new + 1
    caches = init_kv_cache(mcfg, rows, T, dtype)
    tail, state = caches[2]
    phase.expect(state.dtype == jnp.float32 and state.shape[1:] == (
        rows, mcfg.ssm_heads, mcfg.ssm_head_dim, mcfg.ssm_state),
        f"the recurrent state is {state.dtype}{state.shape}")
    _, caches = jax.jit(lambda p, x, m, c: prefill(p, mcfg, x, m, c))(
        params, ids[:, :piece], real[:, :piece], caches)
    n_real = real[:, :piece].sum(axis=1)
    key_mask = jnp.zeros((rows, T), bool).at[:, :piece].set(real[:, :piece])
    lg, caches = jax.jit(lambda p, x, pos, km, c: decode_verify(
        p, mcfg, x, pos, jnp.full((rows,), piece, jnp.int32), km, c))(
        params, ids[:, piece:P],
        n_real[:, None] + jnp.arange(P - piece)[None], key_mask, caches)
    key_mask = key_mask.at[:, piece:P].set(True)
    step = jax.jit(lambda p, tok, pos, t, km, c: decode_step(
        p, mcfg, tok, pos, t, km, c))
    got = [np.asarray(lg[:, -1], np.float32)]
    for t in range(P, T):
        key_mask = key_mask.at[:, t].set(True)
        lg, caches = step(params, ids[:, t], n_real + (t - piece), jnp.int32(t),
                          key_mask, caches)
        got.append(np.asarray(lg, np.float32))
    del caches
    contiguous = within(np.stack(got, axis=1), plain_last[:, steps],
                        ref_last[:, steps], "contiguous_cache")
    phase.finish(config=sz.fh1_config, layers=mcfg.num_hidden_layers,
                 hidden=mcfg.hidden_size, ssm_heads=mcfg.ssm_heads,
                 ssm_state=mcfg.ssm_state, vocab=mcfg.vocab_size,
                 dtype=str(jnp.dtype(dtype)), rows=rows, tokens=T,
                 pieces=(piece, P - piece), decode_steps=n_new,
                 scoring=scoring, contiguous=contiguous)


def phase_sala(sz: Sizes, meter: Meter) -> None:
    """MiniCPM-SALA through the normal path against the plain float32
    reference, under `bf16_agreement`'s rule on logits (`phase_falcon_h1`'s).
    One row holds the whole prompt and selects (past `dense_len`), the other
    is left-padded to a quarter of it and is read dense, in one batch. The
    contiguous cache takes the prompt in two pieces (`prefill`, then a
    `decode_verify` that takes the lightning state and the compressed keys
    over, told the prompt's length) and then a selection, a read of the
    chosen blocks and a pass over the state a step."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    sys.path.insert(0, os.path.join(ROOT, "benchmark"))
    from drivers import serve_sala_ref
    from harness import reference_sala

    from nanorlhf_tpu.core import (ModelConfig, decode_step, init_kv_cache,
                                   init_params, padded_forward_logits, prefill)
    from nanorlhf_tpu.core.model import decode_verify

    phase = Phase("sala", meter)
    with open(os.path.join(ROOT, sz.sala_config)) as f:
        file = json.load(f)
    mcfg = ModelConfig.from_hf_config(file)
    plain_mcfg = dataclasses.replace(mcfg, attention_impl="xla")
    check(mcfg.linear_layers + mcfg.sparse_layers == file["num_hidden_layers"]
          and mcfg.sparse_dense_len == file["sparse_config"]["dense_len"],
          "from_hf_config dropped a mixer or the sparse sizes")
    dtype = jnp.bfloat16 if jax.default_backend() == "tpu" else jnp.float32
    key = jax.random.PRNGKey(sz.seed)
    params = serve_sala_ref.spread(
        jax.jit(lambda k: init_params(mcfg, k, dtype))(key),
        file["assumed"].get("init"), sz.seed)
    pad = 0
    P, piece, n_new = sz.sala_prompt, sz.sala_piece, sz.sala_decode
    T, rows = P + n_new, sz.sala_rows
    ids = np.array(jax.random.randint(jax.random.fold_in(key, 2), (rows, T), 3,
                                      mcfg.vocab_size))
    ids[0, : P - P // 4] = pad      # a short row beside the one that selects
    ids = jnp.asarray(ids, jnp.int32)
    real = ids != pad
    check(int(real[1, :P].sum()) >= mcfg.sparse_dense_len
          > int(real[0].sum()), "one row past dense_len, one under it")

    def within(tested, plain, ref, what):
        return bf16_agreement(phase, tested, plain, ref,
                              np.ones(np.shape(ref), bool), (what, "plain"))

    # the positions that predict a decoded token: P - 1 .. T - 2
    with jax.default_matmul_precision("highest"):
        ref_last = np.asarray(jax.jit(lambda p, x, m: reference_sala.logits(
            p, file, x, pad, last=n_new + 1, mask=m, decoded=n_new))(
                params, ids, real))[:, :-1]
    forward = lambda m: np.asarray(jax.jit(lambda p, x: padded_forward_logits(  # noqa: E731
        p, m, x, pad, response_context_length=P))(params, ids), np.float32)
    plain_last = forward(plain_mcfg)
    scoring = within(forward(mcfg), plain_last, ref_last, "auto_scoring")

    # ---- two pieces, then teacher-forced decode, the contiguous cache ----
    # (a left-padded row's first piece starts at its first real token and
    # is as wide as any: room for its pad tokens behind the row's last slot)
    caches = init_kv_cache(mcfg, rows, -(-(T + piece) // 64) * 64, dtype)
    T_max = caches[0][0].shape[3]
    (state,) = caches[2]
    phase.expect(state.dtype == jnp.float32 and state.shape[1:] == (
        rows, mcfg.lightning_heads, mcfg.lightning_head_dim,
        mcfg.lightning_head_dim), f"the state is {state.dtype}{state.shape}")
    # (the first piece is a part of the whole prompt's call: told so)
    km0 = jnp.zeros((rows, T_max), bool)
    n_real = real[:, :P].sum(axis=1)
    pieces = jax.jit(lambda p, x, pos, at, km, valid, c: decode_verify(
        p, mcfg, x, pos, at, km, c, token_valid=valid, call_keys=n_real))
    position = jnp.cumsum(real, axis=1) - 1
    first = jnp.argmax(real, axis=1).astype(jnp.int32)
    lg = None
    key_mask = km0
    for lo, hi in ((0, piece), (piece, P)):
        # a row's tokens before its first real one are not valid, and a row
        # that has none yet starts later (its `fill` is its first real slot)
        at = jnp.clip(first, lo, hi).astype(jnp.int32)
        width = hi - lo
        gather = at[:, None] + jnp.arange(width)[None]
        toks = jnp.take_along_axis(ids, jnp.minimum(gather, T - 1), axis=1)
        valid = (gather < hi) & jnp.take_along_axis(
            real, jnp.minimum(gather, T - 1), axis=1)
        pos = jnp.take_along_axis(position, jnp.minimum(gather, T - 1), axis=1)
        lg, caches = pieces(params, toks, pos, at, key_mask, valid, caches)
        key_mask = key_mask.at[:, lo:hi].set(real[:, lo:hi])
    last_of = jnp.clip(P - 1 - jnp.clip(first, piece, P), 0, P - piece - 1)
    got = [np.asarray(jnp.take_along_axis(
        lg, last_of[:, None, None], axis=1)[:, 0], np.float32)]
    step = jax.jit(lambda p, tok, pos, t, km, c: decode_step(
        p, mcfg, tok, pos, t, km, c))
    for t in range(P, T - 1):
        key_mask = key_mask.at[:, t].set(True)
        lg, caches = step(params, ids[:, t], n_real + (t - P), jnp.int32(t),
                          key_mask, caches)
        got.append(np.asarray(lg, np.float32))
    del caches
    contiguous = within(np.stack(got, axis=1), plain_last, ref_last,
                        "contiguous_cache")
    phase.finish(config=sz.sala_config, layers=mcfg.num_hidden_layers,
                 hidden=mcfg.hidden_size, lightning=mcfg.linear_layers,
                 sparse=mcfg.sparse_layers, vocab=mcfg.vocab_size,
                 dtype=str(jnp.dtype(dtype)), rows=rows, tokens=T,
                 real_tokens=np.asarray(n_real).tolist(),
                 pieces=(piece, P - piece), decode_steps=n_new,
                 scoring=scoring, contiguous=contiguous)


def run_phases(sz: Sizes, multichip: bool, olmoe: bool = False,
               axk1: bool = False, falcon_h1: bool = False,
               sala: bool = False) -> None:
    """Everything after the device gate. Raises at the first failed phase."""
    from nanorlhf_tpu import native
    from nanorlhf_tpu.core import ModelConfig
    from nanorlhf_tpu.utils.compile_cache import enable_compilation_cache

    cache_dir = enable_compilation_cache()  # as the launchers do
    meter = Meter()
    emit("setup", compile_cache_dir=cache_dir,
         native_available=native.available(),
         tpu_worker_hostnames=os.environ.get("TPU_WORKER_HOSTNAMES"))
    out = os.path.join(ROOT, "output", "chip_smoke")
    if multichip:
        phase_multichip(sz, meter, os.path.join(out, "multichip"))
        return
    if olmoe:
        phase_olmoe(sz, meter)
        return
    if axk1:
        phase_axk1(sz, meter)
        return
    if falcon_h1:
        phase_falcon_h1(sz, meter)
        return
    if sala:
        phase_sala(sz, meter)
        return
    tiny = "tiny" in sz.model.lower()  # entrypoints.common.resolve_model's rule
    phase_kernels(sz, ModelConfig.qwen2_tiny(vocab_size=4096) if tiny
                  else ModelConfig.qwen2_1_5b(), meter)
    trainer, bf16_error = phase_train(sz, meter, os.path.join(out, "train"))
    phase_serve(sz, trainer, bf16_error, meter)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--multichip", action="store_true",
                        help="four chips: sharded GRPO vs one device, only")
    parser.add_argument("--olmoe", action="store_true",
                        help="one chip: OLMoE against its float32 reference, only")
    parser.add_argument("--axk1", action="store_true",
                        help="one chip: A.X-K1's share against its float32 reference, only")
    parser.add_argument("--falcon-h1", action="store_true",
                        help="one chip: Falcon-H1's stage against its float32 reference, only")
    parser.add_argument("--sala", action="store_true",
                        help="one chip: MiniCPM-SALA's stage against its float32 reference, only")
    args = parser.parse_args(argv)

    import jax

    found = jax.devices()
    device = {"platform": found[0].platform, "kind": found[0].device_kind,
              "count": len(found)}
    emit("device", **device, process_count=jax.process_count())
    if device["platform"] != "tpu":
        print("chip_smoke: jax found no TPU; this script proves nothing on "
              f"{device['platform']!r} and runs no phase", file=sys.stderr)
        return 1
    if args.multichip and device["count"] != 4:
        print(f"chip_smoke --multichip needs four chips, found "
              f"{device['count']}", file=sys.stderr)
        return 1

    run_phases(Sizes(), args.multichip, args.olmoe, args.axk1, args.falcon_h1,
               args.sala)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
