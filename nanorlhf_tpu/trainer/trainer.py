"""One RL trainer runtime for all six algorithms.

The reference ships six copy-paste-forked 700-line trainers
(`/root/reference/{GRPO,PPO,RLOO,ReMax,REINFORCE,RAFT}/*_trainer.py`, ~90%
identical — SURVEY.md §1). Here they collapse into a single runtime plus the
per-algorithm branch points SURVEY.md §2.4 tabulates:

  sampling        n per prompt, ReMax extra greedy rollout
  selection       GRPO keep-1-of-N *before* the logprob pass; RLOO/RAFT after
  KL placement    in-reward (PPO/RLOO/ReMax/REINFORCE/RAFT) vs in-loss (GRPO)
  advantage       group z-score / LOO / greedy delta / GAE / γ-discount / none
  loss            token PPO-clip (+k3 KL) / sequence PPO-clip / +value / SFT

TPU execution model (the design inversions of SURVEY.md §7):
- one HBM-resident sharded param tree serves rollout + scoring + update —
  the reference's per-step disk→vLLM handoff and all CPU offload is gone;
- optimizer state is sharded over the mesh (optax + GSPMD), replacing
  `state_to_device(..., 'cpu')`;
- the PPO-epoch × minibatch × microbatch hierarchy
  (`GRPO/grpo_trainer.py:628-707`) becomes one jitted minibatch update with a
  grad-accumulation `lax.scan` inside, stepped per minibatch (the reference's
  `accelerator.accumulate` steps once per minibatch too);
- rollout-phase logprob scoring runs in fixed-size jitted chunks (the
  `22*2316//(ctx+resp)` memory formula, `grpo_trainer.py:534`, becomes a
  static chunk size picked once).
"""

from __future__ import annotations

import dataclasses
import os
import time
from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax

from nanorlhf_tpu.algos import (
    best_of_k_indices,
    discounted_returns,
    gae,
    grpo_group_advantage,
    grpo_turn_advantage,
    keep_one_of_n_indices,
    per_turn_terminal_rewards,
    remax_advantage,
    rloo_advantage,
    sparse_terminal_rewards,
)
from nanorlhf_tpu.algos.losses import (
    grpo_loss,
    ppo_clip_loss_sequence,
    ppo_clip_loss_token,
    sft_loss,
    value_loss_clipped,
)
from nanorlhf_tpu.core.config import ModelConfig
from nanorlhf_tpu.core.lora import LoraConfig, init_lora_params, trainable_mask
from nanorlhf_tpu.core.model import (
    padded_forward_hidden,
    padded_forward_logits,
    score_forward,
    unembedding,
)
from nanorlhf_tpu.ops.fused_logprob import chunked_entropy, fused_logprob
from nanorlhf_tpu.ops.masking import (
    INVALID_LOGPROB,
    first_true_indices,
    logprobs_from_logits,
    masked_whiten,
    response_padding_masks,
    truncate_response,
)
from nanorlhf_tpu.parallel.mesh import (MeshConfig, batch_sharding, make_mesh,
                                        shard_params)
from nanorlhf_tpu.sampler import SamplingParams, compose_check, generate
from nanorlhf_tpu.sampler.sampler import (
    attn_read_frac, kv_in_place, paged_read_items, sample_pick,
)
from nanorlhf_tpu.telemetry import (DEFAULT_RULES, HealthConfig,
                                    HealthMonitor, LatencyHub,
                                    LineageLedger, SLO_RULES, SpanTracer,
                                    StatusExporter, flops_param_count,
                                    peak_flops_per_chip, recompile_counter,
                                    update_flops)
from nanorlhf_tpu.trainer.bucketing import depad_queries, shape_menu
from nanorlhf_tpu.trainer.checkpoint import CheckpointManager
from nanorlhf_tpu.trainer.config import AlgoName, RLConfig
from nanorlhf_tpu.trainer.metrics import (MetricsLogger,
                                          staleness_histogram_metrics)
from nanorlhf_tpu.utils.donation import donate_argnums_on_accel

# Rollout-phase forward chunking. Two independent memory models bound the
# chunk: (1) the reference's empirical activation budget `22*2316` tokens
# (`GRPO/grpo_trainer.py:534`), (2) the [tokens, vocab] logits block, capped
# at ~2 GB bf16 (dominant at LLM-sized vocabularies — the fixed constant
# alone would OOM a 16 GB chip at 152k vocab). Chunks take the min of both.
# Tunable via cfg.local_rollout_forward_batch_size.
ACTIVATION_TOKEN_BUDGET = 22 * 2316
_LOGITS_BYTES_BUDGET = 2 * 1024**3


def forward_token_budget(
    vocab_size: int, bytes_per_elem: int = 2, fused_logprob: bool = False
) -> int:
    """`fused_logprob=True` drops the vocab cap: the fused scorer
    (ops/fused_logprob.py) never materializes a [tokens, vocab] logits
    block — its internal chunking bounds that term independently — so the
    activation budget alone sizes the chunk, and score-pass chunks at LLM
    vocabularies grow ~8× (the "larger microbatches" half of the fused
    op's win)."""
    if fused_logprob:
        return ACTIVATION_TOKEN_BUDGET
    vocab_cap = max(1024, _LOGITS_BYTES_BUDGET // (vocab_size * bytes_per_elem))
    return min(ACTIVATION_TOKEN_BUDGET, vocab_cap)


def fused_response_logprobs(tree, mcfg, query_responses, responses, pad_id,
                            context_length: int, cfg, lora_scale: float = 1.0,
                            remat: bool = False, with_entropy: bool = False,
                            router_stats: bool = False):
    """The ONE fused hidden→logprob scorer call (ops/fused_logprob.py):
    response-position hidden states → per-token logprobs (+ entropy), with
    the cfg's chunk/impl knobs applied. Shared by the chunked scoring fns,
    the update-pass microbatch loss, and SparseGRPOTrainer's bucket fns so
    fused scoring and fused update numerics can never drift apart.
    `router_stats=True` (expert models) returns `(logprobs, stats)`, the
    router's per-row sums from the same forward (ops/moe.py)."""
    hidden = padded_forward_hidden(
        tree, mcfg, query_responses, pad_id, lora_scale=lora_scale,
        remat=remat, response_context_length=context_length,
        router_stats=router_stats,
    )
    stats = None
    if router_stats:
        hidden, stats = hidden
    # tied embeddings ride vocab-major ([V, D] + transposed=True): feeding
    # the .T view to the op's Pallas kernel would stage a full [D, V]
    # transposed copy for the custom call
    w, w_transposed = unembedding(mcfg, tree)
    out = fused_logprob(
        hidden, w, responses, cfg.temperature,
        chunk=cfg.fused_logprob_chunk, impl=fused_logprob_impl(cfg, mcfg),
        with_entropy=with_entropy, transposed=w_transposed,
    )
    return (out, stats) if router_stats else out


def fused_logprob_impl(cfg, mcfg) -> str:
    """What `cfg.fused_logprob_impl` means for this model config. "auto"
    under a multi-device mesh (`mcfg.spmd_mesh`) is the lax chunk scan,
    which GSPMD partitions like any XLA code: the Pallas kernel has no
    shard_map wrap (its weight arrives vocab-sharded, so a wrap needs a
    cross-shard logsumexp), and the TPU compiler refuses a Mosaic kernel it
    would have to partition itself. An explicit "pallas" is passed through
    and fails loudly there."""
    if cfg.fused_logprob_impl == "auto" and mcfg.spmd_mesh is not None:
        return "lax"
    return cfg.fused_logprob_impl


def device_peak_bytes() -> float:
    """Max `peak_bytes_in_use` across local devices — the `mem/peak_bytes_
    in_use` metric. 0.0 where the
    backend reports no memory stats (the CPU test mesh).

    This is the allocator's PROCESS-LIFETIME high-water mark (monotone): it
    answers "what HBM did this run need", not "what did this phase use" —
    a rollout/prefill or compile-time spike higher than the update pass
    dominates the series from then on. The per-phase fused-vs-naive
    attribution lives in `mem/logits_bytes_saved` (analytic) and the
    vocab-scaling memory_analysis assertion in tests/test_fused_logprob.py.
    """
    peak = 0.0
    for d in jax.local_devices():
        try:
            stats = d.memory_stats() or {}
        except Exception:
            stats = {}
        peak = max(peak, float(stats.get("peak_bytes_in_use", 0.0)))
    return peak


def pad_chunk(rows: np.ndarray, chunk: int) -> np.ndarray:
    """Pad a short final chunk up to `chunk` rows by repeating the last row.

    Chunked jitted passes run at ONE fixed shape: a ragged tail (e.g. a prime
    rollout count) is padded instead of shrinking the chunk — the old
    largest-divisor search silently degenerated to chunk=1 on awkward totals.
    Callers slice results back to the real row count.
    """
    n = rows.shape[0]
    if n >= chunk:
        return rows
    reps = np.repeat(rows[-1:], chunk - n, axis=0)
    return np.concatenate([rows, reps], axis=0)


@dataclasses.dataclass
class NoStep:
    """How an update ends without a step: why (the `train.update` span and a
    preemption's message say it), what else the span carries, and what the
    phase that ended it does once the loop has closed the span."""
    why: str
    span_args: dict = dataclasses.field(default_factory=dict)
    then: Optional[Callable[[], None]] = None
    # the update spends one of train()'s budget though it made no step (its
    # rollout is consumed for good); a rollback does not, it replays
    counts: bool = False


@dataclasses.dataclass
class TrainRun:
    """What one `train()` call sets up and every update reads."""
    n: int                       # completions sampled per prompt
    capture: bool                # the sampler returns its logprobs
    score_capture: bool          # ... and they stand in for the policy pass
    target_step: int             # train() returns at this global_step
    body: Callable               # dispatches one rollout (`_rollout_body`)
    sampling: SamplingParams     # ... with these
    counted_to: int = -1         # newest rollout a no-step was charged for
    # the rollout source's handles (`_ensure_handles`): the orchestrator
    # or a RolloutStream, and the overlap meter of whichever it is
    use_orch: bool = False
    orch: Any = None
    stream: Optional["RolloutStream"] = None
    meter: Any = None


@dataclasses.dataclass
class Update:
    """One update's record: what each phase hands the next. A phase sets
    the fields under its name; a later phase may narrow them (selection
    cuts `responses`, `decoded` and `log_scores` to the kept rows)."""
    t0: float
    span_t0: float = 0.0
    no_step: Optional[NoStep] = None
    span_args: dict = dataclasses.field(default_factory=dict)
    # rollout
    ro: Optional[dict] = None    # the rollout payload
    rollout_index: int = -1
    staleness: int = 0
    queue_depth: int = 0
    responses: Any = None        # [rows, T] tokens (numpy from `reward` on)
    captured_lp: Any = None      # sampler logprobs, when captured
    t_busy0: float = 0.0
    queries: Any = None          # [B, ctx] prompts as generated
    batch_size: int = 0
    context_length: int = 0      # context width of the scored batch
    # reward
    question_strings: Optional[list] = None
    decoded: Optional[list] = None
    seg_ages: Any = None         # per-token policy age (in-flight swaps)
    envp: Optional[dict] = None  # multi-turn environment payload
    raw_scores: Any = None       # rewards of all B*n rollouts, as graded
    scores: Any = None           # ... as the advantage reads them
    # select
    log_scores: Any = None       # raw scores of the rows still in the batch
    grpo_adv: Any = None         # group advantage of the kept rows
    env_turn: Optional[tuple] = None   # (turn advantages, turn ends)
    env_loss_mask: Any = None
    queries_rep: Any = None      # prompts row-aligned with `responses`
    qr_len: Any = None           # real tokens a row (length-bucketed phases)
    # score
    qr: Any = None               # [rows, ctx + T] scored tokens
    logprobs: Any = None
    ref_logprobs: Any = None
    router_l: list = dataclasses.field(default_factory=list)
    # advantages
    postprocessed: Any = None    # responses cut at the stop token
    padding_mask: Any = None
    contain_eos: Any = None
    scores_sel: Any = None
    reward_info: Optional[dict] = None
    batch: Optional[dict] = None     # arrays of the update's minibatches
    # update
    lr_step: int = 0
    logits_rows: int = 1         # rows of one update-pass logits buffer
    all_stats: Optional[list] = None
    agg: Optional[dict] = None
    # report, checkpoint
    extra_metrics: dict = dataclasses.field(default_factory=dict)
    metrics: dict = dataclasses.field(default_factory=dict)
    saved: bool = False


class RolloutStream:
    """Prefetchable rollout dispatcher over a stateless generation PRNG.

    `dispatch()` pulls the next prompt batch and ASYNC-dispatches generation
    through `body(queries, gen_key)` (nothing blocks until the caller reads
    the returned arrays). `fetch_or_dispatch()` consumes the prefetched
    rollout if one is pending and records its index in
    `trainer.state["rollouts"]` — the consumed-rollout counter that
    checkpoint/resume persists to fast-forward the data stream and re-key
    generation exactly (an update can end without a step: a sparse all-zero
    skip consumes a rollout, a sentinel quarantine burns one, so global_step
    alone under-counts). The one loop owns one stream a `train()` call; the
    orchestrator takes its place when `rollout_orchestrator` is on.

    Generation keys are `fold_in(base, index)` rather than splits of the
    evolving trainer key: rollout_ahead dispatches rollout k+1 before update
    k's host-side draws, and a shared stream would reorder splits between
    modes (and break bit-exact resume).

    `meter` (an orchestrator.OverlapMeter) records every dispatch's true
    [dispatch, device-ready] window via a waiter thread, so serial /
    rollout_ahead runs report the same rollout/train overlap-fraction
    metric the RolloutOrchestrator does (docs/ORCHESTRATOR.md).
    """

    def __init__(self, trainer, body: Callable, meter=None):
        self._t = trainer
        self._body = body
        self._idx = trainer.state["rollouts"]
        self._pending = None
        if meter is None:
            from nanorlhf_tpu.orchestrator import OverlapMeter

            meter = OverlapMeter()
        self.meter = meter

    def dispatch(self) -> dict:
        from nanorlhf_tpu.orchestrator import note_ready_async

        t = self._t
        queries = np.asarray(next(t._iter))
        key = jax.random.fold_in(t._rollout_base, self._idx)
        lin = getattr(t, "lineage", None)
        if lin is not None and lin.enabled:
            # serial/rollout_ahead runs have no coordinator: the dispatch
            # itself is the lease grant (worker 0, cursor == index)
            lin.lease(self._idx, worker_id=0, cursor=self._idx, length=1)
        t0 = time.perf_counter()  # overlap-meter gen window: consumer clock
        ro = self._body(queries, key)
        # hand the watcher a FROZEN view of the async outputs — blocking on
        # `ro` itself would race the "_index" insertion below
        note_ready_async(self.meter, (ro["gen_out"], ro.get("greedy")), t0,
                         tracer=getattr(t, "tracer", None),
                         span_args={"rollout_index": self._idx})
        ro["_index"] = self._idx
        self._idx += 1
        return ro

    def fetch_or_dispatch(self) -> dict:
        ro = self._pending or self.dispatch()
        self._pending = None
        self._t.state["rollouts"] = ro["_index"] + 1
        return ro

    def prefetch(self) -> None:
        self._pending = self.dispatch()

    @property
    def next_index(self) -> int:
        """Index the next fetch_or_dispatch() will deliver."""
        return self._pending["_index"] if self._pending is not None else self._idx

    def skip(self) -> int:
        """Consume the next data batch WITHOUT dispatching generation — the
        sentinel quarantined this index, and replaying it would pay a full
        rollout (the dominant per-step cost) just to discard the result.
        Only legal with no prefetch pending (an already-dispatched rollout
        can't be undone — the caller discards it instead)."""
        assert self._pending is None
        next(self._t._iter)  # burn the data cursor deterministically
        idx = self._idx
        self._idx += 1
        self._t.state["rollouts"] = self._idx
        lin = getattr(self._t, "lineage", None)
        if lin is not None:
            lin.drop(idx, "sentinel_quarantine",
                     step=self._t.state["global_step"], dispatched=False)
        return idx


class RLTrainer:
    """Unified online-RL trainer.

    Args mirror the reference trainer signature (`GRPO/grpo.py:274-285`):
    config, tokenizer, policy params, (optional) ref params, dataset iterator,
    reward_func(list[str], eos_token) -> array of scores.
    """

    def __init__(
        self,
        config: RLConfig,
        model_config: ModelConfig,
        tokenizer,
        params: dict,
        dataset,
        reward_func: Callable,
        value_params: Optional[dict] = None,
        mesh=None,
        rng_key: Optional[jax.Array] = None,
    ):
        self.cfg = config
        self.mcfg = model_config
        self.mcfg.require(f"training ({type(self).__name__})", "training")
        self.tokenizer = tokenizer
        self.reward_func = reward_func
        self.algo = config.algo

        # disaggregated rollouts (config.rollout_devices>0): generation gets
        # its own device group + mesh; training spans the rest. The trainer
        # owns both meshes — an externally built mesh can't be split safely.
        self.rollout_mesh = None
        # per-generation-mesh copies of the frozen LoRA base, keyed by mesh
        # identity: the single disaggregated mesh AND each fleet worker's
        # group get their own once-resharded base (see _rollout_params)
        self._disagg_base: dict = {}
        # per-worker generation meshes (rollout fleet × disaggregation):
        # None = every worker generates on the shared rollout/train mesh
        self.worker_meshes = None
        if config.rollout_devices > 0:
            if mesh is not None:
                raise ValueError(
                    "rollout_devices>0 builds its own train+rollout meshes; "
                    "pass mesh=None"
                )
            from nanorlhf_tpu.parallel.mesh import (
                split_rollout_devices,
                split_worker_groups,
            )

            train_dev, roll_dev = split_rollout_devices(
                jax.devices(), config.rollout_devices
            )
            self.mesh = make_mesh(config.mesh, devices=train_dev)
            rm_cfg = (config.rollout_mesh if config.rollout_mesh is not None
                      else MeshConfig())
            # the whole-group mesh stays: the synchronous/degraded fallback
            # generates on all reserved devices even when the fleet split
            # them per worker
            self.rollout_mesh = make_mesh(rm_cfg, devices=roll_dev)
            if config.rollout_workers > 1:
                self.worker_meshes = [
                    make_mesh(rm_cfg, devices=group)
                    for group in split_worker_groups(
                        roll_dev, config.rollout_workers
                    )
                ]
        else:
            self.mesh = mesh if mesh is not None else make_mesh(config.mesh)
        # Pallas-kernel SPMD hints (core/config.py spmd_mesh): on a mesh
        # whose batch/tensor axes span >1 device the kernel call sites must
        # shard_map themselves or GSPMD all-gathers their operands
        if (self.mesh.shape.get("data", 1) * self.mesh.shape.get("fsdp", 1)
                * self.mesh.shape.get("tensor", 1)) > 1:
            import dataclasses as _dc

            self.mcfg = _dc.replace(
                self.mcfg, spmd_mesh=self.mesh,
                spmd_batch_axes=("data", "fsdp"), spmd_head_axis="tensor",
            )
        if (config.remat_policy != "full"
                and config.remat_policy != self.mcfg.remat_policy):
            # RLConfig only OVERRIDES when set off its default — a caller
            # who customized ModelConfig.remat_policy directly must not be
            # silently reverted by an untouched RLConfig
            import dataclasses as _dc

            self.mcfg = _dc.replace(
                self.mcfg, remat_policy=config.remat_policy
            )
        if config.total_episodes is None:
            # episodes-from-epochs parity (`GRPO/grpo_trainer.py:216-217`)
            if not hasattr(dataset, "__len__"):
                raise ValueError(
                    "total_episodes=None needs a sized dataset (e.g. "
                    "PromptDataset) to derive episodes from num_train_epochs"
                )
            config.total_episodes = int(config.num_train_epochs * len(dataset))
        config.finalize_world(
            self.mesh.shape.get("data", 1) * self.mesh.shape.get("fsdp", 1)
        )

        # ---- async rollout orchestrator (orchestrator/) ------------------
        if config.rollout_orchestrator:
            if config.rollout_ahead:
                raise ValueError(
                    "rollout_orchestrator generalizes rollout_ahead — enable "
                    "one, not both"
                )
            if config.max_staleness < 0:
                raise ValueError(f"max_staleness={config.max_staleness}")
            if config.staleness_policy not in ("wait", "drop"):
                raise ValueError(
                    f"staleness_policy={config.staleness_policy!r}: wait|drop"
                )
        if config.rollout_workers < 1:
            raise ValueError(f"rollout_workers={config.rollout_workers}")
        if config.rollout_workers > 1 and not config.rollout_orchestrator:
            raise ValueError(
                "rollout_workers > 1 is the fleet generalization of the "
                "async pipeline — it requires rollout_orchestrator=True "
                "(docs/FLEET.md)"
            )
        if config.rollout_transport not in ("inprocess", "rpc"):
            raise ValueError(
                f"rollout_transport={config.rollout_transport!r}: "
                "inprocess | rpc"
            )
        if (config.rollout_transport == "rpc"
                and config.rollout_workers <= 1):
            raise ValueError(
                "rollout_transport='rpc' is the fleet's network seam — it "
                "requires rollout_workers > 1 (docs/FLEET.md)"
            )
        if config.offpolicy_correction not in ("truncated_is", "none"):
            raise ValueError(
                f"offpolicy_correction={config.offpolicy_correction!r}"
            )
        # truncated-IS correction needs the behavior policy's logprobs —
        # only the sampler capture provides them; without capture the PPO
        # ratio clip alone absorbs the staleness drift (rollout_ahead's
        # documented behavior)
        if config.rollout_inflight_swaps:
            if not config.rollout_orchestrator:
                raise ValueError(
                    "rollout_inflight_swaps reads the orchestrator's weight "
                    "store mid-generation — it requires "
                    "rollout_orchestrator=True (docs/ORCHESTRATOR.md)"
                )
            if config.rollout_page_size <= 0 or config.rollout_decode_rows <= 0:
                raise ValueError(
                    "rollout_inflight_swaps swaps weights at chunk boundaries "
                    "of the queued paged scheduler — it requires "
                    "rollout_page_size > 0 and rollout_decode_rows > 0 "
                    "(docs/PAGED_CACHE.md)"
                )
        self._use_is = (
            config.rollout_orchestrator
            and config.max_staleness > 0
            and config.sampler_logprob_capture
            and config.offpolicy_correction == "truncated_is"
        )
        # per-segment IS (docs/ORCHESTRATOR.md §in-flight swaps): only
        # meaningful when generations can span >1 policy version; without
        # swaps every row is single-segment and whole-sequence IS is exact
        self._use_seg = self._use_is and config.rollout_inflight_swaps
        self._orchestrator = None
        self._orch_restore_state = None  # journal from a resumed checkpoint
        from nanorlhf_tpu.orchestrator import OverlapMeter

        # ONE meter for the whole trainer lifetime (stream objects are
        # recreated per train() call): the rollout/train overlap fraction
        # accumulates across calls (`benchmark/drivers/rl.py` calls
        # train(num_updates=1) in a loop)
        self._rollout_meter = OverlapMeter()

        self.key = rng_key if rng_key is not None else jax.random.PRNGKey(config.seed)
        # generation PRNG is a dedicated STATELESS stream keyed by rollout
        # index: rollout_ahead dispatches rollout k+1 before update k's
        # host-side key draws, and a shared evolving stream would reorder
        # splits between modes (and break bit-exact resume — the index-keyed
        # form needs only global_step to reconstruct)
        self._rollout_base = jax.random.fold_in(self.key, 0x5E11)

        # ---- LoRA + ref policy -------------------------------------------
        self.lora_cfg = (
            LoraConfig(r=config.lora_r, alpha=config.lora_alpha)
            if config.use_lora
            else None
        )
        if self.lora_cfg and "lora" not in params:
            self.key, k = jax.random.split(self.key)
            params = {**params, "lora": init_lora_params(
                self.mcfg, self.lora_cfg, k, dtype=jnp.bfloat16
            )}
        self.lora_scale = self.lora_cfg.scale if self.lora_cfg else 1.0

        # value-model LoRA (`PPO/ppo.py:301-332`): adapters + score + embed
        # train, backbone frozen — the Adam state for the value tree shrinks
        # from full-model to adapter-sized
        self.value_lora_cfg = (
            LoraConfig(r=config.value_lora_r, alpha=config.value_lora_alpha)
            if (config.value_use_lora and value_params is not None)
            else None
        )
        if self.value_lora_cfg and "lora" not in value_params:
            self.key, k = jax.random.split(self.key)
            value_params = {**value_params, "lora": init_lora_params(
                self.mcfg, self.value_lora_cfg, k,
                dtype=value_params["embed_tokens"].dtype,
            )}
        self.value_lora_scale = (
            self.value_lora_cfg.scale if self.value_lora_cfg else 1.0
        )

        # ref policy = frozen copy of the base weights (the reference loads
        # the same SFT model twice, `GRPO/grpo.py:218-224`); sharded alike.
        # Copy-on-intake: device_put with an unchanged sharding ALIASES the
        # caller's buffers, and the jitted update donates its inputs — without
        # the copy, training would invalidate the arrays the caller passed in.
        # Ref-free mode (kl_coef == 0, r1-zero parity): no copy, no ref pass.
        if config.score_ref_logprobs is False and config.kl_coef != 0.0:
            # dropping the ref while its KL coefficient is live would
            # silently swap the configured ref-KL objective for a
            # KL-to-old-policy (GRPO) or a zeroed penalty (KL-in-reward)
            raise ValueError(
                "score_ref_logprobs=False requires kl_coef == 0 — with a "
                "live KL coefficient the reference logprobs are part of "
                "the objective, not just a metric"
            )
        self._ref_free = not (
            config.score_ref_logprobs
            if config.score_ref_logprobs is not None
            else config.kl_coef != 0.0
        )
        if self._ref_free:
            self.ref_params = None
        else:
            ref = {k: v for k, v in params.items() if k != "lora"}
            self.ref_params = shard_params(jax.tree.map(jnp.copy, ref), self.mesh)
        self.params = shard_params(jax.tree.map(jnp.copy, params), self.mesh)
        self.value_params = (
            shard_params(jax.tree.map(jnp.copy, value_params), self.mesh)
            if value_params is not None else None
        )
        if self.algo == AlgoName.PPO and self.value_params is None:
            raise ValueError("PPO requires value_params")

        # single-process SPMD: the dataloader yields the GLOBAL batch, sharded
        # over the mesh's (data, fsdp) axes on device_put
        self.dataset = dataset
        self._iter = dataset.loader(config.batch_size, config.seed) \
            if hasattr(dataset, "loader") else iter(dataset)

        # ---- optimizer ----------------------------------------------------
        # The optimizer only ever sees the *trainable* partition of the tree
        # (LoRA adapters + embed/lm_head + value model): Adam moments and grad
        # accumulators never materialize for frozen base weights, and frozen
        # weights can never drift via weight decay.
        self.optimizer = self._build_optimizer()
        trainable, _ = self._partition(self._train_tree(self.params, self.value_params))
        self.opt_state = jax.jit(self.optimizer.init)(trainable)

        # ---- resilience layer (resilience/, docs/RESILIENCE.md) ----------
        from nanorlhf_tpu.resilience import (
            FaultInjector,
            PreemptionGuard,
            ProducerWatchdog,
            SentinelConfig,
            TrainingSentinel,
            WatchdogConfig,
            null_guard,
        )

        self.faults = FaultInjector.from_spec(config.fault_spec)
        self.sentinel = TrainingSentinel(SentinelConfig(
            enabled=config.sentinel,
            spike_zscore=config.sentinel_spike_zscore,
            ewma_alpha=config.sentinel_ewma_alpha,
            warmup_steps=config.sentinel_warmup_steps,
            rollback_budget=config.rollback_budget,
        ))
        self.watchdog = ProducerWatchdog(WatchdogConfig(
            restart_budget=config.producer_restart_budget,
            backoff_base=config.producer_backoff_base,
            backoff_max=config.producer_backoff_max,
            backoff_jitter=config.producer_backoff_jitter,
            degrade_to_sync=config.degrade_to_sync,
            # the jitter exists to DE-correlate replicas that share a
            # training seed (SPMD determinism forces that), so the draw
            # seed must mix in per-process identity or every replica
            # computes the same "random" backoff and stampedes anyway
        ), seed=(config.seed << 20) ^ (jax.process_index() << 10)
            ^ os.getpid())
        self._preemption = (
            PreemptionGuard() if config.graceful_preemption else null_guard()
        )

        # ---- telemetry (telemetry/, docs/OBSERVABILITY.md) ---------------
        # Span tracer + flight recorder: off by default — disabled, every
        # recording call is a cheap no-op, so the instrumentation stays
        # inline unconditionally (tests/test_telemetry.py holds the no-op).
        # The MFU/throughput accounting below is plain arithmetic
        # and is emitted regardless of the flag.
        self.tracer = SpanTracer(
            enabled=config.telemetry,
            max_events=config.telemetry_max_events,
            ring_len=config.flight_recorder_len,
        )
        self._telemetry_dir = config.telemetry_dir or config.output_dir
        # analytic model-FLOPs inputs (telemetry/mfu.py's napkin model)
        self._flops_params = flops_param_count(self.params,
                                                self.mcfg.loop_passes)
        self._peak_flops, self._peak_flops_known = peak_flops_per_chip(
            jax.devices()[0].device_kind, jax.default_backend()
        )
        self._n_devices = len(jax.devices())
        # process-global jax.monitoring backend-compile listener: silent
        # retraces surface as a perf/recompiles step, not a mystery stall
        self._recompiles = recompile_counter()

        self.ckpt = CheckpointManager(
            config.output_dir, config.save_total_limit,
            config.greater_is_better,
            io_retries=config.ckpt_io_retries,
            retry_backoff=config.ckpt_retry_backoff,
            faults=self.faults,
            tracer=self.tracer,
        )
        self.logger = MetricsLogger(config.output_dir, config.report_to)
        # sample lineage ledger (telemetry/lineage.py, docs/OBSERVABILITY.md
        # §6): per-rollout-index provenance — lease, generation, queue,
        # reward, outcome, drop — as rotated JSONL under
        # <telemetry_dir>/lineage/. Off by default; disabled, every emit is
        # a cheap no-op so the instrumentation stays inline unconditionally
        # (tests/test_lineage.py holds the no-op). The key_path
        # string documents the generation-PRNG derivation on lease events
        # (RolloutStream.dispatch below holds the actual fold_in).
        self.lineage = LineageLedger(
            self._telemetry_dir,
            enabled=config.lineage,
            sample_rate=config.lineage_sample_rate,
            key_path="fold_in(fold_in(seed_key, 0x5E11), rollout_index)",
        )
        # latency surface (telemetry/hist.py, docs/OBSERVABILITY.md §7):
        # one mergeable log-bucketed histogram per latency/* key — TTFT,
        # inter-token gap, queue wait, RPC RTT, reward wall, phase
        # durations. Disabled, record() is a cheap no-op so every
        # instrumentation site stays inline (tests/test_latency.py).
        self.latency = LatencyHub(enabled=config.latency)
        # cross-request radix prefix cache (rollout_prefix_cache, serving/
        # radix.py, docs/SERVING.md): the queued rollout path admits rows
        # through it — one long-lived object so the cumulative stats feed
        # pages/shared + /statusz "prefix_cache"; the scheduler resets its
        # pool/tree every generate call (cached KV is params-tied).
        # decode-feature legality is validated ONCE here through the same
        # compose_check generate() re-runs per call — the trainer fails at
        # construction, not mid-run, and the matrix lives in one place
        # (sampler/sampler.py). spec×prefix now COMPOSES (the session
        # seeds the drafter from the radix continuation).
        compose_check(
            SamplingParams(
                page_size=config.rollout_page_size,
                decode_rows=config.rollout_decode_rows,
                spec_k=config.rollout_spec_k,
                prefill_chunk=config.rollout_prefill_chunk),
            prefix_cache=config.rollout_prefix_cache,
            config=self.mcfg)
        self.prefix_cache = None
        if config.rollout_prefix_cache:
            from nanorlhf_tpu.serving.radix import RadixCache
            self.prefix_cache = RadixCache()
        # environments (envs/, docs/ENVIRONMENTS.md): env_name builds an
        # Environment around reward_func. A SINGLE-TURN env unwraps back
        # into a plain reward callable, so generation, reward dispatch
        # (retries, the reward.exec fault site), and every metric stay on
        # the exact non-env code path — the parity pin holds by
        # construction. MULTI-TURN swaps the rollout phase for the paged
        # episode driver (envs/rollout.py) and threads a per-token
        # loss_mask through the scored batch.
        self.env = None
        self._env_multi_turn = False
        if config.env_name:
            from nanorlhf_tpu.envs import build_env

            self.env = build_env(
                config.env_name, reward_func,
                max_turns=config.env_max_turns,
                tool_timeout=config.env_tool_timeout,
                eos_token=tokenizer.eos_token,
            )
            if self.env.max_turns == 1:
                self.reward_func = self.env.as_reward_func()
            else:
                self._env_multi_turn = True
                if self.algo != AlgoName.GRPO:
                    raise ValueError(
                        "multi-turn environments (env_max_turns > 1) are "
                        "wired for GRPO only: per-turn advantages ride the "
                        "group z-score path")
                if config.rollout_page_size <= 0:
                    raise ValueError(
                        "env_max_turns > 1 requires rollout_page_size > 0: "
                        "continuation turns are admitted through the paged "
                        "single-row bucketed prefill")
                if (config.rollout_orchestrator or config.rollout_workers > 1
                        or config.rollout_spec_k > 0
                        or config.sampler_logprob_capture
                        or config.rollout_prefix_cache):
                    raise ValueError(
                        "env_max_turns > 1 is incompatible with the "
                        "orchestrator fleet, spec decode, sampler logprob "
                        "capture, and the prefix cache — the episode driver "
                        "owns the rollout phase")
                tt = config.env_turn_tokens or config.response_length
                budget = (tt * config.env_max_turns
                          + config.env_obs_budget * (config.env_max_turns - 1))
                if budget > config.response_length:
                    raise ValueError(
                        f"episode budget {budget} (env_turn_tokens={tt} * "
                        f"{config.env_max_turns} turns + env_obs_budget="
                        f"{config.env_obs_budget} * "
                        f"{config.env_max_turns - 1} observations) exceeds "
                        f"response_length={config.response_length} — the "
                        "packed episode must fit the scored batch")
        # run-health plane (telemetry/health.py, docs/OBSERVABILITY.md §5):
        # every metrics row folds through streaming aggregates + anomaly
        # rules; CRIT dumps a reason="health" blackbox through the tracer
        # (a no-op when telemetry is off) and optionally arms the sentinel.
        # With the latency surface on, the quantile SLO rules ride along
        # and read the hub's histograms directly (p95 TTFT, p99 queue
        # wait, p95 RPC RTT — docs/OBSERVABILITY.md §7).
        rules = DEFAULT_RULES + (SLO_RULES if config.latency else ())
        self.health = HealthMonitor(
            HealthConfig(
                enabled=config.health,
                fast_alpha=config.health_fast_alpha,
                slow_alpha=config.health_slow_alpha,
                warmup=config.health_warmup_steps,
                window_s=config.health_window_s,
                max_events=config.health_max_events,
                blackbox_on_crit=config.health_blackbox_on_crit,
                rules=rules,
            ),
            tracer=self.tracer,
            blackbox_fn=self._health_blackbox,
            on_crit=self._on_health_crit,
            latency=self.latency,
        )
        # live status endpoints (telemetry/exporter.py): off unless
        # cfg.status_port is set (-1 = ephemeral — tests/CI)
        self.exporter = StatusExporter(
            config.status_port,
            host=config.status_host,
            metrics_fn=self._export_metrics,
            health=self.health,
            statusz_fn=self._statusz,
            latency=self.latency,
        )
        from nanorlhf_tpu.utils.profiling import PhaseTimer, ProfileWindow

        self.timer = PhaseTimer(tracer=self.tracer)
        # windowed XLA profiling (docs/OBSERVABILITY.md): polled at the top
        # of every update; opens at cfg.profile_at_step or when the trigger
        # file is touched on a live run
        self.profile_window = ProfileWindow(
            config.profile_dir or os.path.join(config.output_dir, "profile"),
            at_step=config.profile_at_step,
            num_steps=config.profile_num_steps,
            trigger_file=config.profile_trigger_file
            or os.path.join(config.output_dir, "PROFILE"),
        )
        self._update_fn = self._make_update_fn()
        # int8 rollout weights (core/quant.py): quantize the frozen base
        # projections once under LoRA; full-FT re-quantizes at each dispatch
        self._quant_layers = None
        if config.rollout_quant == "int8" and self.mcfg.num_experts:
            raise ValueError(
                "rollout_quant='int8' with a sparse-expert model: the int8 "
                "kernels of core/quant.py know dense [in, out] projections "
                "only; the experts, most of the weights, would stay bf16 "
                "(docs/MOE.md)"
            )
        if config.rollout_quant == "int8":
            self._refresh_quant_layers()
        elif config.rollout_quant != "none":
            raise ValueError(f"rollout_quant={config.rollout_quant!r}")
        # int8 KV cache: a rollout-only ModelConfig variant — scoring/update
        # paths keep the exact config (they never build a cache)
        if config.kv_cache_quant not in ("none", "int8"):
            raise ValueError(f"kv_cache_quant={config.kv_cache_quant!r}")
        if config.kv_cache_quant == "int8" and self.mcfg.kv_lora_rank:
            raise ValueError(
                "kv_cache_quant='int8' with a latent-attention model: the "
                "cache is one latent a token, which has no int8 form "
                "(core/model._latent_cache_shape, docs/MLA.md)")
        if config.kv_cache_quant == "int8":
            self.mcfg.require("kv_cache_quant='int8'")
        import dataclasses as _dc

        self._rollout_mcfg = (
            _dc.replace(self.mcfg, kv_cache_quant=config.kv_cache_quant)
            if config.kv_cache_quant != self.mcfg.kv_cache_quant else self.mcfg
        )
        if self.rollout_mesh is not None:
            # generation compiles against the ROLLOUT mesh: its kernel SPMD
            # hints must name that mesh (the train-mesh hints inherited from
            # self.mcfg would shard_map kernels over devices generation
            # doesn't run on)
            rsh = self.rollout_mesh.shape
            multi = (rsh.get("data", 1) * rsh.get("fsdp", 1)
                     * rsh.get("tensor", 1)) > 1
            self._rollout_mcfg = _dc.replace(
                self._rollout_mcfg,
                spmd_mesh=self.rollout_mesh if multi else None,
                spmd_batch_axes=("data", "fsdp"),
                spmd_head_axis="tensor",
            )
        # opt_steps counts ACTUAL optimizer.update calls — the schedule index
        # for the `lr` metric (a derived formula drifts when the minibatch
        # loop doesn't divide evenly)
        # "rollouts" counts CONSUMED rollouts (== global_step for the dense
        # runtime; >= for sparse, whose all-zero-advantage skips consume a
        # batch without stepping) — the resume cursor for data + PRNG streams
        self.state = {"episode": 0, "global_step": 0, "opt_steps": 0,
                      "rollouts": 0}

    # ------------------------------------------------------------------ #
    # rollout weight quantization
    # ------------------------------------------------------------------ #

    def _refresh_quant_layers(self, src: Optional[dict] = None):
        from nanorlhf_tpu.core.quant import quantize_layers

        src = self.params if src is None else src
        q = quantize_layers(src["layers"])
        self._quant_layers = shard_params({"layers": q}, self.mesh)["layers"]

    def _rollout_params(self, tree: Optional[dict] = None, mesh=None):
        """The param tree generation samples from: exact everywhere, except
        int8 base projections when rollout_quant is on (LoRA/embed/norm are
        always the live exact arrays — see core/quant.py). With a dedicated
        rollout mesh, the view is re-sharded onto it here — the once-per-
        dispatch param sync (an async device_put tree; the only transfer
        that crosses the train/rollout device groups). `tree` overrides the
        live self.params source — the orchestrator's producer thread passes
        a PUBLISHED snapshot so generation never races the jitted update's
        buffer donation. `mesh` overrides the destination mesh — a fleet
        worker passes its own device group's mesh (docs/FLEET.md)."""
        src = self.params if tree is None else tree
        if self._quant_layers is None:
            tree = src
        else:
            if not self.cfg.use_lora:  # full FT: base changed since last update
                self._refresh_quant_layers(src)
            from nanorlhf_tpu.core.quant import rollout_view

            tree = rollout_view(src, self._quant_layers)
        mesh = mesh if mesh is not None else self.rollout_mesh
        if mesh is not None:
            if self.cfg.use_lora:
                # LoRA freezes the base: re-shard it onto each generation
                # mesh ONCE and reuse; per dispatch only the live adapter
                # subtree (MBs, not the GBs of base projections) crosses
                # the train/rollout device groups
                base = self._disagg_base.get(id(mesh))
                if base is None:
                    base = self._disagg_base[id(mesh)] = shard_params(
                        {k: v for k, v in tree.items() if k != "lora"},
                        mesh,
                    )
                live = shard_params({"lora": tree["lora"]}, mesh)
                tree = {**base, **live}
            else:
                tree = shard_params(tree, mesh)
        return tree

    # ------------------------------------------------------------------ #
    # async rollout orchestrator (orchestrator/, docs/ORCHESTRATOR.md)
    # ------------------------------------------------------------------ #

    def _policy_snapshot(self) -> dict:
        """An immutable view of the current policy for the weight store:
        the TRAINABLE leaves are copied (the jitted update donates exactly
        those buffers — a producer-thread generation reading them live
        would race the donation), frozen leaves alias the live arrays
        (never donated, never mutated). Under LoRA the copy is MBs of
        adapters; full fine-tuning pays a full-tree copy per publish."""
        mask = trainable_mask(self.params, self.lora_cfg)
        return jax.tree.map(
            lambda p, m: jnp.copy(p) if m else p, self.params, mask
        )

    def _ensure_orchestrator(self, body: Callable):
        """Create (once) the rollout pipeline — the single producer thread
        (rollout_workers == 1) or the N-worker fleet (docs/FLEET.md); both
        share the consumer surface, so everything downstream (watchdog,
        sentinel, checkpoints) is mode-blind. The pipeline outlives train()
        calls — it stays warm across repeated train(num_updates=1)
        invocations (how `benchmark/drivers/rl.py` measures) — and is torn
        down by close() or
        resume_from_checkpoint()."""
        if self._orchestrator is None:
            cfg = self.cfg
            if cfg.rollout_workers > 1:
                from nanorlhf_tpu.orchestrator import FleetOrchestrator
                from nanorlhf_tpu.orchestrator.fleet import FleetConfig

                rpc_cfg = None
                if cfg.rollout_transport == "rpc":
                    from nanorlhf_tpu.orchestrator.rpc import RpcConfig

                    rpc_cfg = RpcConfig(
                        host=cfg.fleet_rpc_host,
                        port=cfg.fleet_rpc_port,
                        call_timeout=cfg.fleet_rpc_timeout,
                        attempts=cfg.fleet_rpc_attempts,
                        backoff_base=cfg.fleet_rpc_backoff_base,
                    )

                def batch_fn():
                    # the COORDINATOR is the sole consumer of the data
                    # iterator (under its lock, in strict index order) and
                    # caches each lease's batches — reassignment replays
                    # the same batch without re-burning the cursor
                    return np.asarray(next(self._iter))

                def fleet_dispatch(index: int, queries, tree: dict,
                                   worker_id: int,
                                   weight_refresh=None) -> dict:
                    # the same stateless index-keyed PRNG stream as every
                    # other mode: WHICH worker generates a sample can never
                    # change WHAT is generated (staleness-0 bit parity).
                    # `weight_refresh` arrives only when the transport saw
                    # inflight_swaps=True (4-arg calls stay valid).
                    key = jax.random.fold_in(self._rollout_base, index)
                    gen_mesh = None
                    if self.worker_meshes:
                        gen_mesh = self.worker_meshes[
                            worker_id % len(self.worker_meshes)
                        ]
                    return body(queries, key, tree, gen_mesh, weight_refresh)

                self._orchestrator = FleetOrchestrator(
                    dispatch_fn=fleet_dispatch,
                    batch_fn=batch_fn,
                    initial_params=self._policy_snapshot(),
                    n_workers=cfg.rollout_workers,
                    start_index=self.state["rollouts"],
                    max_staleness=cfg.max_staleness,
                    policy=cfg.staleness_policy,
                    meter=self._rollout_meter,
                    restore=self._orch_restore_state,
                    heartbeat=cfg.producer_heartbeat,
                    faults=self.faults,
                    tracer=self.tracer,
                    lineage=self.lineage,
                    latency=self.latency,
                    fleet=FleetConfig(
                        lease_size=cfg.fleet_lease_size,
                        failure_budget=cfg.fleet_failure_budget,
                        quarantine_base=cfg.fleet_quarantine_base,
                        quarantine_max=cfg.fleet_quarantine_max,
                        backoff_jitter=cfg.fleet_backoff_jitter,
                        straggler_factor=cfg.fleet_straggler_factor,
                        initial_deadline_s=cfg.fleet_initial_deadline,
                        worker_timeout_s=cfg.fleet_initial_deadline,
                        seed=cfg.seed,
                    ),
                    transport=cfg.rollout_transport,
                    rpc=rpc_cfg,
                    inflight_swaps=cfg.rollout_inflight_swaps,
                )
            else:
                from nanorlhf_tpu.orchestrator import RolloutOrchestrator

                def dispatch(index: int, tree: dict) -> dict:
                    # the producer is the SOLE consumer of the data
                    # iterator, and keys come from the stateless
                    # index-keyed stream — the same (data, PRNG) cursors
                    # the synchronous trainer uses, so checkpoint/resume
                    # fast-forwards reproduce the streams
                    queries = np.asarray(next(self._iter))
                    key = jax.random.fold_in(self._rollout_base, index)
                    refresh = None
                    if cfg.rollout_inflight_swaps:
                        # serial/in-process path: poll the orchestrator's
                        # weight store directly (no transport hop), seeded
                        # with the dispatch version the producer pinned
                        from nanorlhf_tpu.orchestrator.weight_store import (
                            make_swap_refresh,
                            store_poll,
                        )

                        refresh = make_swap_refresh(
                            store_poll(self._orchestrator.store),
                            have_version=self._orchestrator.store.version,
                            faults=self.faults, worker=0,
                        )
                    return body(queries, key, tree, None, refresh)

                self._orchestrator = RolloutOrchestrator(
                    dispatch_fn=dispatch,
                    initial_params=self._policy_snapshot(),
                    start_index=self.state["rollouts"],
                    max_staleness=cfg.max_staleness,
                    policy=cfg.staleness_policy,
                    meter=self._rollout_meter,
                    restore=self._orch_restore_state,
                    heartbeat=cfg.producer_heartbeat,
                    faults=self.faults,
                    tracer=self.tracer,
                    lineage=self.lineage,
                    latency=self.latency,
                )
            self._orch_restore_state = None
        return self._orchestrator

    def _reset_data_iterator(self):
        """Rebuild the deterministic loader and fast-forward to the
        consumed-rollout cursor — shared by resume, producer restart, and
        the degraded-mode fallback (all three re-draw anything a dead
        producer may have pulled past the cursor)."""
        self._iter = self.dataset.loader(self.cfg.batch_size, self.cfg.seed) \
            if hasattr(self.dataset, "loader") else iter(self.dataset)
        for _ in range(self.state["rollouts"]):
            next(self._iter)

    def _restart_producer(self, body: Callable):
        """Watchdog restart: tear down the dead pipeline, carry the queue's
        cumulative counters forward, reset the data cursor, and rebuild.
        The index-keyed generation PRNG + deterministic loader make the
        redrawn samples' token streams identical to what the dead producer
        would have delivered (at staleness 0 exactly; at staleness > 0 the
        redraw may sample from fresher weights — the resume semantics)."""
        old = self._orchestrator
        if old is not None:
            self._orch_restore_state = old.journal()
            old.close(join_timeout=5.0)
            self._orchestrator = None
        self._reset_data_iterator()
        return self._ensure_orchestrator(body)

    def rollout_overlap_frac(self) -> float:
        """Cumulative rollout/train overlap fraction (orchestrator metric;
        also measured for serial / rollout_ahead runs); the row's
        `time/rollout_overlap_frac` (tests/test_orchestrator.py)."""
        return self._rollout_meter.overlap_fraction()

    @staticmethod
    def _spec_decode_metrics(spec_stats) -> dict:
        """rollout/draft_acceptance + accepted_per_step + spec_verify_steps
        rows (docs/METRICS.md) from a speculative-decode stats dict. {} when
        the lever is off."""
        if spec_stats is None:
            return {}
        v_steps = float(np.asarray(spec_stats["verify_steps"]))
        return {
            # fraction of drafted tokens accepted; tokens emitted per live
            # row per verify dispatch (the monolithic loop's is identically
            # 1); and the dispatch count itself
            "rollout/draft_acceptance": (
                float(np.asarray(spec_stats["accepted"]))
                / max(float(np.asarray(spec_stats["drafted"])), 1.0)
            ),
            "rollout/accepted_per_step": (
                float(np.asarray(spec_stats["emitted"]))
                / max(float(np.asarray(spec_stats["row_steps"])), 1.0)
            ),
            "rollout/spec_verify_steps": v_steps,
        }

    @staticmethod
    def _paged_metrics(paged_stats) -> dict:
        """rollout/page_utilization + pages_recycled + admitted_midloop rows
        (docs/METRICS.md) from a paged-cache stats dict. The monolithic
        paged path reports utilization with zero recycling/admissions; the
        continuous-batching scheduler reports all three. {} when
        rollout_page_size is off."""
        if paged_stats is None:
            return {}
        out = {
            "rollout/page_utilization": float(
                np.asarray(paged_stats["page_utilization"])),
            "rollout/pages_recycled": float(
                np.asarray(paged_stats["pages_recycled"])),
            "rollout/admitted_midloop": float(
                np.asarray(paged_stats["admitted_midloop"])),
        }
        if "prefix_hit_frac" in paged_stats:
            # radix prefix cache active (rollout_prefix_cache): suffix-only
            # admission prefill + refcount-shared pages (docs/SERVING.md)
            out["rollout/prefix_hit_frac"] = float(
                paged_stats["prefix_hit_frac"])
            out["pages/shared"] = float(paged_stats["shared_pages"])
        if "dispatch_events" in paged_stats:
            # decode-session accounting (continuous batching only,
            # sampler/paged/session.py): total device dispatches =
            # admission launches + decode/verify chunk iterations — the
            # number the spec×prefix composition gate drives down — plus
            # the chunked-prefill admission counters
            out["session/dispatch_events"] = float(
                paged_stats["dispatch_events"])
            out["session/chunked_admissions"] = float(
                paged_stats["chunked_admissions"])
            out["session/prefill_backlog"] = float(
                paged_stats["prefill_backlog_peak"])
        return out

    # ------------------------------------------------------------------ #
    # telemetry: perf/MFU accounting (telemetry/, docs/OBSERVABILITY.md)
    # ------------------------------------------------------------------ #

    def _perf_metrics(self, *, step_wall_s: float, decode_tokens: float,
                      prefill_tokens: float, score_tokens: float,
                      train_tokens: float, rollout_s: float,
                      update_s: float) -> dict:
        """Per-update throughput/MFU rows (docs/METRICS.md `perf/*`): the
        analytic napkin FLOPs model from telemetry/mfu.py. Token counts come
        from the update's record: the rows each phase actually ran, at
        their widths (the sparse trainer's kept rows and bucket widths
        included).

        `perf/tokens_per_sec_rollout` divides by the trainer-OBSERVED
        rollout phase seconds: under the orchestrator that window is just
        the fetch wait, so the metric reads as effective pipeline
        throughput (it rises as overlap hides generation), not raw
        generation speed — the producer's own speed is visible in the
        trace spans."""
        flops = update_flops(
            self._flops_params,
            decode_tokens=decode_tokens, prefill_tokens=prefill_tokens,
            score_tokens=score_tokens, train_tokens=train_tokens,
        )
        all_tokens = decode_tokens + prefill_tokens + score_tokens + train_tokens
        return {
            "perf/mfu": flops / max(step_wall_s, 1e-9)
            / (self._peak_flops * self._n_devices),
            # 0.0 = the peak-FLOPs table fell back to a nominal constant
            # (e.g. CPU 1e12) and perf/mfu above is not a trustworthy
            # utilization number — consumers (/statusz) flag it
            "perf/peak_flops_known": 1.0 if self._peak_flops_known else 0.0,
            "perf/tokens_per_sec_step": all_tokens / max(step_wall_s, 1e-9),
            "perf/tokens_per_sec_update": train_tokens / max(update_s, 1e-9),
            "perf/tokens_per_sec_rollout": (decode_tokens + prefill_tokens)
            / max(rollout_s, 1e-9),
            "perf/model_flops_per_step": flops,
            # cumulative real backend compiles (jax.monitoring): a step
            # where this increments mid-run is a silent retrace
            "perf/recompiles": float(self._recompiles.count),
            "perf/recompile_seconds": self._recompiles.seconds,
            "telemetry/spans_dropped": float(self.tracer.dropped),
        }

    # ------------------------------------------------------------------ #
    # run-health plane (telemetry/health.py + exporter.py)
    # ------------------------------------------------------------------ #

    def _health_blackbox(self, step: int, extra: dict):
        """CRIT hook: dump the flight-recorder ring with reason="health"
        (no-op returning None when the tracer is disabled)."""
        return self.tracer.dump_blackbox(
            self._telemetry_dir, step, "health", extra=extra
        )

    def _on_health_crit(self, step: int, rules: list):
        """Optional escalation: a CRIT verdict arms the TrainingSentinel
        when it was configured off (cfg.health_arm_sentinel) — divergence
        detected by the health plane turns on rollback protection for the
        rest of the run."""
        if self.cfg.health_arm_sentinel and not self.sentinel.cfg.enabled:
            self.sentinel.cfg.enabled = True
            print(f"[health] CRIT at step {step} ({', '.join(rules)}): "
                  "arming training sentinel")

    def _statusz(self) -> dict:
        """JSON state for the exporter's /statusz (called on HTTP threads —
        everything read here is either immutable after __init__ or behind
        its own lock)."""
        latest = self.logger.latest()
        orch = self._orchestrator  # local ref: trainer may close it
        out = {
            # nanolint: allow[determinism.wall-clock] statusz provenance stamp for scrapers, never a duration input
            "unix_time": time.time(),
            "algo": self.cfg.algo.value,
            "step": self.state.get("global_step", 0),
            "episode": self.state.get("episode", 0),
            "policy_version": (orch.version if orch is not None
                               else self.state.get("global_step", 0)),
            "devices": self._n_devices,
            "mfu": latest.get("perf/mfu"),
            # the peak-FLOPs table fell back to a nominal constant → the
            # MFU number above is not trustworthy
            "mfu_trusted": bool(self._peak_flops_known),
            "peak_flops_per_chip": self._peak_flops,
            "staleness_avg": latest.get("orchestrator/staleness"),
            "health": self.health.snapshot(),
            # drop-reason counts since start + the last-N sample ring
            # (telemetry/lineage.py) — the live companion to the ledger
            "lineage": self.lineage.statusz(),
            # latency surface (telemetry/hist.py): per-key count/mean/
            # p50/p95/p99/min/max from the streaming histograms; {} when
            # cfg.latency is off
            "latency": self.latency.snapshot(),
            # paged KV cache (rollout_page_size > 0): latest rollout's pool
            # occupancy / recycling / mid-loop admission snapshot; None when
            # the lever is off
            "pages": getattr(self, "_pages_status", None),
            # radix prefix cache (rollout_prefix_cache): tree size, pool
            # occupancy, cumulative hit/COW/eviction counters
            # (serving/radix.py snapshot); None when the lever is off
            "prefix_cache": (self.prefix_cache.snapshot()
                             if self.prefix_cache is not None else None),
            # decode session (continuous batching): end-of-rollout snapshot
            # — resident rows + per-row feature flags, chunked-prefill
            # backlog, dispatch counters (sampler/paged/session.py
            # status()); None until a queued rollout has run
            "session": getattr(self, "_session_status", None),
        }
        if orch is not None and hasattr(orch, "status_snapshot"):
            out.update(orch.status_snapshot())
        return out

    def _export_metrics(self) -> dict:
        """/metrics provider: the latest flat metric row plus the lineage
        ledger's labeled drop-reason gauges
        (`lineage/dropped_total{reason=...}`) — render_prometheus keeps the
        label set verbatim, so these survive validate_prometheus_text."""
        return {**self.logger.latest(), **self.lineage.metric_rows()}

    # ------------------------------------------------------------------ #
    # optimizer
    # ------------------------------------------------------------------ #

    def _train_tree(self, params, value_params):
        return {"policy": params, "value": value_params} if value_params is not None \
            else {"policy": params}

    def _trainable_tree_mask(self, train_tree):
        mask = {"policy": trainable_mask(train_tree["policy"], self.lora_cfg)}
        if train_tree.get("value") is not None:
            vmask = trainable_mask(train_tree["value"], self.value_lora_cfg)
            if self.value_lora_cfg is not None:
                # score head always trains (`value_modules_to_save` parity,
                # `PPO/ppo.py:157-159`); trainable_mask doesn't know it
                vmask["score"] = True
            mask["value"] = vmask
        return mask

    def _partition(self, train_tree):
        """Split into (trainable, frozen) trees with None at excluded leaves
        (equinox-style partition/combine)."""
        mask = self._trainable_tree_mask(train_tree)
        trainable = jax.tree.map(lambda p, m: p if m else None, train_tree, mask)
        frozen = jax.tree.map(lambda p, m: None if m else p, train_tree, mask)
        return trainable, frozen

    @staticmethod
    def _combine(trainable, frozen):
        return jax.tree.map(
            lambda t, f: f if t is None else t,
            trainable, frozen,
            is_leaf=lambda x: x is None,
        )

    def _build_optimizer(self):
        cfg = self.cfg
        total_steps = max(
            1, cfg.num_total_batches * cfg.num_ppo_epochs * cfg.num_mini_batches
        )

        def sched(lr):
            # cosine_with_min_lr parity (`GRPO/grpo.py:119-121`);
            # warmup_steps=0 must not hit optax's 0-step linear ramp (NaN)
            if cfg.warmup_steps > 0:
                return optax.warmup_cosine_decay_schedule(
                    init_value=0.0,
                    peak_value=lr,
                    warmup_steps=cfg.warmup_steps,
                    decay_steps=total_steps,
                    end_value=lr * cfg.min_lr_rate,
                )
            return optax.cosine_decay_schedule(
                lr, decay_steps=total_steps, alpha=cfg.min_lr_rate
            )

        def adamw(lr):
            tx = optax.adamw(
                sched(lr), eps=cfg.adam_eps, weight_decay=cfg.weight_decay
            )
            if cfg.max_grad_norm:
                tx = optax.chain(optax.clip_by_global_norm(cfg.max_grad_norm), tx)
            return tx

        # separate policy/value LR groups (`PPO/ppo_trainer.py:341-402`);
        # operates on the trainable-only partition, so no freeze transform
        value_lr = cfg.value_learning_rate or cfg.learning_rate
        # the schedule fns are kept for the `lr` metric (the reference logs
        # `lr_scheduler.get_last_lr()`, `GRPO/grpo_trainer.py:744`)
        self._lr_schedules = {
            "policy": sched(cfg.learning_rate), "value": sched(value_lr)
        }
        return optax.multi_transform(
            {"policy": adamw(cfg.learning_rate), "value": adamw(value_lr)},
            param_labels=lambda tree: {
                k: jax.tree.map(lambda _: k, v) for k, v in tree.items()
            },
        )

    # ------------------------------------------------------------------ #
    # jitted pieces
    # ------------------------------------------------------------------ #

    def _make_update_fn(self):
        cfg, mcfg = self.cfg, self.mcfg
        algo = self.algo
        lora_scale = self.lora_scale
        value_lora_scale = self.value_lora_scale
        remat = cfg.gradient_checkpointing
        pad_id = self.tokenizer.pad_token_id
        optimizer = self.optimizer
        grad_accum = cfg.gradient_accumulation_steps
        # truncated-IS off-policy correction (orchestrator staleness > 0 with
        # captured behavior logprobs): static for the whole run, so the
        # minibatch dict's key set — and the jitted update — never changes
        use_is = self._use_is
        is_truncation = cfg.offpolicy_is_truncation
        # per-segment IS (rollout_inflight_swaps): same static-key-set
        # contract — segment_ages is in every minibatch or in none, so the
        # jitted update never recompiles mid-run
        use_seg = self._use_seg

        combine = self._combine
        sp_on = self._sp_on()
        sp_mesh, sp_fsdp_axis = self.mesh, self._fsdp_axis()

        def microbatch_loss(trainable, frozen, mb, context_length):
            train_tree = combine(trainable, frozen)
            if sp_on:
                from nanorlhf_tpu.parallel.sp import sp_score_logprobs

                # ring-attention sequence-parallel forward; the global
                # [B, T, V] logits never materialize — the entropy stat
                # comes back as a per-shard mean pmean'd over the ring.
                # attn_impl matches the SCORING pass (the flash ring is
                # differentiable, `_ring_core_bwd`): old/ref logprobs and
                # new logprobs come from the same kernels, so exp(new−old)
                # ratios carry no kernel-mismatch offset (ADVICE r3)
                new_logprobs, entropy = sp_score_logprobs(
                    train_tree["policy"], mcfg, mb["query_responses"], pad_id,
                    cfg.temperature, sp_mesh, fsdp_axis=sp_fsdp_axis,
                    lora_scale=lora_scale, remat=remat, with_entropy=True,
                    entropy_from_position=context_length - 1,
                    attn_impl=mcfg.attention_impl,
                )
                new_logprobs = new_logprobs[:, context_length - 1 : -1]
            elif cfg.fused_logprob:
                # fused hidden→logprob path (ops/fused_logprob.py): the
                # [micro, T_resp, V] logits block never materializes — the
                # chunked linear-cross-entropy op emits per-token logprobs
                # AND the entropy stat in one pass, and its custom-VJP
                # backward recomputes chunk logits instead of saving them
                new_logprobs, ent_tok = fused_response_logprobs(
                    train_tree["policy"], mcfg, mb["query_responses"],
                    mb["responses"], pad_id, context_length, cfg,
                    lora_scale=lora_scale, remat=remat, with_entropy=True,
                )
                # `policy/entropy_avg_new`, unmasked mean like the reference
                # (`GRPO/grpo_trainer.py:679-687`); the op's entropy output
                # already carries stop-gradient semantics
                entropy = jax.lax.stop_gradient(ent_tok.mean())
            else:
                logits = padded_forward_logits(
                    train_tree["policy"], mcfg, mb["query_responses"], pad_id,
                    lora_scale=lora_scale, remat=remat,
                    response_context_length=context_length,
                )
                # true update-pass entropy over the temperature-scaled logits
                # — `policy/entropy_avg_new`, unmasked mean like the
                # reference (`GRPO/grpo_trainer.py:679-687`) — computed
                # CHUNKED (no stop-gradient f32 full-logits copy; the bf16
                # logits buffer itself is this naive path's cost)
                entropy = jax.lax.stop_gradient(chunked_entropy(
                    logits, cfg.temperature, chunk=cfg.fused_logprob_chunk
                ).mean())
                new_logprobs = logprobs_from_logits(
                    logits, mb["responses"], cfg.temperature
                )
            new_logprobs = jnp.where(
                mb["padding_mask"], INVALID_LOGPROB, new_logprobs
            )
            mask = ~mb["padding_mask"]
            # multi-turn environments: observation/tool tokens are
            # conditioned on but never scored — the env driver's per-token
            # loss_mask (False on observation spans) joins the pad mask
            # here, upstream of every algorithm branch. The key is absent
            # outside env multi-turn runs, so the degenerate case compiles
            # the identical program.
            if "loss_mask" in mb:
                mask = mask & mb["loss_mask"]
            # behavior (stale sampling policy) logprobs for truncated IS —
            # None keeps every loss in its exact synchronous form
            behavior = mb["behavior_logprobs"] if use_is else None
            # per-token policy ages (newest version in row − token's
            # segment version): widens the IS weight into its per-segment
            # form; None keeps the whole-sequence weight bit-exact
            seg_ages = mb["segment_ages"] if use_seg else None

            if algo == AlgoName.GRPO:
                loss, aux = grpo_loss(
                    new_logprobs, mb["logprobs"], mb["ref_logprobs"],
                    mb["advantages"], mask, cfg.cliprange, cfg.kl_coef,
                    behavior_logprobs=behavior, is_truncation=is_truncation,
                    segment_ages=seg_ages,
                )
            elif algo == AlgoName.RLOO:
                loss, aux = ppo_clip_loss_sequence(
                    new_logprobs, mb["logprobs"], mb["advantages_seq"], mask,
                    cfg.cliprange,
                    behavior_logprobs=behavior, is_truncation=is_truncation,
                    segment_ages=seg_ages,
                )
            elif algo == AlgoName.RAFT:
                # RAFT's SFT objective has no ratio to correct — best-of-K
                # selection is off-policy by construction
                loss, aux = sft_loss(new_logprobs, mask)
            elif algo == AlgoName.PPO:
                pg_loss, aux = ppo_clip_loss_token(
                    new_logprobs, mb["logprobs"], mb["advantages"], mask,
                    cfg.cliprange,
                    behavior_logprobs=behavior, is_truncation=is_truncation,
                    segment_ages=seg_ages,
                )
                if sp_on:
                    from nanorlhf_tpu.parallel.sp import sp_score_values

                    # same attn_impl as the value SCORING pass (flash ring
                    # is differentiable) — vpred and mb["values"] come from
                    # the same kernels (ADVICE r3)
                    vpred = sp_score_values(
                        train_tree["value"], mcfg, mb["query_responses"],
                        pad_id, sp_mesh, fsdp_axis=sp_fsdp_axis,
                        lora_scale=value_lora_scale, remat=remat,
                        attn_impl=mcfg.attention_impl,
                    )[:, context_length - 1 : -1, 0]
                else:
                    vpred = score_forward(
                        train_tree["value"], mcfg, mb["query_responses"], pad_id,
                        lora_scale=value_lora_scale, remat=remat,
                    )[:, context_length - 1 : -1, 0]
                vpred = jnp.where(mb["padding_mask_p1"], 0.0, vpred)
                vf_loss, vf_aux = value_loss_clipped(
                    vpred, mb["values"], mb["returns"], ~mb["padding_mask_p1"],
                    cfg.cliprange_value,
                )
                loss = pg_loss + cfg.vf_coef * vf_loss
                aux = {**aux, **vf_aux}
            else:  # REINFORCE / ReMax: token-level PPO-clip
                loss, aux = ppo_clip_loss_token(
                    new_logprobs, mb["logprobs"], mb["advantages"], mask,
                    cfg.cliprange,
                    behavior_logprobs=behavior, is_truncation=is_truncation,
                    segment_ages=seg_ages,
                )
            aux["entropy"] = entropy
            return loss, aux

        mesh = self.mesh
        from jax.sharding import NamedSharding, PartitionSpec as P

        @jax.named_scope("update")
        def update_minibatch(trainable, frozen, opt_state, minibatch, context_length):
            """One optimizer step over `grad_accum` scanned microbatches.

            Grad accumulation, Adam moments and the optax update all live on
            the trainable-only partition — frozen base weights have no
            optimizer footprint and cannot drift.
            """

            def micro(carry, g_idx):
                # slice microbatch g out of the [micro, grad_accum, ...]
                # stack: indexing the REPLICATED axis 1 keeps the sharded
                # row axis 0 intact — no resharding inside the hot loop
                mb = jax.tree.map(
                    lambda x: jax.lax.dynamic_index_in_dim(
                        x, g_idx, axis=1, keepdims=False
                    ),
                    stacked,
                )
                grads_acc = carry
                (loss, aux), grads = jax.value_and_grad(
                    microbatch_loss, has_aux=True
                )(trainable, frozen, mb, context_length)
                grads_acc = jax.tree.map(jnp.add, grads_acc, grads)
                return grads_acc, aux

            zero = jax.tree.map(
                lambda x: jnp.zeros_like(x, dtype=jnp.float32), trainable
            )
            # [local_mini_batch, ...] -> [micro, grad_accum, ...]: the
            # SHARDED row dim stays major, so GSPMD lowers the reshape
            # comm-free (device-contiguous rows stay device-contiguous);
            # reshaping to [grad_accum, micro] instead puts the tiny scan
            # axis first and forces "involuntary full rematerialization"
            # (replicate-then-repartition) every optimizer step (VERDICT r3
            # #2). Microbatch g is the strided row set {g, G+g, 2G+g, ...} —
            # assignment is arbitrary under grad accumulation: the summed
            # gradient and mean stats are partition-invariant.
            stacked = jax.tree.map(
                lambda x: x.reshape((-1, grad_accum) + x.shape[1:]), minibatch
            )
            stacked = jax.tree.map(
                lambda x: jax.lax.with_sharding_constraint(
                    x,
                    NamedSharding(
                        mesh,
                        P(("data", "fsdp"), *([None] * (x.ndim - 1))),
                    ),
                ),
                stacked,
            )
            grads, auxes = jax.lax.scan(
                micro, zero, jnp.arange(grad_accum, dtype=jnp.int32)
            )
            grads = jax.tree.map(lambda g: g / grad_accum, grads)
            with jax.named_scope("optim"):
                updates, opt_state = optimizer.update(grads, opt_state,
                                                      trainable)
                trainable = optax.apply_updates(trainable, updates)
            stats = jax.tree.map(jnp.mean, auxes)
            # global gradient norm: the training sentinel's finite check
            # reads it, and policy/grad_norm_new is a useful health series
            # regardless — a scalar reduction, negligible next to the update
            stats = {**stats, "grad_norm": optax.global_norm(grads)}
            return trainable, opt_state, stats

        from functools import partial

        return partial(
            jax.jit, static_argnums=(4,),
            donate_argnums=donate_argnums_on_accel(0, 2),
        )(update_minibatch)

    # ------------------------------------------------------------------ #
    # sequence parallelism (mesh sp > 1): the logprob/score pass and the
    # update forward run through ring attention with the sequence dim
    # sharded over the sp axis — for BOTH this dense runtime and the
    # SparseGRPOTrainer subclass (VERDICT r1 #3 / ROADMAP #7)
    # ------------------------------------------------------------------ #

    def _sp_on(self) -> bool:
        on = self.mesh.shape.get("sp", 1) > 1
        if on and self.mesh.shape.get("tensor", 1) > 1:
            raise ValueError("sp > 1 with tensor > 1 is not supported")
        return on

    def _fsdp_axis(self):
        return "fsdp" if self.mesh.shape.get("fsdp", 1) > 1 else None

    def _sp_check_widths(self, context_length: int):
        """The sequence dim shards evenly over the sp ring: every jitted
        width (context, response, and their sum) must divide by sp."""
        n_sp = self.mesh.shape.get("sp", 1)
        for name, width in (("context", context_length),
                            ("response_length", self.cfg.response_length)):
            if width % n_sp != 0:
                raise ValueError(
                    f"{name} width {width} not divisible by sp={n_sp}; pick "
                    f"prompt/response widths as multiples of sp"
                )

    def _score_chunk_fn(self):
        """Jitted policy+ref logprob scorer for one rollout chunk (cached —
        repeated train() calls must reuse the compiled executable). With an
        sp mesh axis the forwards run ring-attention sequence-parallel.
        On an expert model (not under sp) the policy forward also returns
        its router's per-row sums, `(logprobs, ref_logprobs, stats)`: the
        `moe/*` counters of the update's row (ops/moe.py)."""
        if hasattr(self, "_score_fn_cached"):
            return self._score_fn_cached
        mcfg, cfg = self.mcfg, self.cfg
        stats = bool(mcfg.num_experts)
        pad_id = self.tokenizer.pad_token_id
        lora_scale = self.lora_scale

        from functools import partial

        if self._sp_on():
            from nanorlhf_tpu.parallel.sp import sp_score_logprobs

            mesh, fsdp_axis = self.mesh, self._fsdp_axis()

            @partial(jax.jit, static_argnums=(3,))
            @jax.named_scope("score")
            def score(params, ref_params, query_responses, context_length: int):
                # same attn_impl as the update pass (ADVICE r3: no
                # scoring/update kernel mismatch)
                lp = sp_score_logprobs(
                    params, mcfg, query_responses, pad_id, cfg.temperature,
                    mesh, fsdp_axis=fsdp_axis, lora_scale=lora_scale,
                    attn_impl=mcfg.attention_impl,
                )[:, context_length - 1 : -1]
                rlp = sp_score_logprobs(
                    ref_params, mcfg, query_responses, pad_id, cfg.temperature,
                    mesh, fsdp_axis=fsdp_axis, attn_impl=mcfg.attention_impl,
                )[:, context_length - 1 : -1]
                return lp, rlp

            self._score_fn_cached = score
            return score

        if cfg.fused_logprob:
            # fused hidden→logprob scoring: no [chunk, T, V] logits block
            # for either forward — the rollout-phase scoring chunk size is
            # no longer bounded by the vocab term of forward_token_budget
            @partial(jax.jit, static_argnums=(3,))
            @jax.named_scope("score")
            def score(params, ref_params, query_responses, context_length: int):
                responses = query_responses[:, context_length:]
                logprobs = fused_response_logprobs(
                    params, mcfg, query_responses, responses, pad_id,
                    context_length, cfg, lora_scale=lora_scale,
                    router_stats=stats,
                )
                ref_logprobs = fused_response_logprobs(
                    ref_params, mcfg, query_responses, responses, pad_id,
                    context_length, cfg,
                )
                if stats:
                    return logprobs[0], ref_logprobs, logprobs[1]
                return logprobs, ref_logprobs

            self._score_fn_cached = score
            return score

        @partial(jax.jit, static_argnums=(3,))
        @jax.named_scope("score")
        def score(params, ref_params, query_responses, context_length: int):
            responses = query_responses[:, context_length:]
            logits = padded_forward_logits(
                params, mcfg, query_responses, pad_id, lora_scale=lora_scale,
                response_context_length=context_length, router_stats=stats,
            )
            router = None
            if stats:
                logits, router = logits
            logprobs = logprobs_from_logits(logits, responses, cfg.temperature)
            ref_logits = padded_forward_logits(
                ref_params, mcfg, query_responses, pad_id,
                response_context_length=context_length,
            )
            ref_logprobs = logprobs_from_logits(ref_logits, responses, cfg.temperature)
            if stats:
                return logprobs, ref_logprobs, router
            return logprobs, ref_logprobs

        self._score_fn_cached = score
        return score

    def _single_score_fn(self, lora_scale: float = 1.0,
                         router_stats: bool = False):
        """Single-model logprob scorer (jitted, cached per lora_scale) —
        scores whatever param tree it is handed. lora_scale=1.0 suits the
        (adapter-free) ref tree; pass self.lora_scale to score the POLICY
        tree, whose adapters must be applied (the ref-free path).
        `router_stats` (expert models, not under sp; the training loop asks
        for it) makes it return `(logprobs, stats)` as `_score_chunk_fn`."""
        cache = getattr(self, "_single_score_cache", None)
        if cache is None:
            cache = self._single_score_cache = {}
        stats = router_stats and bool(self.mcfg.num_experts)
        if (lora_scale, stats) in cache:
            return cache[lora_scale, stats]
        mcfg, cfg = self.mcfg, self.cfg
        pad_id = self.tokenizer.pad_token_id

        from functools import partial

        if self._sp_on():
            from nanorlhf_tpu.parallel.sp import sp_score_logprobs

            mesh, fsdp_axis = self.mesh, self._fsdp_axis()

            @partial(jax.jit, static_argnums=(2,))
            @jax.named_scope("score")
            def score_one(tree, query_responses, context_length: int):
                return sp_score_logprobs(
                    tree, mcfg, query_responses, pad_id, cfg.temperature,
                    mesh, fsdp_axis=fsdp_axis, lora_scale=lora_scale,
                    attn_impl=mcfg.attention_impl,
                )[:, context_length - 1 : -1]
        elif cfg.fused_logprob:
            @partial(jax.jit, static_argnums=(2,))
            @jax.named_scope("score")
            def score_one(tree, query_responses, context_length: int):
                return fused_response_logprobs(
                    tree, mcfg, query_responses,
                    query_responses[:, context_length:], pad_id,
                    context_length, cfg, lora_scale=lora_scale,
                    router_stats=stats,
                )
        else:
            @partial(jax.jit, static_argnums=(2,))
            @jax.named_scope("score")
            def score_one(tree, query_responses, context_length: int):
                responses = query_responses[:, context_length:]
                logits = padded_forward_logits(
                    tree, mcfg, query_responses, pad_id,
                    lora_scale=lora_scale,
                    response_context_length=context_length,
                    router_stats=stats,
                )
                if stats:
                    return (logprobs_from_logits(logits[0], responses,
                                                 cfg.temperature), logits[1])
                return logprobs_from_logits(logits, responses, cfg.temperature)

        cache[lora_scale, stats] = score_one
        return score_one

    def _ref_score_fn(self):
        """Ref-policy-only scorer — the sampler-logprob-capture path skips
        the policy forward entirely."""
        return self._single_score_fn(1.0)

    def _policy_score_fn(self):
        """Policy-only scorer (adapters applied) — the ref-free path's
        replacement for the two-model chunk scorer."""
        return self._single_score_fn(self.lora_scale)

    def _single_scorer_for(self, capture: bool, router_stats: bool = False):
        """The single-model scorer the scoring loop needs, or None when no
        single-model pass runs: ref-free scores the POLICY (unless capture
        already supplies it — then nothing is left to score), ref-full +
        capture scores the REF, ref-full without capture uses the two-model
        chunk scorer instead. The dense `_score` asks for an expert model's
        `router_stats` with it; the sparse trainer's bucketed one does not."""
        if self._ref_free:
            return None if capture else self._single_score_fn(
                self.lora_scale, router_stats)
        return self._single_score_fn(1.0, router_stats) if capture else None

    # ------------------------------------------------------------------ #
    # the training loop: set-up, one list of phases an update, the tail
    # ------------------------------------------------------------------ #

    def train(self, num_updates: Optional[int] = None):
        cfg = self.cfg
        n = cfg.sample_n if self.algo in (AlgoName.GRPO, AlgoName.RLOO, AlgoName.RAFT) else 1
        capture = cfg.sampler_logprob_capture
        sampling = SamplingParams(
            temperature=cfg.temperature, top_p=cfg.top_p, n=n,
            max_tokens=cfg.response_length, capture_logprobs=capture,
            top_k=cfg.rollout_top_k, approx_top_k=cfg.rollout_approx_top_k,
            shared_prompt_prefill=cfg.rollout_shared_prefill,
            spec_k=cfg.rollout_spec_k, spec_ngram=cfg.rollout_spec_ngram,
            page_size=cfg.rollout_page_size,
            decode_rows=cfg.rollout_decode_rows,
            prefill_chunk=cfg.rollout_prefill_chunk,
        )
        if self._env_multi_turn:
            # per-TURN generation budget: the episode driver packs model
            # turns + observations into the response_length-wide scored
            # batch, so each generate leg only runs env_turn_tokens
            sampling = SamplingParams(
                temperature=cfg.temperature, top_p=cfg.top_p, n=n,
                max_tokens=cfg.env_turn_tokens or cfg.response_length,
                top_k=cfg.rollout_top_k,
                approx_top_k=cfg.rollout_approx_top_k,
                shared_prompt_prefill=cfg.rollout_shared_prefill,
                page_size=cfg.rollout_page_size,
                decode_rows=cfg.rollout_decode_rows,
            )
        # after a resume, the default budget is the REMAINING updates, not a
        # fresh full run
        n_updates = (
            max(0, cfg.num_total_batches - self.state["global_step"])
            if num_updates is None else num_updates
        )
        # rollout context widths: r1's de-padding menu (None: as loaded)
        ctx_menu = shape_menu(self.dataset.input_ids.shape[1], min_value=16) \
            if hasattr(self.dataset, "input_ids") else None
        run = TrainRun(
            n=n, capture=capture,
            # with truncated-IS correction the captured logprobs are the
            # STALE behavior policy's — they feed the IS weights, not the
            # "old" logprobs the clip ratio needs, so the policy scoring
            # pass must still run (score_capture=False) to measure π_old on
            # the current params
            score_capture=capture and not self._use_is,
            target_step=self.state["global_step"] + n_updates,
            # the body holds what it reads and no more: an orchestrator
            # keeps it across train() calls
            body=partial(self._rollout_body, sampling, ctx_menu),
            sampling=sampling,
        )
        self._ensure_handles(run)
        # whole-rollout drops (queue stale_drop, fleet late-duplicate) are
        # denominated in samples via this hint — one rollout = batch_size*n
        # completion rows
        self.lineage.rows_hint = cfg.batch_size * n
        if self.state["global_step"] == 0:
            first = self._evaluate(0)
            if first:
                self.logger.log(0, 0, first)
        phases = (self._rollout, self._reward, self._select, self._score,
                  self._advantages, self._update, self._guard, self._publish,
                  self._report, self._checkpoint)
        while self.state["global_step"] < run.target_step:
            up = Update(t0=time.perf_counter())  # sec_per_episode is a duration
            # windowed XLA profiling: open/close the jax.profiler window
            # for the update about to run (cfg.profile_at_step or the
            # on-demand trigger file)
            self.profile_window.poll(self.state["global_step"] + 1)
            # the update's trace span is recorded by _close_update (a
            # with-block could not carry a no-step's arguments)
            up.span_t0 = self.tracer.now_us() if self.tracer.enabled else 0.0
            for phase in phases:
                up.no_step = phase(run, up)
                if up.no_step is not None:
                    break
            self._close_update(run, up)

        # train() returning implies every checkpoint is DURABLE: flush the
        # in-flight async save (saves mid-run overlap training; only this
        # final one blocks)
        self.ckpt.wait()
        # balance any still-open XLA profile window, and rewrite trace.json
        # after EVERY train() call (a train(num_updates=1) driver would
        # otherwise only get a trace at close())
        self.profile_window.stop()
        self._write_trace()
        # load_best_model_at_end parity (`GRPO/grpo.py:149`, resolved via the
        # `_old` one-save-back metric semantics, `grpo_trainer.py:374-382`)
        if cfg.load_best_model_at_end and num_updates is None:
            best = self.ckpt.best_step()
            if best is not None and best != self.state["global_step"]:
                self.params = self.ckpt.restore(best, self._restore_template())["params"]
                if self._quant_layers is not None:
                    self._refresh_quant_layers()
                print(f"loaded best checkpoint (step {best})")
        if cfg.export_hf_dir and num_updates is None:
            # handoff artifact AFTER load_best: the exported policy is the
            # one the run would deploy
            print(f"exporting HF checkpoint to {cfg.export_hf_dir}")
            self.export_model(cfg.export_hf_dir)
        return self.state

    def _evaluate(self, step: int) -> dict:
        """Rows of a held-out evaluation at `step`: asked once before the
        first update (step 0, logged as its own row) and after every update
        (merged into the update's row). The dense runtime has none."""
        return {}

    def _close_update(self, run: TrainRun, up: Update):
        """The one way out of an update, with a step or without: the
        `train.update` span, the phase splits of an update that made no
        step, what the no-step's owner does next, and the preemption poll."""
        step = self.state["global_step"]
        no_step = up.no_step
        if self.tracer.enabled:
            # the update's span on the trainer thread's track, with the
            # correlation args that make trace.json queryable; a no-step
            # carries the step it attempted and why it ended
            self.tracer.add_complete(
                "train.update", up.span_t0, self.tracer.now_us() - up.span_t0,
                step=step + (no_step is not None),
                rollout_index=up.rollout_index, staleness=up.staleness,
                policy_version=run.orch.version if run.use_orch else step,
                **up.span_args, **(no_step.span_args if no_step else {}),
            )
            self.tracer.counter("staleness", up.staleness)
        if no_step is not None:
            # discard the update's phase splits: no row took them, and the
            # next update's time/*_s — and the perf/tokens_per_sec_*
            # divisors that read timer.totals — would otherwise fold in two
            # updates' worth of wall time
            self.timer.summary()
            if no_step.counts and up.rollout_index > run.counted_to:
                # charged once a rollout: a rollback may replay the no-step
                run.target_step, run.counted_to = (run.target_step - 1,
                                                   up.rollout_index)
            if no_step.then is not None:
                no_step.then()
        # ---- PREEMPTION (SIGTERM, docs/RESILIENCE.md) ----------------------
        # polled at the update boundary where state is consistent: flush
        # the in-flight async save, commit an emergency checkpoint, and
        # unwind through the launcher's normal close() path
        if self._preemption.triggered:
            from nanorlhf_tpu.resilience import Preempted

            if not up.saved:
                self._save_checkpoint(run.orch if run.use_orch else None,
                                      up.metrics)
            self.ckpt.wait()
            # blackbox + trace alongside the emergency checkpoint: the
            # post-mortem gets "what was every thread doing at SIGTERM"
            self.tracer.dump_blackbox(
                self._telemetry_dir, self.state["global_step"], "preemption",
            )
            self._write_trace()
            raise Preempted(
                f"SIGTERM at step {self.state['global_step']}"
                f"{f' ({no_step.why})' if no_step else ''}: emergency "
                f"checkpoint committed to {self.cfg.output_dir}"
            )

    # ---- the rollout source -------------------------------------------- #

    def _rollout_body(self, sampling: SamplingParams, ctx_menu, queries,
                      gen_key, gen_tree=None, gen_mesh=None,
                      weight_refresh=None):
        """DISPATCH one rollout (async — nothing blocks until fetched).
        `gen_tree` (orchestrated mode) is a published weight-store
        snapshot; None samples from the live params. `gen_mesh` (fleet
        × disaggregation) is the calling worker's own device group;
        None generates on the shared rollout/train mesh.
        `weight_refresh` (rollout_inflight_swaps) is the store/transport
        poll callback; raw host snapshots it yields are converted to
        rollout-ready params here before the decode driver installs
        them (docs/ORCHESTRATOR.md §in-flight swaps)."""
        cfg, tok = self.cfg, self.tokenizer
        pad_id, eos_id = tok.pad_token_id, tok.eos_token_id
        if ctx_menu is not None:
            # r1's de-padding applied to every algorithm: batches of short
            # prompts roll out / score at a menu-rounded context (warm jit
            # cache) instead of the dataset-wide pad width
            queries = depad_queries(queries, pad_id, ctx_menu)
        if self._sp_on():
            self._sp_check_widths(queries.shape[1])
        bs = batch_sharding(
            gen_mesh if gen_mesh is not None
            else self.mesh if self.rollout_mesh is None
            else self.rollout_mesh
        )
        queries_j = jax.device_put(jnp.asarray(queries), bs)
        prompt_mask = queries_j != pad_id
        gen_params = self._rollout_params(gen_tree, mesh=gen_mesh)
        gen_refresh = None
        if weight_refresh is not None:
            def gen_refresh():
                # device-place a fresh snapshot exactly like the
                # dispatch tree so a swap cannot change sharding; a
                # (version, None) poll result passes through untouched
                version, tree = weight_refresh()
                if tree is None:
                    return version, None
                return version, self._rollout_params(tree, mesh=gen_mesh)
        # speculative decode (rollout_spec_k > 0) appends its acceptance
        # counters here — device scalars fetched at metrics time, after
        # the tokens already forced a sync. The tracer hands the spec
        # path its instrumented driver (draft/verify spans on the
        # "rollout" track) when telemetry is on; a disabled tracer is
        # ignored.
        spec_stats: list = []
        paged_stats: list = []
        if self._env_multi_turn:
            from nanorlhf_tpu.envs.rollout import run_env_episodes

            payload = run_env_episodes(
                gen_params, self._rollout_mcfg, queries_j, prompt_mask,
                gen_key, sampling, self.env,
                eos_token_id=eos_id, pad_token_id=pad_id, tokenizer=tok,
                max_turns=cfg.env_max_turns,
                turn_tokens=sampling.max_tokens,
                obs_budget=cfg.env_obs_budget,
                response_length=cfg.response_length,
                page_size=cfg.rollout_page_size,
                decode_rows=(cfg.env_decode_rows
                             or cfg.rollout_decode_rows),
                lora_scale=self.lora_scale, faults=self.faults,
            )
            return {"queries": queries, "gen_out": payload["tokens"],
                    "greedy": None, "spec_stats": None,
                    "paged_stats": None, "env": payload}
        gen_out = generate(
            gen_params, self._rollout_mcfg, queries_j, prompt_mask, gen_key,
            sampling, eos_token_id=eos_id, pad_token_id=pad_id,
            lora_scale=self.lora_scale,
            spec_stats_out=spec_stats, tracer=self.tracer,
            paged_stats_out=paged_stats, latency=self.latency,
            prefix_cache=self.prefix_cache,
            weight_refresh=gen_refresh,
        )                                               # [B*n, T]
        greedy = None
        if self.algo == AlgoName.REMAX:
            # extra greedy rollout as baseline (`ReMax/remax_trainer.py:166-185`)
            greedy = generate(
                gen_params, self._rollout_mcfg, queries_j, prompt_mask, gen_key,
                SamplingParams(greedy=True, max_tokens=cfg.response_length),
                eos_token_id=eos_id, pad_token_id=pad_id,
                lora_scale=self.lora_scale,
            )
        out = {"queries": queries, "gen_out": gen_out, "greedy": greedy,
               "spec_stats": spec_stats[0] if spec_stats else None,
               "paged_stats": paged_stats[0] if paged_stats else None}
        if weight_refresh is not None and paged_stats:
            # hoist swap provenance to the payload top level: the
            # lineage ledger (telemetry.segments_summary) and the
            # per-segment IS batch assembly read it from here
            ps = paged_stats[0]
            for k in ("segments", "swap_installs", "swap_wait_s"):
                if k in ps:
                    out[k] = ps[k]
        return out

    def _ensure_handles(self, run: TrainRun):
        """(Re)build the rollout source after construction, a sentinel
        rollback (which tears the orchestrator down), or a watchdog
        degradation (which turns the orchestrated run synchronous)."""
        run.use_orch = (self.cfg.rollout_orchestrator
                        and not self.watchdog.degraded)
        if run.use_orch:
            run.orch = self._ensure_orchestrator(run.body)
            run.stream, run.meter = None, run.orch.meter
        else:
            run.orch = None
            if run.stream is None:
                run.stream = RolloutStream(
                    self, run.body, meter=self._rollout_meter
                )
            run.meter = run.stream.meter

    def _degrade_to_sync(self, run: TrainRun):
        """Watchdog budget exhausted: log the mode transition, tear the
        pipeline down, and fall back to synchronous rollouts (staleness
        0) from the consumed cursor instead of killing the run."""
        print(
            "[resilience] producer restart budget "
            f"({self.cfg.producer_restart_budget}) exhausted — degrading to "
            "synchronous rollouts (staleness 0)"
        )
        if self._orchestrator is not None:
            # keep the queue's cumulative dropped/staleness counters:
            # _save_checkpoint journals them from _orch_restore_state in
            # degraded mode so the metric series stays continuous across
            # a later resume (the same continuity _restart_producer has)
            self._orch_restore_state = self._orchestrator.journal()
            self._orchestrator.close(join_timeout=5.0)
            self._orchestrator = None
        self._reset_data_iterator()
        run.stream = None  # force a fresh stream at the restored cursor
        self._ensure_handles(run)

    def _fetch_sample(self, run: TrainRun, up: Update) -> dict:
        """One device-ready rollout, supervised: a dead producer is
        restarted with backoff up to the watchdog budget (then the run
        degrades to sync), and sentinel-quarantined batches are consumed
        and discarded so a post-rollback replay skips the offending
        data instead of re-deriving the same divergence."""
        from nanorlhf_tpu.orchestrator import ProducerFailed
        from nanorlhf_tpu.resilience import ProducerWatchdog

        while True:
            if run.use_orch:
                orch = run.orch
                try:
                    sample = orch.get()
                except ProducerFailed as e:
                    # flight recorder first: the blackbox must capture
                    # what every thread was doing when the producer
                    # died, before the restart machinery mutates state
                    extra = {"error": repr(e.__cause__ or e)}
                    if hasattr(orch, "fleet_stats"):
                        # fleet post-mortem: membership/lease/quarantine
                        # counters at the moment of exhaustion
                        extra["fleet"] = orch.fleet_stats()
                    self.tracer.dump_blackbox(
                        self._telemetry_dir, self.state["global_step"],
                        "producer_failure", extra=extra,
                    )
                    decision, delay = self.watchdog.on_failure()
                    if decision == ProducerWatchdog.RESTART:
                        cause = e.__cause__ or e
                        print(
                            "[resilience] rollout producer died "
                            f"({type(cause).__name__}: {cause}) — restart "
                            f"{self.watchdog.restarts_total} in {delay:.1f}s"
                        )
                        time.sleep(delay)
                        run.orch = self._restart_producer(run.body)
                        continue
                    if decision == ProducerWatchdog.DEGRADE:
                        self._degrade_to_sync(run)
                        continue
                    raise
                self.watchdog.on_success()
                ro = sample.payload
                ro["_index"] = sample.index
                self.state["rollouts"] = sample.index + 1
                up.staleness = orch.version - sample.version
                up.queue_depth = orch.queue.depth()
            else:
                stream = run.stream
                # quarantined indices are skipped BEFORE dispatch (zero
                # rollout cost) — unless a prefetch already paid for one,
                # which the post-fetch discard below handles
                while (stream._pending is None
                       and stream.next_index in self.sentinel.quarantined):
                    idx = stream.skip()
                    print(
                        f"[resilience] skipping quarantined rollout "
                        f"{idx} (sentinel rollback; not dispatched)"
                    )
                ro = stream.fetch_or_dispatch()
            if ro["_index"] in self.sentinel.quarantined:
                # already-generated sample (orchestrated pipeline or a
                # serial prefetch): discard it; the producer gate gets a
                # skip credit (no version publish)
                print(
                    f"[resilience] skipping quarantined rollout "
                    f"{ro['_index']} (sentinel rollback)"
                )
                self.lineage.drop(
                    ro["_index"], "sentinel_quarantine",
                    step=self.state["global_step"], dispatched=True,
                )
                if run.use_orch:
                    run.orch.consumed_without_update()
                continue
            return ro

    # ---- the phases of one update --------------------------------------- #
    # Each takes the run's set-up and the update's record, leaves what the
    # next phase reads on the record, and returns None or the NoStep that
    # ends the update.

    def _rollout(self, run: TrainRun, up: Update):
        """One device-ready rollout (fetched, or dispatched now), the
        serial path's generation provenance, and the rollout_ahead
        prefetch."""
        cfg = self.cfg
        with self.timer.phase("rollout"):
            ro = up.ro = self._fetch_sample(run, up)
            up.rollout_index = ro["_index"]
            if run.capture:
                up.responses, captured_lp = ro["gen_out"]
                up.captured_lp = np.asarray(captured_lp)
            else:
                up.responses = ro["gen_out"]
            jax.block_until_ready(up.responses)
            if ro["greedy"] is not None:
                ro["greedy"].block_until_ready()
        # overlap meter: consumer busy from here (perf_counter — must
        # share the producers' gen-window clock or intersections die)
        up.t_busy0 = time.perf_counter()
        if not run.use_orch and self.lineage.enabled:
            # serial / rollout_ahead path has no producer thread to emit
            # this: generation provenance lands here, once the arrays
            # are device-ready (policy version == global_step — the same
            # convention the trace spans use without an orchestrator)
            from nanorlhf_tpu.telemetry.lineage import (
                segments_summary,
                spec_summary,
            )

            self.lineage.generation(
                up.rollout_index,
                policy_version=self.state["global_step"], worker_id=0,
                spec=spec_summary(ro),
                segments=segments_summary(ro),
                swap_wait_s=ro.get("swap_wait_s"),
            )
        pstats = ro.get("paged_stats")
        if pstats is not None:
            # /statusz "pages" panel reads the latest snapshot; lineage
            # gets one "lease" event per mid-loop admission so a queued
            # sample's provenance records WHICH recycled row produced it
            # and at which decode iteration (runs in every rollout mode)
            self._pages_status = {
                k: (None if pstats[k] is None
                    else float(np.asarray(pstats[k])))
                for k in ("page_utilization", "pages_recycled",
                          "admitted_midloop", "decode_iterations")
            }
            self._pages_status.update(
                rows=pstats["rows"], num_pages=pstats["num_pages"],
                page_size=pstats["page_size"],
            )
            # the continuous-batching scheduler also ships its decode
            # session's end-of-call status for /statusz "session";
            # the monolithic paged paths have no session
            self._session_status = pstats.get("session")
            if self.lineage.enabled:
                for adm in pstats.get("admissions") or []:
                    self.lineage.event(
                        "lease", up.rollout_index, midloop=True,
                        row=adm["row"], queue_index=adm["queue_index"],
                        iteration=adm["iteration"],
                    )
        self.state["episode"] += cfg.batch_size
        up.queries = ro["queries"]
        up.batch_size, up.context_length = up.queries.shape
        if (not run.use_orch and cfg.rollout_ahead
                and self.state["global_step"] + 1 < run.target_step):
            # dispatch rollout k+1 NOW (from the pre-update-k params, one
            # update stale): the device generates while the host below
            # decodes/grades update k's batch
            run.stream.prefetch()

    def _reward(self, run: TrainRun, up: Update):
        """Decode, then the user's reward callable on the host (or the
        scores a multi-turn environment already accrued)."""
        tok, n, ro = self.tokenizer, run.n, up.ro
        up.question_strings = [
            q.replace(tok.pad_token, "") for q in tok.batch_decode(up.queries)
        ]
        question_n = [q for q in up.question_strings for _ in range(n)]
        up.responses = np.asarray(up.responses)
        if self._use_seg and ro.get("segments") is not None:
            # per-token policy AGE (newest version that produced any
            # token of the row, minus the token's own segment version)
            # in response coordinates — the same [0, total) space the
            # scheduler's segment tok_ranges tile. Rows untouched by a
            # swap are all-zero, and zero ages make segment_is_weights
            # reduce bit-exactly to the whole-sequence weight.
            up.seg_ages = np.zeros(up.responses.shape, np.int32)
            for r, segs in enumerate(ro["segments"]):
                newest = max(s["policy_version"] for s in segs)
                for s in segs:
                    lo, hi = s["tok_range"]
                    if newest > s["policy_version"]:
                        up.seg_ages[r, lo:hi] = newest - s["policy_version"]
        up.decoded = tok.batch_decode(up.responses)
        envp = up.envp = ro.get("env")
        if envp is None:
            # how far the rollout's decode read was bounded: every row is
            # on the host here, before a selection cuts any; whether the
            # loop kept its cache in pages that it read in place and, where
            # it did, the work items that read was cut into and those that
            # held less than an item's pages; and whether its sampler took
            # its candidates by selection
            prompt_lens = (np.asarray(up.queries)
                           != tok.pad_token_id).sum(axis=1)
            up.extra_metrics["rollout/attn_read_frac"] = attn_read_frac(
                self._rollout_mcfg, run.sampling, up.context_length,
                up.responses, tok.eos_token_id, prompt_lens=prompt_lens)
            up.extra_metrics["rollout/kv_in_place"] = kv_in_place(
                self._rollout_mcfg, run.sampling, up.responses.shape[0])
            items = paged_read_items(
                self._rollout_mcfg, run.sampling, up.context_length,
                up.responses, tok.eos_token_id, prompt_lens,
                self.params["embed_tokens"].dtype)
            if items is not None:
                (up.extra_metrics["rollout/paged_items"],
                 up.extra_metrics["rollout/paged_short_items"]) = items
            up.extra_metrics["rollout/sample_pick"] = sample_pick(
                self._rollout_mcfg, run.sampling, up.responses.shape[0])
        with self.timer.phase("reward"):
            if envp is not None:
                # multi-turn env: rewards accrued turn-by-turn inside
                # the episode driver (the terminal grader already ran
                # per episode) — no separate dispatch. Lineage gets the
                # usual reward event plus one `turn` event per
                # (episode row, turn), joinable to this rollout's
                # generation event on rollout_index.
                scores = np.asarray(envp["scores"], np.float32)
                if self.lineage.enabled:
                    self.lineage.reward(
                        up.rollout_index, step=self.state["global_step"],
                        scores=[round(float(s), 6) for s in scores],
                        attempt=1,
                        wall_s=envp["stats"]["env/tool_wall_s"],
                    )
                    for rec in envp["turns"]:
                        self.lineage.turn(
                            up.rollout_index,
                            step=self.state["global_step"], **rec,
                        )
            else:
                scores = self._dispatch_reward(
                    [q + r for q, r in zip(question_n, up.decoded)],
                    up.responses,
                    rollout_index=up.rollout_index,
                    step=self.state["global_step"],
                )
        up.raw_scores = scores.copy()  # raw sampled-rollout scores for logging
        if ro["greedy"] is not None:
            greedy = np.asarray(ro["greedy"])
            greedy_scores = self._dispatch_reward(
                [q + r for q, r in zip(up.question_strings,
                                       tok.batch_decode(greedy))],
                greedy,
            )
            # score − score_greedy is the ReMax advantage seed
            # (`ReMax/remax_trainer.py:506-513`); raw scores still logged
            scores = np.asarray(
                remax_advantage(jnp.asarray(scores), jnp.asarray(greedy_scores))
            )
        up.scores = scores

    def _select(self, run: TrainRun, up: Update):
        """GRPO: group advantage + keep-1-of-N BEFORE scoring (RLOO/RAFT
        select after the logprob pass, in `_advantages`)."""
        n, batch_size = run.n, up.batch_size
        up.log_scores = up.raw_scores
        if self.algo != AlgoName.GRPO:
            up.queries_rep = (np.repeat(up.queries, n, axis=0) if n > 1
                              else up.queries)
            return
        adv_flat = np.asarray(grpo_group_advantage(jnp.asarray(up.scores), n))
        self.key, k = jax.random.split(self.key)
        keep = up.keep = np.asarray(keep_one_of_n_indices(k, batch_size, n))
        rows = np.arange(batch_size)

        def kept(x):
            return np.asarray(x).reshape(batch_size, n, -1)[rows, keep]

        up.grpo_adv = adv_flat.reshape(batch_size, n)[rows, keep]
        if up.envp is not None:
            # per-turn advantages z-score each turn column against
            # the FULL group (all N siblings) before the keep
            # filter drops N−1 of them, mirroring the episode-level
            # baseline above; the turn-end positions and the
            # observation loss_mask ride the same selection
            t_adv = np.asarray(grpo_turn_advantage(
                jnp.asarray(up.envp["turn_rewards"]), n))
            up.env_turn = (kept(t_adv), kept(up.envp["turn_ends"]))
            up.env_loss_mask = kept(up.envp["loss_mask"])
        up.responses = kept(up.responses)
        if up.captured_lp is not None:
            up.captured_lp = kept(up.captured_lp)
        if up.seg_ages is not None:
            up.seg_ages = kept(up.seg_ages)
        up.log_scores = up.raw_scores.reshape(batch_size, n)[rows, keep]
        up.decoded = [up.decoded[i * n + j] for i, j in enumerate(keep)]
        if n > 1:
            # the other n−1 completions per prompt leave the batch
            # here: attribute them like any other exclusion
            self.lineage.drop(
                up.rollout_index, "keep_filter",
                count=batch_size * (n - 1),
                step=self.state["global_step"],
            )
        up.queries_rep = up.queries

    def _forward_budget(self) -> int:
        """Tokens one scoring forward may hold. The vocab-cap lift only
        applies when the fused scorer actually runs — an sp mesh routes
        scoring through sp_score_logprobs, which still materializes
        per-shard [chunk, T/sp, V] logits."""
        return forward_token_budget(
            self.mcfg.vocab_size,
            fused_logprob=self.cfg.fused_logprob and not self._sp_on(),
        )

    def _score(self, run: TrainRun, up: Update):
        """LOGPROB PASS (chunked, jitted): policy and reference logprobs of
        the responses, less whatever the sampler captured."""
        cfg, context_length = self.cfg, up.context_length
        qr = up.qr = np.concatenate([up.queries_rep, up.responses], axis=1)
        total = qr.shape[0]
        chunk = cfg.local_rollout_forward_batch_size or max(
            1,
            self._forward_budget() // (context_length + cfg.response_length),
        )
        chunk = max(1, min(total, chunk))
        logprobs_l, ref_logprobs_l = [], []
        ref_free, score_capture = self._ref_free, run.score_capture
        score_fn = self._score_chunk_fn()
        one_fn = self._single_scorer_for(score_capture, router_stats=True)

        def scored(out, n_real):
            """Logprob arrays of one chunk, cut to its real rows; an
            expert model's scorer appends its router sums (ops/moe.py),
            which are kept for the row's `moe/*` counters."""
            out = out if isinstance(out, tuple) else (out,)
            if isinstance(out[-1], dict):
                up.router_l.append(jax.tree.map(
                    lambda a: np.asarray(a)[:n_real] if a.ndim
                    else np.asarray(a), out[-1]))
                out = out[:-1]
            return [np.asarray(a)[:n_real] for a in out]

        with self.timer.phase("logprob"):
            if ref_free and score_capture:
                # zero scoring forwards: policy logprobs came from the
                # sampler, and there is no reference model (kl_coef 0 —
                # the reference's r1 path, `grpo_r1.py:138`)
                pass
            else:
                for i in range(0, total, chunk):
                    n_real = min(chunk, total - i)
                    rows_c = jnp.asarray(pad_chunk(qr[i : i + chunk], chunk))
                    if ref_free:
                        # policy-only forward (adapters applied)
                        lp, = scored(one_fn(
                            self.params, rows_c, context_length), n_real)
                        logprobs_l.append(lp)
                    elif score_capture:
                        # policy logprobs came from the sampler; only the
                        # ref pass runs — half the scoring forwards
                        rlp, = scored(one_fn(
                            self.ref_params, rows_c, context_length),
                            n_real)
                        ref_logprobs_l.append(rlp)
                    else:
                        lp, rlp = scored(score_fn(
                            self.params, self.ref_params, rows_c,
                            context_length,
                        ), n_real)
                        logprobs_l.append(lp)
                        ref_logprobs_l.append(rlp)
        up.logprobs = (
            up.captured_lp if score_capture else np.concatenate(logprobs_l)
        ).astype(np.float32)
        # ref == policy-old in ref-free mode: every KL term and metric
        # reads exactly 0, matching "no reference model"
        up.ref_logprobs = (
            up.logprobs.copy() if ref_free else np.concatenate(ref_logprobs_l)
        )

    def _advantages(self, run: TrainRun, up: Update):
        """Response masks, then the per-algorithm advantage assembly into
        the update's batch (RLOO/RAFT keep 1 of N here)."""
        cfg, tok = self.cfg, self.tokenizer
        pad_id, eos_id = tok.pad_token_id, tok.eos_token_id
        n, batch_size = run.n, up.batch_size
        responses_j = jnp.asarray(up.responses)
        postprocessed = responses_j
        if cfg.stop_token == "eos" and up.envp is None:
            # multi-turn episodes carry INTERIOR per-turn EOS tokens the
            # stop-token truncation would cut at; the driver already
            # packed real tokens left-justified with pads only at the
            # tail, so the first-pad seq_lengths below stay correct
            postprocessed = truncate_response(eos_id, pad_id, responses_j)
        up.postprocessed = np.asarray(postprocessed)
        seq_lengths = np.asarray(first_true_indices(postprocessed == pad_id) - 1)
        padding_mask, padding_mask_p1 = response_padding_masks(
            up.postprocessed, jnp.asarray(seq_lengths)
        )
        padding_mask = up.padding_mask = np.asarray(padding_mask)
        padding_mask_p1 = np.asarray(padding_mask_p1)
        up.logprobs = np.where(padding_mask, INVALID_LOGPROB, up.logprobs)
        up.ref_logprobs = np.where(padding_mask, INVALID_LOGPROB,
                                   up.ref_logprobs)
        behavior_lp = None
        if self._use_is:
            # the STALE sampling policy's logprobs, masked exactly like
            # `logprobs` so the IS weight is 1 at padded positions
            behavior_lp = np.where(
                padding_mask, INVALID_LOGPROB, up.captured_lp
            ).astype(np.float32)

        up.contain_eos = (up.postprocessed == eos_id).any(axis=1)
        scores_sel = up.grpo_adv if self.algo == AlgoName.GRPO else up.scores
        if cfg.missing_eos_penalty is not None:
            scores_sel = scores_sel.copy()
            scores_sel[~up.contain_eos] -= cfg.missing_eos_penalty
        up.scores_sel = scores_sel

        # ---- per-algo advantage assembly ----------------------------------
        batch, keep_inds, up.reward_info = self._assemble_batch(
            scores_sel, up.logprobs, up.ref_logprobs, padding_mask,
            padding_mask_p1, seq_lengths, up.qr, up.responses,
            up.context_length, batch_size, n,
            behavior_lp=behavior_lp, turn_info=up.env_turn,
        )
        up.batch = batch
        if up.env_loss_mask is not None:
            # observation/tool tokens: conditioned on, never scored.
            # The key is only present in env multi-turn runs, so every
            # other mode compiles the identical jitted update.
            batch["loss_mask"] = up.env_loss_mask
        if up.seg_ages is not None:
            # key present only under rollout_inflight_swaps (same
            # conditional-key pattern as loss_mask above): swaps off
            # compiles the identical jitted update
            if keep_inds is not None:
                # RLOO/RAFT keep-1-of-N happens below, AFTER batch
                # assembly — realign the ages the same way
                up.seg_ages = up.seg_ages.reshape(batch_size, n, -1)[
                    np.arange(batch_size), keep_inds
                ]
            batch["segment_ages"] = up.seg_ages

        if keep_inds is not None:
            # RLOO/RAFT selected 1-of-N *after* the logprob pass; realign
            # the decoded strings/scores used for the sample table
            up.decoded = [
                up.decoded[i * n + j] for i, j in enumerate(keep_inds)
            ]
            up.log_scores = up.log_scores.reshape(batch_size, n)[
                np.arange(batch_size), keep_inds
            ]
            self.lineage.drop(
                up.rollout_index, "keep_filter",
                count=batch_size * (n - 1),
                step=self.state["global_step"],
            )

    def _update(self, run: TrainRun, up: Update):
        """PPO-epoch / minibatch / microbatch update: one jitted optimizer
        step a minibatch."""
        cfg, batch = self.cfg, up.batch
        trainable, frozen = self._partition(
            self._train_tree(self.params, self.value_params)
        )
        all_stats = []
        local_bs = batch["responses"].shape[0]
        mini = max(1, local_bs // cfg.num_mini_batches)
        # rows of the update-pass logits buffer (mem/logits_bytes_saved)
        up.logits_rows = max(1, mini // cfg.gradient_accumulation_steps)
        # lr reported for THIS update = schedule at the step count its
        # first optimizer.update saw (the reference's get_last_lr-before-
        # scheduler.step semantics, `grpo_trainer.py:744-750`)
        up.lr_step = self.state["opt_steps"]
        with self.timer.phase("update"):
            for epoch in range(cfg.num_ppo_epochs):
                self.key, pk = jax.random.split(self.key)
                perm = np.asarray(jax.random.permutation(pk, local_bs))
                for start in range(0, local_bs - mini + 1, mini):
                    inds = perm[start : start + mini]
                    mb = {
                        k: jax.device_put(
                            jnp.asarray(v[inds]),
                            batch_sharding(self.mesh, np.asarray(v).ndim),
                        )
                        for k, v in batch.items()
                    }
                    trainable, self.opt_state, stats = self._update_fn(
                        trainable, frozen, self.opt_state, mb,
                        up.context_length
                    )
                    self.state["opt_steps"] += 1
                    # keep stats on device; syncing per minibatch would
                    # serialize update dispatch
                    all_stats.append(stats)
            train_tree = self._combine(trainable, frozen)
            self.params = train_tree["policy"]
            self.value_params = train_tree.get("value")
            up.all_stats = jax.device_get(all_stats)
        up.agg = {
            k: float(np.mean([s[k] for s in up.all_stats]))
            for k in (up.all_stats[0] if up.all_stats else {})
        }

    def _guard(self, run: TrainRun, up: Update):
        """SENTINEL (resilience/, docs/RESILIENCE.md): checked BEFORE the
        weight-store publish so a tripped step never feeds poisoned weights
        to the producer. The update.step fault poisons the OBSERVED stats
        (action=nan) — same code path a real NaN loss/grad takes, without
        hand-corrupting device arrays."""
        agg = up.agg
        if self.faults.fire("update.step") == "nan":
            agg["pg_loss"] = float("nan")
            agg["grad_norm"] = float("nan")
        verdict = self.sentinel.observe(
            agg.get("pg_loss", 0.0), agg.get("grad_norm")
        )
        if verdict is None:
            return None

        def rollback():
            # the span is closed by now, so the flight recorder the
            # rollback dumps holds the tripped update, tagged with the
            # quarantined rollout index; the rollback then tears the
            # pipeline down and rewinds the data/PRNG cursors — rebuild
            # the handles and replay
            self._sentinel_rollback(verdict, up.rollout_index)
            run.stream = None
            self._ensure_handles(run)

        return NoStep("sentinel rollback", then=rollback, span_args={
            "sentinel_verdict": verdict, "quarantined": True})

    def _publish(self, run: TrainRun, up: Update):
        if run.use_orch:
            # one version per optimizer update: snapshot the trainable
            # leaves (donation hazard) and open the producer's gate
            with self.timer.phase("publish"):
                run.orch.publish(self._policy_snapshot())

    def _metrics_row(self, run: TrainRun, up: Update) -> dict:
        """The update's row before the step is counted: names + semantics
        per docs/METRICS.md, then perf/MFU and the phase splits."""
        cfg, agg, ro, orch = self.cfg, up.agg, up.ro, run.orch
        n, batch_size = run.n, up.batch_size
        padding_mask, logprobs = up.padding_mask, up.logprobs
        sec_per_episode = (time.perf_counter() - up.t0) / cfg.batch_size
        # entropy proxy: summed response negative logprob (the reference's
        # `(-logprobs).sum(1).mean()`, `GRPO/grpo_trainer.py:710`, with
        # pad positions masked to 0 instead of contributing the INVALID
        # sentinel); the true entropy is policy/entropy_avg_new below
        mean_entropy = float(
            (-np.where(padding_mask, 0.0, logprobs)).sum(1).mean()
        )
        kl_rollout = float(
            np.where(padding_mask, 0.0, logprobs - up.ref_logprobs).sum(1).mean()
        )
        # GRPO parity: the reference fills kl_old from the UPDATE-pass
        # new-vs-ref KL stats (`GRPO/grpo_trainer.py:668-670,689,728`);
        # every KL-in-reward trainer uses the rollout token-sum KL
        # (`RLOO/rloo_trainer.py:704-706`). kl_rollout_old is always the
        # honest pre-update measurement.
        kl_old = (
            agg.get("refkl_mean", kl_rollout)
            if self.algo == AlgoName.GRPO else kl_rollout
        )
        if self._ref_free:
            # no reference model exists: GRPO's update-pass refkl stat
            # would otherwise report KL-to-OLD-POLICY here (ref stands
            # in as the old logprobs), which is not the metric's meaning
            kl_old = 0.0
        mean_score = float(np.mean(up.raw_scores))
        metrics = {
            "objective/kl_old": kl_old,
            "objective/kl_rollout_old": kl_rollout,
            "objective/entropy_old": mean_entropy,
            "objective/non_score_reward_old": up.reward_info.get(
                "non_score_reward_old", 0.0
            ),
            "eval_objective/rlhf_reward_old": up.reward_info.get(
                "rlhf_reward_old", mean_score
            ),
            "eval_objective/scores_old": mean_score,
            "policy/approxkl_avg_new": agg.get("approxkl", 0.0),
            "policy/clipfrac_avg_new": agg.get("pg_clipfrac", 0.0),
            "policy/entropy_avg_new": agg.get("entropy", 0.0),
            "loss/policy_avg_new": agg.get("pg_loss", 0.0),
            "val/ratio_new": agg.get("ratio_mean", 1.0),
            "val/ratio_var_new": float(np.var(
                [s.get("ratio_mean", 1.0) for s in up.all_stats]
            )) if up.all_stats else 0.0,
            "val/num_eos_tokens_old": float(
                (up.postprocessed == self.tokenizer.eos_token_id).sum()
            ),
            "lr": float(self._lr_schedules["policy"](up.lr_step)),
            "eps": cfg.adam_eps,
            "sec_per_episode": sec_per_episode,
            "episode": self.state["episode"],
        }
        if "vf_loss" in agg:
            metrics["loss/value_avg_new"] = agg["vf_loss"]
            metrics["val/clipfrac_avg_new"] = agg.get("vf_clipfrac", 0.0)
        if run.score_capture:
            # with exact scoring the epoch-1 ratio is identically 1; any
            # deviation here is decode-vs-scoring numerics — the guard
            # for the captured-logprob shortcut
            metrics["sampler_capture/ratio_drift_new"] = abs(
                agg.get("ratio_mean", 1.0) - 1.0
            )
        # rollout/train overlap fraction: measured for EVERY mode
        # (serial ≈ 0, rollout_ahead partial, orchestrator highest) —
        # the pipelining signal
        metrics["time/rollout_overlap_frac"] = run.meter.overlap_fraction()
        if up.router_l:
            from nanorlhf_tpu.ops.moe import moe_counters

            metrics.update(moe_counters(
                up.router_l,
                held=(self.mcfg.experts_held, self.mcfg.experts_offset)
                if self.mcfg.experts_held else None))
        metrics.update(self._spec_decode_metrics(ro.get("spec_stats")))
        metrics.update(self._paged_metrics(ro.get("paged_stats")))
        if up.envp is not None:
            metrics.update(up.envp["stats"])
        if run.use_orch:
            ostats = orch.stats()
            metrics.update({
                "orchestrator/queue_depth": float(up.queue_depth),
                "orchestrator/staleness": float(up.staleness),
                "orchestrator/dropped_total": float(ostats["dropped"]),
                # who-waits-on-whom (cumulative s): trainer starved vs
                # producer gated — which side is the bottleneck
                "orchestrator/consumer_wait_s": ostats["consumer_wait_s"],
                "orchestrator/producer_gate_wait_s": ostats[
                    "producer_gate_wait_s"
                ],
            })
            metrics.update(staleness_histogram_metrics(
                ostats["staleness_counts"]
            ))
            if hasattr(orch, "fleet_stats"):
                # fleet/* series (docs/METRICS.md): membership gauges +
                # cumulative lease/reassignment/quarantine counters
                # (counters survive restart/degrade/resume via the
                # coordinator journal, like the queue's)
                metrics.update({
                    f"fleet/{k}": v
                    for k, v in orch.fleet_stats().items()
                })
        if self._use_is:
            metrics["offpolicy/is_weight_mean_new"] = agg.get(
                "is_weight_mean", 1.0
            )
            metrics["offpolicy/is_trunc_frac_new"] = agg.get(
                "is_trunc_frac", 0.0
            )
        if cfg.rollout_inflight_swaps:
            # in-flight swap provenance (docs/ORCHESTRATOR.md
            # §in-flight swaps): installs + the mean number of policy
            # segments per completion row THIS update consumed (1.0 =
            # no mid-rollout publish landed), plus the cumulative
            # install stall this rollout paid (device-put of the fresh
            # tree at a chunk boundary — the cost drain-and-wait pays
            # as idle time instead)
            segs = ro.get("segments")
            metrics.update({
                "rollout/swap_installs": float(
                    ro.get("swap_installs", 0) or 0),
                "rollout/segments_per_sample": (
                    float(np.mean([len(s) for s in segs]))
                    if segs else 1.0
                ),
                "orchestrator/swap_wait_s": float(
                    ro.get("swap_wait_s", 0.0) or 0.0),
            })
        # resilience series (docs/RESILIENCE.md): cumulative counters so
        # dashboards diff them into rates; degraded_mode is the sticky
        # sync-fallback flag (0 in healthy pipelined runs)
        metrics.update({
            "policy/grad_norm_new": agg.get("grad_norm", 0.0),
            "resilience/producer_restarts": float(
                self.watchdog.restarts_total
            ),
            "resilience/rollbacks": float(self.sentinel.rollbacks),
            "resilience/degraded_mode": float(self.watchdog.degraded),
            "resilience/ckpt_retries": float(self.ckpt.retry_count),
            "resilience/ckpt_fallbacks": float(self.ckpt.fallback_count),
        })
        # memory series (docs/METRICS.md, docs/FUSED_LOGPROB.md):
        # peak_bytes_in_use from the backend (0 on CPU), plus the
        # analytic size of the update-pass full-logits buffer the fused
        # hidden→logprob path avoids per microbatch (param-dtype logits;
        # the naive path's old f32 entropy copy is NOT counted — it is
        # gone in both modes now that the fallback entropy is chunked)
        t_resp = up.batch["responses"].shape[1]
        logits_bytes = (
            up.logits_rows * t_resp * self.mcfg.vocab_size
            * jnp.dtype(self.params["embed_tokens"].dtype).itemsize
        )
        metrics.update({
            "mem/peak_bytes_in_use": device_peak_bytes(),
            # 0 on an sp mesh too: microbatch_loss takes the sp branch
            # there and the fused op never runs
            "mem/logits_bytes_saved": float(
                logits_bytes
                if cfg.fused_logprob and not self._sp_on() else 0.0
            ),
        })
        # what only a subclass's phases measure (their own keys)
        metrics.update(up.extra_metrics)
        # ---- perf/MFU accounting (telemetry/, docs/OBSERVABILITY.md):
        # token counts from THIS update's actual work — decode at the
        # configured response_length (the napkin model's convention),
        # scoring forwards as actually run (0 in ref-free+capture, 1
        # with capture or ref-free, 2 otherwise) over the scored rows at
        # their width, the update over its rows at theirs
        n_rollout_rows = batch_size * n
        local_bs = up.batch["responses"].shape[0]
        score_forwards = (
            0 if (self._ref_free and run.score_capture)
            else 1 if (self._ref_free or run.score_capture) else 2
        )
        metrics.update(self._perf_metrics(
            step_wall_s=time.perf_counter() - up.t0,
            decode_tokens=n_rollout_rows * cfg.response_length,
            prefill_tokens=n_rollout_rows * up.queries.shape[1],
            score_tokens=score_forwards * up.qr.size,
            train_tokens=cfg.num_ppo_epochs * local_bs
            * (up.context_length + t_resp),
            rollout_s=self.timer.totals.get("rollout", 0.0),
            update_s=self.timer.totals.get("update", 0.0),
        ))
        phase_rows = self.timer.summary()
        metrics.update(phase_rows)
        if self.latency.enabled:
            # per-update phase durations into the latency surface: the
            # time/{phase}_s gauges above are the LAST update's splits,
            # the latency/phase_{phase}_s histograms hold every update's
            for k, v in phase_rows.items():
                if k.startswith("time/") and k.endswith("_s"):
                    # "time/rollout_s" -> "latency/phase_rollout_s"
                    self.latency.record(
                        f"latency/phase_{k[5:-2]}_s", float(v))
        return metrics

    def _report(self, run: TrainRun, up: Update):
        """Count the step and log its row, with the evaluation hook's and
        the health plane's keys; the lineage outcome and the sample table."""
        cfg, agg = self.cfg, up.agg
        metrics = up.metrics = self._metrics_row(run, up)
        local_bs = up.batch["responses"].shape[0]
        self.state["global_step"] += 1
        step = self.state["global_step"]
        metrics.update(self._evaluate(step))
        # run-health plane: fold this row into the streaming aggregates,
        # evaluate the anomaly rules, and ride the health/* gauges on
        # the same record (CRIT side effects happen inside observe)
        metrics.update(self.health.observe(step, metrics))
        log_scores = np.asarray(up.log_scores).tolist()
        if self.lineage.enabled:
            # training-outcome event: closes this index's provenance
            # chain with what the update actually consumed
            adv_arr = np.asarray(
                up.batch.get("advantages", up.scores_sel), dtype=np.float32
            )
            if adv_arr.ndim > 1:
                # per-token advantages (PPO/GAE): reduce to per-row means
                adv_arr = adv_arr.mean(axis=tuple(range(1, adv_arr.ndim)))
            self.lineage.outcome(
                up.rollout_index, step=step,
                policy_version=run.orch.version if run.use_orch else step,
                kept=int(local_bs),
                advantage=round(float(adv_arr.mean()), 6),
                scores=[round(float(s), 6) for s in log_scores],
                eos_frac=round(float(up.contain_eos.mean()), 4),
                staleness=up.staleness,
            )
            if self._use_is and agg.get("is_trunc_frac", 0.0) > 0:
                # truncated-IS rows stay IN the update with capped
                # weight — partial influence loss, attributed but not
                # excluded (`partial` marks it for the histogram reader)
                n_trunc = int(round(agg["is_trunc_frac"] * local_bs))
                if n_trunc:
                    self.lineage.drop(
                        up.rollout_index, "is_truncated_weight",
                        count=n_trunc, step=step, partial=True,
                    )
            for i, s in enumerate(log_scores[:8]):
                self.lineage.note_sample(
                    up.rollout_index, step=step,
                    score=round(float(s), 6),
                    response_chars=len(up.decoded[i])
                    if i < len(up.decoded) else None,
                    kept=True,
                )
        # the whole iteration on the phases' clock, up to the row being
        # logged: what it exceeds the sum of time/*_s by is host work
        # between the phases (not a time/*_s key: those are summed as
        # the phase split and folded into latency/phase_*)
        metrics["trainer/iteration_s"] = time.perf_counter() - up.t0
        if step % cfg.logging_steps == 0:
            self.logger.log(step, self.state["episode"], metrics)
            sample_limit = (
                cfg.log_samples_limit
                if cfg.log_samples_limit is not None
                else cfg.num_printed_samples
            )
            self.logger.log_samples(
                step, up.question_strings, up.decoded, up.log_scores,
                sample_limit,
            )
            if self.lineage.enabled:
                # full-text sample records live here now, not in
                # metrics.jsonl (satellite: metrics stays numeric rows)
                for i, (q, r, s) in enumerate(zip(
                        up.question_strings, up.decoded, log_scores)):
                    if i >= sample_limit:
                        break
                    self.lineage.event(
                        "sample", up.rollout_index, step=step, row=i,
                        query=q, response=r, score=round(float(s), 6),
                    )

    def _checkpoint(self, run: TrainRun, up: Update):
        cfg = self.cfg
        if cfg.save_steps and self.state["global_step"] % cfg.save_steps == 0:
            self._save_checkpoint(run.orch if run.use_orch else None,
                                  up.metrics)
            up.saved = True
        # overlap meter: consumer busy window = everything since the
        # sample was fetched (reward, scoring, update, logging, save)
        run.meter.note_busy(up.t_busy0, time.perf_counter())

    def _write_trace(self):
        """Rewrite `<telemetry_dir>/trace.json` from the full buffered span
        history (no-op when telemetry is off). Load it at
        https://ui.perfetto.dev or chrome://tracing."""
        path = self.tracer.write_trace(
            os.path.join(self._telemetry_dir, "trace.json")
        )
        if path is not None:
            print(f"[telemetry] trace written: {path}")
        return path

    def _restore_template(self):
        """Mirror of what checkpoint.save() writes — single source of truth
        for restore structure."""
        like = {"params": self.params}
        if self.cfg.save_optimizer_state:
            like["opt_state"] = self.opt_state
        if self.cfg.save_value_model and self.value_params is not None:
            like["value"] = self.value_params
        return like

    def _save_checkpoint(self, orch, metrics: dict):
        """One checkpoint at the current step — the periodic `save_steps`
        path and the SIGTERM emergency path share it, so an emergency
        checkpoint is exactly as resumable as a scheduled one."""
        extra_state = {"episode": self.state["episode"],
                       "opt_steps": self.state["opt_steps"],
                       "rollouts": self.state["rollouts"],
                       # sentinel/watchdog journals: recovery behavior itself
                       # resumes (rollback spend, quarantined batches,
                       # restart counters, the degraded-mode flag)
                       "resilience": {
                           "sentinel": self.sentinel.journal(),
                           "watchdog": self.watchdog.journal(),
                       },
                       # health-plane journal: aggregate sketches, rule
                       # levels, verdict, trip counts — a resumed run keeps
                       # its learned baselines instead of re-warming and
                       # missing a collapse that started pre-restart
                       "health": self.health.journal(),
                       # lineage journal: monotonic event index + drop
                       # counters, so a resumed ledger appends to the
                       # stream instead of restarting it
                       "lineage": self.lineage.journal(),
                       # latency journal: full histogram states (sparse
                       # bucket counts + scheme), so resumed quantiles
                       # keep the whole run's distribution
                       "latency": self.latency.journal()}
        if orch is not None:
            # journal the queue: pending (dispatched, unconsumed)
            # indices + cumulative drop/staleness counters. Resume
            # re-draws the pending samples from the consumed-rollout
            # cursor — the index-keyed PRNG and deterministic loader
            # reproduce their token streams (docs/ORCHESTRATOR.md)
            extra_state["orchestrator"] = orch.journal()
        elif self._orch_restore_state is not None:
            # degraded mode: the pipeline is gone but its cumulative
            # counters must stay journaled, or a resume zeroes the
            # dropped/staleness series
            extra_state["orchestrator"] = self._orch_restore_state
        cfg = self.cfg
        self.ckpt.save(
            self.state["global_step"], self.params,
            opt_state=self.opt_state if cfg.save_optimizer_state else None,
            rng_key=self.key,
            metric_old=metrics[cfg.metric_for_best_model]
            if cfg.metric_for_best_model in metrics else None,
            extra_state=extra_state,
            value_params=self.value_params if cfg.save_value_model else None,
        )

    def _call_reward(self, prompts_and_responses, responses_ids):
        return np.asarray(
            self.reward_func(prompts_and_responses, self.tokenizer.eos_token),
            dtype=np.float32,
        )

    def _dispatch_reward(self, prompts_and_responses, responses_ids,
                         rollout_index=None, step=None) -> np.ndarray:
        """Reward dispatch with the `reward.exec` injection point and a
        bounded retry: the reward callable is host-side (subprocess graders,
        RM inference) and a transient failure there must not kill a TPU
        run mid-epoch. When `rollout_index` is passed, the lineage ledger
        gets the per-sample scores, the retry attempt that finally landed,
        and the grader wall time (backoff sleeps included — that IS the
        step-time cost)."""
        from nanorlhf_tpu.resilience import retry_with_backoff

        attempts_used = [0]

        def attempt():
            attempts_used[0] += 1
            self.faults.fire("reward.exec")
            return self._call_reward(prompts_and_responses, responses_ids)

        # a dedicated "reward" trace track: the host-side graders
        # (subprocess sympy, RM inference) are a classic hidden step-time
        # eater the device-phase split cannot attribute. span() is a no-op
        # when telemetry is off — one call site either way.
        with self.tracer.span("reward.dispatch", track="reward",
                              rows=len(prompts_and_responses)):
            t0 = time.perf_counter()
            scores = retry_with_backoff(
                attempt, attempts=self.cfg.reward_retries + 1,
                backoff_base=0.1,
            )
        if self.latency.enabled:
            # grader wall incl. retry backoff — the same quantity the
            # lineage reward event records as wall_s
            self.latency.record("latency/reward_s",
                                time.perf_counter() - t0)
        if rollout_index is not None:
            self.lineage.reward(
                rollout_index, step=step,
                scores=[round(float(s), 6) for s in scores.tolist()],
                attempt=attempts_used[0],
                wall_s=round(time.perf_counter() - t0, 6),
            )
        return scores

    def _sentinel_rollback(self, verdict: str, rollout_index: int):
        """Sentinel trip (docs/RESILIENCE.md): charge the rollback budget,
        quarantine the offending rollout index, and restore the last
        committed checkpoint. The in-memory sentinel/watchdog state is
        re-applied after the restore — the checkpoint's (older) journal must
        not erase the trip that triggered this rollback."""
        step_attempted = self.state["global_step"] + 1
        last = self.ckpt.latest_step()
        print(
            f"[resilience] sentinel tripped ({verdict}) at step "
            f"{step_attempted} (rollout {rollout_index}) — rolling back to "
            f"checkpoint {last}"
        )
        # flight recorder FIRST (before note_rollback can raise on budget
        # exhaustion and before the restore rewinds state): the blackbox
        # holds the tripped step's span (tagged with the quarantined
        # rollout index), every thread's in-flight spans, and the latest
        # counter snapshots — alongside the checkpoint it rolls back to
        self.tracer.instant(
            "sentinel.trip", verdict=verdict, rollout_index=rollout_index,
            step=step_attempted,
        )
        self.tracer.dump_blackbox(
            self._telemetry_dir, step_attempted, "sentinel_trip",
            extra={"verdict": verdict, "rollout_index": int(rollout_index),
                   "rollback_to_step": last},
        )
        if last is None:
            raise RuntimeError(
                f"sentinel tripped ({verdict}) at step {step_attempted} with "
                "no committed checkpoint to roll back to — enable save_steps "
                "or disable cfg.sentinel"
            )
        self.sentinel.note_rollback(step_attempted, rollout_index, verdict)
        keep_sentinel = self.sentinel.journal()
        keep_watchdog = self.watchdog.journal()
        # pre-restore statistics rewind with the checkpoint: without this,
        # replayed healthy steps would be folded into the EWMA twice —
        # checkpoints without a resilience journal fall back to zeroed stats
        # (a fresh warmup), which only delays spike detection, never
        # double-counts
        self.sentinel.steps, self.sentinel.ewma, self.sentinel.var = 0, 0.0, 0.0
        self.resume_from_checkpoint(last)
        # the trip's accounting must survive the restore (the checkpoint
        # predates it); EWMA stats stay whatever the checkpoint journaled
        self.sentinel.restore_accounting(keep_sentinel)
        self.watchdog.restore(keep_watchdog)
        self.logger.log_event(rollout_index, {
            "resilience/rollback": 1.0,
            "resilience/rollback_to_step": float(last),
            "resilience/rollbacks": float(self.sentinel.rollbacks),
        })

    def resume_from_checkpoint(self, step: Optional[int] = None):
        """Restore params (+ optimizer state, PRNG key, step/episode counters)
        from a saved checkpoint. `step=None` → latest.

        The reference persists optimizer/scheduler/RNG every save
        (`grpo_trainer.py:345-349`) but ships no resume entry point
        (SURVEY.md §5.3); this is that entry point.
        """
        latest = self.ckpt.latest_step()
        step = step if step is not None else latest
        if step is None:
            raise FileNotFoundError(f"no checkpoints under {self.cfg.output_dir}")
        if self._orchestrator is not None:
            # queued samples were generated from pre-restore params (and the
            # producer's data cursor ran ahead of the consumed counter) —
            # tear the pipeline down; train() rebuilds it from the restored
            # cursor and the journaled counters
            self._orchestrator.close()
            self._orchestrator = None
        restored = self.ckpt.restore(step, self._restore_template())
        if self.ckpt.last_restored_step is not None and \
                self.ckpt.last_restored_step != step:
            # the requested checkpoint was corrupt/torn and restore fell
            # back to an older intact one (docs/RESILIENCE.md ckpt.corrupt)
            # — adopt the step that actually loaded so trainer_state and
            # truncation below track the restored tree
            step = self.ckpt.last_restored_step
        if latest is not None and step < latest:
            # resuming an earlier step abandons the newer trajectory
            self.ckpt.truncate_after(step)
        self.params = restored["params"]
        if self._quant_layers is not None:
            self._refresh_quant_layers()  # re-quantize the RESTORED base
        if "opt_state" in restored:
            self.opt_state = restored["opt_state"]
        if "value" in restored:
            self.value_params = restored["value"]
        tstate = self.ckpt.load_trainer_state(step)
        self.state["global_step"] = tstate["step"]
        self.state["episode"] = tstate.get("episode", 0)
        self.state["opt_steps"] = tstate.get("opt_steps", 0)
        if "rng_key" in tstate:
            raw = jnp.asarray(np.asarray(tstate["rng_key"], dtype=np.uint32))
            self.key = jax.random.wrap_key_data(raw) if tstate.get("rng_key_typed") else raw
        # data-stream position: the loader is a deterministic function of
        # (seed, batch_size), so skipping the persisted consumed-rollout
        # count reproduces the stream the uninterrupted run would see (a
        # rollout_ahead prefetch in flight at save time was abandoned — its
        # batch is re-drawn; sparse-GRPO skip-updates consumed batches
        # without stepping, hence the dedicated counter). Without this a
        # resumed run silently re-trains on the first batches. Pre-counter
        # checkpoints fall back to global_step (exact for the dense runtime).
        # NOTE: under rollout_ahead what's exact is the DATA and PRNG
        # streams, not the sampled trajectories — the abandoned prefetch had
        # sampled from the params as of one update before the checkpoint,
        # while the re-draw samples from the restored (post-update)
        # params, so the first post-resume rollout is one update fresher
        # than the uninterrupted run's would have been.
        self.state["rollouts"] = tstate.get("rollouts", tstate["step"])
        # orchestrator journal: seeds the rebuilt queue's cumulative
        # drop/staleness counters so the metric series stays continuous
        # (pending samples are re-drawn from the rollouts cursor)
        self._orch_restore_state = tstate.get("orchestrator")
        # resilience journal: rollback spend, quarantined batches, restart
        # counters, degraded-mode flag — recovery behavior itself resumes.
        # (The internal sentinel-rollback path re-applies its own in-memory
        # state after this restore; see _sentinel_rollback.)
        res = tstate.get("resilience")
        if res:
            self.sentinel.restore(res.get("sentinel", {}))
            self.watchdog.restore(res.get("watchdog", {}))
        # health journal: restored baselines (EWMA/P² sketches), rule
        # levels, verdict + trip counts — same continuity contract as the
        # fleet counters. Windowed rates re-warm (monotonic clock).
        h = tstate.get("health")
        if h:
            self.health.restore(h)
        # lineage journal: the resumed ledger continues the monotonic
        # event-index stream and since-start drop counters (the files
        # themselves were already re-opened append-mode at construction)
        lj = tstate.get("lineage")
        if lj:
            self.lineage.restore(lj)
        # latency journal: reload every histogram's bucket counts so
        # post-resume quantiles cover the whole run (SchemeMismatch — a
        # checkpoint from a different bucket scheme — propagates: mixing
        # schemes would silently corrupt every quantile)
        hj = tstate.get("latency")
        if hj:
            self.latency.restore(hj)
        self._reset_data_iterator()
        return self.state

    def export_model(self, out_dir: str, dtype: str = "bfloat16") -> str:
        """Write the CURRENT policy as an HF-format checkpoint (config.json
        + model.safetensors), LoRA folded into the base weights — the
        reference's `save_model` output contract (`grpo_trainer.py:321-341`):
        what comes out of training is a directory transformers/vLLM load."""
        from nanorlhf_tpu.core.params import export_hf_checkpoint

        return export_hf_checkpoint(
            self.mcfg, self.params, out_dir,
            lora_scale=self.lora_scale if self.cfg.use_lora else None,
            dtype=dtype, tokenizer=self.tokenizer,
        )

    def close(self):
        # stop serving status endpoints first: the handlers read trainer
        # state that the teardown below starts dismantling
        self.exporter.close()
        if self._orchestrator is not None:
            self._orchestrator.close()  # stop + join the producer thread
            self._orchestrator = None
        # balance an XLA profile window an exception may have left open
        # (otherwise every later start_trace in the process fails), and
        # write the trace a crashed train() never reached
        self.profile_window.stop()
        self._write_trace()
        self.lineage.close()  # flush the provenance ledger
        self.ckpt.close()  # flush any in-flight async checkpoint write
        self.logger.close()
        self._preemption.uninstall()  # restore the previous SIGTERM handler

    # ------------------------------------------------------------------ #
    # per-algo advantage assembly (host-side numpy, shapes already fixed)
    # ------------------------------------------------------------------ #

    def _assemble_batch(self, scores, logprobs, ref_logprobs, padding_mask,
                        padding_mask_p1, seq_lengths, qr, responses,
                        context_length, batch_size, n, behavior_lp=None,
                        turn_info=None):
        cfg = self.cfg
        T = responses.shape[1]
        kl = logprobs - ref_logprobs
        batch = {
            "query_responses": qr,
            "responses": responses,
            "logprobs": logprobs,
            "padding_mask": padding_mask,
            "padding_mask_p1": padding_mask_p1,
        }
        if behavior_lp is not None:
            # rides through every per-algo selection below (RLOO/RAFT map
            # over batch.items()) and into the jitted update's minibatches
            batch["behavior_logprobs"] = behavior_lp

        if self.algo == AlgoName.GRPO:
            # sparse terminal advantage, reversed cumsum γ=1, KL stays in-loss
            if turn_info is not None:
                # multi-turn env episodes: one spike at EACH turn's final
                # model token (per-turn group z-scored advantages from
                # grpo_turn_advantage) instead of one terminal spike — the
                # γ=1 reversed cumsum below then broadcasts each turn's
                # credit as reward-to-go over the tokens that produced it
                turn_adv, turn_ends = turn_info
                rewards = np.asarray(per_turn_terminal_rewards(
                    jnp.asarray(turn_adv), jnp.asarray(turn_ends), T
                ))
            else:
                rewards = np.asarray(sparse_terminal_rewards(
                    jnp.asarray(scores), jnp.asarray(seq_lengths), T
                ))
            if cfg.whiten_rewards:
                rewards = np.asarray(masked_whiten(
                    jnp.asarray(rewards), jnp.asarray(~padding_mask_p1), shift_mean=True
                ))
                rewards = np.where(padding_mask_p1, 0.0, rewards)
            adv = np.asarray(discounted_returns(jnp.asarray(rewards), 1.0))
            if cfg.advantage_whiten:
                adv = np.asarray(masked_whiten(jnp.asarray(adv), jnp.asarray(~padding_mask)))
            adv = np.where(padding_mask, 0.0, adv)
            batch["advantages"] = adv
            batch["ref_logprobs"] = ref_logprobs
            # GRPO keeps KL in-loss: non_score_reward is identically 0, and
            # the reference hard-codes the metric so (`grpo_trainer.py:730`)
            return batch, None, {"non_score_reward_old": 0.0}

        # KL-in-reward family
        kl_penalty = -cfg.kl_coef * np.where(padding_mask, 0.0, kl)
        rewards = np.asarray(sparse_terminal_rewards(
            jnp.asarray(scores), jnp.asarray(seq_lengths), T,
            kl_penalty=jnp.asarray(kl_penalty),
        ))
        if cfg.whiten_rewards:
            rewards = np.asarray(masked_whiten(
                jnp.asarray(rewards), jnp.asarray(~padding_mask_p1), shift_mean=True
            ))
            rewards = np.where(padding_mask_p1, 0.0, rewards)
        # the scores-vs-rlhf_reward split for KL-in-reward algorithms
        # (`RLOO/rloo_trainer.py:704-710`): non_score = the KL penalty alone,
        # rlhf_reward = the full shaped per-sequence reward, both over ALL
        # B·N rollouts (before any 1-of-N selection)
        reward_info = {
            "non_score_reward_old": float(kl_penalty.sum(1).mean()),
            "rlhf_reward_old": float(rewards.sum(1).mean()),
        }

        if self.algo == AlgoName.RLOO:
            rlhf_reward = rewards.sum(1)
            adv_seq = np.asarray(rloo_advantage(jnp.asarray(rlhf_reward), n))
            self.key, k = jax.random.split(self.key)
            keep = np.asarray(keep_one_of_n_indices(k, batch_size, n))
            rows = np.arange(batch_size)
            sel = lambda x: x.reshape(batch_size, n, *x.shape[1:])[rows, keep]
            adv_seq = adv_seq.reshape(batch_size, n)[rows, keep]
            if cfg.advantage_whiten:
                adv_seq = np.asarray(masked_whiten(
                    jnp.asarray(adv_seq), jnp.ones_like(jnp.asarray(adv_seq), bool)
                ))
            batch = {k_: sel(v) for k_, v in batch.items()}
            batch["advantages_seq"] = adv_seq
            return batch, keep, reward_info

        if self.algo == AlgoName.RAFT:
            rlhf_reward = rewards.sum(1)
            if cfg.raft_selection == "random":
                self.key, rk = jax.random.split(self.key)
                keep = np.asarray(best_of_k_indices(jnp.asarray(rlhf_reward), n, key=rk))
            else:
                keep = np.asarray(best_of_k_indices(jnp.asarray(rlhf_reward), n))
            rows = np.arange(batch_size)
            batch = {
                k_: v.reshape(batch_size, n, *v.shape[1:])[rows, keep]
                for k_, v in batch.items()
            }
            return batch, keep, reward_info

        if self.algo == AlgoName.PPO:
            values = self._value_pass(qr, context_length)
            values = np.where(padding_mask_p1, 0.0, values)
            adv, returns = gae(
                jnp.asarray(rewards), jnp.asarray(values), cfg.gamma, cfg.lam
            )
            adv = np.asarray(adv)
            if cfg.advantage_whiten:
                adv = np.asarray(masked_whiten(jnp.asarray(adv), jnp.asarray(~padding_mask)))
            adv = np.where(padding_mask, 0.0, adv)
            batch["advantages"] = adv
            batch["returns"] = np.asarray(returns)
            batch["values"] = values
            return batch, None, reward_info

        # REINFORCE / ReMax: γ-discounted reversed cumsum
        adv = np.asarray(discounted_returns(jnp.asarray(rewards), cfg.gamma))
        if cfg.advantage_whiten:
            adv = np.asarray(masked_whiten(jnp.asarray(adv), jnp.asarray(~padding_mask)))
        adv = np.where(padding_mask, 0.0, adv)
        batch["advantages"] = adv
        return batch, None, reward_info

    def _value_pass(self, qr, context_length):
        """Chunked value prediction (`PPO/ppo_trainer.py:630-634`).

        Unaffected by `cfg.fused_logprob`: the value head projects hidden
        states to [B, T, 1] scores — there is no vocab-sized logits tensor
        to fuse away, so the naive score_forward IS already the memory-
        minimal form (same reason the in-update vpred forward stays as-is).
        """
        total = qr.shape[0]
        # value forward emits [B, T, 1] scores — no vocab-sized logits block —
        # so only the activation-based token budget applies
        chunk = max(1, min(total, ACTIVATION_TOKEN_BUDGET // qr.shape[1]))
        vals = []
        if not hasattr(self, "_value_fn"):
            from functools import partial

            mcfg, pad_id = self.mcfg, self.tokenizer.pad_token_id
            value_lora_scale = self.value_lora_scale

            if self._sp_on():
                from nanorlhf_tpu.parallel.sp import sp_score_values

                mesh, fsdp_axis = self.mesh, self._fsdp_axis()
                # scoring never differentiates → flash ring is legal
                scorer = partial(
                    sp_score_values, config=mcfg, pad_token_id=pad_id,
                    mesh=mesh, fsdp_axis=fsdp_axis,
                    lora_scale=value_lora_scale, attn_impl=mcfg.attention_impl,
                )
            else:
                scorer = partial(score_forward, config=mcfg,
                                 pad_token_id=pad_id,
                                 lora_scale=value_lora_scale)

            @partial(jax.jit, static_argnums=(2,))
            @jax.named_scope("score")
            def value_fn(vparams, qr_chunk, context_length: int):
                v = scorer(vparams, query_responses=qr_chunk)[:, :, 0]
                return v[:, context_length - 1 : -1]

            self._value_fn = value_fn
        for i in range(0, total, chunk):
            n_real = min(chunk, total - i)
            vals.append(np.asarray(
                self._value_fn(self.value_params,
                               jnp.asarray(pad_chunk(qr[i : i + chunk], chunk)),
                               context_length)
            )[:n_real])
        return np.concatenate(vals)
