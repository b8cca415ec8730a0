"""Layered dataclass config — "ALL setting is on the file you run".

Mirrors the reference's config surface: a PPOConfig-style dataclass holding
the TRL-inherited fields every launcher sets (`/root/reference/GRPO/
grpo.py:86-155`, SURVEY.md §5.6) plus algorithm-specific fields, extended
with the mesh/sharding knobs the TPU runtime needs. The derived batch-size
hierarchy reproduces `GRPOTrainer.__init__` exactly
(`/root/reference/GRPO/grpo_trainer.py:216-247`):

    local_batch_size = per_device_train_batch_size
                       × gradient_accumulation_steps × num_mini_batches
    batch_size       = local_batch_size × world_size (= mesh data axes)
"""

from __future__ import annotations

import dataclasses
import enum
import math
from typing import Optional

from nanorlhf_tpu.ops.masking import exact_div
from nanorlhf_tpu.parallel.mesh import MeshConfig


class AlgoName(str, enum.Enum):
    PPO = "ppo"
    GRPO = "grpo"
    RLOO = "rloo"
    REMAX = "remax"
    REINFORCE = "reinforce"
    RAFT = "raft"


@dataclasses.dataclass
class RLConfig:
    # ---- experiment ----
    exp_name: str = "run"
    seed: int = 1
    output_dir: str = "output"
    algo: AlgoName = AlgoName.GRPO

    # ---- models ----
    sft_model_path: str = ""
    reward_model_path: str = ""

    # ---- data ----
    train_dataset_name: str = "Anthropic/hh-rlhf"   # (`GRPO/grpo.py:101`)
    train_dataset_split: str = "train"              # (`GRPO/grpo.py:102`)
    # tokenized-corpus cache dir (data/token_cache.py — the Arrow-cache role
    # `dataset.map` plays for the reference); None disables
    dataset_cache_dir: Optional[str] = None

    # ---- rollout / sampling ----
    response_length: int = 1500          # max new tokens (`GRPO/grpo.py:125`)
    temperature: float = 0.9
    top_p: float = 0.95
    sample_n: int = 4                    # grpo_sample_N / rloo_sample_N / raft_sample_K
    stop_token: str = "eos"
    missing_eos_penalty: Optional[float] = None
    # top-k pre-trim for rollout nucleus sampling (SamplingParams.top_k):
    # 64 keeps the decode step off the full-vocab sort and is exact whenever
    # the 0.95-nucleus fits in 64 tokens — true for instruction-tuned models
    # at production temperatures. 0 = exact full-vocab nucleus, matching the
    # reference's untruncated vLLM top_p (`GRPO/grpo_trainer.py:127`) —
    # the right default for BASE-model policies at high temperature (the
    # r1-zero launcher sets it), where the nucleus can exceed any fixed k
    # early in training and truncation silently narrows exploration
    # (VERDICT r3 #6).
    rollout_top_k: int = 64
    # approx_max_k for the pre-trim (hardware-native O(V); recall 0.99) vs
    # exact lax.top_k (full-vocab sort). Ignored when rollout_top_k=0.
    rollout_approx_top_k: bool = True
    # n>1 rollouts prefill each prompt once and fan the prompt KV out to its
    # N samples (vLLM prefix-sharing analogue; token streams are identical
    # to the repeat path, test-pinned). Off = repeat every prompt ×N before
    # prefill (ablation/debug).
    rollout_shared_prefill: bool = True
    # >0: draft-free speculative rollout decode (sampler/speculative.py,
    # docs/DECODE_ANALYSIS.md): an n-gram/prompt-lookup drafter proposes
    # this many tokens per row from the row's own prompt+output buffer and
    # one batched `decode_verify` forward scores all k+1 candidates —
    # amortizing the HBM-bound per-step weight/cache stream over every
    # accepted token. Greedy rollouts stay bit-exact; sampled rollouts are
    # distribution-exact (rejection sampling). Best on self-repetitive
    # corpora (R1-style math: restated problem text, \boxed{} templates);
    # worst case (acceptance ~0) pays ~one verify forward per token.
    # Per-update acceptance lands in rollout/draft_acceptance /
    # rollout/accepted_per_step (docs/METRICS.md). 0 = off (the monolithic
    # loop, bit-for-bit untouched).
    rollout_spec_k: int = 0
    # n-gram context the drafter matches on (rollout_spec_k > 0 only)
    rollout_spec_ngram: int = 3

    # ---- batch hierarchy ----
    # total_episodes=None → num_train_epochs × dataset size, resolved by the
    # trainer (`GRPO/grpo_trainer.py:216-217`)
    total_episodes: Optional[int] = 10000
    num_train_epochs: float = 1.0
    per_device_train_batch_size: int = 4
    gradient_accumulation_steps: int = 8
    num_mini_batches: int = 16
    num_ppo_epochs: int = 1
    local_rollout_forward_batch_size: Optional[int] = None  # None → memory formula
    # opt-in: reuse the sampler's per-token logprobs as the rollout-policy
    # logprobs, skipping the policy half of the scoring pass (the ref pass
    # still runs). Decode-vs-scoring numerics make epoch-1 ratios deviate
    # from exactly 1; the drift is logged as sampler_capture/ratio_drift_new.
    sampler_logprob_capture: bool = False
    # opt-in PipelineRL-style overlap: the rollout for update k+1 is
    # DISPATCHED (async) before the host-side decode/reward/assembly of
    # update k, so reward grading (sympy subprocesses, RM scoring) overlaps
    # device generation instead of serializing with it. Each rollout then
    # samples from the params of update k-1 (one update stale); the scoring
    # pass still measures the current policy, so the PPO-clip ratio absorbs
    # the off-policy drift exactly as the reference's off-policy-capable
    # losses do (`REINFORCE/reinforce_trainer.py:637`). Rollout PRNG comes
    # from a dedicated stream, so update 1 is bit-identical either way.
    # One place does it, the `rollout` phase of RLTrainer.train(), for every
    # trainer; it is the only overlap SparseGRPOTrainer has, whose skip
    # consumes a rollout without publishing a version (ROADMAP D2).
    rollout_ahead: bool = False
    # >0: DISAGGREGATED rollouts — reserve this many devices (a whole slice
    # on multi-slice pods, parallel/mesh.split_rollout_devices) as a
    # dedicated generation mesh; the training mesh spans the rest. Each
    # dispatch syncs the rollout param view onto the generation mesh (the
    # only cross-group transfer; on a pod it rides DCN once per update),
    # and with rollout_ahead=True generation for update k+1 runs on its own
    # devices WHILE update k trains — overlapping the two device-bound
    # phases, not just device-vs-host. 0 = generation shares the training
    # mesh. Requires the trainer to build its own meshes (mesh=None).
    rollout_devices: int = 0
    # mesh layout for the reserved generation devices (rollout_devices>0):
    # default data=-1 → pure data-parallel over the reserved group with
    # params replicated per device — right for models that fit one chip;
    # set tensor/fsdp for bigger policies.
    rollout_mesh: Optional["MeshConfig"] = None
    # ---- async rollout orchestrator (orchestrator/, docs/ORCHESTRATOR.md).
    # Generalizes rollout_ahead's one-step prefetch into a producer-thread
    # pipeline over a version-tagged weight store and a bounded-staleness
    # sample queue: the rollout mesh runs continuously up to max_staleness
    # policy versions ahead of training, with backpressure (or drops) at the
    # bound. Mutually exclusive with rollout_ahead; pairs naturally with
    # rollout_devices>0 (generation silicon never waits on the train step)
    # and with sampler_logprob_capture=True, which supplies the behavior
    # logprobs the truncated-IS off-policy correction needs.
    rollout_orchestrator: bool = False
    # max allowed (policy_version - sample_version) at consumption — how many
    # optimizer updates old a consumed rollout may be. 0 = fully on-policy
    # (reproduces the synchronous trainer exactly); 1 ≈ rollout_ahead's
    # pipelining; 2+ deepens the pipeline against jitter.
    max_staleness: int = 1
    # what happens to a QUEUED sample that goes over-stale anyway — possible
    # only under an abnormal publish-without-consume cadence (external
    # weight syncs; the producer gate itself is identical in both modes and
    # never admits a sample that could exceed the bound under the normal
    # one-publish-per-consume cadence): "wait" still delivers it (the
    # truncated-IS correction absorbs the extra staleness); "drop" discards
    # it and takes the next fresh sample (orchestrator/dropped_total counts
    # the discards).
    staleness_policy: str = "wait"
    # off-policy correction for stale samples: "truncated_is" re-weights
    # each loss term by min(π_old/μ, offpolicy_is_truncation) using the
    # sampler-captured behavior logprobs μ (algos/losses.truncated_is_weights)
    # — active only when the orchestrator runs at max_staleness > 0 WITH
    # sampler_logprob_capture (otherwise μ is unknown and the PPO ratio clip
    # alone absorbs the drift, as under rollout_ahead). "none" disables.
    offpolicy_correction: str = "truncated_is"  # truncated_is | none
    # ρ̄, the IS weight truncation (IMPALA/V-trace c̄): bounds the correction's
    # variance at a small bias toward under-weighting fresh-policy-favored
    # tokens.
    offpolicy_is_truncation: float = 2.0
    # ---- in-flight mid-sequence weight swaps (docs/ORCHESTRATOR.md
    # §in-flight swaps). PipelineRL-style: instead of draining in-flight
    # generations at a publish (idle rollout silicon) or letting them run
    # whole-sequence stale (every token behind the policy), the decode
    # drivers poll the weight store at their host sync points and install a
    # newer snapshot MID-SEQUENCE; the ledger stamps per-generation
    # `segments` ([{policy_version, tok_range}]) and the loss applies
    # PER-SEGMENT truncated-IS weights (algos/losses.segment_is_weights:
    # older segments get a tighter clamp, ρ̄^(1/(1+age))). Requires
    # rollout_orchestrator with a host-sync rollout path — the queued paged
    # scheduler (rollout_page_size>0 and rollout_decode_rows>0) or the
    # multi-turn env driver; the monolithic one-jit sampler has no swap
    # point. Off (or at max_staleness=0, where no publish can land
    # mid-rollout): bit-identical to main, test-pinned.
    rollout_inflight_swaps: bool = False
    # ---- elastic rollout fleet (orchestrator/fleet.py, docs/FLEET.md).
    # >1 generalizes the orchestrator's single producer thread into N
    # independent, preemptible rollout workers behind a FleetCoordinator:
    # leased rollout-index ranges with EWMA-derived deadlines, per-worker
    # heartbeat liveness, lease revocation + reassignment on worker loss
    # (same cached prompt batches + index-keyed PRNG — staleness-0 token
    # streams are bit-identical under reassignment, test-pinned),
    # consecutive-failure quarantine with jittered exponential backoff,
    # straggler speculative re-dispatch, and elastic join/leave; losing the
    # last worker falls through the producer watchdog to the synchronous
    # degraded mode. Requires rollout_orchestrator=True. Useful pipelining
    # needs max_staleness >= rollout_workers (the staleness gate bounds how
    # many indices can be in flight); pairs with rollout_devices>0, whose
    # device group is then split into per-worker meshes
    # (parallel/mesh.split_worker_groups). 1 = the single producer thread.
    rollout_workers: int = 1
    fleet_lease_size: int = 1          # rollout indices per lease
    fleet_failure_budget: int = 2      # consecutive failures → quarantine
    fleet_quarantine_base: float = 0.5  # re-admission backoff base · 2^k s
    fleet_quarantine_max: float = 30.0
    # ±fraction jitter on quarantine backoff — N workers failing on one
    # cause must not stampede the weight store in lockstep retry waves
    fleet_backoff_jitter: float = 0.25
    fleet_straggler_factor: float = 4.0  # lease deadline = factor·ewma·len
    # pre-EWMA lease deadline AND heartbeat-silence timeout (seconds): must
    # comfortably exceed a cold-cache first compile
    fleet_initial_deadline: float = 600.0
    # worker↔coordinator transport seam (orchestrator/rpc.py): "inprocess"
    # keeps direct calls; "rpc" routes leases/completions/heartbeats/weights
    # through the length-prefixed binary loopback RPC — the same wire path a
    # cross-host fleet uses (lease-epoch fencing, retry/backoff, streamed
    # weight fetch), exercisable on CPU CI. Requires rollout_workers > 1
    # (the trainer rejects rpc with a single worker — the seam only exists
    # inside the fleet orchestrator).
    rollout_transport: str = "inprocess"   # inprocess | rpc
    fleet_rpc_host: str = "127.0.0.1"      # bind + dial address
    fleet_rpc_port: int = 0                # 0 = ephemeral (loopback/CI)
    fleet_rpc_timeout: float = 30.0        # per-attempt socket timeout (s)
    fleet_rpc_attempts: int = 4            # retry_with_backoff attempts/call
    fleet_rpc_backoff_base: float = 0.05   # jittered backoff base (s)

    # ---- optimization ----
    learning_rate: float = 6e-6
    value_learning_rate: Optional[float] = None  # PPO separate value LR (`PPO/ppo.py:118-119`)
    warmup_steps: int = 0
    min_lr_rate: float = 0.1             # cosine_with_min_lr (`GRPO/grpo.py:119-121`)
    adam_eps: float = 1e-8
    weight_decay: float = 0.0
    max_grad_norm: Optional[float] = None

    # ---- RL coefficients ----
    kl_coef: float = 0.01
    # With kl_coef == 0 the reference's r1-zero path runs NO reference model
    # at all (`examples/r1-v0/grpo_r1.py:138` — no ref load, no ref pass);
    # matching that skips the ref weight copy (3 GB HBM at 1.5B) and the ref
    # half of every scoring pass — combined with sampler_logprob_capture the
    # scoring forwards disappear entirely. None = auto (ref-free iff
    # kl_coef == 0); True forces ref scoring anyway (e.g. to monitor KL
    # drift at coef 0). KL metrics read 0 in ref-free mode.
    score_ref_logprobs: Optional[bool] = None
    cliprange: float = 0.2
    cliprange_value: float = 0.01
    vf_coef: float = 0.1
    gamma: float = 1.0
    lam: float = 0.95
    whiten_rewards: bool = False
    advantage_whiten: bool = False       # REINFORCE defaults True in its launcher
    # RAFT 1-of-K selection: "best" = argmax (the reference's documented
    # intent, `RAFT/raft_trainer.py:585-586`), "random" = the as-shipped
    # behavior where a torch.randint overwrites the argmax (`:588`) — exposed
    # as config so bit-parity runs need no code change (ADVICE r1)
    raft_selection: str = "best"

    # ---- LoRA ----
    use_lora: bool = True
    lora_r: int = 64
    lora_alpha: int = 16
    # value-model LoRA (`PPO/ppo.py:141-159`): adapters + score head + embed
    # trainable, backbone frozen — without it the 1.5B value tree is full-FT
    # and pays ~3 GB of extra Adam state the reference doesn't
    value_use_lora: bool = True
    value_lora_r: int = 64
    value_lora_alpha: int = 16

    # ---- memory / kernels ----
    # fused hidden→logprob scoring (ops/fused_logprob.py, docs/
    # FUSED_LOGPROB.md): the scoring and update passes compute per-token
    # logprobs (+ the entropy stat) straight from final hidden states in
    # row-chunked blocks — the [B, T, V] logits tensor, the single largest
    # HBM allocation at LLM vocabularies, never materializes, and the
    # custom-VJP backward recomputes chunk logits instead of saving them.
    # False keeps the naive full-logits path (parity tests, triage); the
    # sequence-parallel (sp>1) passes are unaffected either way — they
    # already shard the head over the ring and never build global logits.
    fused_logprob: bool = True
    # rows (flattened microbatch·tokens) per recomputed logits chunk;
    # None → bytes-budget heuristic (ops/fused_logprob.fused_chunk_rows),
    # which shrinks the chunk as vocabulary grows so peak stays ≈ constant
    fused_logprob_chunk: Optional[int] = None
    # "auto" → Pallas online-logsumexp kernel on one TPU device, lax chunk
    # scan elsewhere and under a multi-device mesh (the kernel has no
    # shard_map wrap, trainer.fused_logprob_impl); "lax" | "pallas" force
    # one (pallas interprets off-TPU)
    fused_logprob_impl: str = "auto"
    gradient_checkpointing: bool = True
    attention_impl: str = "auto"  # xla | pallas | auto (by seq length, on TPU)
    # remat policy under gradient_checkpointing (core/config.remat_policy):
    # "full" recomputes whole layers in the backward; "dots" saves the MXU
    # projection outputs (more HBM, ~1/3 less recompute). Identical
    # gradients either way — a memory/FLOPs tuning knob.
    remat_policy: str = "full"  # full | dots
    # "int8": generation reads weight-only-quantized base projections (per-
    # output-channel scales, core/quant.py) — halves decode's HBM weight
    # traffic. LoRA/embeddings stay exact bf16 in the sampler; scoring and
    # updates always run exact weights. The quantization mismatch is a small
    # off-policy bias the clip TOLERATES by default; pair with
    # sampler_logprob_capture=True to importance-correct it exactly
    # (captured logprobs are the quantized behavior policy's — see
    # core/quant.py). Quantized once under LoRA (base frozen); re-quantized
    # per update when full fine-tuning.
    rollout_quant: str = "none"   # none | int8
    # "int8": the sampler's KV cache is int8 + per-token bf16 scales (core/
    # config.kv_cache_quant) — 1.78x less cache-read bandwidth at hd=128,
    # the dominant decode HBM stream at long responses. Rollout-only
    # (scoring/training have no cache); same off-policy-tolerance story as
    # rollout_quant.
    kv_cache_quant: str = "none"  # none | int8
    # >0: the rollout KV cache switches to the PAGED layout (sampler/paged/,
    # docs/PAGED_CACHE.md) — K/V in a global pool of this-many-token pages
    # addressed through per-row block tables. On its own a pure re-layout
    # (greedy streams bit-identical to contiguous, test-pinned); with
    # rollout_decode_rows > 0 it unlocks true continuous batching. Composes
    # with rollout_spec_k and kv_cache_quant="int8". Use >= 128 on real
    # TPUs (lane-tile alignment for the paged kernels). 0 = no paged
    # FEATURE (no queue, no recycling) and the layout left to the loop: the
    # monolithic one-jit rollout keeps its private cache in pages of 128
    # wherever a decode step then reads each row's own in place (one TPU
    # device, a plain bf16 cache: core/model.decode_loop_page_size; the
    # same tokens, `rollout/kv_in_place` says which) and contiguous
    # elsewhere; the speculative loop stays contiguous.
    rollout_page_size: int = 0
    # rollout_page_size > 0 only. >0: continuous batching — only this many
    # rows are RESIDENT in the decode loop; when a row emits EOS its pages
    # are released and the next queued prompt is prefilled into the freed
    # pool mid-loop (sampler/paged/scheduler.py). Fixes the long-tail
    # straggler cost, works with spec_k, feeds the
    # rollout/page_* metrics + /statusz "pages" + lineage lease events.
    # 0 (or >= the rollout batch) = monolithic paged loop.
    rollout_decode_rows: int = 0
    # continuous batching only (rollout_page_size > 0 AND
    # rollout_decode_rows > 0). True: admissions route through the
    # cross-request radix prefix cache (serving/radix.py,
    # docs/SERVING.md) — repeated prompt prefixes across the rollout
    # queue (the n>1 fanout, dataset-level repeats) install
    # refcount-shared KV pages with zero prefill FLOPs and only the
    # suffix is prefilled. Greedy streams stay bit-identical to the
    # uncached path (test-pinned); sampled streams are equal in
    # distribution only. COMPOSES with rollout_spec_k > 0 — the n-gram
    # drafter seeds its lookup window from the cached continuation of
    # the matched prefix, so overlapping prompts accept drafts from the
    # first generated token (sampler.compose_check holds the full
    # legality matrix). Default off: the cache resets every generate
    # call (KV is params-tied), so it only pays when rollout prompts
    # overlap.
    rollout_prefix_cache: bool = False
    # continuous batching only. >0: any admission whose real prompt
    # suffix exceeds this many tokens is split into KV-only chunk
    # forwards interleaved with the resident rows' decode chunks
    # (sampler/paged/session.py) — a long cold prompt no longer stalls
    # every live stream for its whole prefill, bounding the p95
    # inter-token gap. Greedy streams are bit-identical to 0 (the final
    # chunk samples from the same admission PRNG fold, test-pinned);
    # sampled streams are equal in distribution only (a delayed row
    # decodes at later global PRNG folds). 0 = whole-suffix admissions.
    rollout_prefill_chunk: int = 0

    # ---- environments (envs/, docs/ENVIRONMENTS.md) ----
    # "" = no environment (the classic reward_func pipeline, unchanged).
    # "single_turn" wraps reward_func into SingleTurnEnv — bit-identical
    # to "" (parity-pinned). "python_tool" runs fenced ```python blocks
    # as mid-episode tools over the pooled executor; multi-turn requires
    # GRPO + rollout_page_size > 0 (the continuation turns ride the paged
    # admission path) and is incompatible with the orchestrator fleet,
    # sampler logprob capture, spec decode, and the prefix cache.
    env_name: str = ""
    # episode turn budget; 1 = single-turn semantics for any env
    env_max_turns: int = 1
    # per-turn generation budget (tokens); 0 = response_length. Multi-turn
    # requires env_turn_tokens*max_turns + env_obs_budget*(max_turns-1)
    # <= response_length so the packed episode fits the scored batch.
    env_turn_tokens: int = 0
    # max observation tokens appended per tool call
    env_obs_budget: int = 64
    # wall-clock seconds per tool call (pooled executor per-job timeout)
    env_tool_timeout: float = 5.0
    # resident rows in the multi-turn continuation loop; 0 = all episodes
    env_decode_rows: int = 0

    # ---- resilience (resilience/, docs/RESILIENCE.md) ----
    # fault-injection spec ("point:at=N,..."); None falls back to the
    # NANORLHF_FAULT env var; empty arms nothing. Injection points:
    # ckpt.save, ckpt.restore, rollout.produce, reward.exec, update.step.
    fault_spec: Optional[str] = None
    # training sentinel: per-update finite checks on loss/grad-norm plus an
    # EWMA spike detector; on trip the trainer restores the last committed
    # checkpoint, quarantines the offending batch, and charges the rollback
    # budget. Observation-only when healthy: a no-fault run with the
    # sentinel on is numerically identical to one without it.
    sentinel: bool = True
    sentinel_spike_zscore: float = 6.0
    sentinel_ewma_alpha: float = 0.1
    sentinel_warmup_steps: int = 20
    rollback_budget: int = 2
    # producer watchdog (orchestrated runs): a dead producer thread is
    # restarted with exponential backoff up to `producer_restart_budget`
    # CONSECUTIVE failures (a consumed sample resets the streak); past the
    # budget the run degrades to synchronous rollouts (staleness 0) instead
    # of dying — unless degrade_to_sync=False, which re-raises.
    producer_restart_budget: int = 2
    producer_backoff_base: float = 0.5
    producer_backoff_max: float = 30.0
    # ±fraction jitter on watchdog restart backoff (resilience/retry.py):
    # several supervised pipelines restarted off one shared cause (a weight
    # store hiccup, a flaky filesystem) must not retry in lockstep
    producer_backoff_jitter: float = 0.1
    producer_heartbeat: float = 30.0    # liveness poll interval in get()
    degrade_to_sync: bool = True
    # checkpoint I/O hardening: save/restore attempts retried with backoff
    # (ckpt_io_retries EXTRA attempts after the first). reward_retries
    # likewise for the host-side reward callable.
    ckpt_io_retries: int = 2
    ckpt_retry_backoff: float = 0.5
    reward_retries: int = 1
    # SIGTERM → flush in-flight async save, write an emergency checkpoint
    # at the current step, raise resilience.Preempted (handler installs
    # only from the main thread; elsewhere this degrades to a no-op guard)
    graceful_preemption: bool = True

    # ---- telemetry (telemetry/, docs/OBSERVABILITY.md) ----
    # span tracer + flight recorder: records named spans with correlation
    # args (step, rollout_index, staleness, policy_version) on per-thread
    # tracks — trainer loop, orchestrator producer, reward dispatch,
    # checkpoint I/O — and writes a Perfetto-loadable Chrome trace
    # (`<telemetry_dir>/trace.json`) at the end of every train() call and
    # on close(). The resilience layer dumps the flight-recorder ring as
    # `blackbox_<step>.json` on sentinel trip / producer failure / SIGTERM.
    # Off by default; disabled it is a no-op (tests/test_telemetry.py), its
    # cost enabled is not measured on a chip.
    telemetry: bool = False
    telemetry_dir: Optional[str] = None     # None -> output_dir
    # bounded trace buffer: events past the cap are dropped (counted in the
    # telemetry/spans_dropped metric) so a long run cannot OOM the host
    telemetry_max_events: int = 200_000
    flight_recorder_len: int = 256          # blackbox ring: recent spans kept
    # windowed XLA profiling (utils/profiling.ProfileWindow): wrap
    # jax.profiler around exactly [profile_at_step, +profile_num_steps)
    # updates, writing a TensorBoard-loadable trace to profile_dir
    # (None -> <output_dir>/profile). Independent of `telemetry` — the XLA
    # profile answers "what did the compiler run", the span trace answers
    # "what did the host pipeline do". An on-demand window can be requested
    # on a live run by touching the trigger file (None -> <output_dir>/
    # PROFILE; the file is consumed when the window opens).
    profile_at_step: Optional[int] = None
    profile_num_steps: int = 1
    profile_dir: Optional[str] = None
    profile_trigger_file: Optional[str] = None
    # run-health plane (telemetry/health.py + exporter.py,
    # docs/OBSERVABILITY.md §5): every metric row folds into O(1)-memory
    # streaming aggregates (fast/slow EWMA, P² quantile sketches, windowed
    # counter rates) and a declarative rule set scores the run OK/WARN/CRIT.
    # Health is on by default (every cell of `benchmark/run.py` runs with it;
    # its share is not measured apart); the HTTP exporter is off by default.
    # status_port: 0 = off,
    # -1 = ephemeral port (tests/CI), >0 = fixed port serving /metrics
    # (Prometheus text), /healthz (200/503 from the verdict), /statusz
    # (JSON run state incl. fleet membership + lease table).
    health: bool = True
    health_fast_alpha: float = 0.5        # tracks ~the last 2 rows
    health_slow_alpha: float = 0.05       # the baseline fast is judged by
    health_warmup_steps: int = 8          # min rows per metric before firing
    health_window_s: float = 60.0         # rate-rule sliding window
    health_max_events: int = 64           # transition ring for /statusz
    health_blackbox_on_crit: bool = True  # flight-recorder dump, reason="health"
    health_arm_sentinel: bool = False     # CRIT enables TrainingSentinel if off
    status_port: int = 0
    status_host: str = "127.0.0.1"
    # sample lineage ledger (telemetry/lineage.py, docs/OBSERVABILITY.md
    # §6): one joinable provenance stream per rollout index — lease grant
    # (lease/worker ids, PRNG fold-in path), generation (policy version,
    # spec-decode per-row acceptance), queue transit (staleness at
    # consumption), reward (score, retry attempt, grader wall), and
    # training outcome (advantage, kept vs dropped with a machine-readable
    # drop_reason) — as size-rotated append-only JSONL under
    # <output_dir>/lineage/. Query with tools/inspect_run.py; drop-reason
    # counters + a last-N sample ring ride /statusz and /metrics. Off by
    # default; its cost enabled is not measured on a chip.
    lineage: bool = False
    # fraction of rollout indices recorded (deterministic per-index hash:
    # a sampled index keeps its COMPLETE lease→...→outcome chain; others
    # are skipped at every layer). Drop counters stay exact regardless.
    lineage_sample_rate: float = 1.0
    # latency surface (telemetry/hist.py, docs/OBSERVABILITY.md §7):
    # log-bucketed mergeable streaming histograms over every
    # latency-bearing path — admission→first-token (TTFT), inter-token
    # gaps, queue wait, per-op RPC RTT, reward-grader wall, per-update
    # phase durations — journaled in trainer_state.json, rendered as
    # Prometheus histogram exposition on /metrics, and scored by the
    # quantile SLO rules (health.SLO_RULES). On by default (every cell of
    # `benchmark/run.py` runs with it; its share is not measured apart).
    latency: bool = True

    # ---- checkpoint / eval / logging ----
    save_steps: int = 1
    save_total_limit: int = 8
    save_optimizer_state: bool = True   # opt state + PRNG for exact resume
    save_value_model: bool = True       # PPO: value model in the checkpoint
                                        # (`PPO/ppo_trainer.py:413-416`)
    metric_for_best_model: str = "eval_objective/rlhf_reward_old"
    greater_is_better: bool = True
    load_best_model_at_end: bool = True
    # after the full run (and load_best), also write an HF-format checkpoint
    # (LoRA merged) here — the reference's `save_model` handoff artifact
    export_hf_dir: Optional[str] = None
    eval_steps: int = 1
    logging_steps: int = 1
    num_printed_samples: int = 5         # rich-table rows (`GRPO/grpo_trainer.py:717`)
    # rows per update routed into the lineage ledger's full-text `sample`
    # events (metrics.jsonl no longer carries sample rows — they polluted
    # the metric-row contract consumers like the health monitor iterate).
    # None -> num_printed_samples, the console table's row count.
    log_samples_limit: Optional[int] = None
    report_to: str = "jsonl"             # "jsonl" | "none" (wandb needs egress)

    # ---- mesh ----
    mesh: MeshConfig = dataclasses.field(default_factory=MeshConfig)

    # ---- derived (filled by finalize) ----
    world_size: int = dataclasses.field(default=1, init=False)
    local_batch_size: int = dataclasses.field(default=0, init=False)
    micro_batch_size: int = dataclasses.field(default=0, init=False)
    batch_size: int = dataclasses.field(default=0, init=False)
    mini_batch_size: int = dataclasses.field(default=0, init=False)
    local_mini_batch_size: int = dataclasses.field(default=0, init=False)
    num_total_batches: int = dataclasses.field(default=0, init=False)

    def finalize(self, n_devices: int) -> "RLConfig":
        """Derive the batch hierarchy from `self.mesh` over n_devices."""
        d, f, t, _sp = self.mesh.resolve(n_devices)
        return self.finalize_world(d * f)

    def finalize_world(self, world_size: int) -> "RLConfig":
        """Derive the batch hierarchy. `world_size` = data-parallel extent of
        the mesh (data × fsdp axes — both shard the batch). Preferred over
        finalize() when an explicit Mesh exists: its axis extents are the
        truth, not self.mesh's (an externally built mesh may differ)."""
        self.world_size = world_size
        self.local_batch_size = (
            self.per_device_train_batch_size
            * self.gradient_accumulation_steps
            * self.num_mini_batches
        )
        self.micro_batch_size = self.per_device_train_batch_size * self.world_size
        self.batch_size = self.local_batch_size * self.world_size
        self.mini_batch_size = exact_div(
            self.batch_size, self.num_mini_batches,
            "`batch_size` must be a multiple of `num_mini_batches`",
        )
        self.local_mini_batch_size = exact_div(
            self.local_batch_size, self.num_mini_batches,
            "`local_batch_size` must be a multiple of `num_mini_batches`",
        )
        if self.whiten_rewards and self.local_mini_batch_size < 8:
            raise ValueError(
                f"Per-rank minibatch size {self.local_mini_batch_size} is "
                "insufficient for whitening"
            )
        self.num_total_batches = math.ceil(self.total_episodes / self.batch_size)
        return self
