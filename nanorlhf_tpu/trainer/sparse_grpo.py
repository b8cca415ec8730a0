"""Sparse GRPO — the long-sequence (8k-token) trainer variant of r1-v0.

Re-states `/root/reference/examples/r1-v0/grpo_r1_trainer.py` on the unified
runtime. The four moves that let the reference train 8,000-token responses on
one 40 GB GPU (`examples/r1-v0/README.md:25-28`), here under XLA static
shapes:

1. **sparse filter** — drop samples whose z-scored advantage is 0 (with 0/1
   rewards that's every all-correct/all-wrong group) (`:565-568`);
2. **de-padding** — strip the common left-pad of queries and truncate
   responses to the batch max (`:571-582`), rounded onto a power-of-two menu
   so XLA's compile cache stays warm;
3. **bucket batching** — pack by length under the `max_len × rows ≤ budget`
   memory model, rollout budget 22·2316 / backward budget 4·2316
   (`:589,700,410-435`);
4. **bucket-scaled loss** — each bucket backward is scaled
   `rows / minibatch_rows`, one optimizer step per minibatch (`:786-791`).

Host-side numpy handles all ragged filtering/packing; jit only ever sees the
menu shapes (SURVEY.md §7 hard part #2).
"""

from __future__ import annotations

import time
from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax

from nanorlhf_tpu.algos import (
    discounted_returns,
    grpo_group_advantage,
    keep_one_of_n_indices,
    sparse_terminal_rewards,
)
from nanorlhf_tpu.algos.losses import grpo_loss
from nanorlhf_tpu.ops.masking import (
    INVALID_LOGPROB,
    first_true_indices,
    logprobs_from_logits,
    response_padding_masks,
    truncate_response,
)
from nanorlhf_tpu.core.model import padded_forward_logits
from nanorlhf_tpu.ops.fused_logprob import chunked_entropy
from nanorlhf_tpu.sampler import SamplingParams, generate
from nanorlhf_tpu.trainer.bucketing import (
    create_batches,
    pad_rows,
    round_up_to_menu,
    shape_menu,
)
from nanorlhf_tpu.trainer.trainer import (
    RLTrainer,
    RolloutStream,
    device_peak_bytes,
    forward_token_budget,
    fused_response_logprobs,
)

# forward budget comes from forward_token_budget (activation ∧ vocab caps);
# backward keeps the reference's dedicated constant (`grpo_r1_trainer.py:700`)
BACKWARD_BUDGET = 4 * 2316


class SparseGRPOTrainer(RLTrainer):
    """GRPO + sparse filtering + bucketed variable-length execution.

    `accuracy_func(trainer) -> float`, when given, runs before training and
    every `cfg.eval_steps` updates (MATH-500 greedy eval in r1,
    `grpo_r1_trainer.py:471-475,824-825`).

    The reward callable may use either protocol:
    `(pmt_and_responses, eos_token)` or the r1 signature
    `(pmt_and_responses, responses_ids, tokenizer)` (`grpo_r1.py:250`).
    """

    def __init__(self, *args, accuracy_func: Optional[Callable] = None, **kwargs):
        super().__init__(*args, **kwargs)
        if self._env_multi_turn:
            # single-turn envs work (RLTrainer unwraps them into a plain
            # reward callable, which _call_reward dispatches unchanged);
            # the multi-turn episode driver is wired into the DENSE
            # runtime's rollout phase only
            raise ValueError(
                "SparseGRPOTrainer does not drive multi-turn environments "
                "(env_max_turns > 1) — use the dense RLTrainer")
        self.accuracy_func = accuracy_func
        self._len_menu = shape_menu(
            self.cfg.response_length + self.dataset.input_ids.shape[1], min_value=32
        )
        self._rows_menu = shape_menu(max(self.cfg.batch_size, 1), min_value=1)

    # ------------------------------------------------------------------ #
    # jitted pieces (bucket-shaped)
    # ------------------------------------------------------------------ #

    def _bucket_score_fn(self):
        if hasattr(self, "_bucket_score_cached"):
            return self._bucket_score_cached
        mcfg, cfg = self.mcfg, self.cfg
        pad_id = self.tokenizer.pad_token_id
        lora_scale = self.lora_scale

        if cfg.fused_logprob:
            # fused hidden→logprob scoring (ops/fused_logprob.py): the
            # parent's non-sp fused chunk scorer is shape-polymorphic over
            # bucket widths already (jit per static context_length) — same
            # closure, one copy, no [rows, T, V] logits block per forward
            score = self._score_chunk_fn()
            self._bucket_score_cached = score
            return score

        @partial(jax.jit, static_argnums=(3,))
        def score(params, ref_params, qr, context_length: int):
            resp = qr[:, context_length:]
            lp = logprobs_from_logits(
                padded_forward_logits(params, mcfg, qr, pad_id,
                                      lora_scale=lora_scale,
                                      response_context_length=context_length),
                resp, cfg.temperature,
            )
            rlp = logprobs_from_logits(
                padded_forward_logits(ref_params, mcfg, qr, pad_id,
                                      response_context_length=context_length),
                resp, cfg.temperature,
            )
            return lp, rlp

        self._bucket_score_cached = score
        return score

    def _bucket_grad_fn(self):
        if hasattr(self, "_bucket_grad_cached"):
            return self._bucket_grad_cached
        mcfg, cfg = self.mcfg, self.cfg
        pad_id = self.tokenizer.pad_token_id
        lora_scale = self.lora_scale
        remat = cfg.gradient_checkpointing
        combine = self._combine

        def loss_fn(trainable, frozen, mb, context_length, loss_scale):
            tree = combine(trainable, frozen)
            if cfg.fused_logprob:
                new_lp, ent_tok = fused_response_logprobs(
                    tree["policy"], mcfg, mb["query_responses"],
                    mb["responses"], pad_id, context_length, cfg,
                    lora_scale=lora_scale, remat=remat, with_entropy=True,
                )
                entropy = jax.lax.stop_gradient(ent_tok.mean())
            else:
                logits = padded_forward_logits(
                    tree["policy"], mcfg, mb["query_responses"], pad_id,
                    lora_scale=lora_scale, remat=remat,
                    response_context_length=context_length,
                )
                # chunked entropy: no stop-gradient f32 full-logits copy
                entropy = jax.lax.stop_gradient(chunked_entropy(
                    logits, cfg.temperature, chunk=cfg.fused_logprob_chunk
                ).mean())
                new_lp = logprobs_from_logits(
                    logits, mb["responses"], cfg.temperature
                )
            new_lp = jnp.where(mb["padding_mask"], INVALID_LOGPROB, new_lp)
            mask = ~mb["padding_mask"]
            if "loss_mask" in mb:
                # env observation tokens: conditioned on, never scored
                # (dense runtime's microbatch_loss composes the same way)
                mask = mask & mb["loss_mask"]
            loss, aux = grpo_loss(
                new_lp, mb["logprobs"], mb["ref_logprobs"], mb["advantages"],
                mask, cfg.cliprange, cfg.kl_coef,
            )
            aux["entropy"] = entropy
            return loss * loss_scale, aux

        @partial(jax.jit, static_argnums=(3,))
        def bucket_grads(trainable, frozen, mb, context_length, loss_scale):
            (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                trainable, frozen, mb, context_length, loss_scale
            )
            return grads, aux

        self._bucket_grad_cached = bucket_grads
        return bucket_grads

    # ------------------------------------------------------------------ #
    # sequence-parallel pieces (mesh sp > 1): bucket-shaped SP scoring and
    # grads — `_sp_on`/`_fsdp_axis` come from RLTrainer, which also runs
    # its own dense chunked passes through SP when the axis is present
    # (VERDICT r1 #3: SP is a trainer capability, not a demo)
    # ------------------------------------------------------------------ #

    def _sp_score_fn(self):
        if hasattr(self, "_sp_score_cached"):
            return self._sp_score_cached
        from nanorlhf_tpu.parallel.sp import sp_score_logprobs

        mcfg, cfg, mesh = self.mcfg, self.cfg, self.mesh
        pad_id = self.tokenizer.pad_token_id
        lora_scale = self.lora_scale
        fsdp_axis = self._fsdp_axis()

        @partial(jax.jit, static_argnums=(3,))
        def score(params, ref_params, qr, context_length: int):
            # same attn_impl as `_sp_grad_fn`'s update forward (ADVICE r3)
            lp = sp_score_logprobs(
                params, mcfg, qr, pad_id, cfg.temperature, mesh,
                fsdp_axis=fsdp_axis, lora_scale=lora_scale,
                attn_impl=mcfg.attention_impl,
            )[:, context_length - 1 : -1]
            rlp = sp_score_logprobs(
                ref_params, mcfg, qr, pad_id, cfg.temperature, mesh,
                fsdp_axis=fsdp_axis, attn_impl=mcfg.attention_impl,
            )[:, context_length - 1 : -1]
            return lp, rlp

        self._sp_score_cached = score
        return score

    def _sp_grad_fn(self):
        if hasattr(self, "_sp_grad_cached"):
            return self._sp_grad_cached
        from nanorlhf_tpu.parallel.sp import sp_score_logprobs

        mcfg, cfg, mesh = self.mcfg, self.cfg, self.mesh
        pad_id = self.tokenizer.pad_token_id
        lora_scale = self.lora_scale
        combine = self._combine
        fsdp_axis = self._fsdp_axis()

        def loss_fn(trainable, frozen, mb, context_length, loss_scale):
            tree = combine(trainable, frozen)
            # attn_impl matches `_sp_score_fn` (the flash ring has a
            # backward): old/ref and new logprobs share kernels, so the
            # exp(new−old) ratio has no kernel-mismatch offset (ADVICE r3)
            new_lp, entropy = sp_score_logprobs(
                tree["policy"], mcfg, mb["query_responses"], pad_id,
                cfg.temperature, mesh, fsdp_axis=fsdp_axis,
                lora_scale=lora_scale, remat=cfg.gradient_checkpointing,
                with_entropy=True, entropy_from_position=context_length - 1,
                attn_impl=mcfg.attention_impl,
            )
            new_lp = new_lp[:, context_length - 1 : -1]
            new_lp = jnp.where(mb["padding_mask"], INVALID_LOGPROB, new_lp)
            mask = ~mb["padding_mask"]
            if "loss_mask" in mb:
                # env observation tokens: conditioned on, never scored
                mask = mask & mb["loss_mask"]
            loss, aux = grpo_loss(
                new_lp, mb["logprobs"], mb["ref_logprobs"], mb["advantages"],
                mask, cfg.cliprange, cfg.kl_coef,
            )
            # the global [B, T, V] logits never materialize under SP (that's
            # the point) — the entropy stat is a per-shard mean pmean'd over
            # the ring inside the scorer
            aux["entropy"] = entropy
            return loss * loss_scale, aux

        @partial(jax.jit, static_argnums=(3,))
        def sp_grads(trainable, frozen, mb, context_length, loss_scale):
            (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                trainable, frozen, mb, context_length, loss_scale
            )
            return grads, aux

        self._sp_grad_cached = sp_grads
        return sp_grads

    def _sp_round_len(self, blen: int, cap: int) -> int:
        """Round a bucket length up to an sp-axis multiple (the sequence dim
        shards evenly over the ring); `cap` is the physical qr width."""
        n_sp = self.mesh.shape.get("sp", 1)
        if n_sp == 1:
            return blen
        blen = -(-blen // n_sp) * n_sp
        if blen > cap:
            if cap % n_sp != 0:
                raise ValueError(
                    f"qr width {cap} not divisible by sp={n_sp}; pick "
                    f"response_length/prompt width as multiples of sp"
                )
            blen = cap
        return blen

    def _apply_grads_fn(self):
        if hasattr(self, "_apply_grads_cached"):
            return self._apply_grads_cached
        optimizer = self.optimizer

        from nanorlhf_tpu.trainer.trainer import donate_argnums_on_accel

        @partial(jax.jit, donate_argnums=donate_argnums_on_accel(0, 1))
        def apply_grads(trainable, opt_state, grads):
            updates, opt_state = optimizer.update(grads, opt_state, trainable)
            return optax.apply_updates(trainable, updates), opt_state

        self._apply_grads_cached = apply_grads
        return apply_grads

    # ------------------------------------------------------------------ #
    # reward protocol bridge
    # ------------------------------------------------------------------ #

    def _call_reward(self, pmt_and_responses, responses_ids):
        try:
            return np.asarray(
                self.reward_func(pmt_and_responses, responses_ids, self.tokenizer),
                np.float32,
            )
        except TypeError:
            return np.asarray(
                self.reward_func(pmt_and_responses, self.tokenizer.eos_token),
                np.float32,
            )

    # ------------------------------------------------------------------ #
    # the sparse training loop
    # ------------------------------------------------------------------ #

    def train(self, num_updates: Optional[int] = None):
        cfg, tok = self.cfg, self.tokenizer
        if cfg.rollout_orchestrator:
            raise ValueError(
                "rollout_orchestrator is not supported by SparseGRPOTrainer "
                "yet: the sparse all-zero-advantage skip consumes a rollout "
                "WITHOUT publishing a policy version, which would wedge the "
                "bounded-staleness gate (orchestrator/sample_queue.py). Use "
                "rollout_ahead for overlap on the sparse path."
            )
        pad_id, eos_id = tok.pad_token_id, tok.eos_token_id
        n = cfg.sample_n
        sp_on = self._sp_on()
        score_fn = self._sp_score_fn() if sp_on else self._bucket_score_fn()
        grad_fn = self._sp_grad_fn() if sp_on else self._bucket_grad_fn()
        apply_fn = self._apply_grads_fn()

        if self.accuracy_func is not None and self.state["global_step"] == 0:
            acc = float(self.accuracy_func(self))
            self.logger.log(0, 0, {"initial_accuracy": acc})

        # the single-model scorer branches to the SP variant when sp is on
        # (see RLTrainer._single_scorer_for for the ref-free/capture matrix)
        capture = cfg.sampler_logprob_capture
        ref_fn = self._single_scorer_for(capture)
        sampling = SamplingParams(
            temperature=cfg.temperature, top_p=cfg.top_p, n=n,
            max_tokens=cfg.response_length, capture_logprobs=capture,
            compaction_segments=cfg.rollout_compaction_segments,
            top_k=cfg.rollout_top_k, approx_top_k=cfg.rollout_approx_top_k,
            shared_prompt_prefill=cfg.rollout_shared_prefill,
            spec_k=cfg.rollout_spec_k, spec_ngram=cfg.rollout_spec_ngram,
            page_size=cfg.rollout_page_size,
            decode_rows=cfg.rollout_decode_rows,
        )
        n_updates = (
            max(0, cfg.num_total_batches - self.state["global_step"])
            if num_updates is None else num_updates
        )

        def rollout_body(queries, gk):
            """DISPATCH one rollout (async — nothing blocks until fetched)."""
            q_j = jnp.asarray(queries)
            if self.rollout_mesh is not None:
                from nanorlhf_tpu.parallel.mesh import batch_sharding

                # disaggregated rollouts: prompts land on the generation
                # mesh; _rollout_params() re-shards the param view there
                q_j = jax.device_put(q_j, batch_sharding(self.rollout_mesh))
            spec_stats: list = []
            paged_stats: list = []
            gen_out = generate(
                self._rollout_params(), self._rollout_mcfg, q_j, q_j != pad_id, gk,
                sampling, eos_token_id=eos_id, pad_token_id=pad_id,
                lora_scale=self.lora_scale,
                spec_stats_out=spec_stats, tracer=self.tracer,
                paged_stats_out=paged_stats, latency=self.latency,
            )
            return {"queries": queries, "gen_out": gen_out,
                    "spec_stats": spec_stats[0] if spec_stats else None,
                    "paged_stats": paged_stats[0] if paged_stats else None}

        stream = RolloutStream(self, rollout_body, meter=self._rollout_meter)
        # lineage (telemetry/lineage.py): whole-rollout drops are counted
        # in samples — one rollout here is batch_size*n completion rows
        self.lineage.rows_hint = cfg.batch_size * n
        for update in range(1, n_updates + 1):
            t_start = time.perf_counter()  # sec_per_episode is a duration
            step_t0 = time.perf_counter()
            # telemetry (docs/OBSERVABILITY.md): profile-window poll + the
            # per-update span, same contract as the dense loop
            self.profile_window.poll(self.state["global_step"] + 1)
            span_t0 = self.tracer.now_us() if self.tracer.enabled else 0.0
            self.state["episode"] += cfg.batch_size

            # ---- rollout + reward -----------------------------------------
            t_roll0 = time.perf_counter()
            ro = stream.fetch_or_dispatch()
            rollout_index = ro["_index"]
            queries = ro["queries"]
            batch_size = queries.shape[0]
            if self.lineage.enabled:
                # serial loop: generation provenance is emitted here (the
                # stream's dispatch already logged the lease event)
                from nanorlhf_tpu.telemetry.lineage import spec_summary

                self.lineage.generation(
                    rollout_index,
                    policy_version=self.state["global_step"], worker_id=0,
                    spec=spec_summary(ro),
                )
            pstats = ro.get("paged_stats")
            if pstats is not None:
                # /statusz "pages" snapshot + one lineage "lease" event per
                # mid-loop admission — same contract as the dense loop
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
                if self.lineage.enabled:
                    for adm in pstats.get("admissions") or []:
                        self.lineage.event(
                            "lease", rollout_index, midloop=True,
                            row=adm["row"], queue_index=adm["queue_index"],
                            iteration=adm["iteration"],
                        )
            if capture:
                responses, captured_lp = ro["gen_out"]
                responses = np.asarray(responses)
                captured_lp = np.asarray(captured_lp)
            else:
                responses = np.asarray(ro["gen_out"])
                captured_lp = None
            rollout_s = time.perf_counter() - t_roll0
            if cfg.rollout_ahead and update < n_updates:
                # overlap the NEXT generation with this update's grading —
                # in the r1 path the sympy/subprocess graders are the
                # dominant host cost, so this is where the overlap pays most
                stream.prefetch()
            question_strings = [
                q.replace(tok.pad_token, "") for q in tok.batch_decode(queries)
            ]
            question_n = [q for q in question_strings for _ in range(n)]
            decoded = tok.batch_decode(responses)
            t_rwd0 = time.perf_counter()
            raw_scores = self._call_reward(
                [q + r for q, r in zip(question_n, decoded)], responses
            )
            if self.latency.enabled:
                # grader wall — same quantity the lineage reward event
                # records as wall_s (the sympy/subprocess graders are the
                # dominant host cost in the r1 path)
                self.latency.record("latency/reward_s",
                                    time.perf_counter() - t_rwd0)
            self.lineage.reward(
                rollout_index, step=self.state["global_step"],
                scores=[round(float(s), 6) for s in raw_scores.tolist()],
                attempt=1,  # _call_reward has no retry loop
                wall_s=round(time.perf_counter() - t_rwd0, 6),
            )
            mean_raw_score = float(raw_scores.mean())
            log_responses_length = float(
                np.asarray(first_true_indices(jnp.asarray(responses) == pad_id)).mean()
            )

            # ---- group z-score + keep-1-of-N ------------------------------
            adv_flat = np.asarray(grpo_group_advantage(jnp.asarray(raw_scores), n))
            self.key, kk = jax.random.split(self.key)
            keep = np.asarray(keep_one_of_n_indices(kk, batch_size, n))
            rows = np.arange(batch_size)
            scores = adv_flat.reshape(batch_size, n)[rows, keep]
            responses = responses.reshape(batch_size, n, -1)[rows, keep]
            if captured_lp is not None:
                captured_lp = captured_lp.reshape(batch_size, n, -1)[rows, keep]
            if n > 1:
                # the other n−1 completions per prompt leave the batch here
                self.lineage.drop(
                    rollout_index, "keep_filter",
                    count=batch_size * (n - 1),
                    step=self.state["global_step"],
                )

            # ---- sparse filter (`grpo_r1_trainer.py:565-568`) -------------
            nz = np.where(scores != 0)[0]
            kept_frac = len(nz) / max(batch_size, 1)
            if self.lineage.enabled:
                # the paper's silent zero-advantage skip, made loud: one
                # drop event PER EXCLUDED ROW — the attribution the sparse
                # filter never had (every dropped row has exactly one
                # machine-readable drop_reason)
                for r in np.where(scores == 0)[0]:
                    self.lineage.drop(
                        rollout_index, "sparse_zero_advantage",
                        row=int(r), step=self.state["global_step"],
                        raw_score=round(
                            float(raw_scores.reshape(batch_size, n)[r, keep[r]]),
                            6,
                        ),
                    )
            if len(nz) == 0:
                print(f"[sparse-grpo] update {update}: all advantages zero, skipping")
                # skip marker in the trace: a starved streak shows up as a
                # row of instants instead of a silent gap
                self.tracer.instant(
                    "sparse.skip", rollout_index=self.state["rollouts"],
                    raw_score_mean=mean_raw_score,
                )
                # a metrics row even for the skip (the reference logs
                # nothing here): with sparse/binary rewards, WHY training
                # starves matters — raw_score_mean 0 = uniformly failed,
                # high = uniformly solved; both give zero group advantage.
                # log_event (no 'episode' stamp, rollout-indexed) keeps
                # step-row consumers and TB x-axes intact across
                # consecutive skips at a frozen global_step.
                self.logger.log_event(self.state["rollouts"], {
                    "sparse_skip/raw_score_mean": mean_raw_score,
                    "sparse_skip/rollout_index": self.state["rollouts"],
                })
                # preemption must be polled on the skip path too: a long
                # uniformly-failed/solved streak would otherwise bypass the
                # bottom-of-loop poll every iteration, swallow SIGTERM, and
                # be SIGKILLed at the end of the grace window
                if self._preemption.triggered:
                    from nanorlhf_tpu.resilience import Preempted

                    self._sparse_save({})
                    self.ckpt.wait()
                    self.tracer.dump_blackbox(
                        self._telemetry_dir, self.state["global_step"],
                        "preemption",
                    )
                    self._write_trace()
                    raise Preempted(
                        f"SIGTERM at step {self.state['global_step']} (sparse "
                        f"skip streak): emergency checkpoint committed to "
                        f"{cfg.output_dir}"
                    )
                continue
            scores, queries_f, responses_f = scores[nz], queries[nz], responses[nz]
            if captured_lp is not None:
                captured_lp = captured_lp[nz]

            # ---- de-pad (`:571-582`), menu-rounded ------------------------
            from nanorlhf_tpu.trainer.bucketing import depad_queries

            queries_f = depad_queries(queries_f, pad_id, self._len_menu)
            context_length = queries_f.shape[1]

            post = np.asarray(truncate_response(eos_id, pad_id, jnp.asarray(responses_f)))
            resp_len = np.asarray(first_true_indices(jnp.asarray(post) == pad_id))
            max_resp = round_up_to_menu(
                max(int(resp_len.max()), 1), self._len_menu
            )
            max_resp = min(max_resp, responses_f.shape[1])
            responses_f = responses_f[:, :max_resp]
            post = post[:, :max_resp]

            qr = np.concatenate([queries_f, responses_f], axis=1)
            qr_len = context_length + resp_len

            # ---- bucketed logprob pass (budget 22·2316, capped so the
            # [tokens, vocab] logits block fits HBM — the cap lifts under
            # fused_logprob, whose chunking bounds that block itself; NOT
            # under sp, whose scorer still materializes per-shard logits) ---
            rollout_budget = forward_token_budget(
                self.mcfg.vocab_size,
                fused_logprob=cfg.fused_logprob and not self._sp_on(),
            )
            backward_budget = min(BACKWARD_BUDGET, rollout_budget // 2)
            buckets = create_batches(qr_len, rollout_budget)
            logprobs = np.full(
                (len(scores), max_resp), INVALID_LOGPROB, np.float32
            )
            ref_logprobs = logprobs.copy()
            if captured_lp is not None:
                # policy logprobs came from the sampler; buckets below only
                # run the ref forward (half the scoring work)
                logprobs = captured_lp[:, :max_resp].astype(np.float32)
            ref_free = self._ref_free
            for idxs in ([] if (ref_free and capture) else buckets):
                # ref-free + capture: zero scoring forwards (sampler-captured
                # policy logprobs, no reference model — the r1 setting)
                blen = round_up_to_menu(int(qr_len[idxs].max()), self._len_menu)
                blen = min(max(blen, context_length + 1), qr.shape[1])
                blen = self._sp_round_len(blen, qr.shape[1])
                rows_b = round_up_to_menu(len(idxs), self._rows_menu)
                padded = pad_rows(
                    {"qr": qr[idxs][:, :blen]}, rows_b, {"qr": pad_id}
                )
                width = blen - context_length
                if ref_free:
                    lp = ref_fn(self.params, jnp.asarray(padded["qr"]),
                                context_length)
                    logprobs[idxs, :width] = np.asarray(lp)[: len(idxs)]
                elif capture:
                    rlp = ref_fn(self.ref_params, jnp.asarray(padded["qr"]),
                                 context_length)
                    ref_logprobs[idxs, :width] = np.asarray(rlp)[: len(idxs)]
                else:
                    # (an expert model's chunk scorer appends its router
                    # sums; the sparse loop logs no moe/* counters)
                    lp, rlp = score_fn(
                        self.params, self.ref_params, jnp.asarray(padded["qr"]),
                        context_length,
                    )[:2]
                    logprobs[idxs, :width] = np.asarray(lp)[: len(idxs)]
                    ref_logprobs[idxs, :width] = np.asarray(rlp)[: len(idxs)]
            if ref_free:
                # ref == policy-old: every KL term and metric reads exactly 0
                ref_logprobs = logprobs.copy()

            # ---- masks + advantages ---------------------------------------
            seq_len = np.asarray(first_true_indices(jnp.asarray(post) == pad_id) - 1)
            padding_mask, _ = response_padding_masks(post, jnp.asarray(seq_len))
            padding_mask = np.asarray(padding_mask)
            logprobs = np.where(padding_mask, INVALID_LOGPROB, logprobs)
            ref_logprobs = np.where(padding_mask, INVALID_LOGPROB, ref_logprobs)
            rewards = np.asarray(sparse_terminal_rewards(
                jnp.asarray(scores), jnp.asarray(seq_len), max_resp
            ))
            advantages = np.asarray(discounted_returns(jnp.asarray(rewards), 1.0))
            advantages = np.where(padding_mask, 0.0, advantages)

            # ---- bucketed update (budget 4·2316, loss-scaled) -------------
            t_upd0 = time.perf_counter()
            trainable, frozen = self._partition(
                self._train_tree(self.params, self.value_params)
            )
            all_stats = []
            local_bs = len(scores)
            mini = min(cfg.local_mini_batch_size, local_bs)
            lr_step = self.state.get("opt_steps", 0)
            for epoch in range(cfg.num_ppo_epochs):
                self.key, pk = jax.random.split(self.key)
                perm = np.asarray(jax.random.permutation(pk, local_bs))
                for start in range(0, local_bs, mini):
                    mb_inds = perm[start : start + mini]
                    mini_rows = len(mb_inds)
                    grads_acc = None
                    for bidx in create_batches(qr_len[mb_inds], backward_budget):
                        sel = mb_inds[bidx]
                        blen = round_up_to_menu(int(qr_len[sel].max()), self._len_menu)
                        blen = min(max(blen, context_length + 1), qr.shape[1])
                        blen = self._sp_round_len(blen, qr.shape[1])
                        width = blen - context_length
                        rows_b = round_up_to_menu(len(sel), self._rows_menu)
                        mb = pad_rows(
                            {
                                "query_responses": qr[sel][:, :blen],
                                "responses": responses_f[sel][:, :width],
                                "logprobs": logprobs[sel][:, :width],
                                "ref_logprobs": ref_logprobs[sel][:, :width],
                                "advantages": advantages[sel][:, :width],
                                "padding_mask": padding_mask[sel][:, :width],
                            },
                            rows_b,
                            {"query_responses": pad_id, "responses": pad_id,
                             "logprobs": INVALID_LOGPROB,
                             "ref_logprobs": INVALID_LOGPROB,
                             "padding_mask": True},
                        )
                        mb = {k: jnp.asarray(v) for k, v in mb.items()}
                        # scale by REAL rows (`grpo_r1_trainer.py:786-788`)
                        loss_scale = len(sel) / mini_rows
                        grads, aux = grad_fn(
                            trainable, frozen, mb, context_length,
                            jnp.float32(loss_scale),
                        )
                        grads_acc = grads if grads_acc is None else jax.tree.map(
                            jnp.add, grads_acc, grads
                        )
                        all_stats.append(aux)
                    trainable, self.opt_state = apply_fn(
                        trainable, self.opt_state, grads_acc
                    )
                    self.state["opt_steps"] = self.state.get("opt_steps", 0) + 1
            self.params = self._combine(trainable, frozen)["policy"]
            all_stats = jax.device_get(all_stats)
            update_s = time.perf_counter() - t_upd0

            # ---- metrics / eval / checkpoint ------------------------------
            agg = {
                k: float(np.mean([s[k] for s in all_stats]))
                for k in (all_stats[0] if all_stats else {})
            }
            kl_rollout = float(
                np.where(padding_mask, 0.0, logprobs - ref_logprobs).sum(1).mean()
            )
            metrics = {
                # GRPO parity: update-pass refkl (see docs/METRICS.md);
                # 0 in ref-free mode — the stand-in refkl would report
                # KL-to-old-policy, not a reference KL
                "objective/kl_old": (
                    0.0 if self._ref_free
                    else agg.get("refkl_mean", kl_rollout)
                ),
                "objective/kl_rollout_old": kl_rollout,
                "objective/non_score_reward_old": 0.0,  # GRPO: KL is in-loss
                "eval_objective/rlhf_reward_old": mean_raw_score,
                "eval_objective/scores_old": mean_raw_score,
                "policy/approxkl_avg_new": agg.get("approxkl", 0.0),
                "policy/clipfrac_avg_new": agg.get("pg_clipfrac", 0.0),
                "policy/entropy_avg_new": agg.get("entropy", 0.0),
                "loss/policy_avg_new": agg.get("pg_loss", 0.0),
                "val/ratio_new": agg.get("ratio_mean", 1.0),
                "val/ratio_var_new": float(np.var(
                    [s.get("ratio_mean", 1.0) for s in all_stats]
                )) if all_stats else 0.0,
                "lr": float(self._lr_schedules["policy"](lr_step)),
                "eps": cfg.adam_eps,
                "sparse/kept_frac": kept_frac,
                "eval_response_length": log_responses_length,
                **({"sampler_capture/ratio_drift_new": abs(
                    agg.get("ratio_mean", 1.0) - 1.0
                )} if capture else {}),
                "sec_per_episode": (time.perf_counter() - t_start) / cfg.batch_size,
                # memory series (docs/METRICS.md): saved bytes sized from
                # this update's WIDEST backward bucket (rows bounded by the
                # backward token budget at the max bucket width; resp_len /
                # qr_len are per-row arrays here — variable-length buckets)
                # — the buffer the fused path avoids per grad microbatch
                "mem/peak_bytes_in_use": device_peak_bytes(),
                # 0 on an sp mesh too: the sp grad fn runs there, not fused
                "mem/logits_bytes_saved": float(
                    max(1, backward_budget // (context_length + max_resp))
                    * max_resp * self.mcfg.vocab_size
                    * jnp.dtype(self.params["embed_tokens"].dtype).itemsize
                    if cfg.fused_logprob and not self._sp_on() else 0.0
                ),
                "episode": self.state["episode"],
            }
            # speculative-decode acceptance rows: the dense loop's one
            # definition (RLTrainer._spec_decode_metrics, docs/METRICS.md)
            metrics.update(self._spec_decode_metrics(ro.get("spec_stats")))
            metrics.update(self._paged_metrics(ro.get("paged_stats")))
            # perf/MFU accounting (telemetry/, docs/OBSERVABILITY.md): the
            # dense loop's napkin model with sparse-runtime token counts —
            # scoring/update tokens count only the KEPT (post-filter) rows
            score_forwards = (
                0 if (ref_free and capture)
                else 1 if (ref_free or capture) else 2
            )
            metrics.update(self._perf_metrics(
                step_wall_s=time.perf_counter() - step_t0,
                decode_tokens=batch_size * n * cfg.response_length,
                prefill_tokens=batch_size * n * queries.shape[1],
                score_tokens=score_forwards * len(scores)
                * (context_length + max_resp),
                train_tokens=cfg.num_ppo_epochs * local_bs
                * (context_length + max_resp),
                rollout_s=rollout_s,
                update_s=update_s,
            ))
            if self.latency.enabled:
                # per-update phase durations — the sparse loop times its two
                # phases by hand instead of PhaseTimer, same histogram keys
                self.latency.record("latency/phase_rollout_s", rollout_s)
                self.latency.record("latency/phase_update_s", update_s)
            self.state["global_step"] += 1
            if self.accuracy_func is not None and cfg.eval_steps and \
                    self.state["global_step"] % cfg.eval_steps == 0:
                metrics["eval_accuracy_new"] = float(self.accuracy_func(self))
            # run-health plane: same routing as the dense loop — every row
            # folds into the monitor and the health/* gauges ride along
            metrics.update(
                self.health.observe(self.state["global_step"], metrics)
            )
            kept_scores = raw_scores.reshape(batch_size, n)[rows, keep]
            if self.lineage.enabled:
                # outcome closes the chain: kept rows survived BOTH the
                # keep-1-of-N draw and the sparse zero-advantage filter
                self.lineage.outcome(
                    rollout_index, step=self.state["global_step"],
                    policy_version=self.state["global_step"],
                    kept=int(local_bs),
                    advantage=round(float(scores.mean()), 6),
                    scores=[round(float(s), 6) for s in kept_scores.tolist()],
                    kept_frac=round(kept_frac, 4),
                )
                for r in nz[:8]:
                    self.lineage.note_sample(
                        rollout_index, step=self.state["global_step"],
                        score=round(float(kept_scores[r]), 6),
                        response_chars=len(decoded[r * n + keep[r]]),
                        kept=True,
                    )
            if self.state["global_step"] % cfg.logging_steps == 0:
                self.logger.log(self.state["global_step"], self.state["episode"], metrics)
                kept_decoded = [decoded[i * n + j] for i, j in enumerate(keep)]
                sample_limit = (
                    cfg.log_samples_limit
                    if cfg.log_samples_limit is not None
                    else cfg.num_printed_samples
                )
                self.logger.log_samples(
                    self.state["global_step"], question_strings, kept_decoded,
                    kept_scores, sample_limit,
                )
                if self.lineage.enabled:
                    # full-text records belong to the ledger, not
                    # metrics.jsonl (see MetricsLogger.log_samples)
                    for i, (q, r_txt, s) in enumerate(zip(
                            question_strings, kept_decoded,
                            kept_scores.tolist())):
                        if i >= sample_limit:
                            break
                        self.lineage.event(
                            "sample", rollout_index,
                            step=self.state["global_step"], row=i,
                            query=q, response=r_txt,
                            score=round(float(s), 6),
                        )
            saved_this_step = False
            if cfg.save_steps and self.state["global_step"] % cfg.save_steps == 0:
                self._sparse_save(metrics)
                saved_this_step = True
            if self.tracer.enabled:
                # staleness is structurally 0 here (the sparse loop rejects
                # the orchestrator); kept_rows is the sparse-specific
                # correlation arg
                self.tracer.add_complete(
                    "train.update", span_t0, self.tracer.now_us() - span_t0,
                    step=self.state["global_step"],
                    rollout_index=ro["_index"], staleness=0,
                    policy_version=self.state["global_step"],
                    kept_rows=local_bs,
                )
            # graceful preemption (docs/RESILIENCE.md): the guard installed
            # by RLTrainer.__init__ swallows SIGTERM, so this loop MUST poll
            # it — otherwise a preempted sparse run burns the whole grace
            # window and is SIGKILLed with no emergency checkpoint
            if self._preemption.triggered:
                from nanorlhf_tpu.resilience import Preempted

                if not saved_this_step:
                    self._sparse_save(metrics)
                self.ckpt.wait()
                self.tracer.dump_blackbox(
                    self._telemetry_dir, self.state["global_step"],
                    "preemption",
                )
                self._write_trace()
                raise Preempted(
                    f"SIGTERM at step {self.state['global_step']}: emergency "
                    f"checkpoint committed to {cfg.output_dir}"
                )
        # train() returning implies checkpoints are durable (async saver)
        self.ckpt.wait()
        # balance any open XLA profile window + rewrite trace.json (same
        # end-of-train contract as the dense loop)
        self.profile_window.stop()
        self._write_trace()
        if cfg.export_hf_dir and num_updates is None:
            # handoff artifact (same contract as the dense runtime)
            print(f"exporting HF checkpoint to {cfg.export_hf_dir}")
            self.export_model(cfg.export_hf_dir)
        return self.state

    def _sparse_save(self, metrics: dict):
        """Sparse-runtime checkpoint — shared by the periodic path and the
        SIGTERM emergency path. Persists the consumed-rollout cursor (the
        sparse filter skips updates WITHOUT stepping, so global_step alone
        under-counts the data/PRNG streams on resume) and the resilience
        journal, matching the dense runtime's trainer_state contract."""
        cfg = self.cfg
        self.ckpt.save(
            self.state["global_step"], self.params,
            opt_state=self.opt_state if cfg.save_optimizer_state else None,
            rng_key=self.key,
            metric_old=metrics.get(cfg.metric_for_best_model),
            extra_state={"episode": self.state["episode"],
                         "opt_steps": self.state.get("opt_steps", 0),
                         "rollouts": self.state["rollouts"],
                         "resilience": {
                             "sentinel": self.sentinel.journal(),
                             "watchdog": self.watchdog.journal(),
                         },
                         "health": self.health.journal(),
                         "lineage": self.lineage.journal(),
                         "latency": self.latency.journal()},
        )
