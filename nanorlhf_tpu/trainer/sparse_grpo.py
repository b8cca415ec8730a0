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

from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax

from nanorlhf_tpu.algos.losses import grpo_loss
from nanorlhf_tpu.ops.masking import (
    INVALID_LOGPROB,
    first_true_indices,
    logprobs_from_logits,
    truncate_response,
)
from nanorlhf_tpu.core.model import padded_forward_logits
from nanorlhf_tpu.ops.fused_logprob import chunked_entropy
from nanorlhf_tpu.trainer.bucketing import (
    create_batches,
    depad_queries,
    pad_rows,
    round_up_to_menu,
    shape_menu,
)
from nanorlhf_tpu.trainer.trainer import (
    NoStep,
    RLTrainer,
    TrainRun,
    Update,
    fused_response_logprobs,
)
from nanorlhf_tpu.utils.donation import donate_argnums_on_accel

# forward budget comes from RLTrainer._forward_budget (activation ∧ vocab caps);
# backward keeps the reference's dedicated constant (`grpo_r1_trainer.py:700`)
BACKWARD_BUDGET = 4 * 2316


class SparseGRPOTrainer(RLTrainer):
    """GRPO + sparse filtering + bucketed variable-length execution: the
    loop is `RLTrainer.train()`, of whose phases this class overrides
    `_select`, `_score` and `_update`.

    `accuracy_func(trainer) -> float`, when given, runs before training and
    every `cfg.eval_steps` updates (MATH-500 greedy eval in r1,
    `grpo_r1_trainer.py:471-475,824-825`).

    The reward callable may use either protocol:
    `(pmt_and_responses, eos_token)` or the r1 signature
    `(pmt_and_responses, responses_ids, tokenizer)` (`grpo_r1.py:250`).
    """

    def __init__(self, *args, accuracy_func: Optional[Callable] = None, **kwargs):
        super().__init__(*args, **kwargs)
        self._refuse_unsupported()
        self.accuracy_func = accuracy_func
        self._len_menu = shape_menu(
            self.cfg.response_length + self.dataset.input_ids.shape[1], min_value=32
        )
        self._rows_menu = shape_menu(max(self.cfg.batch_size, 1), min_value=1)

    def _refuse_unsupported(self):
        """What the shared loop carries and the sparse phases cannot yet."""
        if self._env_multi_turn:
            # single-turn envs work (RLTrainer unwraps them into a plain
            # reward callable, which _call_reward dispatches unchanged);
            # the per-turn advantages and the observation loss mask ride
            # the dense selection and update only
            raise ValueError(
                "SparseGRPOTrainer does not drive multi-turn environments "
                "(env_max_turns > 1) — use the dense RLTrainer")
        if self.cfg.rollout_orchestrator:
            raise ValueError(
                "rollout_orchestrator is not supported by SparseGRPOTrainer "
                "yet: the sparse all-zero-advantage skip consumes a rollout "
                "WITHOUT publishing a policy version, which would wedge the "
                "bounded-staleness gate (orchestrator/sample_queue.py). Use "
                "rollout_ahead for overlap on the sparse path."
            )

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
        @jax.named_scope("score")
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
        @jax.named_scope("update")
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
        @jax.named_scope("score")
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
        @jax.named_scope("update")
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

        @partial(jax.jit, donate_argnums=donate_argnums_on_accel(0, 1))
        @jax.named_scope("update")
        def apply_grads(trainable, opt_state, grads):
            with jax.named_scope("optim"):
                updates, opt_state = optimizer.update(grads, opt_state,
                                                      trainable)
                trainable = optax.apply_updates(trainable, updates)
            return trainable, opt_state, optax.global_norm(grads)

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
    # the three phases of RLTrainer.train() that are the sparse runtime's
    # own, and its evaluation hook
    # ------------------------------------------------------------------ #

    def _evaluate(self, step: int) -> dict:
        """`accuracy_func` before training and every `cfg.eval_steps`."""
        if self.accuracy_func is None:
            return {}
        if step == 0:
            return {"initial_accuracy": float(self.accuracy_func(self))}
        if self.cfg.eval_steps and step % self.cfg.eval_steps == 0:
            return {"eval_accuracy_new": float(self.accuracy_func(self))}
        return {}

    def _select(self, run: TrainRun, up: Update):
        """The dense selection (group advantage, keep-1-of-N), then the
        sparse filter (`grpo_r1_trainer.py:565-568`) with a lineage drop for
        every row it excludes, then the de-padding (`:571-582`) rounded
        onto the menu. An all-zero batch ends the update without a step."""
        tok = self.tokenizer
        pad_id, eos_id = tok.pad_token_id, tok.eos_token_id
        up.extra_metrics["eval_response_length"] = float(np.asarray(
            first_true_indices(jnp.asarray(up.responses) == pad_id)).mean())
        super()._select(run, up)
        scores = up.grpo_adv
        nz = np.where(scores != 0)[0]
        if self.lineage.enabled:
            # the paper's silent zero-advantage skip, made loud: one
            # drop event PER EXCLUDED ROW — the attribution the sparse
            # filter never had (every dropped row has exactly one
            # machine-readable drop_reason)
            for r in np.where(scores == 0)[0]:
                self.lineage.drop(
                    up.rollout_index, "sparse_zero_advantage",
                    row=int(r), step=self.state["global_step"],
                    raw_score=round(float(up.log_scores[r]), 6),
                )
        if len(nz) == 0:
            mean_raw_score = float(up.raw_scores.mean())
            print(f"[sparse-grpo] rollout {up.rollout_index}: all "
                  "advantages zero, skipping")
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
            # the rollout is consumed (state["rollouts"] stays advanced)
            # and so is one update of train()'s budget: a starved streak
            # must end with the budget, not wait for steps that never come
            return NoStep("sparse skip streak", counts=True)
        up.extra_metrics["sparse/kept_frac"] = len(nz) / max(up.batch_size, 1)
        up.span_args["kept_rows"] = len(nz)
        # the sample table and the lineage outcome follow the kept rows
        up.question_strings = [up.question_strings[i] for i in nz]
        up.decoded = [up.decoded[i] for i in nz]
        up.log_scores, up.grpo_adv = up.log_scores[nz], scores[nz]

        up.queries_rep = depad_queries(up.queries[nz], pad_id, self._len_menu)
        up.context_length = up.queries_rep.shape[1]
        responses = up.responses[nz]
        post = truncate_response(eos_id, pad_id, jnp.asarray(responses))
        resp_len = np.asarray(first_true_indices(post == pad_id))
        max_resp = min(
            round_up_to_menu(max(int(resp_len.max()), 1), self._len_menu),
            responses.shape[1])
        up.responses = responses[:, :max_resp]
        if up.captured_lp is not None:
            up.captured_lp = up.captured_lp[nz][:, :max_resp]
        up.qr_len = up.context_length + resp_len  # real tokens of each row

    def _bucket_len(self, up: Update, rows) -> int:
        """Menu-rounded width of the bucket holding `rows` of `up.qr`."""
        blen = round_up_to_menu(int(up.qr_len[rows].max()), self._len_menu)
        blen = min(max(blen, up.context_length + 1), up.qr.shape[1])
        return self._sp_round_len(blen, up.qr.shape[1])

    def _score(self, run: TrainRun, up: Update):
        """Bucketed logprob pass under the forward token budget."""
        pad_id, context_length = self.tokenizer.pad_token_id, up.context_length
        qr = up.qr = np.concatenate([up.queries_rep, up.responses], axis=1)
        capture, ref_free = run.score_capture, self._ref_free
        logprobs = np.full(up.responses.shape, INVALID_LOGPROB, np.float32)
        ref_logprobs = logprobs.copy()
        if capture:
            # policy logprobs came from the sampler; buckets below only
            # run the ref forward (half the scoring work)
            logprobs = up.captured_lp.astype(np.float32)
        score_fn = (self._sp_score_fn() if self._sp_on()
                    else self._bucket_score_fn())
        # the single-model scorer branches to the SP variant when sp is on
        # (see RLTrainer._single_scorer_for for the ref-free/capture matrix)
        one_fn = self._single_scorer_for(capture)
        # ref-free + capture: zero scoring forwards (sampler-captured
        # policy logprobs, no reference model — the r1 setting)
        buckets = ([] if ref_free and capture
                   else create_batches(up.qr_len, self._forward_budget()))
        with self.timer.phase("logprob"):
            for idxs in buckets:
                blen = self._bucket_len(up, idxs)
                width = blen - context_length
                rows_b = round_up_to_menu(len(idxs), self._rows_menu)
                padded = jnp.asarray(pad_rows(
                    {"qr": qr[idxs][:, :blen]}, rows_b, {"qr": pad_id})["qr"])
                if ref_free:
                    lp = one_fn(self.params, padded, context_length)
                    logprobs[idxs, :width] = np.asarray(lp)[: len(idxs)]
                elif capture:
                    rlp = one_fn(self.ref_params, padded, context_length)
                    ref_logprobs[idxs, :width] = np.asarray(rlp)[: len(idxs)]
                else:
                    # (an expert model's chunk scorer appends its router
                    # sums; the sparse runtime logs no moe/* counters)
                    lp, rlp = score_fn(
                        self.params, self.ref_params, padded, context_length,
                    )[:2]
                    logprobs[idxs, :width] = np.asarray(lp)[: len(idxs)]
                    ref_logprobs[idxs, :width] = np.asarray(rlp)[: len(idxs)]
        # ref-free: ref == policy-old, every KL term and metric reads 0
        up.logprobs = logprobs
        up.ref_logprobs = logprobs.copy() if ref_free else ref_logprobs

    def _update(self, run: TrainRun, up: Update):
        """Bucketed update under the backward budget (4·2316): each
        bucket's gradient scaled `rows / minibatch_rows`, one optimizer
        step a minibatch (`grpo_r1_trainer.py:786-791`)."""
        cfg, batch, context_length = self.cfg, up.batch, up.context_length
        pad_id = self.tokenizer.pad_token_id
        grad_fn = self._sp_grad_fn() if self._sp_on() else self._bucket_grad_fn()
        apply_fn = self._apply_grads_fn()
        backward_budget = min(BACKWARD_BUDGET, self._forward_budget() // 2)
        trainable, frozen = self._partition(
            self._train_tree(self.params, self.value_params)
        )
        all_stats, norms = [], []
        local_bs = len(up.qr)
        mini = min(cfg.local_mini_batch_size, local_bs)
        up.lr_step = self.state["opt_steps"]
        # saved bytes are sized from the WIDEST backward bucket (rows
        # bounded by the backward budget at the max bucket width)
        up.logits_rows = max(1, backward_budget // up.qr.shape[1])
        fill = {"query_responses": pad_id, "responses": pad_id,
                "logprobs": INVALID_LOGPROB, "ref_logprobs": INVALID_LOGPROB,
                "padding_mask": True}
        with self.timer.phase("update"):
            for epoch in range(cfg.num_ppo_epochs):
                self.key, pk = jax.random.split(self.key)
                perm = np.asarray(jax.random.permutation(pk, local_bs))
                for start in range(0, local_bs, mini):
                    mb_inds = perm[start : start + mini]
                    grads_acc = None
                    for bidx in create_batches(up.qr_len[mb_inds],
                                               backward_budget):
                        sel = mb_inds[bidx]
                        blen = self._bucket_len(up, sel)
                        width = blen - context_length
                        mb = pad_rows(
                            {k: batch[k][sel][:, :(blen if k == "query_responses"
                                                   else width)]
                             for k in (*fill, "advantages")},
                            round_up_to_menu(len(sel), self._rows_menu), fill,
                        )
                        # scale by REAL rows (`grpo_r1_trainer.py:786-788`)
                        grads, aux = grad_fn(
                            trainable, frozen,
                            {k: jnp.asarray(v) for k, v in mb.items()},
                            context_length,
                            jnp.float32(len(sel) / len(mb_inds)),
                        )
                        grads_acc = grads if grads_acc is None else jax.tree.map(
                            jnp.add, grads_acc, grads
                        )
                        all_stats.append(aux)
                    trainable, self.opt_state, gnorm = apply_fn(
                        trainable, self.opt_state, grads_acc
                    )
                    norms.append(gnorm)
                    self.state["opt_steps"] += 1
            self.params = self._combine(trainable, frozen)["policy"]
            up.all_stats, norms = jax.device_get((all_stats, norms))
        up.agg = {
            k: float(np.mean([s[k] for s in up.all_stats]))
            for k in (up.all_stats[0] if up.all_stats else {})
        }
        # the step's gradient norm (what the sentinel and
        # policy/grad_norm_new read), a mean over its optimizer steps
        up.agg["grad_norm"] = float(np.mean(norms))
