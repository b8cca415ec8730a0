"""Serving engine: continuous-batching decode over the radix prefix cache.

The rollout scheduler (`sampler/paged/scheduler.py`) serves a CLOSED
queue — every prompt is known up front and the call returns when the
queue drains. This module reshapes the same machinery into an OPEN
server loop for interactive traffic. Since the decode-session refactor
the engine owns NO decode loop of its own: it constructs a
`sampler.paged.session.DecodeSession` in per-row mode and every request
flows through the same jitted chunk body, admission path, and release
path the rollout scheduler drives — one scheduler code path for gateway
streams and rollout (test-pinned bit-identical to the pre-session
engine). What remains here is open-loop POLICY:

  * SLO-aware shed-vs-admit: `submit()` rejects when the pending queue
    is full or when the LatencyHub's p95 TTFT is over the
    `slo_ttft_p95` rule's warn threshold (telemetry/health.py) — the
    same rule the health monitor pages on, so the gateway starts
    shedding exactly when the alert would fire.
  * Request lifecycle: per-request sampling params ride the session's
    traced [R] arrays (one compiled decode program serves any mix of
    greedy and sampled requests), tokens stream out through per-request
    queues, cancelled rows are reaped with their pages freed.
  * Composition inherited from the session: `prefill_chunk > 0` chunks
    long cold admissions so resident streams keep their inter-token
    cadence while a long prompt prefills; `spec_k > 0` runs draft+verify
    chunks (greedy requests with the full token budget only — the
    accept rule compiles against static sampling params; see
    `sampler.compose_check`).
  * A model that generates by diffusion over blocks (docs/BLOCKDIFF.md)
    goes through the same session, engine and gateway: a request also
    carries `denoising_steps` and `remasking`, a beat delivers the tokens a
    row has FINAL (0 to `sync_every x block_length` of them), budgets and
    EOS hold at a block's granularity (the row ends with the block that
    holds EOS or reaches `max_tokens`; what lies past either is CUT before
    emission), and a request keeps, a token, the denoise step of its block
    that unmasked it.

Admission through one `RadixCache` kept alive for the engine's whole
lifetime (params are fixed, so cached KV never goes stale): a request's
matched prefix installs refcount-shared pages with zero prefill FLOPs
and only the suffix runs through `suffix_logits`. Cold admissions take
the same path with an empty match — the suffix forward starts at the
first real token, so pad KV is never written (and never read).

The beat is pipelined one deep (docs/SERVING.md "The beat"): the loop
dispatches decode chunk k+1 BEFORE it waits for chunk k's report, so the
device runs one chunk into the next while the host streams tokens, releases
ended rows and admits requests; those decisions lag one beat and reach the
device behind the chunk in flight. No admission waits for its first token:
the token stays on the device and is streamed when the host, reading in the
device's own order, comes to it. Which session takes this is the session's
own `looks_ahead` (serving mode without speculation); a speculative engine
runs the same loop with nothing left in flight.

The loop thread's life is accounted for (`LOOP_PHASES`, one
`PhaseTimer.phase` each, and the session's own inside them): `metrics()`
exports the cumulative seconds with a request timeline as sums — queue
wait, and the lag from a first token being queued to the gateway having
flushed it (docs/SERVING.md "Engine loop account"). The account reaches
down to the request: every beat a request lives through after its first
token is booked to it (`_book`) with the seconds the loop stood waiting for
the device and the forwards of other requests the device ran before the
chunk, and a finished request is reduced into never-reset sums, once for
all requests and once for the slow tenth by its own seconds a token
(docs/SERVING.md "A request's account").

Threading: one background loop thread owns the session (carry, block
table, all device dispatch). `submit()` only appends to the pending
deque under `make_condition("serving.engine")`; the one extracted lock
edge is serving.engine -> telemetry.hist (the shed check reads hub
quantiles under the condition). Radix plan/insert run OUTSIDE the
condition, but "serving.engine" is still ranked above "serving.radix"
in LOCK_ORDER so a future admission that does hold both stays
deadlock-free by construction.
"""

from __future__ import annotations

import itertools
import math
import queue
import statistics
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Optional

import jax
import numpy as np

from nanorlhf_tpu.analysis.lockorder import make_condition
from nanorlhf_tpu.sampler.paged.session import (
    SESSION_PHASES, DecodeSession, FirstToken,
)
from nanorlhf_tpu.serving.radix import RadixCache, prompt_key
from nanorlhf_tpu.telemetry.health import SLO_RULES
from nanorlhf_tpu.utils.profiling import PhaseTimer

# what the loop thread does, one `PhaseTimer.phase` each: between them they
# cover the loop's lifetime (docs/SERVING.md "engine loop account")
LOOP_PHASES = ("wait", "admit", "reap", "step", "deliver")

# a finished request's account as `metrics()` sums it, for all requests and
# for the slow tenth (`serving/all_<name>`, `serving/slow_<name>`), beside
# `requests` (1) and `tpot_s_sum` (its seconds a token): the seconds of what
# the request holds under these names
REQUEST_SUMS = ("decode_s", "wait_s", "loaded_s")
# a request is slow at or above a running estimate of this quantile of the
# finished requests' seconds a token: the median of the first `SLOW_WARMUP`
# (none of which is classed), then for every request up by `SLOW_STEP` x 0.9
# (in the logarithm) if it was slow and down by `SLOW_STEP` x 0.1 if not, so
# a tenth are slow whatever came before and however the load drifts
SLOW_QUANTILE = 0.90
SLOW_WARMUP = 20
SLOW_STEP = 0.1
RECENT_REQUESTS = 64    # finished requests `snapshot()` keeps


@dataclass
class ServingRequest:
    """One in-flight request: the stream side reads `out_q` until the
    `None` sentinel (the emitted stream INCLUDES the EOS token when one
    fired)."""
    request_id: int
    tokens: np.ndarray            # real token ids, un-padded
    temperature: float
    top_p: float
    greedy: bool
    max_tokens: int
    t_submit: float
    t_first_token: Optional[float] = None   # stamped as token 0 is queued
    out_q: "queue.Queue" = field(default_factory=queue.Queue)
    n_emitted: int = 0
    cancelled: bool = False       # set by cancel(); loop reaps the row
    kelems: Optional[tuple] = None
    # generation by blocks (docs/BLOCKDIFF.md): the request's parameters,
    # and what it keeps of its generation: a token, the denoise step of its
    # block that unmasked it; the tokens past its EOS or budget, cut before
    # emission, and theirs (a replay of the block needs them: they were in
    # the block when the kept ones were unmasked)
    denoising_steps: Optional[int] = None
    remasking: Optional[str] = None
    n_seen: int = 0               # final tokens read off the reports
    ended: bool = False           # EOS or budget met: the rest is cut
    unmask_steps: list = field(default_factory=list)
    cut_tokens: list = field(default_factory=list)
    cut_unmask_steps: list = field(default_factory=list)
    # the request's account (`ServingEngine._book`), the loop thread's: the
    # row that served it, when its admission started and when its last token
    # was queued, and over the beats it lived through after its first token:
    # their count and seconds, the seconds of them the loop stood waiting for
    # the device, and the beats (with their seconds and the forwards) that
    # carried another request's admission forward or prefill piece
    row: Optional[int] = None
    t_admit: Optional[float] = None
    t_last_token: Optional[float] = None
    beats: int = 0
    decode_s: float = 0.0
    wait_s: float = 0.0
    loaded_beats: int = 0
    loaded_s: float = 0.0
    foreign_forwards: int = 0


class ServingEngine:
    """Open-loop continuous batching over the radix prefix cache.

    `prompt_len` / `max_new_tokens` fix the compiled shapes (prompts are
    left-padded to `prompt_len`; longer prompts are rejected at submit).
    `slo_warn_ttft_s=None` reads the warn threshold, quantile, and
    warmup from the `slo_ttft_p95` rule in telemetry.health.SLO_RULES.
    `prefill_chunk > 0` splits long cold admissions into that many
    prompt tokens per decode chunk; `spec_k > 0` turns on n-gram
    speculative decode (greedy, full-budget requests only)."""

    def __init__(self, params, config, *, eos_token_id, pad_token_id,
                 page_size=16, prompt_len=32, max_new_tokens=32, rows=2,
                 headroom=1.0, sync_every=4, max_queue=64, latency=None,
                 lora_scale=1.0, top_k=64, approx_top_k=True, seed=0,
                 slo_warn_ttft_s: Optional[float] = None,
                 prefill_chunk=0, spec_k=0, spec_ngram=3):
        self.params = params
        self.config = config
        self.eos_token_id = int(eos_token_id)
        self.pad_token_id = int(pad_token_id)
        self.page_size = int(page_size)
        self.prompt_len = int(prompt_len)
        self.max_new_tokens = int(max_new_tokens)
        self.rows = int(rows)
        self.sync_every = int(sync_every)
        self.max_queue = int(max_queue)
        self.prefill_chunk = int(prefill_chunk)
        self.spec_k = int(spec_k)
        self.block_length = int(config.block_length)

        rule = next(r for r in SLO_RULES if r.name == "slo_ttft_p95")
        self._slo_metric = rule.metric
        self._slo_q = rule.quantile
        self._slo_warmup = rule.warmup
        self._slo_warn = (rule.warn if slo_warn_ttft_s is None
                          else float(slo_warn_ttft_s))

        self._hub = latency if (latency is not None
                                and latency.enabled) else None

        self._radix = RadixCache(headroom=headroom)
        # the session sizes the pool (rows * nb + radix headroom), resets
        # the tree ONCE here, and keeps it warm for the engine's lifetime
        self._sess = DecodeSession(
            params, config, rows=self.rows, prompt_len=self.prompt_len,
            max_tokens=self.max_new_tokens, page_size=self.page_size,
            eos_token_id=self.eos_token_id, pad_token_id=self.pad_token_id,
            key=jax.random.PRNGKey(seed),
            admit_key=jax.random.PRNGKey(seed + 1),
            greedy=(self.spec_k > 0), top_k=int(top_k),
            approx_top_k=bool(approx_top_k), lora_scale=float(lora_scale),
            per_row=True, spec_k=self.spec_k, spec_ngram=int(spec_ngram),
            prefix_cache=self._radix, prefill_chunk=self.prefill_chunk,
            sync_every=self.sync_every, latency=self._hub)
        self.T_max = self._sess.T_max
        self.nb = self._sess.nb
        self.num_pages = self._sess.num_pages

        self._owner: list = [None] * self.rows   # row -> ServingRequest

        self._cond = make_condition("serving.engine")
        self._pending: deque = deque()
        self._n_active = 0
        self._running = True
        self._ids = itertools.count()
        self._counters = {"requests": 0, "admitted": 0, "shed": 0,
                          "completed": 0, "cancelled": 0,
                          # a block engine: tokens streamed, and tokens its
                          # rows unmasked past an EOS or a budget
                          "tokens_streamed": 0, "tokens_cut": 0}
        # per-cause shed counters (serving/shed_total{reason=...}):
        # pre-seeded so every reason exports a 0 row from the first
        # scrape — dashboards can alert on rate() without init gaps
        self._shed_reasons = {"queue_full": 0, "slo_ttft_p95": 0,
                              "closed": 0, "pool": 0, "disconnect": 0}
        # the request timeline as sums (seconds, observations): submit ->
        # admission starts; first token queued -> written to the socket
        self._timeline = {"serving/queue_wait_s_sum": 0.0,
                          "serving/queue_wait_s_count": 0,
                          "serving/first_token_lag_s_sum": 0.0,
                          "serving/first_token_lag_s_count": 0,
                          "serving/last_token_lag_s_sum": 0.0,
                          "serving/last_token_lag_s_count": 0}
        # finished requests of two tokens or more, reduced (`_reduce`)
        for kind in ("all", "slow"):
            self._timeline[f"serving/{kind}_requests"] = 0
            for name in ("tpot_s_sum",) + REQUEST_SUMS:
                self._timeline[f"serving/{kind}_{name}"] = 0.0
        # the slow tenth's threshold (seconds a token) and, until it is set,
        # the TPOTs it will be set from: the loop thread's
        self._slow_at: Optional[float] = None
        self._early_tpots: list = []
        self._recent: deque = deque(maxlen=RECENT_REQUESTS)
        self._timer = PhaseTimer(span_prefix="serving.", names=LOOP_PHASES)
        self._thread = threading.Thread(target=self._loop,
                                        name="serving-engine", daemon=True)
        self._thread.start()

    # ------------------------------------------------------------- #
    # client side
    # ------------------------------------------------------------- #

    def submit(self, tokens, *, temperature=1.0, top_p=1.0, greedy=False,
               max_tokens=None, denoising_steps=None, remasking=None):
        """Admission-controlled enqueue. Returns `(request, None)` or
        `(None, shed_reason)` — `"queue_full"` when the pending bound is
        hit, `"slo_ttft_p95"` when the hub's p95 TTFT is over the SLO
        warn threshold (past its warmup count). `denoising_steps` (1 to the
        block length; default: the block length, one token a denoise
        forward) and `remasking` (`sampler.blockdiff.REMASKING`) are a
        block model's alone."""
        toks = np.asarray(tokens, np.int32).ravel()
        if toks.size < 1 or toks.size > self.prompt_len:
            raise ValueError(
                f"prompt length {toks.size} outside [1, {self.prompt_len}]"
                " — the engine's compiled prompt shape is fixed")
        mx = self.max_new_tokens if max_tokens is None else int(max_tokens)
        mx = max(1, min(mx, self.max_new_tokens))
        if self.block_length:
            from nanorlhf_tpu.sampler.blockdiff import REMASKING

            if denoising_steps is None:
                denoising_steps = self.block_length
            if remasking is None:
                remasking = REMASKING[0]
            if (not 1 <= int(denoising_steps) <= self.block_length
                    or remasking not in REMASKING):
                raise ValueError(
                    f"denoising_steps={denoising_steps!r} outside [1, "
                    f"{self.block_length}] or remasking={remasking!r} not "
                    f"one of {REMASKING}")
        elif denoising_steps is not None or remasking is not None:
            raise ValueError(
                "denoising_steps / remasking on a model that does not "
                f"generate by blocks ({self.config.model_type})")
        if self.spec_k > 0 and (not greedy or mx != self.max_new_tokens):
            raise ValueError(
                "a spec-decode engine (spec_k > 0) serves greedy requests "
                "with the full token budget only: the verify/accept rule "
                "compiles against static sampling params — see "
                "sampler.compose_check")
        with self._cond:
            self._counters["requests"] += 1
            reason = self._shed_reason_locked()
            if reason is not None:
                self._counters["shed"] += 1
                self._shed_reasons[reason] = (
                    self._shed_reasons.get(reason, 0) + 1)
                return None, reason
            req = ServingRequest(
                request_id=next(self._ids), tokens=toks,
                temperature=float(temperature), top_p=float(top_p),
                greedy=bool(greedy), max_tokens=mx,
                t_submit=time.perf_counter(),
                denoising_steps=(None if denoising_steps is None
                                 else int(denoising_steps)),
                remasking=remasking)
            self._pending.append(req)
            self._cond.notify_all()
        return req, None

    def _shed_reason_locked(self) -> Optional[str]:
        if not self._running:
            return "closed"
        if len(self._pending) >= self.max_queue:
            return "queue_full"
        if (self._hub is not None
                and self._hub.count(self._slo_metric) >= self._slo_warmup
                and self._hub.quantile(self._slo_metric,
                                       self._slo_q) > self._slo_warn):
            return "slo_ttft_p95"
        return None

    def cancel(self, req: ServingRequest) -> None:
        """The client vanished mid-stream (`gw.disconnect`): stop decoding
        for this request and free its resources — a dead socket must not
        keep a row decoding or pin its KV pages. Still-pending requests
        are shed immediately (reason "disconnect"); an admitted row is
        reaped by the loop thread — which owns the session — on its next
        iteration, counting into `cancelled` (admitted == completed +
        cancelled at quiescence). Idempotent."""
        was_pending = False
        with self._cond:
            if req.cancelled:
                return
            req.cancelled = True
            try:
                self._pending.remove(req)
                was_pending = True
            except ValueError:
                pass
            if was_pending:
                self._counters["shed"] += 1
                self._shed_reasons["disconnect"] = (
                    self._shed_reasons.get("disconnect", 0) + 1)
            self._cond.notify_all()
        if was_pending:
            req.out_q.put(None)

    def stream(self, req: ServingRequest, timeout: float = 120.0):
        """Yield the request's tokens as they land; ends at the `None`
        sentinel (or on `timeout` seconds of silence)."""
        while True:
            try:
                tok = req.out_q.get(timeout=timeout)
            except queue.Empty:
                return
            if tok is None:
                return
            yield tok

    def first_token_sent(self, req: ServingRequest) -> None:
        """The gateway's streaming handler calls this once it has written
        and flushed the request's first token: the seconds since the loop
        queued that token are the way out through the handler thread."""
        self._token_sent("first", req.t_first_token, time.perf_counter())

    def last_token_sent(self, req: ServingRequest, t_sent: float) -> None:
        """`first_token_sent`'s twin for the tokens that make a TPOT: called
        once the stream has ended, with the instant (`time.perf_counter()`)
        at which the handler had written and flushed the request's last
        token, it counts the seconds from the loop's queueing that one."""
        self._token_sent("last", req.t_last_token, t_sent)

    def _token_sent(self, which: str, t_queued: float, t_sent: float) -> None:
        lag = t_sent - t_queued
        with self._cond:
            self._timeline[f"serving/{which}_token_lag_s_sum"] += lag
            self._timeline[f"serving/{which}_token_lag_s_count"] += 1

    # ------------------------------------------------------------- #
    # engine loop (single background thread owns the session)
    # ------------------------------------------------------------- #

    def _idle_locked(self) -> bool:
        return (self._running and not self._pending
                and self._n_active == 0 and not self._sess.unread())

    def _loop(self):
        """A beat: admit into free rows, reap cancels, dispatch the next
        chunk, then read and stream, in the device's own order, what came
        before it: the last chunk's report, then the first tokens of this
        beat's admissions (enqueued between the two chunks). A session that
        `looks_ahead` leaves the chunk just dispatched unread, so the device
        has it while the host is busy; nothing is dispatched once no row
        holds a request, and what is still in flight then is read out."""
        phase = self._timer.phase
        sess = self._sess
        while True:
            with self._cond:
                if self._idle_locked():
                    with phase("wait"):
                        while self._idle_locked():
                            self._cond.wait(0.05)
                if (not self._running and self._n_active == 0
                        and not self._pending and not sess.unread()):
                    break
                admits = []
                free_rows = [r for r in range(self.rows)
                             if self._owner[r] is None]
                while free_rows and self._pending:
                    admits.append((free_rows.pop(0),
                                   self._pending.popleft()))
                self._n_active += len(admits)
            for r, req in admits:
                with phase("admit", request=req.request_id, row=r):
                    self._admit(r, req)
            with phase("reap"):
                self._reap_cancelled()
            ahead = 0
            if any(o is not None for o in self._owner):
                with phase("step"):
                    sess.dispatch()
                ahead = int(sess.looks_ahead)
            while sess.unread() > ahead:
                with phase("step"):
                    got = sess.read()
                with phase("deliver"):
                    self._deliver(got)

    def _admit(self, r: int, req: ServingRequest):
        """The host half of an admission: the plan, and the forward
        enqueued. The first token comes through `_deliver` once the device
        has made it."""
        req.row, req.t_admit = r, time.perf_counter()
        queue_wait = req.t_admit - req.t_submit
        Tp = self.prompt_len
        n = int(req.tokens.size)
        pad_count = Tp - n
        toks_p = np.full(Tp, self.pad_token_id, np.int32)
        toks_p[pad_count:] = req.tokens
        mask = np.zeros(Tp, bool)
        mask[pad_count:] = True
        req.kelems = prompt_key(toks_p, mask)
        try:
            self._sess.admit(
                r, toks_p, mask, req.request_id, budget=req.max_tokens,
                temperature=req.temperature, top_p=req.top_p,
                greedy=req.greedy, t_start=req.t_submit,
                **({"denoising_steps": req.denoising_steps,
                    "remasking": req.remasking} if self.block_length else {}))
        except RuntimeError:
            # pool sizing makes this unreachable (rows*nb live refs max,
            # the rest evictable) — shed rather than crash if it fires
            with self._cond:
                self._counters["shed"] += 1
                self._shed_reasons["pool"] = (
                    self._shed_reasons.get("pool", 0) + 1)
                self._n_active -= 1
            req.out_q.put(None)
            return
        self._owner[r] = req
        with self._cond:
            self._counters["admitted"] += 1
            self._timeline["serving/queue_wait_s_sum"] += queue_wait
            self._timeline["serving/queue_wait_s_count"] += 1
        if self._hub is not None:
            self._hub.record("latency/queue_wait_s", queue_wait)

    def _reap_cancelled(self):
        """Loop-thread only: free rows whose owner was cancelled. The
        session forces the done flag (the jitted chunk then skips the
        row) and releases pages exactly as a completion would, so a
        disconnect can never leak what a completion would have freed."""
        for r in range(self.rows):
            req = self._owner[r]
            if req is None or not req.cancelled:
                continue
            self._sess.cancel_row(r)
            self._owner[r] = None
            req.out_q.put(None)
            with self._cond:
                self._counters["cancelled"] += 1
                self._n_active -= 1
                self._cond.notify_all()

    def _deliver(self, got):
        """Stream what the session read: an admission's first token, or a
        chunk's report. A report speaks only for the rows that still hold
        the request its chunk ran for (`BeatReport.current`), and a request
        takes nothing from a report before its first token: the device made
        that before the first chunk the row ran in, and the reads keep the
        device's order. A cancelled request takes nothing more, whatever
        its row went on to in a chunk already dispatched: the next beat's
        reap ends it, as `cancel` promises."""
        if isinstance(got, FirstToken):
            req = self._owner[got.row]
            if (req is not None and req.request_id == got.index
                    and not req.cancelled):
                req.t_first_token = req.t_last_token = time.perf_counter()
                req.out_q.put(got.token)
                req.n_emitted = 1
                # its first beat's report brings the wait since the report
                # before it, and this much of that came before the token
                req.wait_s = -got.wait_s
            return
        if self.block_length:
            return self._deliver_blocks(got)
        for r in np.flatnonzero(got.current):
            req = self._owner[r]
            if req is None or req.n_emitted == 0 or req.cancelled:
                continue
            self._book(req, got)
            n = int(got.n_gen[r])
            if n > req.n_emitted:
                req.t_last_token = time.perf_counter()
            for tok in got.new_tokens(r, req.n_emitted).tolist():
                req.out_q.put(tok)
            req.n_emitted = n
            if got.done[r]:
                self._finish(r, req, gen_tokens=(
                    got.new_tokens(r, 0) if self.spec_k > 0 else None))

    def _book(self, req, got):
        """Book the beat of report `got` to a request that had its first
        token before it: the report's period (the request's first such beat
        counts from its first token, which the host read after the report
        before this one), the seconds of it the loop stood waiting for the
        device (the first beat's likewise: `_deliver` took off what came
        before the token), and, if the device ran forwards of other
        requests before the chunk, the same period and their count. The
        request's own admission forward is not foreign to it: it stood
        before the first chunk the row ran in, which is the first beat
        booked here. (Not so where tokens come by blocks: a row has no token
        before a report brings it, so that beat is never booked.)"""
        first = req.beats == 0
        period = got.t - req.t_first_token if first else got.period_s
        own = 1 if first and not self.block_length else 0
        foreign = got.foreign - own
        req.beats += 1
        req.decode_s += period
        req.wait_s += got.wait_s
        if foreign > 0:
            req.loaded_beats += 1
            req.loaded_s += period
            req.foreign_forwards += foreign

    def _finish(self, r, req, gen_tokens=None, **counted):
        """Row `r`'s request is over: release the row, reduce its account
        and count it (`counted`: further counters and what each gains), then
        close its stream, so whoever reads the stream's end finds the request
        counted."""
        self._sess.release(r, gen_tokens=gen_tokens)
        self._owner[r] = None
        self._reduce(req)
        with self._cond:
            self._counters["completed"] += 1
            for name, n in counted.items():
                self._counters[name] += n
            self._n_active -= 1
            self._cond.notify_all()
        req.out_q.put(None)

    def _reduce(self, req):
        """A finished request into the sums of `metrics()` and the records
        of `snapshot()`. One of two tokens or more has a TPOT on this loop's
        clock, its seconds a token after the first: it goes to the hub
        (`latency/tpot_s`) and, with the request's account, to the sums of
        all requests; a request at or above the running `SLOW_QUANTILE`
        (as it stood before this request moved it) goes to the slow tenth's
        sums as well."""
        record = self._record(req, time.perf_counter())
        kinds, tokens = (), req.n_emitted - 1
        if tokens >= 1:
            tpot = (req.t_last_token - req.t_first_token) / tokens
            kinds = ("all", "slow") if self._is_slow(tpot) else ("all",)
            if self._hub is not None:
                self._hub.record("latency/tpot_s", tpot)
        with self._cond:
            for kind in kinds:
                self._timeline[f"serving/{kind}_requests"] += 1
                self._timeline[f"serving/{kind}_tpot_s_sum"] += tpot
                for name in REQUEST_SUMS:
                    self._timeline[f"serving/{kind}_{name}"] += record[name]
            self._recent.append(record)

    def _is_slow(self, tpot: float) -> bool:
        """Class one finished request's seconds a token against the running
        quantile, and move the quantile by it (see `SLOW_STEP`)."""
        if self._slow_at is None:
            self._early_tpots.append(tpot)
            if len(self._early_tpots) == SLOW_WARMUP:
                self._slow_at = statistics.median(self._early_tpots)
            return False
        slow = tpot >= self._slow_at
        self._slow_at *= math.exp(SLOW_STEP * (slow - (1 - SLOW_QUANTILE)))
        return slow

    @staticmethod
    def _record(req, t_finish) -> dict:
        """A finished request as `snapshot()["recent_requests"]` keeps it:
        who and where, its instants in seconds since its `submit()`, its
        tokens, and its account."""
        return {
            "request_id": req.request_id, "row": req.row,
            **{name: None if t is None else t - req.t_submit
               for name, t in (("t_admit", req.t_admit),
                               ("t_first_token", req.t_first_token),
                               ("t_last_token", req.t_last_token),
                               ("t_finish", t_finish))},
            "n_emitted": req.n_emitted,
            **{name: getattr(req, name)
               for name in ("beats", "loaded_beats", "foreign_forwards")
               + REQUEST_SUMS}}

    def _deliver_blocks(self, got):
        """`_deliver` for a model that generates by blocks: a report brings
        the tokens a row has FINAL since the last one, with the denoise
        step that unmasked each. They stream until the request's EOS or
        budget; the rest of the row's last block is cut (and kept, for a
        replay). The row ends when the session says so: at the commit of
        the block that held the EOS or met the budget."""
        pending = self._sess.pending_rows()
        for r in np.flatnonzero(got.current):
            req = self._owner[r]
            # (a row between two pieces of its prompt is parked done; the
            # install changes its occupant, so no earlier flight speaks for it)
            if req is None or req.cancelled or r in pending:
                continue
            if req.n_emitted and not req.ended:     # from its first token
                self._book(req, got)                # to its last
            toks = got.new_tokens(r, req.n_seen).tolist()
            steps = got.new_steps(r, req.n_seen).tolist()
            req.n_seen = int(got.n_gen[r])
            now = time.perf_counter()
            for tok, step in zip(toks, steps):
                if req.ended:
                    req.cut_tokens.append(tok)
                    req.cut_unmask_steps.append(step)
                    continue
                req.t_last_token = now
                if req.n_emitted == 0:
                    req.t_first_token = now
                req.unmask_steps.append(step)
                req.out_q.put(tok)
                req.n_emitted += 1
                req.ended = (tok == self.eos_token_id
                             or req.n_emitted >= req.max_tokens)
            if got.done[r]:
                self._finish(r, req, tokens_streamed=req.n_emitted,
                             tokens_cut=len(req.cut_tokens))

    # ------------------------------------------------------------- #
    # observability
    # ------------------------------------------------------------- #

    def queue_depth(self) -> int:
        """Live pending-queue length — the autoscaler's leading-indicator
        input (loadgen/autoscaler.py `queue_high`): the queue fills
        before p95 TTFT degrades enough to flip an SLO rule."""
        with self._cond:
            return len(self._pending)

    def metrics(self) -> dict:
        """Flat scalar row for /metrics — the serving/* registry keys
        (METRICS.md) plus the pool's live shared-page gauge."""
        with self._cond:
            c = dict(self._counters)
            reasons = dict(self._shed_reasons)
            timeline = dict(self._timeline)
            pending = len(self._pending)
            active = self._n_active
        snap = self._radix.snapshot()
        rows = {
            "serving/requests": c["requests"],
            "serving/admitted": c["admitted"],
            "serving/shed": c["shed"],
            "serving/completed": c["completed"],
            "serving/cancelled": c["cancelled"],
            "serving/pending": pending,
            "serving/active": active,
            "serving/prefix_hit_tokens": snap["hit_tokens"],
            "serving/prefix_hit_frac": snap["hit_frac"],
            "serving/cow_splits": snap["cow_splits"],
            "serving/evicted_pages": snap["evicted_pages"],
            "serving/prefill_token_dispatch": self._sess.dispatch_tokens,
            "serving/attn_live_pages": self._sess.attn_live_pages,
            "serving/attn_table_pages": self._sess.attn_table_pages,
            "serving/attn_in_place": self._sess.attn_in_place,
            "serving/paged_items": self._sess.paged_items,
            "serving/paged_short_items": self._sess.paged_short_items,
            "serving/prefill_pieces": self._sess.prefill_pieces,
            "serving/prefill_read_in_place": self._sess.prefill_read_in_place,
            "serving/kv_write_by_page": self._sess.kv_write_by_page,
            "serving/kv_write_live_rows": self._sess.kv_write_live_rows,
            "serving/layer_kernels_in_place":
                self._sess.layer_kernels_in_place,
            "serving/qkv_kernels_in_place": self._sess.qkv_kernels_in_place,
            "serving/pool_donated": self._sess.pool_donated,
            "serving/kv_bytes_per_token": self._sess.kv_bytes_per_token,
            "serving/latent_cache": self._sess.latent_cache,
            "serving/loop_passes_per_token": self._sess.config.loop_passes,
            "serving/cache_layers": self._sess.config.cache_layers,
            "serving/pool_reserved_slots": self._sess.pool_reserved_slots,
            "serving/pool_live_slots": self._sess.global_slots_read,
            # a page pool of two kinds (docs/SWA.md): 0 window layers and an
            # empty window pool for every model without them
            "serving/window_layers": self._sess.window_layers,
            "serving/kv_bytes_per_token_global":
                self._sess.kv_bytes_per_token_global,
            "serving/kv_bytes_per_token_window":
                self._sess.kv_bytes_per_token_window,
            "serving/pool_pages_global": self._sess.num_pages,
            "serving/pool_pages_window": self._sess.num_pages_window,
            "serving/window_pages_reused": self._sess.window_pages_reused,
            "serving/window_pages_reused_in_decode":
                self._sess.window_pages_reused_in_decode,
            "serving/rows_past_window": self._sess.rows_past_window,
            "serving/live_row_steps": self._sess.live_row_steps,
            "serving/global_slots_read": self._sess.global_slots_read,
            "serving/window_slots_read": self._sess.window_slots_read,
            # a state that is not a page (docs/STATE.md): 0 layers and bytes
            # for every model without conv layers
            "serving/state_layers": self._sess.state_layers,
            "serving/page_layers": self._sess.config.page_layers,
            "serving/state_bytes_per_row": self._sess.state_bytes_per_row,
            # what the live rows held of each, summed over the decode steps
            # so far: a state a row, a page slot a token inside the bounds
            "serving/state_live_bytes": (self._sess.live_row_steps
                                         * self._sess.state_bytes_per_row),
            "serving/page_live_bytes": (
                self._sess.global_slots_read
                * self._sess.kv_bytes_per_token_global
                + self._sess.window_slots_read
                * self._sess.kv_bytes_per_token_window),
            "serving/state_resets": self._sess.state_resets,
            "serving/state_piece_carries": self._sess.state_piece_carries,
            "serving/state_tokens": self._sess.state_tokens,
            "serving/sparse_rows": self._sess.sparse_rows,
            "serving/sparse_slots_read": self._sess.sparse_slots_read,
            "serving/sparse_slots_held": self._sess.sparse_slots_held,
            "serving/select_rows_run": self._sess.select_rows_run,
            "serving/select_rows_resident": self._sess.select_rows_resident,
            "serving/decode_steps": self._sess.iterations(),
            "serving/held_experts_hit": self._sess.held_experts_hit,
            # the rows (generation by blocks: positions) the sampler ran
            # over, and those a step has: the needed ones are gathered
            # into an eighth, a quarter, a half or all of them
            # (docs/PAGED_CACHE.md "The rows a step scores")
            "serving/sample_rows": self._sess.sample_rows,
            "serving/sample_slots": self._sess.sample_slots,
            # static, a tuple (no Prometheus series): the sizes among them
            # whose sampler takes its candidates by selection
            # (`sampler._PICK_ROWS`)
            "serving/sample_pick_sizes": self._sess.sample_pick_sizes,
            "pages/shared": snap["shared_pages"],
        }
        if self.block_length:
            # generation by blocks (docs/BLOCKDIFF.md): the carry's own
            # counts as the last read brought them, the prompts' tails, and
            # this loop's account of what it streamed and cut (completed
            # requests': `tokens_unmasked` = streamed + cut once none runs)
            live, commit, unmasked, closed, _ = (
                int(c) for c in self._sess.block_counts)
            rows.update({
                "serving/block_length": self.block_length,
                "serving/block_forwards": live,
                "serving/commit_forwards": commit,
                "serving/tokens_unmasked": unmasked,
                "serving/blocks_done": closed,
                "serving/prompt_tail_tokens": self._sess.prompt_tail_tokens,
                "serving/tokens_streamed": c["tokens_streamed"],
                "serving/tokens_cut": c["tokens_cut"],
            })
        for reason, n in sorted(reasons.items()):
            rows[f'serving/shed_total{{reason="{reason}"}}'] = n
        # the loop account and the request timeline: cumulative seconds,
        # read from the loop thread's timers (pre-seeded: no key comes or
        # goes under this thread)
        for name in LOOP_PHASES:
            rows[f"serving/loop_{name}_s"] = self._timer.cumulative[name]
        # a beat is a decode chunk dispatched (the loop's step span is also
        # entered to read: once a report, once a first token)
        rows["serving/loop_beats"] = (
            self._sess.timer.cumulative_counts["dispatch"])
        rows["serving/beats_overlapped"] = self._sess.beats_overlapped
        rows["serving/first_tokens_deferred"] = (
            self._sess.first_tokens_deferred)
        # the beats by kind (a loaded beat carried an admission forward or a
        # prefill piece before its chunk), as the session read them
        rows["serving/beats_clean"] = self._sess.beats_clean
        rows["serving/beats_loaded"] = self._sess.beats_loaded
        rows["serving/beat_clean_s"] = self._sess.beat_clean_s
        rows["serving/beat_loaded_s"] = self._sess.beat_loaded_s
        rows["serving/foreign_forwards"] = self._sess.foreign_forwards
        for name in SESSION_PHASES:
            rows[f"serving/session_{name}_s"] = (
                self._sess.timer.cumulative[name])
        rows.update(timeline)
        return rows

    def snapshot(self) -> dict:
        """JSON-able /statusz section: engine shape + live occupancy +
        the radix tree's own snapshot under `prefix_cache` + the decode
        session's row/backlog/feature view under `session` + the seconds a
        token at which a request now counts as slow under `slow_at_s` + the
        last finished requests' records under `recent_requests`, oldest
        first."""
        with self._cond:
            c = dict(self._counters)
            reasons = dict(self._shed_reasons)
            pending = len(self._pending)
            active = self._n_active
            recent = list(self._recent)
        return {
            "rows": self.rows,
            "active": active,
            "pending": pending,
            "prompt_len": self.prompt_len,
            "max_new_tokens": self.max_new_tokens,
            "page_size": self.page_size,
            "num_pages": self.num_pages,
            "counters": c,
            "shed_reasons": reasons,
            "prefill_token_dispatch": self._sess.dispatch_tokens,
            "slo": {"rule": "slo_ttft_p95", "warn_s": self._slo_warn,
                    "quantile": self._slo_q, "warmup": self._slo_warmup},
            "prefix_cache": self._radix.snapshot(),
            "session": self._sess.status(),
            "slow_at_s": self._slow_at,
            "recent_requests": recent,
        }

    @property
    def radix(self) -> RadixCache:
        return self._radix

    @property
    def session(self) -> DecodeSession:
        return self._sess

    def close(self, timeout: float = 30.0) -> None:
        """Stop admitting, drain active rows, shed the pending queue
        (each pending request's stream ends at the sentinel), join the
        loop thread. Idempotent."""
        with self._cond:
            if not self._running and self._thread is None:
                return
            self._running = False
            pending = list(self._pending)
            self._pending.clear()
            self._counters["shed"] += len(pending)
            self._shed_reasons["closed"] = (
                self._shed_reasons.get("closed", 0) + len(pending))
            self._cond.notify_all()
        for req in pending:
            req.out_q.put(None)
        if self._thread is not None:
            self._thread.join(timeout=timeout)
            self._thread = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
