"""RolloutOrchestrator — producer-thread rollout pipeline over the
version-tagged weight store and the bounded-staleness queue.

Generalizes the trainer's one-step `rollout_ahead` prefetch into a
configurable pipelined depth (PipelineRL / LlamaRL): a daemon producer
thread pulls prompt batches, grabs the LATEST published policy snapshot,
dispatches generation, blocks until the sample is device-ready, and
enqueues it version-tagged; the trainer consumes via `get()` and publishes
a new version after every optimizer update. With disaggregated rollout
devices the producer's generation executes on its own mesh WHILE the
consumer's scoring/update runs on the train mesh — and, unlike
rollout_ahead (whose prefetch lives inside one `train()` call), the
pipeline stays warm across `train(num_updates=1)` invocations.

Determinism contract: the producer is the ONLY consumer of the trainer's
data iterator, and generation PRNG keys come from the trainer's stateless
index-keyed stream (`fold_in(base, index)`), so the data and PRNG streams
are exactly the ones the synchronous trainer would see — the basis of the
checkpoint/resume journal (docs/ORCHESTRATOR.md).
"""

from __future__ import annotations

import contextlib
import threading

from nanorlhf_tpu.analysis.lockorder import make_lock
import time
from typing import Callable, Optional

import jax

from nanorlhf_tpu.orchestrator.sample_queue import (
    BoundedStalenessQueue,
    ProducerFailed,
    QueuedSample,
)
from nanorlhf_tpu.orchestrator.weight_store import VersionedWeightStore
from nanorlhf_tpu.telemetry.lineage import (
    segments_summary as _segments_summary,
    spec_summary as _spec_summary,
)


def _merge_intervals(ivs: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[tuple[float, float]] = []
    for t0, t1 in sorted(ivs):
        if out and t0 <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], t1))
        else:
            out.append((t0, t1))
    return out


def _sweep_overlap(gen, busy) -> float:
    """Σ |gen_i ∩ busy_j| over two merged, sorted interval lists."""
    overlap, j = 0.0, 0
    for g0, g1 in gen:
        while j < len(busy) and busy[j][1] <= g0:
            j += 1
        k = j
        while k < len(busy) and busy[k][0] < g1:
            overlap += min(g1, busy[k][1]) - max(g0, busy[k][0])
            k += 1
    return overlap


class OverlapMeter:
    """Rollout/train overlap accounting from measured wall-clock intervals.

    Producers record generation busy windows [dispatch, device-ready];
    the consumer records its own busy windows (everything between fetching
    a sample and asking for the next one — reward, scoring, update).
    `overlap_fraction()` = |union(gen) ∩ union(busy)| / |union(gen)|: the
    fraction of generation wall-clock that ran CONCURRENTLY with useful
    trainer work. 0 for the serial trainer (generation only runs while the
    consumer waits); → 1 when the pipeline fully hides generation.

    With N producers (the rollout fleet) the generation windows of
    different workers legitimately OVERLAP each other; the union in the
    numerator/denominator counts concurrently-generating wall-clock once,
    which is the honest "fraction of generation time hidden by training"
    reading. Producers tag their intervals with a per-producer `track`
    (fleet workers use their worker id); the default track 0 reproduces
    the single-producer behavior exactly.

    The metric is cumulative over the trainer's lifetime but the interval
    history is NOT: past `_COMPACT_AT` stored intervals the prefix below a
    watermark is folded into scalar accumulators (overlap seconds + gen
    seconds), so a long run pays O(_COMPACT_AT) per reading instead of an
    ever-growing sweep. The watermark is the minimum over every track (gen
    and busy) of that track's latest recorded end-time: each TRACK records
    chronologically non-overlapping windows (a worker's next dispatch
    starts after its previous sample is device-ready), so every FUTURE
    interval starts at or after its own track's last end ≥ the watermark —
    clipping both histories there makes the folded / retained
    decomposition exact, not an approximation. (Taking the min over the
    raw append order instead would be wrong with N producers: arrivals
    interleave, so the last-appended interval's end is not a lower bound
    on future starts.) A producer that leaves for good must be retired
    (`retire_gen_track`) or its stale watermark pins compaction forever.
    """

    _COMPACT_AT = 4096

    def __init__(self):
        self._lock = make_lock("orchestrator.meter")
        self._gen: list[tuple[float, float]] = []
        self._busy: list[tuple[float, float]] = []
        self._gen_ends: dict[int, float] = {}    # track -> latest end time
        self._busy_ends: dict[int, float] = {}
        self._overlap_acc = 0.0   # folded prefix: overlap seconds
        self._gen_acc = 0.0       # folded prefix: generation seconds

    def note_gen(self, t0: float, t1: float, track: int = 0) -> None:
        with self._lock:
            self._gen.append((t0, t1))
            self._gen_ends[track] = max(self._gen_ends.get(track, t1), t1)
            self._maybe_compact()

    def note_busy(self, t0: float, t1: float, track: int = 0) -> None:
        with self._lock:
            self._busy.append((t0, t1))
            self._busy_ends[track] = max(self._busy_ends.get(track, t1), t1)
            self._maybe_compact()

    def retire_gen_track(self, track: int) -> None:
        """A producer left the fleet for good: stop holding the compaction
        watermark down at its last recorded window."""
        with self._lock:
            self._gen_ends.pop(track, None)

    def _maybe_compact(self) -> None:
        # caller holds the lock
        if len(self._gen) + len(self._busy) < self._COMPACT_AT \
                or not self._gen or not self._busy:
            return
        if not self._gen_ends or not self._busy_ends:
            # every producing track on one side was retired while its
            # intervals are still retained (e.g. all fleet workers lost
            # before the degraded fallback records again): no watermark
            # exists, so skip — the next note_gen/note_busy re-adds a
            # track (whose windows start later in wall-clock) and
            # compaction resumes
            return
        cutoff = min(
            min(self._gen_ends.values()), min(self._busy_ends.values())
        )

        def clip(ivs):
            below, above = [], []
            for t0, t1 in ivs:
                if t1 <= cutoff:
                    below.append((t0, t1))
                elif t0 >= cutoff:
                    above.append((t0, t1))
                else:  # straddler: split exactly at the watermark
                    below.append((t0, cutoff))
                    above.append((cutoff, t1))
            return below, above

        gen_lo, gen_hi = clip(_merge_intervals(self._gen))
        busy_lo, busy_hi = clip(_merge_intervals(self._busy))
        self._overlap_acc += _sweep_overlap(gen_lo, busy_lo)
        self._gen_acc += sum(t1 - t0 for t0, t1 in gen_lo)
        self._gen, self._busy = gen_hi, busy_hi

    def overlap_fraction(self) -> float:
        with self._lock:
            gen = _merge_intervals(self._gen)
            busy = _merge_intervals(self._busy)
            overlap = self._overlap_acc + _sweep_overlap(gen, busy)
            total = self._gen_acc + sum(t1 - t0 for t0, t1 in gen)
        if total <= 0.0:
            return 0.0
        return min(1.0, max(0.0, overlap / total))


def note_ready_async(meter: OverlapMeter, payload, t0: float,
                     tracer=None, span_args: Optional[dict] = None) -> None:
    """Record [t0, device-ready] into `meter` without blocking the caller —
    a daemon waiter thread block_until_ready's the (async-dispatched)
    payload. Lets the synchronous RolloutStream report honest generation
    busy windows for the same overlap metric the orchestrator emits.

    With a telemetry.SpanTracer the same window is also recorded as a
    `rollout.generate` ASYNC trace event on the "rollout" track (explicit
    start/duration; async because rollout_ahead's prefetch makes
    consecutive windows overlap, which complete "X" spans on one track
    cannot express) — so serial / rollout_ahead runs show their generation
    lane in trace.json just like orchestrated runs do."""
    tp0 = tracer.now_us() if tracer is not None and tracer.enabled else None

    def _wait():
        try:
            jax.block_until_ready(payload)
        except Exception:
            return  # the consumer surfaces dispatch errors; meter stays silent
        meter.note_gen(t0, time.perf_counter())
        if tp0 is not None:
            args = span_args or {}
            tracer.add_async(
                "rollout.generate", tp0, tracer.now_us() - tp0,
                aid=args.get("rollout_index", id(payload)), track="rollout",
                **args,
            )

    threading.Thread(target=_wait, daemon=True,
                     name="rollout-ready-watch").start()


class RolloutOrchestrator:
    """Producer thread + version store + bounded-staleness queue.

    `dispatch_fn(index, params_tree) -> payload` pulls the next prompt
    batch, folds the generation key for `index`, and async-dispatches
    generation from `params_tree` (a published snapshot — never the live
    donated training tree). `initial_params` becomes version 0.
    """

    def __init__(
        self,
        dispatch_fn: Callable[[int, dict], dict],
        initial_params: dict,
        start_index: int = 0,
        max_staleness: int = 1,
        policy: str = "wait",
        meter: Optional[OverlapMeter] = None,
        restore: Optional[dict] = None,
        heartbeat: float = 30.0,
        faults=None,
        tracer=None,
        lineage=None,
        latency=None,
    ):
        self.store = VersionedWeightStore()
        self.store.publish(initial_params)  # version 0
        self.queue = BoundedStalenessQueue(
            max_staleness, policy, start_index=start_index, lineage=lineage,
            latency=latency,
        )
        if restore:
            self.queue.restore_counters(restore)
        self.meter = meter if meter is not None else OverlapMeter()
        self.max_staleness = max_staleness
        self._dispatch_fn = dispatch_fn
        self._next_index = start_index
        self._heartbeat = heartbeat
        self._faults = faults  # resilience.FaultInjector ("rollout.produce")
        # telemetry.SpanTracer: generation spans land on the producer
        # thread's own track — the trainer-vs-producer overlap picture
        self._tracer = tracer
        # telemetry.LineageLedger: per-index lease + generation provenance
        # (the single producer is "worker 0" with an implicit lease)
        self._lineage = lineage
        # telemetry.LatencyHub: generation-wall + TTFT histograms. The
        # monolithic sampler is one jit (prefill + while_loop), so the
        # first token is not separately observable without splitting the
        # compiled graph; dispatch→device-ready is recorded as the TTFT
        # UPPER BOUND (exact per-request TTFT comes from the paged
        # scheduler's admission stamps — docs/OBSERVABILITY.md §7).
        self._latency = latency
        self.producer_error: Optional[BaseException] = None
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._produce, daemon=True, name="rollout-producer"
        )
        self._thread.start()

    # ---------------------------------------------------------------- #
    # producer loop
    # ---------------------------------------------------------------- #

    def _produce(self):
        try:
            while not self._stop.is_set():
                idx = self._next_index
                if not self.queue.wait_to_produce(idx, self._stop):
                    break
                if self._faults is not None:
                    # resilience injection point — BEFORE the dispatch touches
                    # the data iterator, so a supervised restart redraws from
                    # an unburned cursor (docs/RESILIENCE.md)
                    self._faults.fire("rollout.produce")
                version, tree = self.store.latest()
                lin = self._lineage
                if lin is not None and lin.enabled:
                    # the single producer IS the lease grant: the dispatch
                    # below burns the data cursor + PRNG stream for `idx`
                    lin.lease(idx, worker_id=0, cursor=idx, length=1)
                tr = self._tracer
                span = (
                    # the producer is one long-lived thread, so the span
                    # lands on its own trace.json track — the generation
                    # lane of the producer/trainer overlap picture
                    tr.span("rollout.generate", rollout_index=idx,
                            policy_version=version)
                    if tr is not None and tr.enabled
                    else contextlib.nullcontext()
                )
                # monotonic: gen windows must share the consumer's busy-
                # window clock (perf_counter) or the overlap meter's
                # interval intersection silently goes to zero; wall clock
                # would also expose gen_s to NTP steps
                t0 = time.perf_counter()
                with span:
                    payload = self._dispatch_fn(idx, tree)
                    # block HERE (producer thread): the consumer receives
                    # device-ready samples, and [t0, t1] is the true
                    # generation busy window for the overlap meter
                    jax.block_until_ready(payload)
                t1 = time.perf_counter()
                self.meter.note_gen(t0, t1)
                if self._latency is not None and self._latency.enabled:
                    # one observation per generation event, so the TTFT
                    # sketch's _count stays joinable against the lineage
                    # ledger's generation-event count
                    self._latency.record("latency/generation_s", t1 - t0)
                    self._latency.record("latency/ttft_s", t1 - t0)
                if lin is not None and lin.enabled:
                    lin.generation(
                        idx, policy_version=version, worker_id=0,
                        gen_s=round(t1 - t0, 6),
                        spec=_spec_summary(payload),
                        segments=_segments_summary(payload),
                        swap_wait_s=payload.get("swap_wait_s"),
                    )
                self.queue.put(QueuedSample(idx, version, payload, t0, t1))
                if tr is not None and tr.enabled:
                    tr.counter("orchestrator/queue_depth", self.queue.depth())
                self._next_index += 1
        except BaseException as e:  # surfaces in the consumer's get()
            self.producer_error = e
            self.queue.fail(e)

    # ---------------------------------------------------------------- #
    # consumer API (the trainer)
    # ---------------------------------------------------------------- #

    @property
    def version(self) -> int:
        return self.store.version

    def get(self) -> QueuedSample:
        """Next sample — waits as long as the producer is making progress.

        No hard deadline: a cold-cache first generation can legitimately
        compile for minutes (a cold `grpo-1.5b-r512` cell sets up in 98-131 s,
        PERF.md section 6), so the wait only aborts when the producer
        thread is actually DEAD without having reported an error through
        `queue.fail()` (which covers every exception path in `_produce`).
        The heartbeat interval just bounds how often liveness is checked.
        A dead producer raises ProducerFailed (never a silent spin): the
        queue surfaces the stored terminal exception when one was reported,
        and a thread that died without reporting (e.g. killed at interpreter
        teardown before its except clause ran) raises it with whatever
        `producer_error` holds."""
        while True:
            try:
                return self.queue.get(timeout=self._heartbeat)
            except TimeoutError:
                if not self._thread.is_alive():
                    raise ProducerFailed(
                        "rollout producer thread died without reporting an "
                        "error through the queue"
                    ) from self.producer_error

    def producer_alive(self) -> bool:
        return self._thread.is_alive()

    def consumed_without_update(self) -> None:
        """A fetched sample was discarded without an optimizer update (a
        sentinel-quarantined batch): credit the producer gate so the
        pipeline doesn't deadlock waiting for a publish that never comes."""
        self.queue.credit_skip()

    def publish(self, tree: dict) -> int:
        """Publish a post-update policy snapshot; wakes the producer gate."""
        v = self.store.publish(tree)
        self.queue.advance_version(v)
        return v

    def stats(self) -> dict:
        # overlap lives on the meter (trainer reads meter.overlap_fraction()
        # directly) — recomputing the sweep here per update would be waste
        return {
            "queue_depth": self.queue.depth(),
            "dropped": self.queue.dropped,
            "staleness_counts": dict(self.queue.staleness_counts),
            # who-waits-on-whom (sample_queue.py): trainer starved vs
            # producer gated — the two numbers that say which side of the
            # pipeline is the bottleneck (docs/OBSERVABILITY.md)
            "consumer_wait_s": self.queue.consumer_wait_s,
            "producer_gate_wait_s": self.queue.producer_gate_wait_s,
        }

    def status_snapshot(self) -> dict:
        """/statusz seam (telemetry/exporter.py): queue counters + policy
        version, JSON-able and safe from any thread (single-producer
        pipelines have no fleet table)."""
        return {"queue": {**self.stats(), "version": self.version}}

    def journal(self) -> dict:
        """Checkpoint payload (trainer_state.json "orchestrator" key)."""
        return self.queue.journal()

    def close(self, join_timeout: float = 30.0) -> None:
        self._stop.set()
        self.queue.advance_version(self.queue.version)  # wake any waiter
        self._thread.join(timeout=join_timeout)
