"""Tracing / profiling utilities (SURVEY.md §5.1, docs/OBSERVABILITY.md).

The reference's observability is ad-hoc: an unused memory_profiler import, a
commented-out CUDA memory recorder, and one wall-clock print per update
(`/root/reference/GRPO/grpo_trainer.py:57,469,726`). The TPU-native
equivalents:

- `PhaseTimer`: per-phase wall-clock split (rollout / reward / logprob /
  update) the reference only has implicitly — `block_until_ready` at phase
  end so device async dispatch doesn't lie about where the time goes. Timing
  uses `time.perf_counter()` (monotonic): wall-clock `time.time()` jumps
  under NTP steps, which corrupts phase splits and everything downstream of
  them (the cumulative MFU accounting integrates these numbers over a run).
  Every phase is also a `jax.profiler.TraceAnnotation` (the profiler's own
  clock: `trainer.<phase>`, `serving.<phase>`, `session.<phase>`), and with a
  telemetry.SpanTracer attached a trace span on the calling thread's track.
- `DEVICE_SCOPES`: the one vocabulary of `jax.named_scope` names the jitted
  programs carry, so that a profiler trace says what DEVICE time was spent
  on (the host half is `PhaseTimer`'s).
- `trace_profile`: a `jax.profiler` trace context writing a TensorBoard-
  loadable profile (XLA op breakdown, HBM usage) to a directory; start/stop
  stay balanced on exception, so a failed step doesn't wedge the profiler
  for the rest of the process.
- `ProfileWindow`: cfg-driven windowed profiling — the trainer polls it each
  update, and it wraps `trace_profile` around exactly the configured steps
  (`profile_at_step`/`profile_num_steps`) or around a window requested
  on-demand by touching a trigger file. Whole-run always-on profiling is
  useless at scale (GBs of XLA trace per minute); a 1–2 step window at a
  chosen step is what actually gets loaded into TensorBoard.
"""

from __future__ import annotations

import contextlib
import os
import time
from typing import Optional

import jax


# The names of the device's time. Every jitted body opens with its program's
# scope and the model's parts carry theirs, all through `jax.named_scope`
# directly: metadata of the compiled program (`op_name`), no operation, no
# option. A trace taken with `ProfileWindow` / `touch PROFILE` shows them in
# TensorBoard's op profile and trace viewer; `benchmark/harness/
# scope_trace.py` reduces them to seconds by scope (docs/OBSERVABILITY.md
# section 4, which also has the warm-cache trap).
#
# program level, at the top of a jitted body:
#   prefill   the one-jit rollout's prompt forward (`sampler._prefill_state`),
#             an admission's forward (`_admit_one`, `radix.suffix_logits`),
#             a prefill piece of a chunked admission (`_prefill_chunk_fwd`)
#   decode    a decode step (`sampler._decode_body`, every read extent;
#             `session._session_decode_body`)
#   verify    a speculative step (`speculative._draft_fn` + `_verify_fn`)
#   install   what an admission does beside its forward (`_install_row`,
#             `_first_token`, `_admit_sample`, `_beat_report`, `_end_row`,
#             page alloc / release, `radix.copy_page`)
#   score     the trainer's no-gradient forwards (policy, reference, value)
#   update    `update_minibatch` and its kin; `loss` and `optim` inside it
#   sync      the weight hand-off to the rollout's copy
# layer level (`core/model.py`, `core/mla.py`, the samplers, `ops/`):
#   embed, norm, attn (attn.qkv: projections + rotary; attn.write: the new
#   tokens' K/V into the cache; attn.read: QK^T, softmax, PV or the kernel;
#   attn.out), mlp (the residual add inside), head (final norm + unembedding),
#   sample, logprob.
# The older families stay INSIDE these, innermost around their kernels, whose
# custom calls take their names from them: `attn.global` / `attn.window`
# (a pattern model's read, in `attn`), `mla.*` (in `attn`), `moe.*` (in `mlp`).
# `attn.paged_flash` sits inside those two around ONE kernel, the T > 1 read
# of a prefill piece over the pages in place (ops/paged_prefill_attention):
# a custom call named `attn.global*` / `attn.window*` is a DECODE read to
# the benchmark's trace reader (harness/attn_trace.py), and this one must
# not be, whatever names it (its jitted wrapper today).
# `attn.gate` (docs/AFMOE.md) is the product of the attention's output with
# the sigmoid of the gate's projection, between the read and `attn.out`; the
# projection itself is one of `attn.qkv`'s, and the two branch norms of such
# a model run under `norm` inside `attn.out` and `mlp`.
# Generation by blocks (docs/BLOCKDIFF.md; the program scope of a block forward
# is `decode`): `attn.block` sits inside `attn.read` around the block read,
# a block's queries over the rows' live pages in place (the paged decode
# kernel's custom call takes its name from it) or its plain form; and
# `sample.unmask`, inside `sample`, is what follows the per-position draw:
# the choice of the positions to unmask, the block's and the row's state
# (the benchmark's reduction knows no `sample.` family and reads it as
# `sample`; the cell's own reader, layer_metrics/unmask_share.py, tells it apart).
# A conv layer's operator (docs/STATE.md) stands in the attention's slot and
# takes names of its family, so that a reader that keeps `attn.*` keeps it:
# `attn.conv` around `attn.conv.in` (the input projection and the gate),
# `attn.conv.mix` (the state's read and the taps), `attn.write` (the state's
# write) and `attn.conv.out`.
# A hybrid layer's state-space mixer (docs/SSM.md) runs BESIDE the attention,
# which keeps `attn.qkv/.write/.read/.out`: `attn.ssm` around `attn.ssm.in`
# (the input projection and its multipliers), `attn.ssm.conv` (the tail's
# read, the taps, `dt` and `A`), `attn.ssm.scan` (a piece's recurrence in
# chunks) or `attn.ssm.update` (a decode step's pass over the rows' state),
# `attn.ssm.gate` (the skip, the gate and the group norm), `attn.write` (both
# state leaves' write) and `attn.ssm.out`.
# A lightning layer (docs/SALA.md) keeps a state and no pages: `attn.linear`
# around `attn.linear.in` (the projections, the head norms, rotary),
# `attn.linear.scan` (a piece's recurrence in chunks) or `attn.linear.update`
# (a decode step's pass over the rows' state), `attn.linear.gate` (the head
# norm and the gate), `attn.write` (the state's write at a piece) and
# `attn.linear.out`. A sparse layer names its steps as a model of one kind
# does (no `attn.global` around them): `attn.qkv/.write/.read/.gate/.out`
# and, between the write and the read, `attn.compress` (the compressed keys
# this call completed, written beside K and V) and `attn.select` (the row's
# compressed keys read, the scores, the group sum, the pooling, the top-k; a
# decode step's work list); `attn.read` is the read of the chosen blocks, or
# the dense read of a call none of whose rows selects.
DEVICE_SCOPES = (
    "prefill", "decode", "verify", "install", "score", "update", "sync",
    "embed", "norm", "attn", "attn.qkv", "attn.write", "attn.read",
    "attn.out", "attn.gate", "attn.paged_flash", "attn.conv", "attn.conv.in",
    "attn.conv.mix", "attn.conv.out", "mlp", "head", "sample", "logprob",
    "loss", "optim", "attn.block", "sample.unmask", "attn.ssm",
    "attn.ssm.in", "attn.ssm.conv", "attn.ssm.scan", "attn.ssm.update",
    "attn.ssm.gate", "attn.ssm.out", "attn.linear", "attn.linear.in",
    "attn.linear.scan", "attn.linear.update", "attn.linear.gate",
    "attn.linear.out", "attn.compress", "attn.select",
)


class PhaseTimer:
    """Accumulates monotonic wall-clock per named phase; one line per update.

    `phase()` is the program's one span call: it also writes a
    `jax.profiler.TraceAnnotation` named `span_prefix + name`, so while a
    profiler session runs the phase sits in the profiler's own trace, on the
    clock of the device events (with no session that is a flag check).
    `meta` goes to that annotation and nowhere else: the profiler shows it
    as the event's arguments (`request=`, `row=`: the spans of one request
    share an identifier), and the block is handed the annotation, so what
    is known only at its end can follow (`set_metadata`).

    `names` pre-seeds the never-reset totals, so that a thread other than
    the one that runs the phases can read `cumulative` / `cumulative_counts`
    while they are being added to: no key comes or goes."""

    def __init__(self, tracer=None, span_prefix: str = "trainer.",
                 names: tuple = ()):
        self.totals: dict[str, float] = {}
        self.counts: dict[str, int] = {}
        # never reset: whole-run phase split (a reader across updates, e.g.
        # tests/test_one_loop.py, while the per-update summary() resets)
        self.cumulative: dict[str, float] = {n: 0.0 for n in names}
        self.cumulative_counts: dict[str, int] = {n: 0 for n in names}
        # optional telemetry.SpanTracer: phases double as trace spans
        self.tracer = tracer
        self.span_prefix = span_prefix

    @contextlib.contextmanager
    def phase(self, name: str, **meta):
        """Callers must block on the phase's outputs inside the block (e.g.
        `jax.block_until_ready(...)`) or async dispatch shifts time into the
        next phase."""
        label = self.span_prefix + name
        span = (
            self.tracer.span(label)
            if self.tracer is not None and self.tracer.enabled
            else contextlib.nullcontext()
        )
        t0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation(label, **meta) as ann, span:
                yield ann
        finally:
            dt = time.perf_counter() - t0
            self.totals[name] = self.totals.get(name, 0.0) + dt
            self.counts[name] = self.counts.get(name, 0) + 1
            self.cumulative[name] = self.cumulative.get(name, 0.0) + dt
            self.cumulative_counts[name] = (
                self.cumulative_counts.get(name, 0) + 1)

    def summary(self, reset: bool = True) -> dict:
        out = {f"time/{k}_s": v for k, v in self.totals.items()}
        if reset:
            self.totals, self.counts = {}, {}
        return out


@contextlib.contextmanager
def trace_profile(log_dir: str, enabled: bool = True):
    """jax.profiler trace scope: `with trace_profile('/tmp/prof'): step()`.

    The finally-stop keeps start/stop BALANCED when the profiled body
    raises — without it the process-global profiler stays active and every
    later start_trace in the process fails with "already started"."""
    if not enabled:
        yield
        return
    os.makedirs(log_dir, exist_ok=True)
    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


class ProfileWindow:
    """Windowed XLA profiling around exactly N configured updates.

    `poll(step)` is called at the TOP of each update with the 1-based step
    about to run: the window opens when `step == at_step` (or when the
    trigger file appears — `touch <output_dir>/PROFILE` on a live run) and
    closes after `num_steps` updates. `stop()` is idempotent and must be
    reachable from the trainer's close() path so an exception inside a
    profiled step still balances start/stop."""

    def __init__(self, log_dir: str, at_step: Optional[int] = None,
                 num_steps: int = 1, trigger_file: Optional[str] = None):
        self.log_dir = log_dir
        self.at_step = at_step
        self.num_steps = max(1, int(num_steps))
        self.trigger_file = trigger_file
        self.windows = 0          # completed windows (test/debug introspection)
        self._cm = None
        self._stop_at: Optional[int] = None
        self._armed = at_step is not None

    @property
    def active(self) -> bool:
        return self._cm is not None

    def _trigger_requested(self) -> bool:
        if not self.trigger_file or not os.path.exists(self.trigger_file):
            return False
        try:
            os.remove(self.trigger_file)  # consume the request
        except OSError:
            pass
        return True

    def poll(self, step: int) -> None:
        """Advance the window state machine for the update about to run."""
        if self.active and step >= self._stop_at:
            self.stop()
        if self.active:
            return
        start = self._armed and self.at_step is not None and step >= self.at_step
        if start:
            self._armed = False  # one cfg-driven window per run
        if start or self._trigger_requested():
            self._start(step)

    def _start(self, step: int) -> None:
        self._cm = trace_profile(self.log_dir)
        self._cm.__enter__()
        self._stop_at = step + self.num_steps
        print(f"[profile] XLA trace window open: steps {step}.."
              f"{self._stop_at - 1} -> {self.log_dir}")

    def stop(self) -> None:
        """Close an open window (idempotent; called from poll, the end of
        train(), and trainer.close())."""
        if self._cm is None:
            return
        cm, self._cm = self._cm, None
        self._stop_at = None
        try:
            cm.__exit__(None, None, None)
        finally:
            self.windows += 1
