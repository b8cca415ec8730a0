"""Telemetry subsystem (nanorlhf_tpu/telemetry/, docs/OBSERVABILITY.md):

- SpanTracer records cross-thread spans/counters into a bounded buffer +
  flight-recorder ring, disabled is a no-op, and the written trace.json
  passes the Chrome trace-event schema validator (the tier-1 CI gate);
- the flight recorder lands `blackbox_<step>.json` on a fault-injected
  sentinel trip, tagged with the quarantined rollout index;
- a 2-update orchestrated smoke train with telemetry on produces a
  Perfetto-loadable trace whose producer-thread generation spans overlap
  the trainer's update spans, and perf/mfu + perf/tokens_per_sec_update
  reach metrics.jsonl (the ISSUE-4 acceptance);
- ProfileWindow opens/closes the XLA profiler around exactly the
  configured updates (cfg knob + trigger file), and trace_profile stays
  start/stop-balanced when the profiled body raises;
- MetricsLogger rows stay pure scalars under perf/ keys and its atexit
  close barrier is registered/unregistered correctly.
"""

import json
import math
import os
import threading
import time

import numpy as np
import pytest

from nanorlhf_tpu.telemetry import (
    BACKEND_COMPILE_EVENT,
    RecompileCounter,
    SpanTracer,
    peak_flops_per_chip,
    recompile_counter,
    update_flops,
    validate_trace_events,
    validate_trace_file,
)
from nanorlhf_tpu.trainer import AlgoName
from nanorlhf_tpu.trainer.metrics import MetricsLogger
from nanorlhf_tpu.utils.profiling import PhaseTimer, ProfileWindow, trace_profile

from test_trainer_smoke import make_trainer


def _metric_rows(outdir):
    rows = []
    with open(outdir / "metrics.jsonl") as f:
        for line in f:
            r = json.loads(line)
            if "samples" not in r:
                rows.append(r)
    return rows


# ---------------------------------------------------------------------------
# SpanTracer units (jax-free)
# ---------------------------------------------------------------------------


def test_tracer_disabled_is_noop(tmp_path):
    tr = SpanTracer(enabled=False)
    with tr.span("x", step=1) as args:
        assert args == {}
    tr.add_complete("y", 0.0, 1.0)
    tr.instant("z")
    tr.counter("c", 3)
    assert tr.write_trace(str(tmp_path / "t.json")) is None
    assert tr.dump_blackbox(str(tmp_path), 0, "test") is None
    assert not (tmp_path / "t.json").exists()
    assert tr.dropped == 0


def test_spans_nest_and_validate(tmp_path):
    tr = SpanTracer(enabled=True)
    with tr.span("outer", step=1) as args:
        args["rollout_index"] = 7  # correlation id learned mid-span
        with tr.span("inner"):
            time.sleep(0.001)
    tr.instant("marker", verdict="spike")
    tr.counter("depth", 2)
    events = tr.trace_events()
    assert validate_trace_events(events) == []
    outer = [e for e in events if e.get("name") == "outer"]
    assert outer[0]["args"]["rollout_index"] == 7
    # thread-name metadata for the recording thread is present
    assert any(e["ph"] == "M" and e["name"] == "thread_name" for e in events)
    path = tr.write_trace(str(tmp_path / "trace.json"))
    assert validate_trace_file(path) == []
    payload = json.load(open(path))
    assert payload["otherData"]["spans_dropped"] == 0


def test_cross_thread_spans_get_distinct_tracks():
    tr = SpanTracer(enabled=True)

    def work():
        with tr.span("producer.work"):
            pass

    t = threading.Thread(target=work, name="fake-producer")
    t.start()
    t.join()
    with tr.span("trainer.work"):
        pass
    evs = [e for e in tr.trace_events() if e["ph"] == "X"]
    tids = {e["name"]: e["tid"] for e in evs}
    assert tids["producer.work"] != tids["trainer.work"]


def test_logical_tracks_and_counters():
    tr = SpanTracer(enabled=True)
    with tr.span("ckpt.save", track="ckpt", step=3):
        pass
    tr.counter("staleness", np.float32(1.0))  # numpy scalar coerced
    events = tr.trace_events()
    assert validate_trace_events(events) == []
    ckpt = next(e for e in events if e.get("name") == "ckpt.save")
    names = {e["args"]["name"] for e in events
             if e["ph"] == "M" and e["name"] == "thread_name"}
    assert "ckpt" in names and "counters" in names
    c = next(e for e in events if e["ph"] == "C")
    assert c["args"]["value"] == 1.0
    # logical-track tids are small synthetic ints, not thread idents
    assert ckpt["tid"] < 1000


def test_event_buffer_bounded_ring_keeps_recent():
    tr = SpanTracer(enabled=True, max_events=5, ring_len=3)
    for i in range(10):
        tr.add_complete(f"s{i}", float(i), 0.5)
    assert tr.dropped == 5
    assert len([e for e in tr.trace_events() if e["ph"] == "X"]) == 5
    ring = tr.snapshot_blackbox(0, "test")["spans"]
    assert [e["name"] for e in ring] == ["s7", "s8", "s9"]


def test_async_events_may_overlap_but_x_spans_may_not():
    tr = SpanTracer(enabled=True)
    # rollout_ahead readiness windows overlap — async b/e pairs are legal
    tr.add_async("rollout.generate", 0.0, 100.0, aid=0, track="rollout")
    tr.add_async("rollout.generate", 50.0, 100.0, aid=1, track="rollout")
    assert validate_trace_events(tr.trace_events()) == []
    # the same shape as complete "X" spans on one track is a violation
    bad = [
        {"name": "a", "ph": "X", "ts": 0.0, "dur": 100.0, "pid": 1, "tid": 1},
        {"name": "b", "ph": "X", "ts": 50.0, "dur": 100.0, "pid": 1, "tid": 1},
    ]
    assert any("partially overlaps" in e for e in validate_trace_events(bad))


def test_validator_catches_missing_keys_and_nan_durations():
    assert validate_trace_events([]) == ["traceEvents missing or empty"]
    errs = validate_trace_events([
        {"name": "no-keys", "ph": "X"},
        {"name": "nan-dur", "ph": "X", "ts": 0.0, "dur": float("nan"),
         "pid": 1, "tid": 1},
        {"name": "bad-ts", "ph": "i", "ts": float("inf"), "pid": 1, "tid": 1},
        {"name": "neg-dur", "ph": "X", "ts": 0.0, "dur": -1.0,
         "pid": 1, "tid": 1},
    ])
    assert len(errs) == 4


def test_blackbox_snapshot_carries_open_spans(tmp_path):
    tr = SpanTracer(enabled=True)
    entered = threading.Event()
    release = threading.Event()

    def stuck():
        with tr.span("rollout.generate", rollout_index=4):
            entered.set()
            release.wait(5.0)

    t = threading.Thread(target=stuck, name="rollout-producer")
    t.start()
    entered.wait(5.0)
    try:
        bb = tr.snapshot_blackbox(9, "producer_failure")
    finally:
        release.set()
        t.join()
    opened = [s for s in bb["open_spans"] if s["name"] == "rollout.generate"]
    assert opened and opened[0]["thread"] == "rollout-producer"
    assert opened[0]["args"]["rollout_index"] == 4
    path = tr.dump_blackbox(str(tmp_path), 9, "producer_failure",
                            extra={"error": "boom"})
    assert os.path.basename(path) == "blackbox_9.json"
    assert json.load(open(path))["extra"]["error"] == "boom"


def test_span_args_coerced_to_json_scalars(tmp_path):
    tr = SpanTracer(enabled=True)
    tr.add_complete("s", 0.0, 1.0, a=np.float32(2.5), b=float("nan"),
                    c=object(), d=None, e=True)
    ev = [e for e in tr.trace_events() if e["ph"] == "X"][0]
    assert ev["args"]["a"] == 2.5
    assert isinstance(ev["args"]["b"], str)  # non-finite → stringified
    assert isinstance(ev["args"]["c"], str)
    assert ev["args"]["d"] is None and ev["args"]["e"] is True
    # the written file is valid JSON end to end
    assert validate_trace_file(tr.write_trace(str(tmp_path / "t.json"))) == []


# ---------------------------------------------------------------------------
# MFU accounting + recompile counter
# ---------------------------------------------------------------------------


def test_update_flops_napkin_model():
    # forward-only tokens at 2N, trained tokens at 3·2N
    assert update_flops(10, decode_tokens=3) == 60.0
    assert update_flops(10, train_tokens=3) == 180.0
    assert update_flops(
        10, decode_tokens=1, prefill_tokens=2, score_tokens=3, train_tokens=4
    ) == (1 + 2 + 3) * 20.0 + 4 * 60.0


def test_peak_flops_lookup():
    v5p, known = peak_flops_per_chip("TPU v5p", "tpu")
    assert known and v5p == 459e12
    trillium, known = peak_flops_per_chip("TPU v6e", "tpu")
    assert known and trillium == 918e12
    v5e, known = peak_flops_per_chip("TPU v5 lite", "tpu")
    assert known and v5e == 197e12
    with pytest.raises(ValueError, match="TPU v99"):
        peak_flops_per_chip("TPU v99", "tpu")  # never a silent default
    cpu, known = peak_flops_per_chip("cpu", "cpu")
    assert not known and cpu > 0  # finite so the MFU series stays plottable


def test_recompile_counter_listener_and_singleton():
    c = RecompileCounter()
    c._on_event(BACKEND_COMPILE_EVENT, 1.5)
    c._on_event("/jax/some/other/event", 9.0)
    assert c.count == 1 and c.seconds == 1.5
    assert recompile_counter() is recompile_counter()  # process-global


def test_recompile_counter_sees_real_backend_compile():
    import jax
    import jax.numpy as jnp

    c = recompile_counter()
    before = c.count
    # a fresh traced constant → new cache key → a REAL backend compile
    # (in-memory jit cache and the persistent compile cache can't serve it)
    salt = float(np.random.default_rng().random())
    out = jax.jit(lambda x: x * salt)(jnp.ones((3,)))
    out.block_until_ready()
    assert c.count > before


# ---------------------------------------------------------------------------
# PhaseTimer + ProfileWindow + trace_profile
# ---------------------------------------------------------------------------


def test_phase_timer_monotonic_and_spans():
    tr = SpanTracer(enabled=True)
    timer = PhaseTimer(tracer=tr)
    with timer.phase("rollout"):
        time.sleep(0.002)
    s = timer.summary()
    assert s["time/rollout_s"] > 0
    assert timer.totals == {}  # summary resets per-update totals...
    assert timer.cumulative["rollout"] > 0  # ...but never the run totals
    names = [e["name"] for e in tr.trace_events() if e["ph"] == "X"]
    assert "trainer.rollout" in names


def test_trace_profile_balanced_on_exception(tmp_path):
    d1, d2 = str(tmp_path / "p1"), str(tmp_path / "p2")
    with pytest.raises(ValueError, match="boom"):
        with trace_profile(d1):
            raise ValueError("boom")
    assert os.path.isdir(d1)  # dir created even though the body raised
    # the profiler was stopped by the finally — a new trace can start
    with trace_profile(d2):
        pass
    assert os.path.isdir(d2)


def test_profile_window_cfg_step_and_trigger_file(tmp_path):
    trigger = str(tmp_path / "PROFILE")
    w = ProfileWindow(str(tmp_path / "prof"), at_step=2, num_steps=1,
                      trigger_file=trigger)
    w.poll(1)
    assert not w.active
    w.poll(2)
    assert w.active and os.path.isdir(str(tmp_path / "prof"))
    w.poll(3)
    assert not w.active and w.windows == 1
    w.poll(4)
    assert not w.active  # the cfg-driven window fires once per run
    # on-demand window: touching the trigger file opens one and consumes it
    open(trigger, "w").close()
    w.poll(5)
    assert w.active and not os.path.exists(trigger)
    w.stop()  # idempotent close (the trainer's close() path)
    w.stop()
    assert not w.active and w.windows == 2


# ---------------------------------------------------------------------------
# MetricsLogger satellites
# ---------------------------------------------------------------------------


def test_metrics_rows_stay_pure_scalars(tmp_path):
    lg = MetricsLogger(str(tmp_path), "jsonl")
    lg.log(1, 16, {
        "perf/mfu": np.float32(0.31),
        "perf/tokens_per_sec_update": np.float64(1234.5),
        "perf/recompiles": 3,
        "telemetry/spans_dropped": 0.0,
    })
    lg.close()
    rows = _metric_rows(tmp_path)
    assert len(rows) == 1
    for k, v in rows[0].items():
        assert isinstance(v, (int, float)), f"{k} is {type(v)}"
    assert rows[0]["perf/mfu"] == pytest.approx(0.31, rel=1e-6)


def test_metrics_logger_atexit_close_registered(tmp_path, monkeypatch):
    import atexit

    registered, unregistered = [], []
    monkeypatch.setattr(atexit, "register",
                        lambda fn, *a, **k: (registered.append(fn), fn)[1])
    monkeypatch.setattr(atexit, "unregister",
                        lambda fn: unregistered.append(fn))
    lg = MetricsLogger(str(tmp_path), "jsonl")
    # the abnormal-exit flush barrier is armed at construction
    assert len(registered) == 1 and registered[0].__self__ is lg
    lg.log(1, 1, {"a": 1.0})
    lg.close()
    assert unregistered, "close() must disarm the atexit barrier"
    lg.close()  # idempotent: handles already None
    assert _metric_rows(tmp_path)[0]["a"] == 1.0
    nosink = MetricsLogger(str(tmp_path), "none")
    assert len(registered) == 1  # nothing to flush → no barrier armed
    nosink.close()


# ---------------------------------------------------------------------------
# flight recorder via deterministic fault injection (ISSUE-4 satellite)
# ---------------------------------------------------------------------------


def test_flight_recorder_blackbox_on_sentinel_trip(tmp_path, monkeypatch):
    """NANORLHF_FAULT poisons update 2's observed stats → sentinel trip →
    the resilience layer dumps `blackbox_2.json` next to the checkpoint it
    rolls back to, with the tripped step's span carrying the quarantined
    rollout index."""
    monkeypatch.setenv("NANORLHF_FAULT", "update.step:at=2,action=nan")
    tr = make_trainer(AlgoName.REINFORCE, tmp_path, total_episodes=48,
                      telemetry=True)
    state = tr.train()
    tr.close()
    assert state["global_step"] == 3
    assert tr.sentinel.quarantined == {1}  # update 2 consumed rollout 1

    bb_path = tmp_path / "reinforce" / "blackbox_2.json"
    assert bb_path.exists(), os.listdir(tmp_path / "reinforce")
    bb = json.load(open(bb_path))
    assert bb["reason"] == "sentinel_trip"
    assert bb["extra"]["rollout_index"] == 1
    assert bb["extra"]["verdict"] == "nonfinite"
    # every ring event is schema-shaped (ph/ts/pid/tid, finite ts)
    assert bb["spans"], "flight-recorder ring is empty"
    for e in bb["spans"]:
        assert {"ph", "ts", "pid", "tid"} <= set(e)
        assert math.isfinite(e["ts"])
    # the tripped update's span is in the ring, tagged quarantined
    trips = [e for e in bb["spans"] if e.get("name") == "train.update"
             and e.get("args", {}).get("quarantined")]
    assert trips, [e.get("name") for e in bb["spans"]]
    assert trips[-1]["args"]["rollout_index"] == 1
    assert trips[-1]["args"]["sentinel_verdict"] == "nonfinite"
    # the sentinel.trip instant marker made it too
    assert any(e.get("name") == "sentinel.trip" for e in bb["spans"])


# ---------------------------------------------------------------------------
# 2-update telemetry smoke (ISSUE-4 acceptance; the named tier1.yml step)
# ---------------------------------------------------------------------------


def test_telemetry_smoke_trace_schema_overlap_and_perf_metrics(tmp_path):
    """Orchestrated 2-update GRPO smoke with telemetry on: trace.json is
    schema-valid, producer-thread generation spans overlap trainer update
    spans (the pipelining picture), spans carry correlation args, and the
    perf/mfu + perf/tokens_per_sec_update rows reach metrics.jsonl."""
    tr = make_trainer(AlgoName.GRPO, tmp_path, total_episodes=32,
                      telemetry=True, rollout_orchestrator=True,
                      max_staleness=2, sampler_logprob_capture=True)
    state = tr.train()
    assert state["global_step"] == 2
    trace_path = tmp_path / "grpo" / "trace.json"
    assert trace_path.exists()
    assert validate_trace_file(str(trace_path)) == []

    evs = json.load(open(trace_path))["traceEvents"]
    upd = [e for e in evs if e.get("name") == "train.update"]
    gen = [e for e in evs if e.get("name") == "rollout.generate"
           and e.get("ph") == "X"]
    assert len(upd) == 2 and len(gen) >= 2
    # producer spans live on their own thread track
    assert {e["tid"] for e in gen}.isdisjoint({e["tid"] for e in upd})
    for e in upd:
        assert {"step", "rollout_index", "staleness",
                "policy_version"} <= set(e["args"])
    for e in gen:
        assert {"rollout_index", "policy_version"} <= set(e["args"])
    # generation wall-clock ran concurrently with trainer update spans
    overlap = sum(
        max(0.0, min(g["ts"] + g["dur"], u["ts"] + u["dur"])
            - max(g["ts"], u["ts"]))
        for g in gen for u in upd
    )
    assert overlap > 0.0
    # checkpoint I/O + reward dispatch got their logical tracks
    names = {e.get("name") for e in evs}
    assert "ckpt.save" in names and "reward.dispatch" in names

    rows = _metric_rows(tmp_path / "grpo")
    last = rows[-1]
    assert last["perf/mfu"] > 0.0
    assert last["perf/tokens_per_sec_update"] > 0.0
    assert last["perf/tokens_per_sec_step"] > 0.0
    assert last["perf/recompiles"] >= 1.0  # this run compiled something
    assert last["telemetry/spans_dropped"] == 0.0
    assert "orchestrator/consumer_wait_s" in last
    assert "orchestrator/producer_gate_wait_s" in last
    tr.close()


def test_profile_window_via_trainer_config(tmp_path):
    """cfg.profile_at_step wires the (previously unused) trace_profile
    through the trainer: the XLA profile dir is created for exactly the
    configured window and the window is closed by end-of-train."""
    prof_dir = str(tmp_path / "xla_prof")
    tr = make_trainer(AlgoName.REINFORCE, tmp_path, total_episodes=16,
                      profile_at_step=1, profile_dir=prof_dir)
    tr.train()
    tr.close()
    assert os.path.isdir(prof_dir)
    assert tr.profile_window.windows == 1
    assert not tr.profile_window.active


def test_telemetry_off_writes_no_trace(tmp_path):
    """telemetry=False is the default and must leave no trace/blackbox
    artifacts (the acceptance's 'disabled is the default')."""
    tr = make_trainer(AlgoName.REINFORCE, tmp_path, total_episodes=16)
    assert tr.cfg.telemetry is False
    tr.train()
    tr.close()
    out = tmp_path / "reinforce"
    assert not (out / "trace.json").exists()
    assert not list(out.glob("blackbox_*.json"))
    # perf accounting is emitted regardless of the tracer flag
    last = _metric_rows(out)[-1]
    assert "perf/mfu" in last and "perf/tokens_per_sec_update" in last
