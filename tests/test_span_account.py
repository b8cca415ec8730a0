"""The host half of the tracing (ISSUE 24): `PhaseTimer.phase` as the one
span call on the profiler's clock, the serving engine's loop account, the
request timeline, and the trainer's `trainer/iteration_s`.

Structural only: keys, counts and orderings, never a duration's size.
"""

import glob
import json
import os
import sys
import threading
import time
import urllib.request

import pytest

import jax
import jax.numpy as jnp

from nanorlhf_tpu.core import ModelConfig, init_params
from nanorlhf_tpu.sampler.paged.session import SESSION_PHASES
from nanorlhf_tpu.serving.engine import LOOP_PHASES, ServingEngine
from nanorlhf_tpu.serving.gateway import ServingGateway
from nanorlhf_tpu.telemetry.hist import LatencyHub
from nanorlhf_tpu.utils.profiling import PhaseTimer

EOS, PAD = 3, 0

LOOP_KEYS = [f"serving/loop_{p}_s" for p in LOOP_PHASES]
SESSION_KEYS = [f"serving/session_{p}_s" for p in SESSION_PHASES]
TIMELINE_KEYS = ["serving/queue_wait_s_sum", "serving/queue_wait_s_count",
                 "serving/first_token_lag_s_sum",
                 "serving/first_token_lag_s_count"]
# what the paged decode read touches (ISSUE 28), counted by the session
ATTENTION_KEYS = ["serving/attn_live_pages", "serving/attn_table_pages",
                  "serving/attn_in_place"]
ACCOUNT_KEYS = (LOOP_KEYS + ["serving/loop_beats"] + SESSION_KEYS
                + TIMELINE_KEYS + ATTENTION_KEYS)


# --------------------------------------------------------------------- #
# PhaseTimer: the one span call
# --------------------------------------------------------------------- #

def _annotations(trace_dir):
    from jax.profiler import ProfileData
    path = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    data = ProfileData.from_file(path)
    return {e.name for p in data.planes for ln in p.lines for e in ln.events}


def test_phase_is_in_the_profilers_trace_under_its_prefixed_name(tmp_path):
    timer = PhaseTimer(span_prefix="unit.")
    with timer.phase("outside"):        # no session: counted, not recorded
        pass
    jax.profiler.start_trace(str(tmp_path))
    try:
        with timer.phase("alpha"):
            with timer.phase("beta"):
                pass
    finally:
        jax.profiler.stop_trace()
    names = _annotations(str(tmp_path))
    assert {"unit.alpha", "unit.beta"} <= names
    assert "unit.outside" not in names
    assert timer.cumulative_counts == {"outside": 1, "alpha": 1, "beta": 1}


def test_counts_and_seconds_are_never_reset_and_names_are_preseeded():
    timer = PhaseTimer(span_prefix="unit.", names=("a", "b"))
    assert timer.cumulative == {"a": 0.0, "b": 0.0}
    assert timer.cumulative_counts == {"a": 0, "b": 0}
    for _ in range(3):
        with timer.phase("a"):
            pass
    assert timer.summary() == {"time/a_s": timer.cumulative["a"]}
    with timer.phase("a"):
        pass
    assert timer.counts == {"a": 1}                 # per-update: reset
    assert timer.cumulative_counts == {"a": 4, "b": 0}
    assert list(timer.cumulative) == ["a", "b"]     # no key came or went


def test_phase_counts_when_the_body_raises():
    timer = PhaseTimer(span_prefix="unit.", names=("a",))
    with pytest.raises(KeyError):
        with timer.phase("a"):
            raise KeyError("x")
    assert timer.cumulative_counts["a"] == 1


def test_phase_still_forwards_to_an_enabled_span_tracer():
    from nanorlhf_tpu.telemetry.tracer import SpanTracer
    tracer = SpanTracer(enabled=True)
    timer = PhaseTimer(tracer=tracer, span_prefix="unit.")
    with timer.phase("gamma"):
        pass
    assert "unit.gamma" in {e["name"] for e in tracer.trace_events()}


# --------------------------------------------------------------------- #
# the engine's loop account and the request timeline
# --------------------------------------------------------------------- #

@pytest.fixture(scope="module")
def tiny():
    config = ModelConfig.qwen2_tiny(vocab_size=128)
    params = init_params(config, jax.random.PRNGKey(7), jnp.float32)
    return config, params


def _engine(tiny, **kw):
    config, params = tiny
    return ServingEngine(params, config, eos_token_id=EOS, pad_token_id=PAD,
                         page_size=4, prompt_len=12, max_new_tokens=8, rows=2,
                         seed=0, **kw)


def _run(eng, prompts):
    reqs = [eng.submit(p, greedy=True)[0] for p in prompts]
    return [list(eng.stream(r)) for r in reqs]


PROMPTS = [[5, 6, 7, 8, 9, 10], [11, 12, 13], [20, 21, 22, 23], [30, 31],
           [5, 6, 7, 8, 9, 10]]


@pytest.fixture(scope="module")
def two_reads(tiny):
    """A handful of requests, a read, more requests, a read."""
    t0 = time.perf_counter()
    eng = _engine(tiny)
    try:
        assert all(_run(eng, PROMPTS))
        first = eng.metrics()
        assert all(_run(eng, PROMPTS[:3]))
        second = eng.metrics()
    finally:
        eng.close()
    return first, second, time.perf_counter() - t0


@pytest.mark.parametrize("key", ACCOUNT_KEYS)
def test_account_key_is_exported_and_never_decreases(two_reads, key):
    first, second, _ = two_reads
    assert key in first and key in second
    assert 0 <= first[key] <= second[key]


def test_account_counts_admissions_and_beats(two_reads):
    first, second, lifetime = two_reads
    for m in (first, second):
        assert m["serving/queue_wait_s_count"] == m["serving/admitted"]
        assert m["serving/loop_beats"] > 0
        # nobody streamed over HTTP: the engine alone reports no lag
        assert m["serving/first_token_lag_s_count"] == 0
    assert second["serving/admitted"] == len(PROMPTS) + 3
    assert 0 < second["serving/attn_live_pages"] < second["serving/attn_table_pages"]
    assert second["serving/loop_beats"] > first["serving/loop_beats"]
    # the five phases are disjoint parts of the loop thread's life
    assert sum(second[k] for k in LOOP_KEYS) <= lifetime
    # the session's beat phases sit inside the loop's step, its admission
    # phases inside the loop's admit
    assert (second["serving/session_dispatch_s"]
            + second["serving/session_sync_s"]
            + second["serving/session_prefill_tick_s"]
            <= second["serving/loop_step_s"])
    assert (second["serving/session_plan_s"]
            + second["serving/session_admit_forward_s"]
            <= second["serving/loop_admit_s"])


def test_queue_wait_reaches_the_hub_once_per_admission(tiny):
    hub = LatencyHub(enabled=True)
    eng = _engine(tiny, latency=hub)
    try:
        _run(eng, PROMPTS[:3])
        m = eng.metrics()
    finally:
        eng.close()
    assert hub.count("latency/queue_wait_s") == m["serving/admitted"] == 3
    assert hub.count("latency/ttft_s") == 3


def test_chunked_admission_stamps_its_first_token(tiny):
    """With chunked prefill the first token comes out of `_deliver`, not
    `_admit`: the stamp the gateway's report subtracts from is set there."""
    eng = _engine(tiny, prefill_chunk=2)
    try:
        req, _ = eng.submit([5, 6, 7, 8, 9, 10, 11, 12, 13], greedy=True)
        assert list(eng.stream(req))
        assert eng.session.chunked_admissions == 1
        assert req.t_submit <= req.t_first_token <= time.perf_counter()
        eng.first_token_sent(req)
        m = eng.metrics()
    finally:
        eng.close()
    assert m["serving/first_token_lag_s_count"] == 1
    assert m["serving/first_token_lag_s_sum"] >= 0
    assert m["serving/session_prefill_tick_s"] > 0


def _post(port, payload):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/generate", data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    return urllib.request.urlopen(req, timeout=120).read().decode()


@pytest.mark.parametrize("streamed,plain", [(3, 0), (0, 2), (2, 2)])
def test_first_token_lag_counts_streamed_requests_only(tiny, streamed, plain):
    eng = _engine(tiny)
    gw = ServingGateway(eng, port=-1)
    try:
        for i in range(streamed):
            lines = _post(gw.port, {"tokens": PROMPTS[i], "greedy": True,
                                    "stream": True}).splitlines()
            assert json.loads(lines[-1])["done"] is True
        for i in range(plain):
            assert json.loads(_post(gw.port, {"tokens": PROMPTS[i],
                                              "greedy": True}))["tokens"]
        m = eng.metrics()
        text = urllib.request.urlopen(
            f"http://127.0.0.1:{gw.port}/metrics", timeout=30).read().decode()
    finally:
        gw.close()
        eng.close()
    assert m["serving/first_token_lag_s_count"] == streamed
    assert (m["serving/first_token_lag_s_sum"] > 0) == (streamed > 0)
    assert m["serving/admitted"] == streamed + plain
    # an operator reads the same account at /metrics
    from nanorlhf_tpu.telemetry.exporter import validate_prometheus_text
    assert validate_prometheus_text(text) == []
    assert "nanorlhf_serving_loop_step_s" in text
    assert "nanorlhf_serving_first_token_lag_s_count" in text


def test_metrics_is_safe_from_another_thread_while_the_loop_runs(tiny):
    eng = _engine(tiny)
    errors, stop = [], threading.Event()
    reads = [[], []]        # one list per scraping thread, in its own order

    def scrape(mine):
        try:
            while not stop.is_set():
                mine.append(eng.metrics())
        except Exception as e:      # noqa: BLE001 (the test's whole point)
            errors.append(e)

    threads = [threading.Thread(target=scrape, args=(r,)) for r in reads]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)     # switch threads inside the copies
    try:
        for t in threads:
            t.start()
        for _ in range(3):
            assert all(_run(eng, PROMPTS))
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=30)
        sys.setswitchinterval(interval)
        eng.close()
    assert not errors and not any(t.is_alive() for t in threads)
    for mine in reads:
        assert len(mine) > 10
        assert all(set(ACCOUNT_KEYS) <= set(m) for m in (mine[0], mine[-1]))
        for key in ("serving/loop_beats", "serving/loop_step_s",
                    "serving/queue_wait_s_count"):
            values = [m[key] for m in mine]
            assert values == sorted(values)


def test_the_rollouts_queued_path_gets_the_session_account(tiny):
    """The rollout scheduler drives the same `DecodeSession`, so its beats
    are accounted under the same names."""
    from nanorlhf_tpu.sampler.paged.session import DecodeSession
    config, params = tiny
    sess = DecodeSession(params, config, rows=2, prompt_len=8, max_tokens=4,
                         page_size=4, eos_token_id=EOS, pad_token_id=PAD,
                         key=jax.random.PRNGKey(0), greedy=True, sync_every=2)
    ids = jnp.asarray([[PAD, PAD, 5, 6, 7, 8, 9, 10],
                       [PAD, PAD, PAD, PAD, PAD, 11, 12, 13]], jnp.int32)
    sess.bootstrap(ids, ids != PAD)
    sess.step()
    assert set(sess.timer.cumulative) == set(SESSION_PHASES)
    assert sess.timer.cumulative_counts["dispatch"] == 1
    assert sess.timer.cumulative_counts["sync"] == 1
    assert sess.timer.cumulative_counts["plan"] == 0    # no radix cache


# --------------------------------------------------------------------- #
# the trainer's remainder
# --------------------------------------------------------------------- #

def test_trainer_row_has_iteration_seconds_over_its_phases(tmp_path):
    from nanorlhf_tpu.trainer import AlgoName
    from tests.test_trainer_smoke import make_trainer

    tr = make_trainer(AlgoName.GRPO, tmp_path, save_steps=0)
    tr.train()
    rows = [json.loads(line)
            for line in open(tmp_path / "grpo" / "metrics.jsonl")]
    rows = [r for r in rows if "episode" in r]
    assert len(rows) == 2
    for r in rows:
        phases = {k: v for k, v in r.items()
                  if k.startswith("time/") and k.endswith("_s")}
        assert {"time/rollout_s", "time/update_s"} <= set(phases)
        assert r["trainer/iteration_s"] >= sum(phases.values())
    # the phases carry the trainer's prefix into a profiler trace
    assert tr.timer.span_prefix == "trainer."
    assert tr.timer.cumulative_counts["rollout"] == 2
