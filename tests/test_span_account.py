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
                  "serving/attn_in_place", "serving/paged_items",
                  "serving/paged_short_items"]
# the request's decode account (ISSUE 51): the session's beats by kind, the
# way out of a request's last token, and finished requests reduced, all and
# the slow tenth
BEAT_KEYS = ["serving/beats_clean", "serving/beats_loaded",
             "serving/beat_clean_s", "serving/beat_loaded_s",
             "serving/foreign_forwards"]
LAST_LAG_KEYS = ["serving/last_token_lag_s_sum",
                 "serving/last_token_lag_s_count"]
REQUEST_NAMES = ("requests", "tpot_s_sum", "decode_s", "wait_s", "loaded_s")
ALL_KEYS = [f"serving/all_{n}" for n in REQUEST_NAMES]
SLOW_KEYS = [f"serving/slow_{n}" for n in REQUEST_NAMES]
REQUEST_KEYS = BEAT_KEYS + LAST_LAG_KEYS + ALL_KEYS + SLOW_KEYS
ACCOUNT_KEYS = (LOOP_KEYS + ["serving/loop_beats"] + SESSION_KEYS
                + TIMELINE_KEYS + ATTENTION_KEYS + REQUEST_KEYS)


# --------------------------------------------------------------------- #
# PhaseTimer: the one span call
# --------------------------------------------------------------------- #

def _events(trace_dir):
    """name -> the arguments of each of its events in a profiler trace."""
    from jax.profiler import ProfileData
    path = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    out = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                out.setdefault(e.name, []).append(dict(e.stats))
    return out


def _annotations(trace_dir):
    return set(_events(trace_dir))


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


def _over_http(tiny, streamed, plain):
    """`streamed` streamed and `plain` whole-body requests through a gateway:
    the engine's metrics, and what /metrics and /statusz serve after them."""
    eng = _engine(tiny)
    gw = ServingGateway(eng, port=-1)
    get = lambda path: urllib.request.urlopen(      # noqa: E731
        f"http://127.0.0.1:{gw.port}{path}", timeout=30).read().decode()
    try:
        for i in range(streamed):
            lines = _post(gw.port, {"tokens": PROMPTS[i], "greedy": True,
                                    "stream": True}).splitlines()
            assert json.loads(lines[-1])["done"] is True
        for i in range(plain):
            assert json.loads(_post(gw.port, {"tokens": PROMPTS[i],
                                              "greedy": True}))["tokens"]
        return eng.metrics(), get("/metrics"), json.loads(get("/statusz"))
    finally:
        gw.close()
        eng.close()


@pytest.mark.parametrize("streamed,plain", [(3, 0), (0, 2), (2, 2)])
def test_first_token_lag_counts_streamed_requests_only(tiny, streamed, plain):
    m, text, _ = _over_http(tiny, streamed, plain)
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
                    "serving/queue_wait_s_count", "serving/beats_clean",
                    "serving/beat_loaded_s", "serving/foreign_forwards",
                    "serving/all_requests", "serving/all_decode_s",
                    "serving/all_wait_s"):
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
# a request's decode account (ISSUE 51)
# --------------------------------------------------------------------- #

def test_phase_hands_its_arguments_to_the_trace_and_to_nothing_else(tmp_path):
    timer = PhaseTimer(span_prefix="unit.", names=("a",))
    with timer.phase("a", request=9):   # no session: a flag check
        pass
    jax.profiler.start_trace(str(tmp_path))
    try:
        with timer.phase("a", request=3, row=1) as span:
            span.set_metadata(its=4)    # what only the block's end knows
    finally:
        jax.profiler.stop_trace()
    assert _events(str(tmp_path))["unit.a"] == [
        {"request": 3, "row": 1, "its": 4}]
    assert timer.cumulative_counts == {"a": 2}
    assert list(timer.cumulative) == ["a"]      # the arguments cost no key


@pytest.mark.parametrize("key", REQUEST_KEYS)
def test_request_account_key_is_zero_before_the_first_request(tiny, fresh, key):
    assert fresh[key] == 0


@pytest.fixture(scope="module")
def fresh(tiny):
    eng = _engine(tiny)
    try:
        return eng.metrics()
    finally:
        eng.close()


def _long_engine(tiny, **kw):
    """Sixteen beats a request, so that one can arrive while another
    decodes (the shapes differ from `_engine`'s in the budget alone)."""
    config, params = tiny
    eng = ServingEngine(params, config, eos_token_id=EOS, pad_token_id=PAD,
                        page_size=4, prompt_len=12, max_new_tokens=32,
                        rows=2, sync_every=2, seed=0, **kw)
    # and no beat under 10 ms, whatever the machine: the second request of
    # `_first_then` finds the first one decoding
    dispatch = eng.session.dispatch
    eng.session.dispatch = lambda: (time.sleep(0.01), dispatch())[1]
    return eng


def _spy_on_book(eng) -> list:
    """The request ids `eng._book` is called with from here on, in order."""
    booked, book = [], eng._book
    eng._book = lambda req, got: (booked.append(req.request_id),
                                  book(req, got))[1]
    return booked


def _first_then(eng, first, then):
    """Submit `first`, and `then` once `first`'s first token is out."""
    a, _ = eng.submit(first, greedy=True)
    tokens_a = [a.out_q.get(timeout=120)]
    b, _ = eng.submit(then, greedy=True)
    tokens_a += list(eng.stream(a))
    return a, b, tokens_a, list(eng.stream(b))


def test_a_lone_requests_beats_are_all_clean(tiny):
    eng = _long_engine(tiny)
    try:
        req, _ = eng.submit(PROMPTS[0], greedy=True)
        assert len(list(eng.stream(req))) > 4
        m = eng.metrics()
    finally:
        eng.close()
    assert req.beats >= 2 and req.row == 0
    assert (req.loaded_beats, req.loaded_s, req.foreign_forwards) == (0, 0.0, 0)
    assert 0 < req.wait_s <= req.decode_s
    # the session knows no owner: the beat behind the admission is loaded
    assert (m["serving/beats_loaded"], m["serving/foreign_forwards"]) == (1, 1)
    assert m["serving/beats_clean"] == req.beats - 1
    assert m["serving/all_loaded_s"] == 0
    assert m["serving/all_decode_s"] == pytest.approx(req.decode_s)


def test_an_admission_loads_the_next_beat_of_the_request_that_decodes(tiny):
    eng = _long_engine(tiny)
    try:
        a, b, tokens_a, tokens_b = _first_then(eng, PROMPTS[0], PROMPTS[1])
        m = eng.metrics()
    finally:
        eng.close()
    assert len(tokens_a) > 8 and tokens_b
    # b's admission forward stood before one of a's chunks, and is b's own
    assert (a.loaded_beats, a.foreign_forwards) == (1, 1)
    assert 0 < a.loaded_s < a.decode_s
    assert (b.loaded_beats, b.foreign_forwards) == (0, 0)
    assert m["serving/foreign_forwards"] == m["serving/admitted"] == 2
    assert m["serving/beats_loaded"] == 2
    assert m["serving/all_loaded_s"] == pytest.approx(a.loaded_s)


def test_the_wait_for_anothers_first_token_is_in_the_residents_wait(
        tiny, monkeypatch):
    """The loop stands in `read()` for a first token until the admission
    forward has run: that wait is the device's share of the beat of every
    resident request, and not of the admitted one, whose account starts at
    the token."""
    from nanorlhf_tpu.sampler.paged import session as session_module

    class _Late:
        def __init__(self, tok):
            self.tok = tok

        def __int__(self):
            time.sleep(0.5)
            return int(self.tok)

    class _LateFirst(session_module._First):
        def __init__(self, pend, tok):
            super().__init__(pend, _Late(tok))

    eng = _long_engine(tiny)
    reports, book = [], eng._book
    eng._book = lambda req, got: (reports.append((req.request_id, got)),
                                  book(req, got))[1]
    try:
        assert all(_run(eng, PROMPTS[2:3]))         # compiled
        monkeypatch.setattr(session_module, "_First", _LateFirst)
        a, b, tokens_a, tokens_b = _first_then(eng, PROMPTS[0], PROMPTS[1])
    finally:
        eng.close()
    assert len(tokens_a) > 8 and tokens_b
    # a's own first token (half a second, before its account) and b's
    # (inside it): a's loaded beat waited for b's admission forward
    assert 0.5 <= a.wait_s < 1.0
    assert a.wait_s <= a.decode_s
    loaded = [got for who, got in reports       # (a's first beat carries
              if who == a.request_id][1:]       # a's own admission)
    loaded = [got for got in loaded if got.foreign]
    assert len(loaded) == 1 and loaded[0].wait_s >= 0.5
    assert loaded[0].wait_s <= loaded[0].period_s
    # b's first beat is the same report: what of its wait came before b's
    # token is not b's
    assert 0 <= b.wait_s < 0.5 and b.wait_s <= b.decode_s


def test_a_chunked_admission_is_one_foreign_forward_a_piece(tiny):
    eng = _long_engine(tiny, prefill_chunk=2)
    try:
        # nine prompt tokens in pieces of two: five pieces, a beat each
        a, b, tokens_a, tokens_b = _first_then(
            eng, [5, 6], [5, 6, 7, 8, 9, 10, 11, 12, 13])
        m = eng.metrics()
    finally:
        eng.close()
    assert len(tokens_a) > 12 and tokens_b
    assert eng.session.chunked_admissions == 1
    assert m["serving/prefill_pieces"] == 5
    assert (a.foreign_forwards, a.loaded_beats) == (5, 5)
    assert b.foreign_forwards == 0      # its last piece is its own
    # every forward of more than one token: the pieces and a's admission
    assert m["serving/foreign_forwards"] == 5 + 1


@pytest.fixture(scope="module")
def staggered(tiny):
    """Requests of several lengths through two rows, each admitted as a row
    comes free beside one that decodes."""
    eng = _long_engine(tiny)
    booked = _spy_on_book(eng)
    try:
        reqs = [eng.submit(p, greedy=True, max_tokens=n)[0]
                for p, n in zip(PROMPTS + PROMPTS, (32, 9, 17, 2, 1, 25, 6,
                                                    32, 3, 12))]
        streams = [list(eng.stream(r)) for r in reqs]
        m, snap = eng.metrics(), eng.snapshot()
    finally:
        eng.close()
    return reqs, streams, m, snap, booked


def test_the_sums_over_all_requests_are_the_requests_own(staggered):
    reqs, streams, m, _, booked = staggered
    assert [r.n_emitted for r in reqs] == [len(s) for s in streams]
    counted = [r for r in reqs if r.n_emitted >= 2]
    assert 0 < len(counted) < len(reqs)     # one of a single token is not
    assert m["serving/all_requests"] == len(counted)
    assert len(booked) == sum(r.beats for r in reqs)    # one helper, a row-beat
    for name in ("decode_s", "wait_s", "loaded_s"):
        assert m[f"serving/all_{name}"] == pytest.approx(
            sum(getattr(r, name) for r in counted))
    assert m["serving/all_tpot_s_sum"] == pytest.approx(sum(
        (r.t_last_token - r.t_first_token) / (r.n_emitted - 1)
        for r in counted))
    assert all(m[k] == 0 for k in SLOW_KEYS)    # under twenty: no class


def test_decode_seconds_tile_first_token_to_last(staggered):
    """A request's periods run from its first token to the last report that
    spoke for it, and that report's tokens are queued within the beat."""
    reqs, _, m, _, _ = staggered
    beat = max(m["serving/beat_clean_s"] / m["serving/beats_clean"],
               m["serving/beat_loaded_s"] / m["serving/beats_loaded"])
    for r in reqs:
        if r.n_emitted >= 2:
            span = r.t_last_token - r.t_first_token
            assert r.decode_s <= span <= r.decode_s + beat
            assert r.wait_s <= r.decode_s and r.loaded_s <= r.decode_s
            assert r.loaded_beats <= r.beats
    # and the session's beats hold every row-beat's period at least once
    assert (m["serving/beat_clean_s"] + m["serving/beat_loaded_s"]
            >= max(r.decode_s for r in reqs))


def test_recent_requests_name_the_row_and_the_instants(staggered):
    reqs, _, _, snap, _ = staggered
    recent = {r["request_id"]: r for r in snap["recent_requests"]}
    assert set(recent) == {r.request_id for r in reqs}
    for r in reqs:
        rec = recent[r.request_id]
        assert rec["row"] == r.row and rec["row"] in (0, 1)
        assert rec["n_emitted"] == r.n_emitted
        assert (0 <= rec["t_admit"] <= rec["t_first_token"]
                <= rec["t_last_token"] <= rec["t_finish"])
        assert rec["t_first_token"] == pytest.approx(
            r.t_first_token - r.t_submit)
        for name in ("beats", "decode_s", "wait_s", "loaded_beats",
                     "loaded_s", "foreign_forwards"):
            assert rec[name] == getattr(r, name)
    json.dumps(snap["recent_requests"])     # /statusz serves it as it is


def test_recent_requests_keeps_the_last_sixty_four(tiny):
    eng = _engine(tiny, max_queue=128)
    try:
        reqs = [eng.submit(PROMPTS[i % 5], greedy=True, max_tokens=2)[0]
                for i in range(70)]
        assert all(list(eng.stream(r)) for r in reqs)
        recent = eng.snapshot()["recent_requests"]
    finally:
        eng.close()
    assert len(recent) == 64
    assert {r["request_id"] for r in recent} == {
        r.request_id for r in reqs[-64:]}


def _finished(request_id, tpot, tokens=8):
    from nanorlhf_tpu.serving.engine import ServingRequest
    return ServingRequest(
        request_id=request_id, tokens=None, temperature=1.0, top_p=1.0,
        greedy=True, max_tokens=tokens + 1, t_submit=0.0, t_first_token=1.0,
        t_admit=0.5, t_last_token=1.0 + tpot * tokens, n_emitted=tokens + 1,
        row=0, beats=2, decode_s=tpot * tokens, wait_s=tpot, loaded_beats=1,
        loaded_s=tpot * 3)


def _tpots(shape, n=400):
    """Seconds a token of `n` finished requests, log-normal about 5 ms:
    `steady`; `outliers`, three of the first twenty a hundred times that (a
    warm-up request whose decode compiled); `drift_down` / `drift_up`, the
    level falling or rising by a third over the run; `hub`, steady, with a
    latency hub beside the engine."""
    import numpy as np
    tpots = 0.005 * np.exp(0.3 * np.random.default_rng(0).normal(size=n))
    if shape == "outliers":
        tpots[[0, 7, 13]] *= 100
    if shape.startswith("drift"):
        tpots *= np.linspace(1.0, 2 / 3 if shape == "drift_down" else 4 / 3, n)
    return tpots


@pytest.mark.parametrize("shape", ["steady", "hub", "outliers", "drift_down",
                                   "drift_up"])
def test_the_slow_tenth_is_a_tenth_whatever_came_before(tiny, shape):
    """Requests reduced by hand with TPOTs of a known shape: none of the
    first twenty is slow, then about a tenth are, and they are the slow
    ones of their time."""
    import numpy as np
    latency = LatencyHub(enabled=True) if shape == "hub" else None
    eng = _engine(tiny, latency=latency)
    eng.close()
    tpots = _tpots(shape)
    for i, tpot in enumerate(tpots[:20]):
        eng._reduce(_finished(i, float(tpot)))
    early, at = eng.metrics(), eng.snapshot()["slow_at_s"]
    for i, tpot in enumerate(tpots[20:], 20):
        eng._reduce(_finished(i, float(tpot)))
    m = eng.metrics()
    assert early["serving/all_requests"] == 20
    assert all(early[k] == 0 for k in SLOW_KEYS)
    assert at == pytest.approx(float(np.median(tpots[:20])))
    assert m["serving/all_requests"] == 400
    assert m["serving/all_tpot_s_sum"] == pytest.approx(float(tpots.sum()))
    if latency is not None:
        assert latency.count("latency/tpot_s") == 400
    assert 0.05 <= m["serving/slow_requests"] / m["serving/all_requests"] <= 0.20
    # the threshold has come to the last hundred's ninth decile
    assert eng.snapshot()["slow_at_s"] == pytest.approx(
        float(np.quantile(tpots[-100:], 0.9)), rel=0.25)
    # the slow ones are the slow ones, and bring their accounts with them
    assert (m["serving/slow_tpot_s_sum"] / m["serving/slow_requests"]
            > float(np.quantile(tpots[20:], 0.8)))
    assert m["serving/slow_decode_s"] == pytest.approx(
        8 * m["serving/slow_tpot_s_sum"])
    assert m["serving/slow_loaded_s"] == pytest.approx(
        3 / 8 * m["serving/slow_decode_s"])


def test_no_request_is_slow_among_the_first_twenty(tiny):
    hub = LatencyHub(enabled=True)
    eng = _engine(tiny, latency=hub)
    try:
        assert all(_run(eng, PROMPTS + PROMPTS))
        m, snap = eng.metrics(), eng.snapshot()
    finally:
        eng.close()
    assert hub.count("latency/tpot_s") == m["serving/all_requests"] == 10
    assert all(m[k] == 0 for k in SLOW_KEYS)
    assert snap["slow_at_s"] is None


@pytest.mark.parametrize("block,foreign,loaded", [
    (0, 1, 0), (0, 3, 2), (4, 1, 1), (0, 0, 0)])
def test_a_requests_first_beat_counts_from_its_first_token(block, foreign,
                                                           loaded):
    """`_book` by hand: the first beat runs from the first token and leaves
    the request's own admission forward out (a block engine's first booked
    beat never holds it); the next takes the report's period whole."""
    from types import SimpleNamespace
    from nanorlhf_tpu.sampler.paged.session import BeatReport
    book = lambda req, got: ServingEngine._book(      # noqa: E731
        SimpleNamespace(block_length=block), req, got)
    req = _finished(0, 0.0)
    req.beats = req.loaded_beats = 0
    req.decode_s = req.wait_s = req.loaded_s = 0.0
    req.t_first_token = 10.0
    book(req, BeatReport(4, None, period_s=0.75, wait_s=0.125, foreign=foreign,
                         t=10.5))
    assert (req.beats, req.decode_s, req.wait_s) == (1, 0.5, 0.125)
    assert (req.loaded_beats, req.foreign_forwards) == (int(loaded > 0), loaded)
    assert req.loaded_s == (0.5 if loaded else 0.0)
    book(req, BeatReport(4, None, period_s=0.25, wait_s=0.0625, foreign=2,
                         t=10.75))
    assert (req.beats, req.decode_s, req.wait_s) == (2, 0.75, 0.1875)
    assert req.foreign_forwards == loaded + 2
    assert req.loaded_s == (0.75 if loaded else 0.25)


@pytest.mark.parametrize("streamed,plain", [(3, 0), (0, 2), (2, 2)])
def test_last_token_lag_counts_streamed_requests_only(tiny, streamed, plain):
    m, text, status = _over_http(tiny, streamed, plain)
    assert m["serving/last_token_lag_s_count"] == streamed
    assert (m["serving/last_token_lag_s_sum"] > 0) == (streamed > 0)
    assert m["serving/all_requests"] == streamed + plain
    assert [r["row"] for r in status["recent_requests"]] == [0] * (
        streamed + plain)
    assert "nanorlhf_serving_last_token_lag_s_count" in text
    assert "nanorlhf_serving_slow_requests" in text


def test_tpot_is_a_family_of_the_hub_and_of_metrics_text(tiny):
    hub = LatencyHub(enabled=True)
    eng = _engine(tiny, latency=hub)
    gw = ServingGateway(eng, port=-1)
    try:
        assert all(_run(eng, PROMPTS[:3]))
        req, _ = eng.submit(PROMPTS[3], greedy=True, max_tokens=1)
        assert len(list(eng.stream(req))) == 1      # one token: no TPOT
        text = urllib.request.urlopen(
            f"http://127.0.0.1:{gw.port}/metrics", timeout=30).read().decode()
    finally:
        gw.close()
        eng.close()
    assert hub.count("latency/tpot_s") == 3
    assert "nanorlhf_latency_tpot_s_count 3" in text


def test_the_spans_of_one_request_share_its_identifier(tiny, tmp_path):
    eng = _long_engine(tiny, prefill_chunk=4)
    try:
        assert all(_first_then(eng, PROMPTS[1], PROMPTS[0])[2:])   # compiled
        first = eng.metrics()["serving/admitted"]
        jax.profiler.start_trace(str(tmp_path))
        try:
            # (other tokens: a prefix hit would not be chunked)
            a, b, *_ = _first_then(eng, [30, 31, 32],
                                   [40, 41, 42, 43, 44, 45])
        finally:
            jax.profiler.stop_trace()
    finally:
        eng.close()
    events = _events(str(tmp_path))
    ids = {a.request_id: a.row, b.request_id: b.row}
    assert sorted(ids) == [first, first + 1]
    for name in ("serving.admit", "session.plan"):
        assert {(e["request"], e["row"]) for e in events[name]} == set(
            ids.items())
    # a's three tokens go in one forward, b's six in two pieces
    assert [(e["request"], e["row"]) for e in events["session.admit_forward"]
            ] == [(a.request_id, a.row)]
    assert [(e["request"], e["row"]) for e in events["session.prefill_tick"]
            ] == [(b.request_id, b.row)] * 2
    syncs = events["session.sync"]
    firsts = [e for e in syncs if "request" in e]
    assert {(e["request"], e["row"]) for e in firsts} == set(ids.items())
    beats = [e for e in syncs if "request" not in e]
    assert all(set(e) == {"its", "foreign"} for e in beats)
    assert sum(e["foreign"] for e in beats) == 3
    assert sum(e["its"] for e in beats) >= 31


def test_the_block_engine_books_through_the_same_helper():
    """Tokens come by blocks: a request has none before a report brings its
    first, so its account starts at the report after that one."""
    from tests.test_sdar import CFG, ENGINE, lay_weights, prompt_of
    eng = ServingEngine(lay_weights(), CFG, **ENGINE)
    booked = _spy_on_book(eng)
    try:
        reqs = [eng.submit(prompt_of(n, 11), greedy=True, max_tokens=12,
                           denoising_steps=steps)[0]
                for n, steps in ((8, 4), (20, 2), (13, 4), (9, 1))]
        streams = [list(eng.stream(r, timeout=120)) for r in reqs]
        m = eng.metrics()
        recent = eng.snapshot()["recent_requests"]
    finally:
        eng.close()
    assert [len(s) for s in streams] == [12] * 4
    assert m["serving/all_requests"] == 4
    assert sum(r["n_emitted"] - 1 for r in recent) == 4 * 11
    assert sum(r["beats"] for r in recent) == len(booked)
    assert all(r.beats >= 1 and r.row in (0, 1, 2) for r in reqs)
    assert m["serving/all_decode_s"] == pytest.approx(
        sum(r.decode_s for r in reqs))
    assert [r["row"] for r in recent] == [
        q.row for q in sorted(reqs, key=lambda q: q.t_last_token)]
    # the prompts' whole blocks went in pieces of eight, and those forwards
    # stood before chunks: every one is in the session's count
    assert m["serving/foreign_forwards"] == eng.session.launches
    assert m["serving/beats_loaded"] >= 1 and m["serving/beats_clean"] >= 1
    for r in reqs:
        assert r.t_first_token <= r.t_last_token
        assert r.loaded_beats <= r.beats and r.loaded_s <= r.decode_s


def test_a_block_requests_last_token_lag_leaves_the_commit_beat_out():
    """A budget that ends inside a block: the row is finished a beat after
    its last token went out, at the block's commit. The way out is timed to
    the flush of that token, not to the stream's end, and the request's
    account stops at the token too."""
    from tests.test_sdar import CFG, ENGINE, lay_weights, prompt_of
    eng = ServingEngine(lay_weights(), CFG, **ENGINE)
    gw = ServingGateway(eng, port=-1)
    ask = {"tokens": prompt_of(8, 11), "greedy": True, "stream": True,
           "max_tokens": 6, "denoising_steps": 4}
    try:
        assert json.loads(_post(gw.port, ask).splitlines()[-1])["n"] == 6
        dispatch = eng.session.dispatch     # compiled: now no beat under 0.2 s
        eng.session.dispatch = lambda: (time.sleep(0.2), dispatch())[1]
        before = eng.metrics()
        assert json.loads(_post(gw.port, ask).splitlines()[-1])["n"] == 6
        m, rec = eng.metrics(), eng.snapshot()["recent_requests"][-1]
    finally:
        gw.close()
        eng.close()
    assert rec["n_emitted"] == 6
    assert rec["t_finish"] - rec["t_last_token"] >= 0.2     # the commit beat
    lag = (m["serving/last_token_lag_s_sum"]
           - before["serving/last_token_lag_s_sum"])
    assert (m["serving/last_token_lag_s_count"]
            - before["serving/last_token_lag_s_count"]) == 1
    assert 0 < lag < 0.2
    span = rec["t_last_token"] - rec["t_first_token"]
    assert rec["decode_s"] == pytest.approx(span, abs=0.05)


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
