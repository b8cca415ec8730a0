"""Decode session: feature composition on one scheduler loop (ISSUE 18).

The session refactor's acceptance contract, pinned:

  * COMPOSITION PARITY — greedy queued output is bit-identical across
    every legal feature combination: plain, radix prefix cache, spec
    decode, spec UNDER radix, chunked prefill on/off (× radix). The
    existing per-feature parity suites (test_paged_cache, test_serving,
    test_speculative, test_envs) now run THROUGH the session — `generate`
    has no non-session queued path — so this file pins only the
    combinations that used to be illegal.
  * DISPATCH A/B — on an overlapping corpus, spec+radix combined issues
    STRICTLY fewer dispatch events (admission launches + decode/verify
    chunk iterations) than either feature alone, and strictly fewer
    prefill tokens than spec alone. Events, not tokens, is the honest
    combined-vs-radix metric: a verify step dispatches k+1 tokens where
    plain decode dispatches 1, trading tokens-per-launch for fewer
    launches (docs/DECODE_ANALYSIS.md §dispatch accounting).
  * DRAFTER SEEDING — satellite (b): admissions seed the n-gram drafter
    from the radix tree's cached continuation of the matched prefix
    (radix.extend_text / matched_continuation), so repeat prompts accept
    drafts from the first generated token instead of cold-starting.
  * ONE CODE PATH — serving/engine.py owns no decode loop: its chunk fn
    IS the session's, and a gateway-shaped per-row stream equals the
    rollout scheduler's greedy stream for the same prompt.
  * compose_check — the single legality matrix: what still raises, and
    that everything else constructs.

CI runs this file as the `session-parity` tier-1 step under
NANORLHF_LOCK_CHECK=1.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from nanorlhf_tpu.core import ModelConfig, init_params
from nanorlhf_tpu.sampler import SamplingParams, compose_check, generate
from nanorlhf_tpu.serving.radix import RadixCache, prompt_key

EOS, PAD = 3, 0
TP = 12          # padded prompt width
MT = 8           # max_tokens


@pytest.fixture(scope="module")
def tiny():
    config = ModelConfig.qwen2_tiny(vocab_size=128)
    params = init_params(config, jax.random.PRNGKey(7), jnp.float32)
    return config, params


def _left_pad(rows, T, pad=PAD):
    ids = np.full((len(rows), T), pad, np.int32)
    for i, r in enumerate(rows):
        ids[i, T - len(r):] = r
    ids = jnp.asarray(ids)
    return ids, ids != pad


# one 8-real-token family repeated: maximal prefix overlap, so radix
# full-hits every repeat and (after the first release extends the tree
# with the generated text) the drafter seed covers the whole greedy
# continuation of rows 3..6
FAMILY = [5, 6, 7, 8, 9, 10, 11, 12]
OVERLAP = [FAMILY] * 6


def _run(tiny, *, spec_k=0, radix=False, prefill_chunk=0, greedy=True,
         prompts=OVERLAP, key=0):
    config, params = tiny
    ids, mask = _left_pad(prompts, TP)
    sp = SamplingParams(max_tokens=MT, greedy=greedy, page_size=4,
                        decode_rows=2, spec_k=spec_k,
                        prefill_chunk=prefill_chunk,
                        temperature=1.0, top_p=0.9)
    stats, spec_stats = [], []
    out = generate(params, config, ids, mask, jax.random.PRNGKey(key),
                   sp, eos_token_id=EOS, pad_token_id=PAD,
                   paged_stats_out=stats, spec_stats_out=spec_stats,
                   prefix_cache=RadixCache() if radix else None)
    return np.asarray(out), stats[0], (spec_stats[0] if spec_stats
                                       else None)


@pytest.fixture(scope="module")
def ab(tiny):
    """The four corners of the spec×radix square, one call each."""
    runs = {}
    runs["plain"] = _run(tiny)
    runs["radix"] = _run(tiny, radix=True)
    runs["spec"] = _run(tiny, spec_k=3)
    runs["both"] = _run(tiny, spec_k=3, radix=True)
    return runs


def test_spec_under_radix_three_way_bit_parity(ab):
    """Greedy output identical across plain / radix / spec / spec+radix:
    the composition that raised ValueError before the session exists and
    changes dispatch shape ONLY."""
    ref = ab["plain"][0]
    for name in ("radix", "spec", "both"):
        np.testing.assert_array_equal(
            ref, ab[name][0], err_msg=f"{name} diverged from plain")


def test_combined_strictly_fewer_dispatch_events(ab):
    """THE perf gate: spec+radix < min(each alone) in dispatch EVENTS on
    the overlapping corpus — the radix hit removes prefill iterations
    and the SEEDED drafter removes decode iterations that unseeded spec
    cannot (the continuation lives in the tree, not in the repeat row's
    own prompt). Also: combined moves strictly fewer prefill tokens than
    spec alone (the radix half of the win, token-denominated)."""
    ev = {k: v[1]["dispatch_events"] for k, v in ab.items()}
    assert ev["both"] < min(ev["radix"], ev["spec"]), ev
    assert (ab["both"][1]["prefill_token_dispatch"]
            < ab["spec"][1]["prefill_token_dispatch"])
    # the mechanism, not just the outcome: the seed window is armed and
    # seeded acceptance strictly beats unseeded on this corpus
    feats = ab["both"][1]["session"]["features"]
    assert feats["spec_k"] == 3 and feats["prefix_cache"]
    assert feats["drafter_seed_window"] > 0
    acc_both = int(np.asarray(ab["both"][2]["accepted"]))
    acc_spec = int(np.asarray(ab["spec"][2]["accepted"]))
    assert acc_both > acc_spec, (acc_both, acc_spec)


@pytest.mark.parametrize("radix", [False, True],
                         ids=["cold-pool", "radix"])
def test_chunked_prefill_bit_identical(tiny, radix):
    """prefill_chunk on/off: greedy streams bit-identical (the final
    chunk runs the same bucketed suffix forward and samples from the
    same admission fold), with the chunked run actually chunking —
    backlog observed, admissions split."""
    out0, st0, _ = _run(tiny, radix=radix)
    out1, st1, _ = _run(tiny, radix=radix, prefill_chunk=4)
    np.testing.assert_array_equal(out0, out1)
    assert st0["chunked_admissions"] == 0
    assert st1["chunked_admissions"] > 0
    assert st1["prefill_backlog_peak"] > 0
    # chunking must not change WHAT ran, only when: same decode output,
    # same rows admitted
    assert st1["admitted_midloop"] >= st0["admitted_midloop"]


def test_session_stats_surface(ab):
    """The /statusz `session` section the trainer re-exports: mode,
    per-row flags, counters — shaped for tools/inspect_run.py."""
    s = ab["both"][1]["session"]
    assert s["mode"] == "rollout"
    assert s["rows"] == 2 and len(s["row_flags"]) == 2
    assert s["counters"]["dispatch_events"] == (
        s["counters"]["launches"] + s["counters"]["decode_iterations"])
    assert s["pending_prefill"] == {"rows": [], "backlog_tokens": 0}


# --------------------------------------------------------------------- #
# drafter seeding primitives (satellite b)
# --------------------------------------------------------------------- #

def test_radix_text_extension_and_continuation():
    rc = RadixCache()
    rc.reset(num_pages=16, page_size=4)
    toks = np.asarray(FAMILY, np.int32)
    row = np.full(TP, PAD, np.int32)
    row[TP - len(toks):] = toks
    mask = row != PAD
    key = prompt_key(row, mask)
    plan = rc.plan(key, pad_count=TP - len(toks), n_blocks=5,
                   prompt_len=TP)
    rc.insert(key, plan.row_pages, TP)
    # nothing generated yet: the continuation of the full prompt is empty
    assert rc.matched_continuation(key, 8).size == 0
    gen = [40, 41, 42, 43]
    rc.extend_text(key + tuple(t * 2 + 1 for t in gen))
    np.testing.assert_array_equal(rc.matched_continuation(key, 8), gen)
    # window truncates from the front of the continuation
    np.testing.assert_array_equal(rc.matched_continuation(key, 2),
                                  gen[:2])
    # an unknown prompt has no continuation
    other = prompt_key(np.roll(row, 1), mask)
    assert rc.matched_continuation(other, 8).size == 0
    # text-only leaves hold no pages: releasing the one holder frees the
    # whole pool (the extension can never leak a page)
    rc.release(plan.row_pages.copy())
    rc.reset(num_pages=16, page_size=4)
    assert rc.pool.free_count == 16


# --------------------------------------------------------------------- #
# one scheduler code path: serving == rollout (tentpole composition 3)
# --------------------------------------------------------------------- #

def test_engine_has_no_private_decode_loop():
    import nanorlhf_tpu.sampler.paged.scheduler as sched
    import nanorlhf_tpu.sampler.paged.session as session
    import nanorlhf_tpu.serving.engine as engine

    # the engine's pre-session loop primitives are GONE, not just unused
    for name in ("_engine_chunk", "_engine_decode_body", "_engine_install",
                 "_ENGINE_STATIC"):
        assert not hasattr(engine, name), name
    # the rollout scheduler drives the session's chunk fns, not copies
    assert sched._decode_chunk is session._decode_chunk
    assert sched._spec_chunk is session._spec_chunk
    assert sched.DecodeSession is session.DecodeSession


def test_gateway_stream_equals_rollout_stream(tiny):
    """Same prompt, same greedy params: the engine's per-request stream
    and the rollout scheduler's row are the same token sequence — the
    pin that serving and rollout share one scheduler code path."""
    from nanorlhf_tpu.sampler.paged.session import DecodeSession
    from nanorlhf_tpu.serving.engine import ServingEngine

    config, params = tiny
    rollout, _, _ = _run(tiny, prompts=[FAMILY])
    row = rollout[0]
    eos = np.nonzero(row == EOS)[0]
    want = row[:int(eos[0]) + 1] if eos.size else row

    eng = ServingEngine(params, config, eos_token_id=EOS,
                        pad_token_id=PAD, page_size=4, prompt_len=TP,
                        max_new_tokens=MT, rows=2, seed=0)
    try:
        assert isinstance(eng.session, DecodeSession)
        req, reason = eng.submit(FAMILY, greedy=True)
        assert reason is None
        got = np.asarray(list(eng.stream(req)), np.int32)
        snap = eng.snapshot()
    finally:
        eng.close()
    np.testing.assert_array_equal(got, want)
    sess = snap["session"]
    assert sess["mode"] == "serving"
    assert sess["features"]["per_row_sampling"]
    assert len(sess["row_flags"]) == eng.rows


def test_engine_chunked_prefill_stream_identical(tiny):
    """Engine with prefill_chunk: the long cold prompt's stream is
    bit-identical to the unchunked engine (first token rides _deliver
    instead of the admission return), and the session counted the
    chunked admission."""
    from nanorlhf_tpu.serving.engine import ServingEngine

    config, params = tiny

    def serve(prefill_chunk):
        eng = ServingEngine(params, config, eos_token_id=EOS,
                            pad_token_id=PAD, page_size=4, prompt_len=TP,
                            max_new_tokens=MT, rows=2, seed=0,
                            prefill_chunk=prefill_chunk)
        try:
            req, reason = eng.submit(FAMILY, greedy=True)
            assert reason is None
            toks = list(eng.stream(req))
            snap = eng.snapshot()
        finally:
            eng.close()
        return toks, snap

    t0, s0 = serve(0)
    t1, s1 = serve(4)
    assert t0 == t1
    assert s0["session"]["counters"]["chunked_admissions"] == 0
    assert s1["session"]["counters"]["chunked_admissions"] == 1
    assert s1["counters"]["completed"] == 1


def test_engine_spec_greedy_stream_identical(tiny):
    """Engine with spec_k: greedy streams match the non-spec engine
    bit-for-bit (verify accepts the argmax chain), and non-greedy /
    short-budget submits are rejected up front — the verify rule
    compiles against static sampling params."""
    from nanorlhf_tpu.serving.engine import ServingEngine

    config, params = tiny

    def serve(spec_k):
        eng = ServingEngine(params, config, eos_token_id=EOS,
                            pad_token_id=PAD, page_size=4, prompt_len=TP,
                            max_new_tokens=MT, rows=2, seed=0,
                            spec_k=spec_k)
        try:
            if spec_k:
                with pytest.raises(ValueError, match="greedy"):
                    eng.submit(FAMILY, greedy=False)
                with pytest.raises(ValueError, match="greedy"):
                    eng.submit(FAMILY, greedy=True, max_tokens=2)
            req, reason = eng.submit(FAMILY, greedy=True)
            assert reason is None
            return list(eng.stream(req))
        finally:
            eng.close()

    assert serve(0) == serve(3)


def _serve_by_hand(tiny, impl):
    """A serving-mode session driven beat by beat: two rows at different
    depths, a third admitted mid-run, one released when its budget ends.
    Returns (tokens of each row, the session)."""
    import dataclasses

    from nanorlhf_tpu.sampler.paged.session import DecodeSession

    config, params = tiny
    config = dataclasses.replace(config, attention_impl=impl)
    sess = DecodeSession(
        params, config, rows=3, prompt_len=TP, max_tokens=MT, page_size=4,
        eos_token_id=config.vocab_size + 1, pad_token_id=PAD,
        key=jax.random.PRNGKey(0), greedy=True, per_row=True,
        prefix_cache=RadixCache(True, headroom=1.0), sync_every=2)

    def admit(r, real, budget):
        ids, mask = _left_pad([list(range(5, 5 + real))], TP)
        sess.admit(r, np.asarray(ids[0]), np.asarray(mask[0]), r,
                   budget=budget, temperature=1.0, top_p=1.0, greedy=True)

    admit(0, 8, 8)            # first real slot 4, seven decode steps
    admit(1, 3, 3)            # first real slot 9, two
    sess.step()
    admit(2, 12, 4)           # no padding, three, from the second beat
    done, _ = sess.step()
    assert done[1]
    sess.release(1)           # its table row is the sentinel from here on
    while not sess.step()[0].all():
        pass
    return np.asarray(sess.state[1]), sess


def test_in_place_read_serves_the_gathered_views_tokens(tiny):
    """ISSUE 28: the session's greedy tokens are the same whether the decode
    step gathers the rows' pages into a view (`"xla"`) or reads them from
    the stacked pool in place (the Pallas kernel, interpret mode here), with
    rows at different depths, one admitted mid-run and one released; and the
    host's count of what the kernel touches is exact: page 4, prompt width
    12, so row 0 reads blocks 1..3 for four steps and 1..4 for three, row 1
    blocks 2..3 twice, row 2 blocks 0..3 three times; seven steps of 3 rows
    x 5 blocks are what the view would have built."""
    view, s_view = _serve_by_hand(tiny, "xla")
    in_place, s_in_place = _serve_by_hand(tiny, "pallas")
    np.testing.assert_array_equal(in_place, view)
    assert (view[0] != PAD).all() and (view[1, :3] != PAD).all()
    for sess in (s_view, s_in_place):
        assert sess.attn_live_pages == (4 * 3 + 3 * 4) + 2 * 2 + 3 * 4 == 40
        assert sess.attn_table_pages == 7 * 3 * 5
    assert (s_view.attn_in_place, s_in_place.attn_in_place) == (0, 1)
    # ... cut into work items of four pages (ISSUE 61): a step a row is one
    # item here, short wherever the row reads two or three blocks; the view
    # has no items
    assert (s_in_place.paged_items, s_in_place.paged_short_items) == (
        7 + 2 + 3, 4 + 2)
    assert (s_view.paged_items, s_view.paged_short_items) == (0, 0)


# --------------------------------------------------------------------- #
# compose_check: the one legality matrix
# --------------------------------------------------------------------- #

ILLEGAL = [
    (dict(), True, "continuous batching"),
    (dict(page_size=4), True, "continuous batching"),
    (dict(prefill_chunk=4), False, "prefill_chunk"),
    (dict(page_size=4, prefill_chunk=4), False, "prefill_chunk"),
]

LEGAL = [
    dict(page_size=4, decode_rows=2, spec_k=3),
    dict(page_size=4, decode_rows=2, prefill_chunk=4, spec_k=3),
    dict(page_size=4, spec_k=3),
]


@pytest.mark.parametrize("kw,pc,match", ILLEGAL)
def test_compose_check_illegal(kw, pc, match):
    with pytest.raises(ValueError, match=match):
        compose_check(SamplingParams(**kw), prefix_cache=pc)


@pytest.mark.parametrize("kw", LEGAL)
def test_compose_check_legal(kw):
    compose_check(SamplingParams(**kw), prefix_cache=(
        kw.get("page_size", 0) > 0 and kw.get("decode_rows", 0) > 0))


# --------------------------------------------------------------------- #
# the beat pipelined one deep (ISSUE 35): chunk k+1 is dispatched before
# chunk k is read, and nothing the host then does changes a greedy stream
# --------------------------------------------------------------------- #

def _drive(tiny, requests, *, ahead, rows=2, prefill_chunk=0, sync_every=2,
           headroom=1.0, cancel=None):
    """The serving engine's loop by hand over a serving-mode session:
    requests `(real tokens, budget)` go into free rows in order, every beat
    dispatches a chunk while a row holds a request, and the host reads what
    the device owes it in the device's order, leaving the newest chunk
    unread when `ahead`. `cancel=(beat, request)` cancels that request's row
    after the beat's admissions. Returns (streams by request, session, log)."""
    from nanorlhf_tpu.sampler.paged.session import DecodeSession, FirstToken

    config, params = tiny
    sess = DecodeSession(
        params, config, rows=rows, prompt_len=TP, max_tokens=MT, page_size=4,
        eos_token_id=EOS, pad_token_id=PAD, key=jax.random.PRNGKey(0),
        greedy=True, per_row=True, prefill_chunk=prefill_chunk,
        prefix_cache=RadixCache(True, headroom=headroom),
        sync_every=sync_every)
    owner = [None] * rows
    streams = {q: [] for q in range(len(requests))}
    queue = list(range(len(requests)))
    log = {"beats": 0, "reused_under_flight": 0, "order": []}
    freed_under_flight = set()
    while queue or any(o is not None for o in owner) or sess.unread():
        for r in range(rows):
            if owner[r] is None and queue:
                q = queue.pop(0)
                real, budget = requests[q]
                ids, mask = _left_pad([real], TP)
                sess.admit(r, np.asarray(ids[0]), np.asarray(mask[0]), q,
                           budget=budget, temperature=1.0, top_p=1.0,
                           greedy=True)
                owner[r] = q
                if sess.unread() and freed_under_flight & set(
                        sess.table_np[r].tolist()):
                    log["reused_under_flight"] += 1
        if cancel is not None and cancel[0] == log["beats"]:
            r = owner.index(cancel[1])
            sess.cancel_row(r)
            owner[r] = None
        keep = 0
        if any(o is not None for o in owner):
            sess.dispatch()
            keep = int(ahead)
        if not sess.unread():
            freed_under_flight.clear()
        while sess.unread() > keep:
            got = sess.read()
            if isinstance(got, FirstToken):
                if owner[got.row] == got.index:
                    streams[got.index].append(got.token)
                    log["order"].append(("first", got.index))
                continue
            log["order"].append(("report", got.its))
            for r in np.flatnonzero(got.current):
                q = owner[r]
                if q is None or not streams[q]:
                    continue
                streams[q].extend(
                    got.new_tokens(r, len(streams[q])).tolist())
                if got.done[r]:
                    if sess.unread():
                        freed_under_flight |= {
                            int(pg) for pg in sess.table_np[r]
                            if pg < sess.num_pages}
                    sess.release(r)
                    owner[r] = None
        log["beats"] += 1
    return streams, sess, log


_LONG = [5, 6, 7, 8, 9, 10, 11, 12, 13, 14, 15]
LOOKAHEAD_CASES = {
    # more requests than rows, budgets that end with a chunk and inside one
    "admissions_and_releases": dict(requests=[
        (FAMILY, 8), ([11, 12, 13], 3), ([20, 21, 22, 23], 6), ([30, 31], 5),
        (FAMILY, 7), ([40, 41, 42], 1)]),
    # a long cold prompt admitted in pieces beside resident rows
    "chunked_admission": dict(prefill_chunk=4, requests=[
        ([20, 21, 22], 8), (_LONG, 6), ([30, 31], 4), (_LONG[:9], 5)]),
    "budget_ends_inside_a_chunk": dict(sync_every=4, requests=[
        (FAMILY, 2), ([11, 12, 13], 3), ([20, 21, 22, 23], 4), ([30, 31], 6),
        ([50, 51, 52, 53, 54], 7)]),
    # no spare page: an admission takes what a release just let go
    "pages_reused_under_flight": dict(headroom=0.0, rows=3, requests=[
        ([60 + i, 70 + i, 80 + i, 90 + i, 100 + i], 3 + i % 4)
        for i in range(9)]),
}


@pytest.fixture(scope="module")
def lookahead(tiny):
    return {name: (_drive(tiny, ahead=False, **kw),
                   _drive(tiny, ahead=True, **kw))
            for name, kw in LOOKAHEAD_CASES.items()}


@pytest.mark.parametrize("case", sorted(LOOKAHEAD_CASES))
def test_lookahead_streams_equal_the_serial_sessions(lookahead, case):
    """Greedy streams with a chunk always in flight are the serial
    session's, token for token; every request got its whole budget or
    ended on EOS; and the look-ahead runs did overlap."""
    (serial, s_serial, _), (ahead, s_ahead, log) = lookahead[case]
    assert ahead == serial
    for q, (_, budget) in enumerate(LOOKAHEAD_CASES[case]["requests"]):
        toks = ahead[q]
        assert len(toks) == budget or toks[-1] == EOS, (q, toks)
    assert s_serial.beats_overlapped == 0
    assert s_ahead.beats_overlapped > 0
    assert s_ahead.first_tokens_deferred == len(ahead)
    assert s_ahead.unread() == 0
    # the pool is whole again: nothing stranded under a flight
    for sess in (s_serial, s_ahead):
        assert (sess.table_np == sess.num_pages).all()
        snap = sess._radix.snapshot()
        assert snap["free_pages"] + snap["cached_pages"] == sess.num_pages


def test_lookahead_reuses_released_pages_under_the_flight(lookahead):
    """The case without a spare page really does hand a released row's
    pages to the next admission while a chunk that still held them in its
    table was unread, and a chunked admission's final piece queues its first
    token behind the chunk in flight."""
    _, (_, _, log) = lookahead["pages_reused_under_flight"]
    assert log["reused_under_flight"] > 0
    (_, s_serial, _), (_, s_ahead, _) = lookahead["chunked_admission"]
    assert s_serial.chunked_admissions == s_ahead.chunked_admissions == 2


@pytest.mark.parametrize("case", sorted(LOOKAHEAD_CASES))
def test_first_token_is_read_before_its_rows_first_chunk(lookahead, case):
    """Reads keep the device's order: a first token comes out once a
    request, in the order of admission, and always with a report between
    one beat's first tokens and the next beat's (the chunk dispatched
    between them). `_drive`, like the engine, takes nothing from a report
    for a row whose first token it has not read: were one read late, its
    row's tokens of that report would be missing from the stream."""
    _, (ahead, _, log) = lookahead[case]
    firsts = [e[1] for e in log["order"] if e[0] == "first"]
    assert firsts == sorted(ahead)
    reports = [e for e in log["order"] if e[0] == "report"]
    assert sum(its for _, its in reports) > 0
    assert log["order"][0] == ("first", 0)


def test_one_request_costs_the_lag_one_empty_chunk(tiny):
    """A lone request: the chunk dispatched before the host saw it end runs
    no iteration (`~all(done)`), which is the whole cost of the lag; nothing
    is dispatched once no row holds a request."""
    (serial, s_serial, l_serial) = _drive(tiny, [(FAMILY, 7)], ahead=False)
    (ahead, s_ahead, l_ahead) = _drive(tiny, [(FAMILY, 7)], ahead=True)
    assert ahead == serial and ahead[0]
    assert s_ahead.iterations() == s_serial.iterations()
    chunks = lambda s: s.timer.cumulative_counts["dispatch"]    # noqa: E731
    assert chunks(s_ahead) == chunks(s_serial) + 1
    assert [e for e in l_ahead["order"] if e[0] == "report"][-1] == (
        "report", 0)
    assert s_ahead.unread() == 0


@pytest.mark.parametrize("beat", [1, 2])
def test_cancel_under_flight_frees_the_row_for_the_next(tiny, beat):
    """A live row cancelled while a chunk that decodes it is in flight: its
    pages go back at once, the next request decodes in the same row and
    every other stream is what it is without the cancel."""
    requests = [(FAMILY, 8), ([11, 12, 13], 8), ([20, 21, 22, 23], 6),
                ([30, 31], 5)]
    whole, _, _ = _drive(tiny, requests, ahead=False)
    got, sess, _ = _drive(tiny, requests, ahead=True, cancel=(beat, 0))
    assert got[0] == whole[0][:len(got[0])] and len(got[0]) < len(whole[0])
    assert {q: got[q] for q in (1, 2, 3)} == {q: whole[q] for q in (1, 2, 3)}
    snap = sess._radix.snapshot()
    assert snap["free_pages"] + snap["cached_pages"] == sess.num_pages
    assert (sess.table_np == sess.num_pages).all()


@pytest.mark.parametrize("mode", ["rollout", "speculative"])
def test_serial_sessions_never_overlap(tiny, mode):
    """Which session looks ahead is what the session is: the rollout
    scheduler reads `done` off the carry to refill and speculation verifies
    against it, so neither may dispatch past an unread chunk, and neither
    does in a whole `generate` or behind a speculative engine."""
    from nanorlhf_tpu.sampler.paged.session import DecodeSession
    from nanorlhf_tpu.serving.engine import ServingEngine

    config, params = tiny
    kw = dict(rows=2, prompt_len=TP, max_tokens=MT, page_size=4,
              eos_token_id=EOS, pad_token_id=PAD, key=jax.random.PRNGKey(0),
              greedy=True, sync_every=2)
    assert DecodeSession(params, config, per_row=True,
                         prefix_cache=RadixCache(True), **kw).looks_ahead
    if mode == "rollout":
        sess = DecodeSession(params, config, **kw)
        assert not sess.looks_ahead
        ids, mask = _left_pad([FAMILY, [11, 12, 13]], TP)
        sess.bootstrap(ids, mask)
        while not sess.step()[0].all():
            assert sess.unread() == 0
        assert sess.beats_overlapped == 0
        return
    eng = ServingEngine(params, config, eos_token_id=EOS, pad_token_id=PAD,
                        page_size=4, prompt_len=TP, max_new_tokens=MT,
                        rows=2, seed=0, spec_k=3)
    try:
        assert not eng.session.looks_ahead
        reqs = [eng.submit(p, greedy=True)[0] for p in (FAMILY, [11, 12, 13])]
        assert all(list(eng.stream(r)) for r in reqs)
        m = eng.metrics()
    finally:
        eng.close()
    assert m["serving/beats_overlapped"] == 0 < m["serving/loop_beats"]
