"""The decode session hands its page pool on instead of copying it (ISSUE 30).

Every session program that takes the pool donates it wherever
`utils/donation.donate_argnums_on_accel` allows, which on the CPU backend is
nowhere (the persistent-cache fault its docstring records). So each case here
drives one mode of the session twice, as the rule leaves it and with the rule
answering as it does on a chip, the persistent compile cache off around that
(jax deletes a donated array on the CPU too, so a consumed array that is
touched again raises here as it would there), and holds the second run to the
first: the same tokens and captured logprobs bit for bit, nothing raises
"Array has been deleted", and the keys the session folds for every admission
outlive it. The compiled side (both pool leaves aliased input to output at
the cell's shapes, no copy of the pool left) is tests/test_chip_compile.py's.
"""

import contextlib
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from nanorlhf_tpu.core import ModelConfig, init_params
from nanorlhf_tpu.sampler import SamplingParams, generate
from nanorlhf_tpu.sampler.paged.session import DecodeSession
from nanorlhf_tpu.serving.engine import ServingEngine
from nanorlhf_tpu.serving.radix import RadixCache
from nanorlhf_tpu.utils import donation

EOS, PAD = 3, 0
TP, MT, PAGE = 12, 8, 4

# twelve real tokens, no padding: three pages of four
BASE = list(range(5, 17))
SHORT = [40, 41, 42]
# nine tokens of BASE, so the match ends inside the third page: a
# copy-on-write split, then a three-token suffix in one forward
HIT_COW = BASE[:9] + [70, 71, 72]
# six tokens of BASE: a split inside the second page, and a six-token suffix
# that a prefill chunk of four takes two beats over
HIT_COW_CHUNKED = BASE[:6] + list(range(60, 66))


@contextlib.contextmanager
def donation_as_on_a_chip():
    """The rule answers for an accelerator; no program built meanwhile is
    written to (or read back from) the persistent compile cache."""
    from jax.experimental.compilation_cache import compilation_cache

    rule = donation.donate_argnums_on_accel
    saved = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    donation.donate_argnums_on_accel = lambda *nums, platform=None: nums
    try:
        yield
    finally:
        donation.donate_argnums_on_accel = rule
        jax.config.update("jax_enable_compilation_cache", saved)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def tiny():
    config = ModelConfig.qwen2_tiny(vocab_size=128)
    return config, init_params(config, jax.random.PRNGKey(7), jnp.float32)


def _padded(real, width=TP):
    ids = np.full(width, PAD, np.int32)
    ids[width - len(real):] = real
    return ids, ids != PAD


class Poller:
    """A second thread that reads what other threads may read, in a loop,
    for as long as the `with` block runs."""

    def __init__(self, read):
        self.read, self.reads, self.errors = read, 0, []
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True)

    def _run(self):
        while not self._stop.is_set():
            try:
                self.read()
                self.reads += 1
            except Exception as e:  # noqa: BLE001 — any failure fails the test
                self.errors.append(repr(e))
                return

    def __enter__(self):
        self._thread.start()
        return self

    def __exit__(self, *exc):
        self._stop.set()
        self._thread.join(timeout=30)
        assert not self._thread.is_alive()
        assert not self.errors, self.errors
        assert self.reads > 0


def serving_by_hand(tiny, donated):
    """A serving-mode session under the radix cache with chunked prefill,
    beat by beat: a cold admission over three prefill beats, a cold one in
    one forward, a cancel, a prefix hit with a copy-on-write split, a hit
    whose suffix is chunked, releases; `status()` polled from another thread
    throughout."""
    config, params = tiny
    key, admit_key = jax.random.PRNGKey(0), jax.random.PRNGKey(1)
    radix = RadixCache(True, headroom=1.0)
    sess = DecodeSession(
        params, config, rows=3, prompt_len=TP, max_tokens=MT, page_size=PAGE,
        eos_token_id=config.vocab_size + 1, pad_token_id=PAD, key=key,
        admit_key=admit_key, greedy=True, per_row=True, prefix_cache=radix,
        prefill_chunk=4, sync_every=2)
    assert sess.pool_donated == int(donated)
    first = {}

    def admit(r, real, budget, index):
        ids, mask = _padded(real)
        first[index] = sess.admit(r, ids, mask, index, budget=budget,
                                  temperature=1.0, top_p=1.0, greedy=True)

    def read():
        before = sess.iterations()
        status = sess.status()
        assert 0 <= status["live_rows"] <= 3
        assert (before <= status["counters"]["decode_iterations"]
                <= sess.iterations())

    with Poller(read):
        admit(0, BASE, 8, 0)                  # chunked: 4 + 4, then the rest
        assert first[0] is None and sess.is_pending(0)
        pool = sess.state[3]
        admit(1, SHORT, 8, 1)                 # one suffix forward
        assert pool[0].is_deleted() == donated    # an admission consumed it
        pool, out = sess.state[3], sess.state[1]
        sess.step()
        assert pool[1].is_deleted() == donated    # and so did a beat,
        assert out.is_deleted() == donated        # with the rest of the carry
        cancelled = np.asarray(sess.state[1][1]).copy()
        sess.cancel_row(1)
        while sess.has_pending():
            sess.step()
        admit(2, HIT_COW, 4, 2)
        admit(1, HIT_COW_CHUNKED, 5, 3)
        assert first[3] is None
        done = sess.step()[0]
        while sess.has_pending() or not done.all():
            done = sess.step()[0]
        for r in range(3):
            sess.release(r)
    snap = radix.snapshot()
    assert snap["cow_splits"] == 2 and sess.hit_tokens == 9 + 6
    assert sess.chunked_admissions == 2
    assert sess.status()["live_rows"] == 0
    assert not key.is_deleted() and not admit_key.is_deleted()
    assert not sess._admit_key.is_deleted()
    return {"out": np.asarray(sess.state[1]), "n_gen": np.asarray(sess.state[7]),
            "cancelled": cancelled, "first": [first[1], first[2]],
            "iterations": sess.iterations()}


def engine_and_gateway_thread(tiny, donated):
    """The engine's loop thread owns the session; this thread streams four
    requests (one prompt long enough to prefill in chunks, two that share
    its prefix) while a third polls `snapshot()` and `metrics()`."""
    config, params = tiny
    eng = ServingEngine(params, config, eos_token_id=EOS, pad_token_id=PAD,
                        page_size=PAGE, prompt_len=TP, max_new_tokens=MT,
                        rows=2, seed=0, prefill_chunk=4)
    try:
        def read():
            assert eng.snapshot()["session"]["rows"] == 2
            assert eng.metrics()["serving/pool_donated"] == int(donated)

        with Poller(read):
            streams = []
            for prompt in (BASE, SHORT, HIT_COW, HIT_COW_CHUNKED):
                req, reason = eng.submit(prompt, greedy=True)
                assert reason is None
                streams.append(req)
            streams = [list(eng.stream(req, timeout=60)) for req in streams]
        counters = eng.snapshot()["counters"]
    finally:
        eng.close()
    assert counters["completed"] == 4 and all(streams)
    return {f"stream{i}": np.asarray(s) for i, s in enumerate(streams)}


def queued_rollout(tiny, donated, **sampling):
    """`generate()` through the queued scheduler: six prompts over two
    rows, so every row is released and admitted again mid-loop."""
    config, params = tiny
    radix = sampling.pop("radix", False)
    prompts = [BASE, SHORT, HIT_COW, BASE, HIT_COW_CHUNKED, SHORT + [9]]
    ids = jnp.asarray(np.stack([_padded(p)[0] for p in prompts]))
    key = jax.random.PRNGKey(5)
    sp = SamplingParams(max_tokens=MT, greedy=True, page_size=PAGE,
                        decode_rows=2, temperature=1.0, top_p=0.9, **sampling)
    stats, spec_stats = [], []
    out = generate(params, config, ids, ids != PAD, key, sp,
                   eos_token_id=EOS, pad_token_id=PAD, paged_stats_out=stats,
                   spec_stats_out=spec_stats,
                   prefix_cache=RadixCache() if radix else None)
    assert not key.is_deleted()
    assert stats[0]["session"]["live_rows"] == 0
    got = {"stats": np.asarray([stats[0]["decode_iterations"],
                                stats[0]["dispatch_events"],
                                stats[0]["chunked_admissions"]])}
    if sp.capture_logprobs:
        got["tokens"], got["logprobs"] = map(np.asarray, out)
    else:
        got["tokens"] = np.asarray(out)
    if spec_stats:      # read after the session's last donating call
        got["accepted"] = np.asarray(spec_stats[0]["accepted"])
    return got


def env_driver(tiny, donated):
    """envs/rollout.py drives `_admit_one`, `_install_row` and
    `_decode_chunk` over a carry of its own."""
    from test_envs import EchoEnv, _run_driver, text_reward

    _, out = _run_driver(EchoEnv(text_reward, max_turns=2), greedy=True)
    assert out["admissions"] == 4
    return {k: np.asarray(out[k]) for k in ("tokens", "loss_mask",
                                            "turn_ends")}


def olmoe_session(tiny, donated):
    """The expert model's paged session, as tests/test_moe.py drives it."""
    from test_moe import PAD as MOE_PAD, V, tiny as olmoe, tokens, weights

    cfg = olmoe()
    prompts = tokens(rows=3, T=8)
    sess = DecodeSession(
        weights(cfg), cfg, rows=3, prompt_len=8, max_tokens=6, page_size=4,
        eos_token_id=V + 5, pad_token_id=MOE_PAD, key=jax.random.PRNGKey(0),
        greedy=True, capture_logprobs=True, sync_every=2)
    sess.bootstrap(prompts, prompts != MOE_PAD)
    pool = sess.state[3]
    while not sess.step()[0].all():
        pass
    assert pool[0].is_deleted() == donated
    return {"tokens": np.asarray(sess.state[1]),
            "logprobs": np.asarray(sess.state[2])}


MODES = {
    "serving-radix-chunked-cow-cancel": serving_by_hand,
    "engine-polled-from-another-thread": engine_and_gateway_thread,
    "rollout-bootstrap-logprobs": lambda t, d: queued_rollout(
        t, d, capture_logprobs=True),
    "rollout-bootstrap-chunked": lambda t, d: queued_rollout(
        t, d, capture_logprobs=True, prefill_chunk=4),
    "rollout-radix-chunked-logprobs": lambda t, d: queued_rollout(
        t, d, capture_logprobs=True, radix=True, prefill_chunk=4),
    "speculative-carry": lambda t, d: queued_rollout(t, d, spec_k=3),
    "speculative-seeded-under-radix": lambda t, d: queued_rollout(
        t, d, spec_k=3, radix=True),
    "env-episode-driver": env_driver,
    "olmoe-bootstrap-logprobs": olmoe_session,
}


@pytest.mark.parametrize("mode", MODES)
def test_donating_session_equals_the_copying_one(tiny, mode):
    drive = MODES[mode]
    want = drive(tiny, False)
    with donation_as_on_a_chip():
        got = drive(tiny, True)
    assert want.keys() == got.keys()
    for name in want:
        np.testing.assert_array_equal(got[name], want[name], err_msg=name)


def test_a_consumed_pool_fails_loudly(tiny):
    """What is left of a session whose donating call did not hand a pool
    back: the next program that is given the carry raises, nothing decodes
    on freed pages. (The call itself is played here by deleting the pool,
    which is what a donating call does to its argument before it fails.)"""
    config, params = tiny
    sess = DecodeSession(
        params, config, rows=2, prompt_len=TP, max_tokens=MT, page_size=PAGE,
        eos_token_id=EOS, pad_token_id=PAD, key=jax.random.PRNGKey(0),
        greedy=True, per_row=True, prefix_cache=RadixCache(True))
    ids, mask = _padded(BASE)
    sess.admit(0, ids, mask, 0, budget=4, temperature=1.0, top_p=1.0,
               greedy=True)
    for leaf in sess.state[3]:
        leaf.delete()
    with pytest.raises(RuntimeError, match="deleted"):
        sess.step()
    with pytest.raises(RuntimeError, match="deleted"):
        sess.admit(1, ids, mask, 1, budget=4, temperature=1.0, top_p=1.0,
                   greedy=True)
    assert sess.status()["rows"] == 2       # the host's record still reads


def test_the_rule_looks_at_where_the_pool_lives():
    """`jit_donating` asks the one rule about the platform of the donated
    argument's devices: a CPU array is not donated, a program lowered for a
    described accelerator is, whatever the default backend."""
    cpu = jnp.zeros((2,))
    assert donation.platform_of((cpu, None)) == "cpu"
    assert donation.platform_of(()) == jax.default_backend()
    assert donation.donate_argnums_on_accel(0, 2) == ()
    assert donation.donate_argnums_on_accel(0, 2, platform="tpu") == (0, 2)

    add = donation.jit_donating(lambda a, b: a + b, donate=0)
    assert not add.donates(cpu)
    assert float(add(cpu, cpu)[0]) == 0.0 and not cpu.is_deleted()
    with donation_as_on_a_chip():
        assert add.donates(cpu)
        add(cpu, jnp.ones((2,)))
        assert cpu.is_deleted()
