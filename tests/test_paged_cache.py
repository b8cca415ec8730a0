"""Paged KV cache + continuous batching (sampler/paged/, ISSUE 10).

Pins the acceptance contract: allocator invariants under jit, the paged
Pallas kernels vs their gather-then-reference XLA oracles (f32 + int8 +
k-query verify), greedy bit-parity of the monolithic paged layout vs the
contiguous cache on the CPU mesh (page size dividing AND not dividing the
logical width), composition with speculative decode / int8 / shared-prefill
fanout, the continuous-batching scheduler finishing a long-tail corpus in
STRICTLY fewer decode iterations than the fixed-batch schedule while
emitting identical greedy rows, and the trainer wiring (rollout/page_*
metric rows, checkpoint/resume over the paged rollout path).

The long-tail oracle reuses test_speculative's "cycle model": a Markov
chain over single tokens, so each row's greedy length is constructed by
hand and the fixed-batch iteration count is analytic (per batch: longest
row minus one).
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from nanorlhf_tpu.core import ModelConfig, init_params
from nanorlhf_tpu.sampler import SamplingParams, generate
from nanorlhf_tpu.sampler.paged.pages import (
    PageState, alloc_row, blocks_per_row, full_table, init_page_state,
    release_row,
)

EOS, PAD = 3, 0


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


def _gen(model, key=0, max_tokens=19, prompts=None, stats=None, **kw):
    cfg, params = model
    ids, mask = prompts if prompts is not None else _left_pad(
        [[5, 6, 7, 8], [PAD, 9, 10], [11, 12, 13, 14]], 5
    )
    sp = SamplingParams(max_tokens=max_tokens, **kw)
    return generate(params, cfg, ids, mask, jax.random.PRNGKey(key), sp,
                    eos_token_id=EOS, pad_token_id=PAD,
                    paged_stats_out=stats)


# --------------------------------------------------------------------- #
# allocator: free-list/block-table invariants, fully jitted
# --------------------------------------------------------------------- #

def test_allocator_invariants_under_jit():
    N, R, nb = 12, 4, 3
    alloc = jax.jit(alloc_row)
    release = jax.jit(release_row)
    st = init_page_state(N, R, nb)
    assert int(st.top) == N and (np.asarray(st.table) == N).all()

    # allocate all four rows: every page handed out exactly once
    for r in range(R):
        st, ok = alloc(st, r, nb)
        assert bool(ok)
    tab = np.asarray(st.table)
    assert int(st.top) == 0
    assert sorted(tab.ravel().tolist()) == list(range(N))

    # exhausted pool: ok=False and the state is UNCHANGED
    st2, ok = alloc(st, 0, 1)
    assert not bool(ok)
    np.testing.assert_array_equal(np.asarray(st2.table), tab)
    assert int(st2.top) == int(st.top)

    # release row 2, realloc into row 1's old slot: the SAME pages come back
    freed = set(tab[2].tolist())
    st, m = release(st, 2)
    assert int(m) == nb and int(st.top) == nb
    assert (np.asarray(st.table)[2] == N).all()
    # idempotent: releasing a sentinel row is a no-op
    st3, m2 = release(st, 2)
    assert int(m2) == 0 and int(st3.top) == nb
    st, ok = alloc(st, 2, nb)
    assert bool(ok) and set(np.asarray(st.table)[2].tolist()) == freed

    # partial allocation (traced n_blocks < nb): sentinel tail on the row
    st = init_page_state(N, R, nb)
    st, ok = alloc(st, 1, jnp.int32(2))
    row = np.asarray(st.table)[1]
    assert bool(ok) and int(st.top) == N - 2
    assert (row[:2] < N).all() and row[2] == N


def test_blocks_per_row_and_full_table():
    assert blocks_per_row(24, 8) == 3 and blocks_per_row(25, 8) == 4
    t = np.asarray(full_table(3, 2))
    np.testing.assert_array_equal(t, [[0, 1], [2, 3], [4, 5]])


# --------------------------------------------------------------------- #
# paged kernels vs XLA oracles (interpret mode off-TPU)
# --------------------------------------------------------------------- #

def _scattered_pool(rng, B, KV, hd, P, nb, extra=2):
    """Pool whose pages are a random permutation (plus one sentinel block),
    with the logical contiguous view returned for cross-checking."""
    N = B * nb + extra
    perm = rng.permutation(N - 1)[: B * nb].reshape(B, nb).astype(np.int32)
    perm[0, -1] = N                       # released block on row 0
    T = nb * P
    k_log = rng.standard_normal((B, KV, T, hd)).astype(np.float32)
    v_log = rng.standard_normal((B, KV, T, hd)).astype(np.float32)
    k_pool = np.zeros((N, KV, P, hd), np.float32)
    v_pool = np.zeros((N, KV, P, hd), np.float32)
    for b in range(B):
        for j in range(nb):
            if perm[b, j] < N:
                k_pool[perm[b, j]] = k_log[b, :, j * P:(j + 1) * P, :]
                v_pool[perm[b, j]] = v_log[b, :, j * P:(j + 1) * P, :]
    return (jnp.asarray(perm), jnp.asarray(k_pool), jnp.asarray(v_pool),
            N, T)


# rows of the in-place kernel's parity case, each (start, filled, live):
# the layouts one serving step mixes. P = 8, five blocks a row
_PAGED_ROWS = [
    (0, 17, True),     # from slot 0, ends inside its third page
    (3, 24, True),     # start inside a page, filled on a page boundary
    (9, 10, True),     # filled - start = 1
    (16, 40, False),   # done: the caller discards it, the kernel skips it
    (2, 20, True),     # released: every table entry the sentinel
    (11, 11, True),    # empty range
    (8, 33, True),     # shares its first two pages with the next row
    (8, 29, True),
    (0, 40, True),     # all five blocks: two items when an item is four pages
]


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 2e-2)],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("layer", [0, 1, 2])
@pytest.mark.parametrize("KV,G", [(2, 6), (16, 1), (4, 7), (8, 6), (4, 32)],
                         ids=["gqa2x6", "mha16", "gqa4x7", "gqa8x6", "block4x32"])
@pytest.mark.parametrize("item_pages,tile_rows,items", [
    (1, 4, [3, 3, 1, 0, 0, 0, 4, 3, 5]), (4, 9, [1, 1, 1, 0, 0, 0, 1, 1, 2]),
    (2, 4, [2, 2, 1, 0, 0, 0, 2, 2, 3])],
    ids=["1page-3tiles", "4pages-1tile", "2pages-3tiles"])
def test_paged_decode_kernel_matches_oracle(rng, monkeypatch, item_pages,
                                            tile_rows, items, KV, G, layer,
                                            dtype, tol):
    """The in-place read (whole stacks in, the layer a scalar, the step's
    work list from `paged_decode_plan`) against the gathered-view oracle on
    the layer's slab: Qwen's and OLMoE's head geometry, SmallThinker's,
    Trinity's and SDAR's block read (a block's 4 positions x 8 heads a KV
    head), each layer of a stack, scattered and shared pages, a sentinel
    past a row's last block, and rows without work (done, released, empty)
    among live ones, which read zero; with a work item of one page (what
    OLMoE's 512 KB pages get on the chip), of two (Trinity's) and of four
    (Qwen2.5's), the last item of a row short; with the nine rows in one
    tile and in three (the last one padded; with items of two pages the
    middle tile's first row has no item and its last row's last item is
    short)."""
    from nanorlhf_tpu.ops import decode_attention
    from nanorlhf_tpu.ops.decode_attention import (
        paged_decode_attention, paged_decode_plan, paged_pages_per_item,
        reference_paged_decode_attention,
    )

    monkeypatch.setattr(decode_attention, "_PAGED_ITEM_PAGES", item_pages)
    # one item a step of the kernel's loop, what every pool but
    # Qwen2.5-1.5B's gets on the chip (at these sizes every item is small);
    # several a step are tests/test_decode_attention.py's (ISSUE 61)
    monkeypatch.setattr(decode_attention, "_PAGED_STEP_ITEMS", 1)
    sub = 32 // jnp.dtype(dtype).itemsize
    row_bytes = KV * sub * -(-G // sub) * 16 * jnp.dtype(dtype).itemsize
    monkeypatch.setattr(decode_attention, "_PAGED_TILE_BYTES",
                        tile_rows * row_bytes)

    L, hd, P, nb = 3, 16, 8, 5
    B = len(_PAGED_ROWS)
    N = B * nb + 2
    table = rng.permutation(N)[: B * nb].reshape(B, nb).astype(np.int32)
    table[0, -1] = N                      # unallocated tail of a live row
    table[4, :] = N                       # a released row
    table[7, :2] = table[6, :2]           # a shared prefix
    table = jnp.asarray(table)
    k_pool, v_pool = (
        jnp.asarray(rng.standard_normal((L, N, KV, P, hd)), dtype)
        for _ in range(2))
    q = jnp.asarray(rng.standard_normal((B, KV * G, hd)), dtype)
    start, filled, live = (jnp.asarray(c) for c in zip(*_PAGED_ROWS))
    start, filled = start.astype(jnp.int32), filled.astype(jnp.int32)

    assert paged_pages_per_item(k_pool) == item_pages
    plan = paged_decode_plan(table, start, filled, page_size=P, num_pages=N,
                             pages_per_item=item_pages, live=live)
    np.testing.assert_array_equal(np.diff(np.asarray(plan.row_off)), items)
    got = np.asarray(paged_decode_attention(
        q, k_pool, v_pool, jnp.int32(layer), plan, interpret=True),
        np.float32)
    want = np.asarray(reference_paged_decode_attention(
        q, k_pool[layer], v_pool[layer], table, start, filled), np.float32)
    works = np.asarray([0, 1, 2, 6, 7, 8])
    np.testing.assert_allclose(got[works], want[works], rtol=tol, atol=tol)
    assert not got[[3, 4, 5]].any()


def test_paged_decode_q8_kernel_matches_oracle(rng):
    from nanorlhf_tpu.ops.decode_attention import (
        paged_decode_attention_q8, reference_paged_decode_attention_q8,
    )

    B, KV, G, hd, P, nb = 3, 2, 4, 16, 8, 4
    table, _, _, N, T = _scattered_pool(rng, B, KV, hd, P, nb)
    q = jnp.asarray(rng.standard_normal((B, KV * G, hd)).astype(np.float32))
    kq = jnp.asarray(rng.integers(-127, 127, (N, KV, P, hd)).astype(np.int8))
    vq = jnp.asarray(rng.integers(-127, 127, (N, KV, P, hd)).astype(np.int8))
    ks = jnp.asarray(rng.uniform(0.005, 0.02, (N, KV, 8, P)).astype(np.float32))
    vs = jnp.asarray(rng.uniform(0.005, 0.02, (N, KV, 8, P)).astype(np.float32))
    start = jnp.asarray([0, 2, 7], jnp.int32)
    filled = jnp.asarray([13, 24, 19], jnp.int32)
    want = reference_paged_decode_attention_q8(q, kq, ks, vq, vs, table,
                                               start, filled)
    got = paged_decode_attention_q8(q, kq, ks, vq, vs, table, start, filled,
                                    interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_paged_verify_kernel_matches_oracle(rng):
    from nanorlhf_tpu.ops.decode_attention import (
        paged_decode_verify_attention,
        reference_paged_decode_verify_attention,
    )

    B, KV, G, hd, P, nb, Tq = 3, 2, 4, 16, 8, 5, 4
    table, k_pool, v_pool, N, T = _scattered_pool(rng, B, KV, hd, P, nb)
    q = jnp.asarray(
        rng.standard_normal((B, KV * G, Tq, hd)).astype(np.float32))
    start = jnp.asarray([0, 3, 9], jnp.int32)
    fill = jnp.asarray([10, 22, 15], jnp.int32)   # row 1 straddles a page
    want = reference_paged_decode_verify_attention(q, k_pool, v_pool, table,
                                                   start, fill)
    got = paged_decode_verify_attention(q, k_pool, v_pool, table, start,
                                        fill, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# --------------------------------------------------------------------- #
# monolithic paged layout: bit-parity with the contiguous cache
# --------------------------------------------------------------------- #

@pytest.mark.parametrize("page_size", [8, 5])  # 8 | 24 = Tp+max_tokens; 5 ∤
def test_greedy_paged_bit_identical(tiny, page_size):
    mono = _gen(tiny, greedy=True)
    paged = _gen(tiny, greedy=True, page_size=page_size)
    np.testing.assert_array_equal(np.asarray(mono), np.asarray(paged))


def test_paged_capture_logprobs_bit_identical(tiny):
    mt, mlp = _gen(tiny, greedy=True, capture_logprobs=True)
    pt, plp = _gen(tiny, greedy=True, capture_logprobs=True, page_size=8)
    np.testing.assert_array_equal(np.asarray(mt), np.asarray(pt))
    np.testing.assert_array_equal(np.asarray(mlp), np.asarray(plp))


def test_paged_int8_kv_cache_bit_identical(tiny):
    cfg, params = tiny
    q_model = (dataclasses.replace(cfg, kv_cache_quant="int8"), params)
    mono = _gen(q_model, greedy=True)
    for P in (8, 5):
        paged = _gen(q_model, greedy=True, page_size=P)
        np.testing.assert_array_equal(np.asarray(mono), np.asarray(paged))


def test_paged_spec_matches_monolithic(tiny):
    """spec_k composes with page_size: paged verify writes land through the
    block table and the greedy stream still equals the plain monolithic
    loop (greedy spec is bit-exact, paged is a pure re-layout)."""
    mono = _gen(tiny, greedy=True)
    for P in (8, 5):
        paged = _gen(tiny, greedy=True, spec_k=3, page_size=P)
        np.testing.assert_array_equal(np.asarray(mono), np.asarray(paged))


def test_paged_shared_prefill_fanout_bit_identical(tiny):
    prompts = _left_pad([[5, 6, 7], [9, 10, 11]], 4)
    mono = _gen(tiny, greedy=True, n=2, prompts=prompts)
    paged = _gen(tiny, greedy=True, n=2, page_size=8, prompts=prompts)
    assert paged.shape == (4, 19)
    np.testing.assert_array_equal(np.asarray(mono), np.asarray(paged))


def test_paged_sampled_stream_bit_identical(tiny):
    """Sampled (non-greedy) monolithic paged: the logits are bit-identical,
    so the SAME key draws the SAME stream."""
    mono = _gen(tiny, key=11, temperature=0.9)
    paged = _gen(tiny, key=11, temperature=0.9, page_size=8)
    np.testing.assert_array_equal(np.asarray(mono), np.asarray(paged))


def test_cache_extra_gated_to_contiguous(tiny):
    """The spec path's cache_extra slack must NOT inflate the paged pool:
    pool pages == B * ceil((Tp+max_tokens)/P) exactly, slack or not —
    over-budget verify writes drop at the table-routed scatter instead."""
    from nanorlhf_tpu.sampler.sampler import _prefill_state

    cfg, params = tiny
    ids, mask = _left_pad([[5, 6, 7, 8]], 5)
    kw = dict(max_tokens=7, eos_token_id=EOS, pad_token_id=PAD,
              temperature=1.0, top_p=0.95, greedy=True, lora_scale=1.0,
              top_k=64, capture_logprobs=False, approx_top_k=True)
    P = 4
    nb = blocks_per_row(5 + 7, P)
    state = _prefill_state(params, cfg, ids, mask, jax.random.PRNGKey(0),
                           cache_extra=3, page_size=P, **kw)
    assert state[3][0].shape[1] == 1 * nb       # pool pages, NO slack
    assert state[4].shape[1] == 5 + 7           # key_mask width, NO slack
    contig = _prefill_state(params, cfg, ids, mask, jax.random.PRNGKey(0),
                            cache_extra=3, **kw)
    assert contig[4].shape[1] == 5 + 7 + 3      # contiguous keeps the slack


# --------------------------------------------------------------------- #
# continuous batching: long-tail corpus, strictly fewer iterations
# --------------------------------------------------------------------- #

def _chain_model():
    """Markov chains: v -> v+1 -> ... -> 30 -> EOS, so a prompt ending in
    token v generates exactly (30 - v) + 1 tokens greedily. Long-tail
    lengths are then just a choice of start tokens."""
    from tests.test_speculative import cycle_model

    sigma = list(range(32))
    for t in range(10, 30):
        sigma[t] = t + 1
    sigma[30] = EOS
    return cycle_model(sigma, vocab=32)


def _chain_prompts(starts, Tp=2):
    return _left_pad([[9, v] for v in starts], Tp)


def test_queued_long_tail_fewer_iterations_same_tokens():
    """The acceptance gate: one straggler per R-row wave. The fixed-batch
    schedule pays (longest row - 1) decode iterations PER WAVE; the
    scheduler backfills finished rows mid-loop and must land strictly
    under that — while emitting exactly the monolithic greedy rows."""
    model = _chain_model()
    # lengths 20, 3, 18, 4, 16, 3, 14, 5 (start v -> 31 - v tokens)
    starts = [11, 28, 13, 27, 15, 28, 17, 26]
    lengths = [31 - v for v in starts]
    prompts = _chain_prompts(starts)
    R, max_tokens = 2, 24

    mono = _gen(model, greedy=True, max_tokens=max_tokens, prompts=prompts)
    stats = []
    queued = _gen(model, greedy=True, max_tokens=max_tokens, prompts=prompts,
                  page_size=4, decode_rows=R, stats=stats)
    np.testing.assert_array_equal(np.asarray(mono), np.asarray(queued))

    # analytic fixed-batch schedule at the same resident-batch size R
    fixed_iters = sum(max(lengths[i:i + R]) - 1
                      for i in range(0, len(lengths), R))
    st = stats[0]
    assert st["decode_iterations"] < fixed_iters, (
        f"queued {st['decode_iterations']} >= fixed {fixed_iters}")
    assert st["admitted_midloop"] == len(starts) - R
    assert st["pages_recycled"] > 0
    assert 0.0 < st["page_utilization"] <= 1.0
    # every admission names a valid resident row and queue entry
    for adm in st["admissions"]:
        assert 0 <= adm["row"] < R
        assert R <= adm["queue_index"] < len(starts)


def test_queued_spec_composes_and_matches():
    """spec_k over the recycled pool: same greedy rows, and the verify
    dispatch count lands under the plain queued iteration count on the
    self-repetitive tail (the drafter pays off mid-queue too)."""
    from tests.test_speculative import cycle_model

    sigma = list(range(16))
    sigma[5], sigma[6], sigma[7], sigma[8] = 6, 7, 8, 5   # 4-cycle, no EOS
    model = cycle_model(sigma)
    prompts = _left_pad([[5, 6, 7, 8, 5]] * 6, 6)
    mono = _gen(model, greedy=True, max_tokens=24, prompts=prompts)
    plain_stats, spec_stats = [], []
    q_plain = _gen(model, greedy=True, max_tokens=24, prompts=prompts,
                   page_size=4, decode_rows=2, stats=plain_stats)
    q_spec = _gen(model, greedy=True, max_tokens=24, prompts=prompts,
                  page_size=4, decode_rows=2, spec_k=4, stats=spec_stats)
    np.testing.assert_array_equal(np.asarray(mono), np.asarray(q_plain))
    np.testing.assert_array_equal(np.asarray(mono), np.asarray(q_spec))
    assert (spec_stats[0]["decode_iterations"]
            < plain_stats[0]["decode_iterations"])


def test_queued_sampled_rows_terminate_and_fill_contract():
    """Sampled queued rollouts: not bit-pinned (admission re-keys rows),
    but every row must satisfy the output contract — tokens before the
    first PAD, nothing after an EOS, shapes exact."""
    cfg = ModelConfig.qwen2_tiny(vocab_size=64)
    params = init_params(cfg, jax.random.PRNGKey(3), jnp.float32)
    prompts = _left_pad([[5 + i, 6, 7] for i in range(7)], 4)
    out = _gen((cfg, params), key=5, max_tokens=10, prompts=prompts,
               temperature=1.0, page_size=4, decode_rows=3)
    rows = np.asarray(out)
    assert rows.shape == (7, 10)
    for r in rows:
        if EOS in r.tolist():
            e = r.tolist().index(EOS)
            assert (r[e + 1:] == PAD).all()


# --------------------------------------------------------------------- #
# trainer wiring: metrics rows + checkpoint/resume over the paged path
# --------------------------------------------------------------------- #

# --------------------------------------------------------------------- #
# the loop's own choice of layout (ISSUE 52): page_size == 0 under
# `core/model.decode_loop_page_size`
# --------------------------------------------------------------------- #

@pytest.fixture(scope="module")
def wide():
    """Heads of 128 lanes, what the in-place kernels take: a dense model
    and an expert model of one kind of layer."""
    models = {}
    for name, config in (("dense", ModelConfig.qwen2_tiny(vocab_size=128)),
                         ("olmoe", ModelConfig.olmoe_tiny(vocab_size=128))):
        config = dataclasses.replace(config, head_dim=128)
        models[name] = (config, init_params(config, jax.random.PRNGKey(7),
                                            jnp.float32))
    return models


# three rows whose left pads end in block 0, 1 and 1 of pages of 128
_OWN_TP, _OWN_NEW, _OWN_LENS = 160, 130, (160, 20, 3)


@pytest.mark.parametrize("case", ["greedy", "sampled_with_one_key",
                                  "fanout_4", "olmoe_greedy"])
def test_the_loops_own_pages_emit_the_contiguous_loops_tokens(wide, case):
    """`generate` with `page_size == 0` where the predicate says pages
    (`attention_impl="pallas"`: the kernels interpreted) runs ONE loop over
    pools of 128-slot pages under the identity table, reads each row's own
    pages in place and writes by slice, and emits the
    tokens of the contiguous loop (`"xla"`: the extents) bit for bit, greedy,
    sampled with one key and fanned out by 4, the captured logprobs equal to
    float32 roundoff (an online softmax over pages against one over the
    extent); `attn_read_frac` is the pages a count by hand gives,
    `kv_in_place` 1, and the paged stats say what the pool held."""
    from nanorlhf_tpu.core import model as M
    from nanorlhf_tpu.sampler import sampler as S

    config, params = wide["olmoe" if case.startswith("olmoe") else "dense"]
    pages = dataclasses.replace(config, attention_impl="pallas")
    plain = dataclasses.replace(config, attention_impl="xla")
    assert M.decode_loop_page_size(pages) == 128
    assert M.decode_loop_page_size(plain) == M.decode_loop_page_size(config) == 0
    Tp, new = _OWN_TP, _OWN_NEW
    ids, mask = _left_pad([list(range(5, 5 + n)) for n in _OWN_LENS], Tp)
    n = {"fanout_4": 4, "sampled_with_one_key": 1}.get(case, 2)
    sampling = SamplingParams(
        greedy="greedy" in case, temperature=0.9, n=n, max_tokens=new,
        capture_logprobs=True)
    key = jax.random.PRNGKey(11)
    want, want_lp = generate(params, plain, ids, mask, key, sampling,
                             eos_token_id=-1, pad_token_id=PAD)
    stats = []
    got, got_lp = generate(params, pages, ids, mask, key, sampling,
                           eos_token_id=-1, pad_token_id=PAD,
                           paged_stats_out=stats)
    got = np.asarray(got)
    np.testing.assert_array_equal(got, np.asarray(want))
    np.testing.assert_allclose(np.asarray(got_lp), np.asarray(want_lp),
                               rtol=0, atol=1e-4)
    assert got.shape == (3 * n, new)
    # one loop over the pages, one an extent over the contiguous cache
    assert S._read_loops(pages, Tp, new) == [(Tp + new, new)]
    assert S._read_loops(plain, Tp, new) == [(256, 97), (Tp + new, new)]
    # by hand: steps 1 .. 129 write slots 160 .. 288: block 1 for 96 steps,
    # block 2 for 33; the row without a pad reads from block 0, the others'
    # pads end in block 1; of 3 pages a row
    assert (-(-(Tp + new) // 128), new - 1) == (3, 129)
    by_hand = n * ((2 * 96 + 3 * 33) + 2 * (1 * 96 + 2 * 33))
    lens = np.asarray(_OWN_LENS)
    frac = S.attn_read_frac(pages, sampling, Tp, got, -1, prompt_lens=lens)
    assert frac == pytest.approx(by_hand / (129 * 3 * n * 3))
    assert S.kv_in_place(pages, sampling, 3 * n) == 1
    # ... cut into work items (ISSUE 61): float32 pages of 2 KV heads go
    # four an item, of 4 heads two; under four pages every item is short,
    # under two the items of one and three pages are
    items = S.paged_read_items(pages, sampling, Tp, got, -1, lens, jnp.float32)
    assert items == {2: (n * 129 * 3, n * 129 * 3),
                     4: (n * (129 * 3 + 33), n * (33 + 2 * 96))}[
        pages.num_key_value_heads]
    assert S.paged_read_items(plain, sampling, Tp, got, -1, lens,
                              jnp.float32) is None
    # the contiguous loop's counter goes by its extents, and says so
    assert S.kv_in_place(plain, sampling, 3 * n) == 0
    assert S.attn_read_frac(plain, sampling, Tp, got, -1, prompt_lens=lens
                            ) == S.attn_read_frac(plain, sampling, Tp, got, -1)
    (st,) = stats
    assert (st["page_size"], st["num_pages"], st["rows"]) == (128, 9 * n, 3 * n)
    used = n * sum(_OWN_LENS) + int((got != PAD).sum())
    assert float(st["page_utilization"]) == pytest.approx(
        used / (9 * n * 128))
    plain_stats = []
    generate(params, plain, ids[:1], mask[:1], key,
             dataclasses.replace(sampling, max_tokens=2), eos_token_id=-1,
             pad_token_id=PAD, paged_stats_out=plain_stats)
    assert plain_stats == []        # a contiguous cache reports no pages


def test_a_row_that_ends_early_stops_the_count_of_pages(wide):
    """The loop runs until its LONGEST row ends: `attn_read_frac` over pages
    counts the steps taken, every row at every one of them (the loop marks
    no row dead), and a gathered view (an explicit page size off the
    kernel's rule) reads everything."""
    from nanorlhf_tpu.sampler import sampler as S

    config, _ = wide["dense"]
    pages = dataclasses.replace(config, attention_impl="pallas")
    sampling = SamplingParams(max_tokens=300)
    responses = np.full((4, 300), 7, np.int32)
    responses[:, 40] = EOS          # two prompts x 2: every row ends at 41
    responses[0, 40], responses[0, 99] = 7, EOS     # but one, at 100
    lens = np.asarray([100, 200])   # starts 156 (block 1) and 56 (block 0)
    # steps 1 .. 99 write slots 256 .. 354: block 2; of 5 pages a row
    by_hand = 99 * 2 * (2 + 3)
    assert S.attn_read_frac(pages, sampling, 256, responses, EOS,
                            prompt_lens=lens) == pytest.approx(
        by_hand / (99 * 4 * 5))
    # two and three pages a row a step: one short item each, of four pages
    assert S.paged_read_items(pages, sampling, 256, responses, EOS, lens,
                              jnp.float32) == (99 * 4, 99 * 4)
    view = dataclasses.replace(config, attention_impl="xla")
    assert S.attn_read_frac(
        view, dataclasses.replace(sampling, page_size=16), 256, responses,
        EOS, prompt_lens=lens) == 1.0
    assert S.paged_read_items(
        view, dataclasses.replace(sampling, page_size=16), 256, responses,
        EOS, lens, jnp.float32) is None
    # an explicit page size under the kernel's rule is read in place too
    own = dataclasses.replace(sampling, page_size=64)
    assert S.kv_in_place(pages, own, 4) == 1
    # slots 256 .. 354 of pages of 64: blocks 4 (64 steps) and 5 (35)
    by_hand = 2 * (64 * (3 + 5) + 35 * (4 + 6))
    assert S.attn_read_frac(pages, own, 256, responses, EOS, prompt_lens=lens
                            ) == pytest.approx(by_hand / (99 * 4 * 9))


def _paged_trainer(tmp_path, decode_rows=4):
    from nanorlhf_tpu.data import ToyTokenizer, load_prompt_dataset
    from nanorlhf_tpu.parallel import MeshConfig
    from nanorlhf_tpu.trainer import AlgoName, RLConfig, RLTrainer

    mcfg = ModelConfig.qwen2_tiny(vocab_size=512)
    tok = ToyTokenizer(vocab_size=512)
    params = init_params(mcfg, jax.random.PRNGKey(0), jnp.bfloat16)
    dataset = load_prompt_dataset("synthetic:32", tok, max_prompt_len=16)

    def reward(pmt_and_responses, eos_token):
        return np.asarray([float(len(s) % 3) for s in pmt_and_responses],
                          np.float32)

    cfg = RLConfig(
        algo=AlgoName.GRPO, output_dir=str(tmp_path), response_length=16,
        sample_n=2, per_device_train_batch_size=1,
        gradient_accumulation_steps=1, num_mini_batches=1,
        total_episodes=64, rollout_page_size=4,
        rollout_decode_rows=decode_rows,
        use_lora=True, save_steps=1, mesh=MeshConfig(data=-1),
        report_to="jsonl", logging_steps=1, sentinel=False,
    )
    return RLTrainer(cfg, mcfg, tok, params, dataset, reward)


def test_trainer_paged_metrics_and_resume(tmp_path):
    """2-update GRPO smoke over the continuous-batching rollout path: the
    metrics rows must carry rollout/page_utilization + pages_recycled +
    admitted_midloop (docs/METRICS.md), /statusz must expose the "pages"
    section, and a checkpoint/resume must continue training over the same
    paged path."""
    import json
    import os

    trainer = _paged_trainer(tmp_path / "ck")
    try:
        trainer.train(num_updates=2)
        status = trainer._statusz()
        assert status["pages"] is not None
        assert status["pages"]["page_size"] == 4
        assert status["pages"]["page_utilization"] is not None
        saved_step = trainer.state["global_step"]
    finally:
        trainer.close()
    rows = [json.loads(l) for l in open(
        os.path.join(str(tmp_path / "ck"), "metrics.jsonl")
    ) if l.strip()]
    step_rows = [r for r in rows if "rollout/page_utilization" in r]
    assert len(step_rows) >= 2
    for r in step_rows:
        assert 0.0 < r["rollout/page_utilization"] <= 1.0
        assert r["rollout/pages_recycled"] >= 0.0
        assert r["rollout/admitted_midloop"] >= 0.0

    tr2 = _paged_trainer(tmp_path / "ck")
    try:
        tr2.resume_from_checkpoint()
        assert tr2.state["global_step"] == saved_step
        tr2.train(num_updates=1)
        assert tr2.state["global_step"] == saved_step + 1
    finally:
        tr2.close()
