"""The in-place paged decode read alone on the chip
(`ops/decode_attention.paged_decode_attention`), at the served cells' pool
shapes and live rows (docs/PAGED_CACHE.md "The read's cost"; the table
there is this script's).

    chiprun -- python3 tools/bench_paged_read.py [CASE ...]

Every case is first checked ON THE DEVICE against
`reference_paged_decode_attention` over its live rows (dead rows read zero),
then timed: `REPS` calls inside one jitted loop, the layer going round the
stack. Three readings a case: the kernel as it is; with its compute stubbed
(the copies and their waits alone); with its copies stubbed (the compute
over whatever the buffers hold). The stubs live here and not in `ops/`: they
swap `decode_attention._paged_item_fold` and the module's `make_async_copy`
while the call is traced. `empty_us` is the same call with no live row (the
wrapper's pad and slice, the launch, the zeroed outputs), so
`us_item = (call_us - empty_us) / items`; `pct_bytes` is the K and V bytes of
the slots inside the rows' bounds at the chip's bandwidth
(benchmark/harness/peaks.json) over `call_us - empty_us`. The `rollout` cases
also time XLA's masked read of a contiguous cache cut to the extent that
step reads there (`xla_extent_us`: what the one-jit rollout runs wherever
its cache is not paged, `core/model.decode_read_extents`). The `.held<h>`
cases give every live row one item of `h` pages, so their `us_item` is an
item's cost by the pages it holds; `step_items` is the items a step of the
kernel's loop folds at the case's geometry. One JSON line a case on stdout,
all of them in `chiprun_out/paged_read/`.
"""
import contextlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from nanorlhf_tpu.ops import decode_attention as dec  # noqa: E402

REPS = 300
P, HD = 128, 128
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def hbm_bytes_per_s():
    """The device's bandwidth from the benchmark's table of peaks; a device
    that is not in it is an error."""
    with open(os.path.join(ROOT, "benchmark", "harness", "peaks.json")) as f:
        return json.load(f)[jax.devices()[0].device_kind]["hbm_bytes_per_s"]

# A step of the one-jit rollout over its pages (ISSUE 52): prompts of 64-256
# tokens left-padded to 256, 512 new tokens, so 6 pages a row; at `filled`
# slots written a contiguous cache is read up to `extent`. The start, the
# middle and the end of a response: ~2.7, 4.7 and 5.7 pages a row.
ROLLOUT_STEPS = {"start": (264, 512), "mean": (520, 640), "end": (760, 768)}


def rollout_step(name):
    """`(filled, extent)` of a `[olmoe.]rollout.<step>` case, else None."""
    head, _, step = name.rpartition(".")
    return ROLLOUT_STEPS.get(step) if head.endswith("rollout") else None


# (name, layers, pages, kv heads, query rows a kv head, rows, table blocks,
#  live rows, pages a live row)
CASES = [
    # serve-trinity-reason: a window layer's pool; 10 rows and the ~19 of the
    # window's end
    ("trinity.10", 4, 1344, 8, 6, 32, 72, 10, 14),
    ("trinity.19", 4, 1344, 8, 6, 32, 72, 19, 14),
    # serve-1.5b-chat, and PR 28's full pool (192 items)
    ("chat", 28, 807, 2, 6, 64, 12, 8, 4),
    ("chat.full", 28, 807, 2, 6, 64, 12, 64, 12),
    # serve-smallthinker-longshort: the global pool, three long rows
    ("st.global", 2, 4224, 4, 7, 32, 128, 3, 70),
    # serve-lfm2-chat: two 64-wide heads a 128-lane row
    ("lfm2", 2, 2560, 4, 4, 64, 40, 40, 6),
    # serve-sdar-blockgen: a block's 4 positions x 8 heads a kv head
    ("sdar.block", 7, 1625, 4, 32, 64, 25, 24, 8),
    # OLMoE's geometry (chip_smoke.py --olmoe): a page an item
    ("olmoe", 16, 400, 16, 1, 64, 6, 32, 5),
    # the one-jit rollout's shape (ROADMAP S3): 64 rows, 416 of 608 slots
    ("rollout", 28, 400, 2, 6, 64, 5, 64, 5),
    # the same loop step by step, and at OLMoE's geometry (a page an item)
    *[("rollout." + at, 28, 384, 2, 6, 64, 6, 64, 6) for at in ROLLOUT_STEPS],
    *[("olmoe.rollout." + at, 16, 384, 16, 1, 64, 6, 64, 6)
      for at in ROLLOUT_STEPS],
    # an item's cost by the pages it holds (ISSUE 61): every live row ONE
    # item of 1..4 pages, at the rollout's geometry (four items a step of the
    # kernel's loop) and at LFM2's (items of 1 MB, one a step)
    *[("rollout.held%d" % h, 28, 384, 2, 6, 64, 6, 64, h) for h in (1, 2, 3, 4)],
    *[("lfm2.held%d" % h, 2, 2560, 4, 4, 64, 40, 40, h) for h in (1, 2, 3, 4)],
]


def build(name, L, N, KV, G, B, nb, live, pages):
    """Pools, queries and a step's bounds: `live` rows scattered among `B`
    hold `pages` scattered pages each, the last one part filled."""
    rng = np.random.RandomState(0)
    table = np.full((B, nb), N, np.int32)
    rows = np.sort(rng.permutation(B)[:live])
    perm = rng.permutation(N)
    start, filled = np.zeros(B, np.int32), np.zeros(B, np.int32)
    for j, r in enumerate(rows):
        table[r, :pages] = perm[j * pages:(j + 1) * pages]
        if name == "rollout":   # a left-padded prompt, 416 slots filled
            start[r] = 192 - (29 * j) % 160
            filled[r] = start[r] + 416
        elif rollout_step(name):    # its left pad, every row at one slot
            start[r] = (29 * j) % 193
            filled[r] = rollout_step(name)[0]
        else:
            filled[r] = pages * P - (37 * j) % P
    mask = np.zeros(B, bool)
    mask[rows] = True
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    k_pool, v_pool = (jax.random.normal(k, (L, N, KV, P, HD), jnp.bfloat16)
                      for k in keys[:2])
    q = jax.random.normal(keys[2], (B, KV * G, HD), jnp.bfloat16)
    return dict(q=q, k_pool=k_pool, v_pool=v_pool, table=jnp.asarray(table),
                start=jnp.asarray(start), filled=jnp.asarray(filled),
                live=jnp.asarray(mask), rows=rows, pages=pages)


def plan_of(c, live=None):
    return dec.paged_decode_plan(
        c["table"], c["start"], c["filled"], page_size=P,
        num_pages=c["k_pool"].shape[1],
        pages_per_item=dec.paged_pages_per_item(c["k_pool"]),
        live=c["live"] if live is None else live)


def check(c, plan, layer=1):
    """The read against the oracle over the live rows; dead rows zero."""
    got = jax.jit(dec.paged_decode_attention)(
        c["q"], c["k_pool"], c["v_pool"], jnp.int32(layer), plan)
    rows = c["rows"]
    want = dec.reference_paged_decode_attention(
        c["q"][rows], c["k_pool"][layer], c["v_pool"][layer],
        c["table"][rows, :c["pages"]], c["start"][rows], c["filled"][rows])
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    dead = np.setdiff1d(np.arange(got.shape[0]), rows)
    return {"max_err": float(np.abs(got[rows] - want).max()),
            "dead_zero": not got[dead].any()}


def time_us(read, c, plan, stacks=None):
    """µs a call: REPS calls in one jitted loop, the layer going round."""
    k, v = stacks or (c["k_pool"], c["v_pool"])
    L = k.shape[0]

    def run(q, k, v, plan):
        def body(i, acc):
            return acc + read(q, k, v, i % L, plan).astype(jnp.float32)
        return jax.lax.fori_loop(0, REPS, body, jnp.zeros(q.shape, jnp.float32))
    run = jax.jit(run)
    args = (c["q"], k, v, plan)
    jax.block_until_ready(run(*args))
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        jax.block_until_ready(run(*args))
        best = min(best, time.perf_counter() - t0)
    return best / REPS * 1e6


@contextlib.contextmanager
def swapped(owner, name, stub):
    real = getattr(owner, name)
    setattr(owner, name, stub)
    try:
        yield
    finally:
        setattr(owner, name, real)


def compute_stubbed():
    """An item's fold returns the state it was given: copies and waits only."""
    return swapped(dec, "_paged_item_fold",
                   lambda q, k, v, valid, state, scale: state)


class _NoCopy:
    start = wait = lambda self: None


def copies_stubbed():
    """No page is fetched: the compute runs over what the buffers hold."""
    return swapped(dec.pltpu, "make_async_copy", lambda *a, **k: _NoCopy())


def xla_extent_read_us(c, extent=608, layers=4):
    """XLA's masked read of a contiguous cache cut to `extent` slots
    (`core/model.decode_read_extents`; 608 of 768 is the mean of the
    rollout's three), the plain form of the one-jit rollout's read, timed
    the same way."""
    B, KV = c["q"].shape[0], c["k_pool"].shape[2]
    stacks = [jax.random.normal(key, (layers, B, KV, extent, HD), jnp.bfloat16)
              for key in jax.random.split(jax.random.PRNGKey(1), 2)]

    def read(q, k, v, layer, bounds):
        return dec.reference_decode_attention(q, k[layer], v[layer], *bounds)
    return time_us(read, c, (c["start"], c["filled"]), stacks)


def case(spec):
    name, L, N, KV, G, B, nb, live, pages = spec
    read = dec.paged_decode_attention
    c = build(*spec)
    plan = plan_of(c)
    items = int(plan.row_off[-1])
    slots = int(jnp.sum(jnp.where(c["live"], c["filled"] - c["start"], 0)))
    floor_us = slots * KV * HD * 2 * 2 / hbm_bytes_per_s() * 1e6
    C = dec.paged_pages_per_item(c["k_pool"])
    row = {"case": name, "L": L, "N": N, "KV": KV, "G": G, "B": B,
           "live": live, "pages": pages, "item_pages": C,
           "step_items": dec._paged_items_per_step(c["k_pool"], C),
           "items": items, "slots": slots, "floor_us": floor_us}
    row.update(check(c, plan))
    row["call_us"] = time_us(read, c, plan)
    row["empty_us"] = time_us(read, c, plan_of(c, jnp.zeros_like(c["live"])))
    net = row["call_us"] - row["empty_us"]
    row["us_item"] = net / items
    row["pct_bytes"] = 100 * floor_us / net
    with compute_stubbed():
        row["copies_only_us"] = time_us(read, c, plan)
    with copies_stubbed():
        row["compute_only_us"] = time_us(read, c, plan)
    if name == "rollout":
        row["xla_extent_us"] = xla_extent_read_us(c)
    elif rollout_step(name):
        row["extent"] = rollout_step(name)[1]
        row["xla_extent_us"] = xla_extent_read_us(c, row["extent"])
    return row


def main():
    print(jax.devices(), flush=True)
    want = sys.argv[1:]
    out = []
    for spec in CASES:
        if want and spec[0] not in want:
            continue
        row = case(spec)
        out.append(row)
        print(json.dumps(row), flush=True)
    os.makedirs("chiprun_out/paged_read", exist_ok=True)
    with open("chiprun_out/paged_read/%s.json"
              % ("_".join(want) or "all"), "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
