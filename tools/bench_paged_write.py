"""The paged K/V write alone on the chip: the row scatter, the write by page
and the decode step's live-row kernel, a K and V pair a layer, at the served
cells' pool shapes (docs/PAGED_CACHE.md "The write"; the measurement beside
`core/model._PAGE_WRITE_MIN_ROWS` is this script's).

    chiprun -- python3 tools/bench_paged_write.py [decode]

Every case first checks on a small pool that the forms agree bit for bit ON
THE DEVICE, then times `REPS` writes of each form inside one jitted loop over
the real-sized, donated pools. `decode`: the decode-step cases only;
`rollout`: the one-jit rollout's step alone (ISSUE 52: 64 live rows under the
identity table, every row at one slot, where the table's own form is one
`dynamic_update_slice`, `core/model._identity_slot_write`; `contiguous_us` is
the same write into the contiguous cache `[L, B, KV, T, hd]` it replaces). One
JSON line a case on stdout, all of them in `chiprun_out/paged_write/`.
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from nanorlhf_tpu.core import model as M  # noqa: E402
from nanorlhf_tpu.ops.paged_cache_write import (  # noqa: E402
    paged_row_write, paged_write_plan,
)
from nanorlhf_tpu.sampler.paged.pages import full_table  # noqa: E402

REPS = 400
out = []


def table_for(B, nb, N, live, rng):
    r = N // B      # a ring of r pages a row, laid over its nb blocks
    t = (rng.permutation(B)[:, None] * r + np.arange(nb)[None] % r).astype(np.int32)
    t[live:] = N
    return jnp.asarray(t)


def writer(form, P):
    if form == "kernel":
        def w(k, v, nk, nv, layer, table, ci):
            plan = paged_write_plan(table, ci, page_size=P, num_pages=k.shape[1])
            return paged_row_write(k, v, nk[:, :, 0], nv[:, :, 0], layer, plan)
        return w
    if form == "slice":
        return lambda k, v, nk, nv, layer, table, ci: (
            M._identity_slot_write(k, nk, layer, ci),
            M._identity_slot_write(v, nv, layer, ci))
    f = M._paged_row_scatter if form == "scatter" else M._paged_page_write
    return lambda k, v, nk, nv, layer, table, ci: (
        f(k, nk, layer, table, ci, P), f(v, nv, layer, table, ci, P))


def contiguous_us(L, KV, hd, B, T_max, nk, nv, slot):
    """The contiguous cache's write of the same step (`M._cache_update`)."""
    def run(k, v, nk, nv, slot):
        return jax.lax.fori_loop(
            0, REPS, lambda i, kv: tuple(
                M._cache_update(c, n, i % L, slot) for c, n in zip(kv, (nk, nv))),
            (k, v))
    run = jax.jit(run, donate_argnums=(0, 1))
    kv = [jnp.zeros((L, B, KV, T_max, hd), jnp.bfloat16) for _ in "kv"]
    kv = jax.block_until_ready(run(*kv, nk, nv, slot))
    t0 = time.perf_counter()
    jax.block_until_ready(run(*kv, nk, nv, slot))
    return (time.perf_counter() - t0) / REPS * 1e6


def case(name, L, N, KV, P, hd, B, T, nb, live, forms, start=7):
    rng = np.random.RandomState(0)
    nk = jax.random.normal(jax.random.PRNGKey(1), (B, KV, T, hd), jnp.bfloat16)
    nv = jax.random.normal(jax.random.PRNGKey(2), (B, KV, T, hd), jnp.bfloat16)
    ci = jnp.asarray(start + 131 * np.arange(B), jnp.int32) % (nb * P - T + 1)
    identity = "slice" in forms
    if identity:     # the one-jit rollout: every row at one slot, row r's
        ci = jnp.int32(3 * P + start)       # pages r nb .. r nb + nb - 1
    # the forms agree bit for bit on a small pool (the identity table's is
    # the real one's pages, so two layers of it)
    Ns, Ls = (B * nb, 2) if identity else (B * min(nb, 16), L)
    small = [jax.random.normal(jax.random.PRNGKey(3 + i), (Ls, Ns, KV, P, hd),
                               jnp.bfloat16) for i in range(2)]
    ts = full_table(B, nb) if identity else table_for(B, nb, Ns, live, rng)
    res = {f: jax.jit(writer(f, P))(*small, nk, nv, jnp.int32(Ls - 1), ts, ci)
           for f in forms}
    ref = res[forms[0]]
    same = {f: bool(all(jnp.array_equal(a, b) for a, b in zip(ref, r)))
            for f, r in res.items()}
    changed = not bool(jnp.array_equal(ref[0], small[0]))
    del res, small, ref
    table = ts if identity else table_for(B, nb, N, live, rng)
    row = {"case": name, "L": L, "N": N, "KV": KV, "hd": hd, "B": B, "T": T,
           "live": live, "same": same, "changed": changed}
    for f in forms:
        w = writer(f, P)

        def run(k, v, nk, nv, table, ci, w=w):
            return jax.lax.fori_loop(
                0, REPS, lambda i, kv: w(*kv, nk, nv, i % L, table, ci),
                (k, v))
        run = jax.jit(run, donate_argnums=(0, 1))
        k = jnp.zeros((L, N, KV, P, hd), jnp.bfloat16)
        v = jnp.zeros((L, N, KV, P, hd), jnp.bfloat16)
        k, v = run(k, v, nk, nv, table, ci)
        jax.block_until_ready((k, v))
        t0 = time.perf_counter()
        k, v = run(k, v, nk, nv, table, ci)
        jax.block_until_ready((k, v))
        row[f + "_us"] = (time.perf_counter() - t0) / REPS * 1e6
        del k, v
    if identity:
        row["contiguous_us"] = contiguous_us(L, KV, hd, B, nb * P, nk, nv, ci)
    out.append(row)
    print(json.dumps(row), flush=True)


P = 128
SCATTER_KERNEL, SCATTER_PAGE = ["scatter", "kernel"], ["scatter", "page"]
# (name, L, N, KV, hd, B, T, table blocks, rows that hold pages, forms)
DECODE = [
    # serve-1.5b-chat: 64 rows of which ~4 are live; none; all
    *[("chat.decode", 28, 807, 2, 128, 64, 1, 12, live, SCATTER_KERNEL)
      for live in (0, 4, 16, 64)],
    # serve-smallthinker-longshort: the window and the global pool, 32 rows
    *[("st.window.decode", 3, 1344, 4, 128, 32, 1, 128, live, SCATTER_KERNEL)
      for live in (4, 32)],
    ("st.global.decode", 1, 4224, 4, 128, 32, 1, 128, 4, SCATTER_KERNEL),
    # serve-lfm2-chat: two attention layers of 4 packed heads, 37 of 64 live
    ("lfm2.decode", 2, 1100, 4, 128, 64, 1, 17, 37, SCATTER_KERNEL),
]
ROLLOUT_FORMS = ["scatter", "kernel", "slice"]
ROLLOUT = [
    # grpo-1.5b-r512's and grpo-olmoe-r512's rollout: 64 rows x 6 pages
    ("rollout.decode", 28, 384, 2, 128, 64, 1, 6, 64, ROLLOUT_FORMS),
    ("olmoe.rollout.decode", 16, 384, 16, 128, 64, 1, 6, 64, ROLLOUT_FORMS),
]
FORWARDS = [
    # one row's admission buckets and pieces
    *[("chat.suffix", 28, 807, 2, 128, 1, T, 12, 1, SCATTER_PAGE)
      for T in (2, 8, 16, 32, 64, 128, 256, 1024)],
    *[("st.window.piece", 3, 1344, 4, 128, 1, T, 128, 1, SCATTER_PAGE)
      for T in (16, 64, 128, 1024)],
    ("st.global.piece", 1, 4224, 4, 128, 1, 1024, 128, 1, SCATTER_PAGE),
    # serve-axk1-docqa: the latent pool's c_kv leaf
    ("axk1.latent.piece", 7, 2720, 1, 512, 1, 1024, 68, 1, SCATTER_PAGE),
    ("axk1.latent.bucket", 7, 2720, 1, 512, 1, 64, 68, 1, SCATTER_PAGE),
    # the paged rollout's prefill: 16 rows of 256 tokens at once
    ("rollout.prefill", 28, 400, 2, 128, 16, 256, 6, 16, SCATTER_PAGE),
]


def main():
    print(jax.devices(), flush=True)
    which = sys.argv[1] if sys.argv[1:] in (["decode"], ["rollout"]) else "all"
    for name, L, N, KV, hd, B, T, nb, live, forms in {
            "decode": DECODE, "rollout": ROLLOUT,
            "all": DECODE + ROLLOUT + FORWARDS}[which]:
        case(name, L, N, KV, P, hd, B, T, nb, live, forms)
    os.makedirs("chiprun_out/paged_write", exist_ok=True)
    with open("chiprun_out/paged_write/%s.json" % which, "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
