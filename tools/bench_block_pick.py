"""The sparse layer's pick of its top blocks alone on the chip:
`core/sala.top_blocks` (a threshold search, a placement and a sort of width
k) beside `jax.lax.top_k`, which the chip's compiler lowers to a full sort
of the 1,040 block scores, at the `serve-sala-docchat` cell's shapes
(docs/SALA.md; PERF.md PR 58 has the table `sala._PICK_QUERIES` was set
from).

    chiprun -- python3 tools/bench_block_pick.py

Each form is first checked ON THE DEVICE against `lax.top_k` (values and
indices bit for bit, scores with ties, a run of the forced value and a tail
of -1), then timed two ways, `REPS` calls inside one jitted loop each:
`alone`, the pick over `[1, 2, Tq, 1040]` scores that are already there; and
`select`, all of `select_blocks` (scores, pooling and the pick) with that
pick forced, as its callers run it: a document's 1,024-token piece (four
blocks of 256 queries under `sala._in_query_blocks`, the compressed keys of
66,560 slots) and a decode step's selecting row (`Tq = 1`). The compiler
lays the scores out for the code around the pick, so `alone` and `select`
need not agree: the bound follows `select`. One JSON line a case on stdout
(microseconds a call), all of them in `chiprun_out/block_pick/`.
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from nanorlhf_tpu.core import ModelConfig, sala  # noqa: E402

REPS, DISTINCT = 200, 4
CONFIG = ModelConfig.minicpm_sala()
# docchat-steady: rows of 66,560 slots = 4,160 compressed keys = 1,040 blocks
SLOTS, K = 66560, CONFIG.sparse_topk
NC = SLOTS // CONFIG.sparse_kernel_stride
NB = SLOTS // CONFIG.sparse_block_size
FORMS = {"top_k": jax.lax.top_k, "top_blocks": sala.top_blocks}
TQ = (256, 16, 1)


def scores(Tq, seed=0):
    """`DISTINCT` sets of block scores as `select_blocks` ranks them: ties,
    block 0 and 32 local blocks forced, nothing seen past the own block."""
    r = np.random.default_rng(seed).random(
        (DISTINCT, 1, 2, Tq, NB)).astype(np.float32) * 3
    r = np.round(r * 64) / 64
    r[..., :1] = r[..., 868:900] = 2.0 * CONFIG.num_attention_heads
    r[..., 900:] = -1.0
    return jnp.asarray(r)


def looped(call, lead):
    """`call(i) -> [.., k]` int32 `REPS` times in one program, summed."""
    def run(*operands):
        return jax.lax.fori_loop(
            0, REPS, lambda i, acc: acc + call(i % DISTINCT, *operands),
            jnp.zeros(lead + (K,), jnp.int32))
    return jax.jit(run)


def time_us(run, *operands):
    """Microseconds a call, and the loop's sum."""
    jax.block_until_ready(run(*operands))
    t0 = time.perf_counter()
    got = jax.block_until_ready(run(*operands))
    return (time.perf_counter() - t0) / REPS * 1e6, np.asarray(got)


def alone(form, Tq):
    pick, xs = FORMS[form], scores(Tq)
    got, ref = jax.jit(pick, static_argnums=1)(xs, K), jax.lax.top_k(xs, K)
    same = all(np.array_equal(np.asarray(a).view(np.uint32),
                              np.asarray(b).view(np.uint32))
               for a, b in zip(got, ref))

    def call(i, xs):
        vals, idx = pick(xs[i], K)
        return idx + (vals >= 0)
    return time_us(looped(call, (1, 2, Tq)), xs)[0], same


def select(form, Tq):
    """`select_blocks` with the pick forced, over a row that holds 57,000
    tokens: its last `Tq` queries in one block (`Tq = 1`: a decode step), or
    a 1,024-token piece in four blocks of 256."""
    ks = jax.random.split(jax.random.PRNGKey(1), 2)
    T = 1024 if Tq == 256 else Tq
    q = jax.random.normal(ks[0], (DISTINCT, 1, 32, T, 128), jnp.bfloat16)
    kc = jax.random.normal(ks[1], (1, 2, NC, 128), jnp.bfloat16)
    t = 57000 - T + jnp.arange(T, dtype=jnp.int32)[None, None]

    def block(qb, tb):
        idx, ok = sala.select_blocks(CONFIG, qb, kc, tb[:, 0])
        return jnp.where(ok, idx, -1)

    def call(i, q):
        return sala._in_query_blocks(block, T, 32 * NC * 4, q[i], t)

    bound = sala._PICK_QUERIES
    sala._PICK_QUERIES = 0 if form == "top_blocks" else 1 << 30
    try:
        return time_us(looped(call, (1, 2, T)), q)
    finally:
        sala._PICK_QUERIES = bound


def main():
    print(jax.devices(), flush=True)
    out = []
    for Tq in TQ:
        chosen = {}
        for form in FORMS:
            us, same = alone(form, Tq)
            select_us, chosen[form] = select(form, Tq)
            row = {"scores": [1, 2, Tq, NB], "k": K, "form": form,
                   "alone_us": round(us, 1), "equals_top_k": same,
                   "select_us": round(select_us, 1),
                   "select_equals_top_k": bool(np.array_equal(
                       chosen[form], chosen["top_k"])),
                   "select_is": "a 1,024-token piece: 4 blocks of 256"
                   if Tq == 256 else f"one block of {Tq} queries"}
            out.append(row)
            print(json.dumps(row), flush=True)
    os.makedirs("chiprun_out/block_pick", exist_ok=True)
    with open("chiprun_out/block_pick/table.json", "w") as fh:
        json.dump(out, fh, indent=1)


if __name__ == "__main__":
    main()
