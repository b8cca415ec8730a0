"""A serve cell's two session programs alone on the chip, at the cell's real
size, outside the benchmark:

    python benchmark/tools/step_alone.py <workload> [<live rows>]

The cell's weights (seed 7) and page pool; then, timed over five calls each
after one that compiles: the KV-only prefill chunk (`_prefill_chunk_fwd`,
`engine.prefill_chunk` tokens) at the start and near the end of a prompt of
`tenant_prompt_len` + 128 tokens, and the serving decode chunk
(`_serving_chunk`, `sync_every` steps) with `<live rows>` rows live (default:
all) at that fill, the others done. What a beat's parts cost with nothing
else on the device: the numbers PERF.md sets beside `chunk_ms` and
`axk1_decode_step_ms`, which hold both and the host. Prints one JSON line and
writes `chiprun_out/step_alone_<workload>.json`.
"""

from __future__ import annotations

import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, ROOT]

from harness import cell as cells, model      # noqa: E402

CALLS = 5


def main(argv) -> int:
    workload = argv[0]
    import jax
    import jax.numpy as jnp
    import numpy as np

    if jax.devices()[0].platform != "tpu":
        print("step_alone: no TPU, no number", file=sys.stderr)
        return 2
    from nanorlhf_tpu.core.model import init_paged_kv_cache
    from nanorlhf_tpu.sampler.paged import session as sess
    from nanorlhf_tpu.sampler.paged.pages import blocks_per_row
    from nanorlhf_tpu.serving.radix import RadixCache
    from nanorlhf_tpu.utils.compile_cache import enable_compilation_cache

    enable_compilation_cache()
    cell = cells.load_cell(os.path.join(ROOT, "BENCHMARK.json"), workload)
    mix, e = cell.traffic, cell.traffic["engine"]
    mcfg = model.model_config(cell.config)
    params = model.init_weights(mcfg, 7, model.dtype_of(cell.config))
    R, Tp, new = int(e["rows"]), int(e["prompt_len"]), int(e["max_new_tokens"])
    page, C = int(e["page_size"]), int(e["prefill_chunk"])
    live = int(argv[1]) if len(argv) > 1 else R
    T_max = Tp + new
    nb = blocks_per_row(T_max, page)
    pages = R * nb + RadixCache(headroom=float(e["headroom"])).extra_pages(R, nb)
    caches = init_paged_kv_cache(mcfg, pages, page, model.dtype_of(cell.config))
    prompt = min(int(mix["tenant_prompt_len"]) + 128, Tp)
    first = Tp - prompt                     # the left-padded row's first slot
    table = jnp.arange(nb, dtype=jnp.int32)
    out = {"workload": workload, "rows": R, "live_rows": live, "prompt": prompt}

    def timed(call, carry, ready):
        carry = call(carry)
        jax.block_until_ready(ready(carry))
        t = time.perf_counter()
        for _ in range(CALLS):
            carry = call(carry)
        jax.block_until_ready(ready(carry))
        return carry, (time.perf_counter() - t) / CALLS * 1e3

    for label, fill in (("start", first), ("end", first + (prompt - 1) // C * C)):
        mask = np.zeros((1, T_max), bool)
        mask[0, first:fill] = True

        def chunk(caches, fill=fill, mask=mask):
            return sess._prefill_chunk_fwd(
                params, mcfg, jnp.ones((1, C), jnp.int32),
                jnp.arange(C, dtype=jnp.int32)[None] + (fill - first),
                jnp.asarray([fill], jnp.int32), jnp.asarray(mask), caches,
                table, page_size=page, lora_scale=1.0)

        caches, out[f"prefill_chunk_ms_at_{label}"] = timed(
            chunk, caches, lambda c: c)
    done = jnp.arange(R) >= live
    state = (jnp.int32(1), jnp.zeros((R, new), jnp.int32),
             jnp.zeros((R, new), jnp.float32), caches,
             jnp.zeros((R, T_max), bool).at[:, first:Tp].set(True), done,
             jnp.ones((R,), jnp.int32), jnp.ones((R,), jnp.int32),
             jnp.full((R,), prompt, jnp.int32), jax.random.PRNGKey(0))
    statics = dict(Tp=Tp, max_tokens=new, page_size=page,
                   sync_every=int(e["sync_every"]),
                   eos_token_id=int(mix["eos_token_id"]),
                   pad_token_id=int(mix["pad_token_id"]), temperature=1.0,
                   top_p=0.95, greedy=False, lora_scale=1.0, top_k=64,
                   capture_logprobs=False, approx_top_k=True)
    args = (jnp.tile(table[None], (R, 1)), jnp.ones((R,), jnp.float32),
            jnp.ones((R,), jnp.float32), jnp.zeros((R,), bool),
            jnp.full((R,), new, jnp.int32))
    # (a chip's share of an expert layer hands a count back beside the carry)
    carry_of = (lambda r: r[0]) if mcfg.experts_held else (lambda r: r)

    def steps(state):
        return carry_of(sess._serving_chunk(params, mcfg, state, *args, **statics))

    _, per_chunk = timed(steps, state, lambda s: s[0])
    out["decode_step_ms"] = per_chunk / int(e["sync_every"])
    print(json.dumps(out), flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out",
                           f"step_alone_{workload}.json"), "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
