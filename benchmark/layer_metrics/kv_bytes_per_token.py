"""serving: `serving/kv_bytes_per_token` of `engine.metrics()`: the page
pool's bytes over its token slots, every layer and array of the cache
counted. A guard that the cache is the latent one: 1,152 B a layer for
A.X-K1 in bf16 (per-head K and V would be 40,960). Nothing where the program
exports no such counter."""


def read(run):
    end = (run.get("counters") or {}).get("end", {})
    return end.get("serving/kv_bytes_per_token")
