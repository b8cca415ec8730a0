"""serving: `serving/prefix_hit_tokens` gained over the window, over the
prompt tokens of the requests due in it (the client's records), in %. The
two ends differ by the few requests in flight at the window's edges."""


def read(run):
    if "counters" not in run:
        return None
    c = run["counters"]
    hit = c["end"]["serving/prefix_hit_tokens"] - c["start"]["serving/prefix_hit_tokens"]
    prompt = sum(r["prompt_len"] for r in run["records"])
    return 100.0 * hit / prompt if prompt else None
