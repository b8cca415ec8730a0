"""`loadgen_child.py` for a model that generates by diffusion over blocks
(docs/BLOCKDIFF.md): every request also carries `denoising_steps` and
`remasking`, and draws its budget from a short list.

The child, its clock, its records and its summary are `loadgen_child`'s, by
import; what differs:

- the schedule (`block_requests`): `trafficgen.serve_requests`' (instants,
  prompts, sampling parameters), then, a segment, `max_tokens` in equal
  shares from the mix's `max_tokens_choices` and `denoising_steps` in equal
  shares from its `denoising_steps_choices`, both exact a segment and paired
  by a permutation drawn from the mix's `schedule_seed` (so every seed offers
  the same work); `remasking` is the mix's;
- the request's body (`fire`): `loadgen_child.fire` posts a fixed set of
  keys, so the two more ride in through `json.dumps`, which the module looks
  up when it builds the body: a body that holds `max_tokens` gains the two
  keys of the request at hand, found by the identity of its token list. A
  body that finds none is COUNTED, not passed over: the child's summary
  says how many bodies gained the keys (`block_bodies`) and how many did
  not (`plain_bodies`), the child exits 3 on one that did not, and the
  driver holds the run to it (a request without the keys would run the
  gateway's defaults, and the half at 2 steps would vanish unseen). Nothing
  else of the child is touched. (A hook in `loadgen_child.fire` for further
  body keys is a `benchmark` PR's: PERF.md section 7.)
"""

from __future__ import annotations

import json
import os
import sys
import types

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from harness import loadgen_child, trafficgen  # noqa: E402

S_BLOCK = 7     # a stream of draws of its own (trafficgen's are 1-6)


def block_requests(mix: dict, seed: int, segments, vocab_size: int,
                   rate_rps: float | None = None) -> list:
    reqs = trafficgen.serve_requests(mix, seed, segments, vocab_size,
                                     rate_rps=rate_rps)
    shape_seed = int(mix.get("schedule_seed", seed))
    budgets = [int(b) for b in mix["max_tokens_choices"]]
    steps = [int(s) for s in mix["denoising_steps_choices"]]
    start, at = 0.0, 0
    for k, length in enumerate(segments):
        end = start + float(length)
        n = sum(1 for r in reqs[at:] if r["t"] < end)
        rng = np.random.default_rng([shape_seed, S_BLOCK, k])
        which_budget = rng.permutation(n) % len(budgets)
        which_steps = rng.permutation(n) % len(steps)
        for i, r in enumerate(reqs[at:at + n]):
            r["max_tokens"] = budgets[which_budget[i]]
            r["denoising_steps"] = steps[which_steps[i]]
            r["remasking"] = mix["remasking"]
        start, at = end, at + n
    return reqs


class _Json:
    """`json`, whose `dumps` lays the current request's block parameters
    over a `/generate` body; everything else is the module's."""

    def __init__(self):
        self.extra = {}
        self.laid = self.missed = 0

    def __getattr__(self, name):
        return getattr(json, name)

    def dumps(self, obj, **kw):
        if isinstance(obj, dict) and "max_tokens" in obj and "tokens" in obj:
            more = self.extra.get(id(obj["tokens"]))
            self.laid += more is not None
            self.missed += more is None
            obj = {**obj, **(more or {})}
        elif isinstance(obj, dict) and "scheduled" in obj:  # the summary
            obj = {**obj, "block_bodies": self.laid,
                   "plain_bodies": self.missed}
        return json.dumps(obj, **kw)


def main() -> int:
    shim = _Json()

    def requests(*args, **kwargs):
        reqs = block_requests(*args, **kwargs)
        # `fire` hands the request's own token list to the body: its
        # identity finds the request's two parameters
        shim.extra = {id(r["tokens"]): {
            "denoising_steps": r["denoising_steps"],
            "remasking": r["remasking"]} for r in reqs}
        return reqs

    loadgen_child.trafficgen = types.SimpleNamespace(
        serve_requests=requests, digest=trafficgen.digest)
    loadgen_child.json = shim
    rc = loadgen_child.main()
    if shim.missed or not shim.laid:
        print(f"loadgen_child_block: {shim.missed} request bodies went "
              f"without denoising_steps / remasking ({shim.laid} with)",
              file=sys.stderr)
        return 3
    return rc


if __name__ == "__main__":
    sys.exit(main())
