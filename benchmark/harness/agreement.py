"""The comparison that decides `correct` for numerics.

Copied from `chip_smoke.py` (`bf16_agreement`, `BF16_SLACK`, the near-tie
rule of its serve phase): how far two bf16 paths may sit from a float32
reference is MEASURED in the same run, not guessed. The path under test may
be `BF16_SLACK` times further from float32 than the plain bf16 XLA path
(mean, max); a served greedy token may sit below the reference's top by
`GAP_SLACK` times what the plain path's own argmax does, measured alike.
"""

from __future__ import annotations

import numpy as np

BF16_SLACK = (1.5, 2.0)     # (mean, max) of |tested - f32| over |plain - f32|
GAP_SLACK = (1.5, 4.0)      # (mean, max) of a served argmax's gap over the plain path's
KL_STEP1_TOL = 1e-2         # k3 KL per token, policy == reference at step 1


def stat(x) -> dict:
    return {"mean_abs": float(np.mean(x)), "max_abs": float(np.max(x))}


def bf16_agreement(tested, plain, reference, real) -> tuple:
    """(ok, detail): `tested` and `plain` are the same logprobs from two
    bf16 paths, `reference` is float32; `real` masks the tokens that count."""
    e_t = np.abs(tested - reference)[real]
    e_p = np.abs(plain - reference)[real]
    ok = bool(e_t.mean() <= BF16_SLACK[0] * e_p.mean() + 1e-3
              and e_t.max() <= BF16_SLACK[1] * e_p.max() + 1e-2)
    return ok, {"tested_vs_float32": stat(e_t), "plain_vs_float32": stat(e_p),
                "tested_vs_plain": stat(np.abs(tested - plain)[real]),
                "tokens": int(real.sum())}


def follows_greedy(ref_logits, served, plain_logits) -> tuple:
    """(ok, detail) for served greedy tokens. `ref_logits [n, V]` are the
    float32 reference's next-token logits at each served position, computed
    with the SERVED tokens as context (so after a flip the comparison goes on
    from the served token); `plain_logits` the same from the plain bf16 XLA
    path; `served [n]` what the system streamed.

    With random weights the top logits lie close together and bf16 flips the
    argmax often, so a flip alone is no fault. How far below the reference's
    top a bf16 argmax may land is measured in the same run on the plain path
    (its own argmax's gap under the reference); the served tokens' gaps may
    be GAP_SLACK times that (mean, max). A wrong cache, mask or position
    picks tokens the reference puts nats lower, on a whole request."""
    served = np.asarray(served)
    rows = np.arange(len(served))
    top = ref_logits.max(axis=-1)
    gap = top - ref_logits[rows, served]
    plain_gap = top - ref_logits[rows, plain_logits.argmax(axis=-1)]
    ok = bool(gap.mean() <= GAP_SLACK[0] * plain_gap.mean() + 1e-3
              and gap.max() <= GAP_SLACK[1] * plain_gap.max() + 5e-2)
    return ok, {"tokens": int(len(served)), "flips": int((gap > 0).sum()),
                "plain_flips": int((plain_gap > 0).sum()),
                "gap": stat(gap), "plain_gap": stat(plain_gap)}
