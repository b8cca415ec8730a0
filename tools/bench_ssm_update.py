"""The decode step's pass over the recurrent state alone on the chip:
`ops/ssm.ssm_update_in_place` (one Pallas call aliased to the stack, the
live rows only) beside the XLA form it took the place of (`ssm_update` on
the sliced layer and `dynamic_update_slice`, every resident row), at the
`serve-falcon-h1-assist` cell's shapes (docs/SSM.md; PERF.md PR 50 has the
table the head-block size was chosen from).

    chiprun -- python3 tools/bench_ssm_update.py [HEADS_A_BLOCK ...]

Each form is first checked ON THE DEVICE against `ssm_update` on a small
stack (live rows close, every other row bit for bit), then timed as `REPS`
calls inside one jitted loop over the real-sized, donated stack, a layer a
call in turn. `copy` is the kernel with its arithmetic taken out (the
block goes back as it came): what the pipeline's copies alone cost. One JSON
line a case on stdout (microseconds a call and the share of the LIVE rows'
bytes, read and written once, at 819 GB/s), all of them in
`chiprun_out/ssm_update/`.
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from nanorlhf_tpu.ops import ssm  # noqa: E402

REPS = 200
HBM_BYTES_PER_S = 819e9
# falcon-h1-34b-l5 under assist-steady: 5 layers, 48 rows, 32 heads of
# [128, 256], 2 groups
L, R, H, P, G, N = 5, 48, 32, 128, 2, 256
LIVE = (8, 16, 31, 48)


def operands(B, key=0):
    k = jax.random.split(jax.random.PRNGKey(key), 5)
    return (jax.random.normal(k[0], (B, H, P), jnp.float32),
            jax.nn.softplus(jax.random.normal(k[1], (B, H), jnp.float32)),
            -jnp.exp(jax.random.normal(k[2], (H,), jnp.float32)),
            jax.random.normal(k[3], (B, G, N), jnp.float32),
            jax.random.normal(k[4], (B, G, N), jnp.float32))


def xla_form(S, layer, live, xs, dt, A, Bm, Cm):
    """The parent's decode step: slice, `ssm_update`, put back."""
    before = jax.lax.dynamic_slice(
        S, (layer, 0, 0, 0, 0), (1,) + S.shape[1:])[0]
    y, after = ssm.ssm_update(
        xs, jnp.where(live[:, None], dt, 0), A, Bm, Cm, before)
    return y, jax.lax.dynamic_update_slice(S, after[None], (layer, 0, 0, 0, 0))


def _copy_kernel(layer_ref, row0_ref, n_ref, rows_ref, fresh_ref, a_ref,
                 dx_ref, b_ref, c_ref, s_ref, y_ref, o_ref, *, has_fresh):
    y_ref[...] = jnp.zeros_like(y_ref)
    o_ref[...] = s_ref[...]


def form(name):
    if name == "xla":
        return xla_form
    hb = int(name.split(".")[1])

    def f(S, layer, live, xs, dt, A, Bm, Cm):
        real = ssm._in_place_kernel
        if name.startswith("copy"):
            ssm._in_place_kernel = _copy_kernel
        try:
            return ssm.ssm_update_in_place(
                S, layer, 0, live, None, xs, dt, A, Bm, Cm, heads_a_block=hb)
        finally:
            ssm._in_place_kernel = real
    return f


def live_mask(n, seed=0):
    m = np.zeros(R, bool)
    m[np.random.RandomState(seed).permutation(R)[:n]] = True
    return jnp.asarray(m)


def check(name):
    """On the device, at the cell's widths and 2 layers of 48 rows: the live
    rows' state and `y` against `ssm_update`, every other row bit for bit."""
    S = jax.random.normal(jax.random.PRNGKey(9), (2, R, H, P, N), jnp.float32)
    ops, live = operands(R, 1), live_mask(17, 3)
    y, S2 = jax.jit(form(name))(S, jnp.int32(1), live, *ops)
    yr, Sr = jax.jit(xla_form)(S, jnp.int32(1), live, *ops)
    lv = np.asarray(live)
    S, S2, Sr = (np.asarray(a) for a in (S, S2, Sr))
    return {
        "state_live_max_err": float(np.abs(S2[1][lv] - Sr[1][lv]).max()),
        "state_live_equal": bool(np.array_equal(S2[1][lv], Sr[1][lv])),
        "others_bit_for_bit": bool(np.array_equal(S2[0], S[0])
                                   and np.array_equal(S2[1][~lv], S[1][~lv])),
        "y_live_max_err": float(np.abs(np.asarray(y)[lv]
                                       - np.asarray(yr)[lv]).max()),
        "y_scale": float(np.abs(np.asarray(yr)[lv]).mean()),
        "y_others_zero": bool((np.asarray(y)[~lv] == 0).all()),
    }


def time_us(name, n_live):
    f, ops, live = form(name), operands(R), live_mask(n_live)

    def run(S, live, *ops):
        def one(i, carry):
            S, acc = carry
            y, S = f(S, i % L, live, *ops)
            return S, acc + y
        return jax.lax.fori_loop(
            0, REPS, one, (S, jnp.zeros((R, H, P), jnp.float32)))

    run = jax.jit(run, donate_argnums=0)
    S = jnp.zeros((L, R, H, P, N), jnp.float32)
    S, acc = run(S, live, *ops)
    jax.block_until_ready((S, acc))
    t0 = time.perf_counter()
    S, acc = run(S, live, *ops)
    jax.block_until_ready((S, acc))
    return (time.perf_counter() - t0) / REPS * 1e6


def main():
    print(jax.devices(), flush=True)
    blocks = [int(a) for a in sys.argv[1:]] or [8, 16, 32]
    names = ["xla"] + [f"{k}.{hb}" for hb in blocks for k in ("kernel", "copy")]
    out = []
    for name in names:
        row = {"form": name}
        if not name.startswith("copy"):
            row["check"] = check(name)
        for n_live in LIVE:
            us = time_us(name, n_live)
            floor_us = n_live * 2 * H * P * N * 4 / HBM_BYTES_PER_S * 1e6
            row[f"live{n_live}_us"] = round(us, 1)
            row[f"live{n_live}_share_of_floor"] = round(floor_us / us, 3)
        out.append(row)
        print(json.dumps(row), flush=True)
    os.makedirs("chiprun_out/ssm_update", exist_ok=True)
    with open("chiprun_out/ssm_update/table.json", "w") as fh:
        json.dump(out, fh, indent=1)


if __name__ == "__main__":
    main()
