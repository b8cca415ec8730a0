"""The k largest of a row by SELECTION, not by sorting the row.

XLA:TPU lowers `jax.lax.top_k` to a full sort of the last axis, and so does
`approx_max_k`'s aggregation of its candidates: 0.39 ms a decode step to keep
64 of 9,600 at 64 rows. `top_k_select` finds the k-th largest key by a search
over the keys' bits, takes what lies above it and as many of its equals as k
leaves room for, and sorts those k: `lax.top_k`'s bits. `core/sala.top_blocks`
(PR 58) is the same mechanism at a `C` small enough for a `C x C` triangle of
ones and a `k x C` placement; here everything along the axis goes by groups
of 128 lanes, so `C` is free: counts are a triangle inside a group and a
running sum over the groups, and a taken entry reaches its place through the
group it lies in (a one-hot matmul over the groups, then a compare over that
group's 128 lanes). A gather would do the same and costs more on the chip
than all the rest: 42 us for 64 x 64 scalars (tools/bench_sample_pick.py;
PERF.md PR 60).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_LANES = 128
_u32, _i32 = jnp.uint32, jnp.int32


def _grouped(x):
    """`[..., C]` -> `[..., G, 128]`, the tail of the last group zeros."""
    C = x.shape[-1]
    G = -(-C // _LANES)
    if G * _LANES != C:
        x = jnp.pad(x, ((0, 0),) * (x.ndim - 1) + ((0, G * _LANES - C),))
    return x.reshape(*x.shape[:-1], G, _LANES)


def _counts(m):
    """`m [..., G, 128]` bool -> `(inside, before)` int32: how many hold up
    to and at each lane inside its group (a matmul with a triangle of ones,
    exact in float32), and how many in the groups before it `[..., G]`."""
    lane = jnp.arange(_LANES, dtype=_i32)
    upto = (lane[:, None] <= lane[None]).astype(jnp.bfloat16)
    inside = jnp.einsum("...gl,lm->...gm", m.astype(jnp.bfloat16), upto,
                        preferred_element_type=jnp.float32).astype(_i32)
    total = inside[..., -1]
    return inside, jnp.cumsum(total, axis=-1) - total


def _bytes(x, n: int):
    """Non-negative integers under `256 ** n` as `n` bfloat16 bytes, lowest
    first, side by side on the last axis: what a one-hot matmul carries
    exactly."""
    return jnp.concatenate(
        [((x >> (8 * i)) & 255).astype(jnp.bfloat16) for i in range(n)],
        axis=-1)


def _at_lane(rows, lane, n: int):
    """`rows [..., k, n * 128]` float32, `_bytes`' planes of each place's
    group; `lane [..., k, 128]` bool, one lane a place: the int32 whose bytes
    lie in that lane (the fourth byte wraps into the sign)."""
    whole = sum(rows[..., i * _LANES:(i + 1) * _LANES].astype(_i32) << (8 * i)
                for i in range(n))
    return jnp.sum(jnp.where(lane, whole, 0), axis=-1)


def _rows_of(groups, data):
    """`groups [..., k]` int32, `data [..., G, W]` bfloat16: each place's row
    `data[..., groups, :]` as float32 `[..., k, W]`, by a one-hot matmul over
    the groups (exact: one term a sum)."""
    G = data.shape[-2]
    hot = groups[..., None] == jnp.arange(G, dtype=_i32)
    return jnp.einsum("...kg,...gw->...kw", hot.astype(jnp.bfloat16), data,
                      preferred_element_type=jnp.float32)


def take_at(ints, pos):
    """`jnp.take_along_axis(ints, pos, axis=-1)` for int32 `ints [..., C]` in
    `[0, 2 ** 24)` and `pos [..., k]`, without a gather: the group's row by
    `_rows_of`, the lane by a compare."""
    rows = _rows_of(pos // _LANES, _bytes(_grouped(ints), 3))
    lane = (pos % _LANES)[..., None] == jnp.arange(_LANES, dtype=_i32)
    return _at_lane(rows, lane, 3)


def top_k_select(values, k: int):
    """`jax.lax.top_k(values, k)` bit for bit over float32 `values [..., C]`,
    `(vals, pos)` `[..., k]`: the values descending, equal ones the lower
    position first, in `lax.top_k`'s total order (0.0 before -0.0; `-inf`
    entries are values like any other; no NaN). `k <= C`.

    1. the floats become unsigned keys of the same order (the sign flip);
    2. each row's k-th largest key is found exactly by a search over the
       key's 32 bits, the highest first: a bit stays set where at least k
       keys reach the candidate;
    3. every key above that threshold is taken, and of those equal to it the
       lowest-placed as far as k goes (`_counts`);
    4. place j is the taken entry with j taken ones before it: its group is
       the one whose count before it first passes j, its lane the one whose
       count inside the group is the rest (`_rows_of` brings the group's
       counts and keys), and a sort of width k orders the pairs by (key
       descending, position ascending); the keys become floats again."""
    bits = jax.lax.bitcast_convert_type(values, _u32)
    key = jnp.where(bits >> 31 == 1, ~bits, bits | _u32(1 << 31))

    def bit(i, thr):
        cand = thr | (_u32(1 << 31) >> i.astype(_u32))
        reach = jnp.sum(key >= cand[..., None], axis=-1, dtype=_i32)
        return jnp.where(reach >= k, cand, thr)

    thr = jax.lax.fori_loop(0, 32, bit, jnp.zeros(values.shape[:-1], _u32))
    # (a last group's tail holds key 0, under every float's: never taken)
    key, thr = _grouped(key), thr[..., None, None]
    above, tied = key > thr, key == thr
    room = k - jnp.sum(above, axis=(-2, -1), keepdims=True, dtype=_i32)
    inside, before = _counts(tied)
    taken = above | (tied & (inside + before[..., None] <= room))
    inside, before = _counts(taken)                     # [..., G, 128], [..., G]
    j = jnp.arange(k, dtype=_i32)
    begun = before[..., None, :] <= j[:, None]          # [..., k, G]
    group = jnp.sum(begun, axis=-1, dtype=_i32) - 1
    # 1-based among the group's taken (`before` only grows along G)
    rest = j + 1 - jnp.max(jnp.where(begun, before[..., None, :], 0), axis=-1)
    rows = _rows_of(group, jnp.concatenate(
        [jnp.where(taken, inside, 0).astype(jnp.bfloat16), _bytes(key, 4)],
        axis=-1))                                       # [..., k, 5 * 128]
    lane = rows[..., :_LANES] == rest[..., None].astype(jnp.float32)
    held = jax.lax.bitcast_convert_type(
        _at_lane(rows[..., _LANES:], lane, 4), _u32)
    pos = group * _LANES + jnp.sum(
        jnp.where(lane, jnp.arange(_LANES, dtype=_i32), 0), axis=-1)
    down, pos = jax.lax.sort((~held, pos), num_keys=2)
    held = ~down
    bits = jnp.where(held >> 31 == 1, held ^ _u32(1 << 31), ~held)
    return jax.lax.bitcast_convert_type(bits, values.dtype), pos
