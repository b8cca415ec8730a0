"""Mamba-2's selective recurrence (docs/SSM.md), a head's state `S [P, N]`:

    S_t = a_t S_(t-1) + d_t * x_t (outer) B_t        a_t = exp(d_t * A)
    y_t = S_t C_t

in its two forms: `ssd_scan`, a piece of T tokens in chunks (the products
within a chunk on the matrix unit, the state handed from chunk to chunk, and
from piece to piece by the caller), and `ssm_update`, one token a row (a pass
over the row's state, bound by its bytes). Both are float32 XLA: the state is
float32, and rounding it to bfloat16 every token is a different result (a
head that forgets slowly keeps every token's rounding as long as it keeps
the token; the benchmark's cell holds the served state itself to the float32
reference's, docs/SSM.md). A step on a CACHE is `ssm_update_in_place`: the
same arithmetic as one Pallas call on the whole stacked state, which visits
the live rows only and moves each once (XLA made the step two fusions and
three passes over every resident row: PERF.md, PR 50).

A token with `d_t = 0` neither decays nor feeds the state (`a_t = 1`, the
outer product 0), so the caller marks a pad, or a row nobody listens to, by
its `dt` alone and the state that leaves is the one after the last real
token. `A` is negative, so every exponent here is <= 0.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from nanorlhf_tpu.ops.attention import _interpret_default
from nanorlhf_tpu.ops.paged_cache_write import rows_first

_EXACT = jax.lax.Precision.HIGHEST
# heads of one grid step of `ssm_update_in_place`: their `[P, N]` float32
# states are one block, in and out, two deep (PERF.md PR 50 has the table)
_BLOCK_BYTES = 4 << 20


def ssm_update(xs, dt, A, Bm, Cm, state):
    """One token a row. `xs` [B, H, P], `dt` [B, H] float32 (0: leave the
    row's state as it is), `A` [H] float32, `Bm`, `Cm` [B, G, N] (a group
    for H / G heads), `state` [B, H, P, N] float32. Returns `(y [B, H, P]
    float32, the new state)`."""
    B_, H, P = xs.shape
    G, N = Bm.shape[1:]
    f32 = jnp.float32
    heads = lambda m: jnp.broadcast_to(                         # noqa: E731
        m.astype(f32)[:, :, None, :], (B_, G, H // G, N)).reshape(B_, H, N)
    fed = (xs.astype(f32) * dt[..., None])[..., None] * heads(Bm)[:, :, None, :]
    new = jnp.exp(dt * A)[..., None, None] * state + fed
    return jnp.sum(new * heads(Cm)[:, :, None, :], axis=-1), new


def ssd_scan(xs, dt, A, Bm, Cm, state, chunk: int):
    """A piece of T tokens a row, in chunks of `chunk`. `xs` [B, T, H, P],
    `dt` [B, T, H] float32 (0 at a pad), `A` [H] float32, `Bm`, `Cm`
    [B, T, G, N], `state` [B, H, P, N] float32: what the row held before the
    piece (zeros for a row that starts here). Returns `(y [B, T, H, P]
    float32, the state after the piece)`.

    With `c_i` the running sum of `d A` within a chunk (inclusive),
    `y_i = sum_(j<=i) exp(c_i - c_j) (C_i . B_j) d_j x_j + exp(c_i) C_i S_in`
    and `S_out = exp(c_last) S_in + sum_j exp(c_last - c_j) d_j x_j (outer)
    B_j`: three matmuls a chunk and one small serial scan over the chunks.
    T is padded up to whole chunks with `dt = 0` tokens."""
    B_, T, H, P = xs.shape
    G, N = Bm.shape[2:]
    Hg, f32 = H // G, jnp.float32
    short = -T % chunk
    if short:
        xs, dt, Bm, Cm = (jnp.pad(a, ((0, 0), (0, short)) + ((0, 0),)
                                  * (a.ndim - 2)) for a in (xs, dt, Bm, Cm))
    c, l = (T + short) // chunk, chunk
    x = (xs.astype(f32) * dt[..., None]).reshape(B_, c, l, G, Hg, P)
    Bc = Bm.astype(f32).reshape(B_, c, l, G, N)
    Cc = Cm.astype(f32).reshape(B_, c, l, G, N)
    # [B, c, G, Hg, l]: a head's running log-decay within its chunk
    cum = jnp.cumsum((dt * A).reshape(B_, c, l, G, Hg), axis=2).transpose(
        0, 1, 3, 4, 2)
    lower = jnp.tril(jnp.ones((l, l), bool))
    among = jnp.where(lower, jnp.exp(jnp.where(
        lower, cum[..., :, None] - cum[..., None, :], 0)), 0)
    cb = jnp.einsum("bcign,bcjgn->bcgij", Cc, Bc, precision=_EXACT)
    y = jnp.einsum("bcghij,bcjghp->bcighp", cb[:, :, :, None] * among, x,
                   precision=_EXACT)
    to_end = jnp.exp(cum[..., -1:] - cum).transpose(0, 1, 4, 2, 3)
    fed = jnp.einsum("bcjghp,bcjgn->bcghpn", x * to_end[..., None], Bc,
                     precision=_EXACT)
    whole = jnp.exp(cum[..., -1])                                # [B, c, G, Hg]

    def handed(S, chunk_):
        fed_c, whole_c = chunk_
        return whole_c[..., None, None] * S + fed_c, S

    out, before = jax.lax.scan(
        handed, state.astype(f32).reshape(B_, G, Hg, P, N),
        (jnp.moveaxis(fed, 1, 0), jnp.moveaxis(whole, 1, 0)))
    carried = jnp.einsum("bcign,cbghpn->bcighp", Cc, before, precision=_EXACT)
    y = y + carried * jnp.exp(cum).transpose(0, 1, 4, 2, 3)[..., None]
    return (y.reshape(B_, c * l, H, P)[:, :T], out.reshape(B_, H, P, N))


def ssm_token_scan(xs, dt, A, Bm, Cm, state):
    """`ssd_scan`'s plain form and its oracle: `ssm_update` over the T
    tokens in order."""
    def step(S, token):
        x_t, dt_t, B_t, C_t = token
        y, S = ssm_update(x_t, dt_t, A, B_t, C_t, S)
        return S, y

    out, ys = jax.lax.scan(
        step, state.astype(jnp.float32),
        tuple(jnp.moveaxis(a, 1, 0) for a in (xs, dt, Bm, Cm)))
    return jnp.moveaxis(ys, 0, 1), out


def _in_place_kernel(layer_ref, row0_ref, n_ref, rows_ref, fresh_ref, a_ref,
                     dx_ref, b_ref, c_ref, s_ref, y_ref, o_ref, *,
                     has_fresh: bool):
    """One live row's block of `hb` heads: `s_ref` `[hb, P, N]` in, `o_ref`
    the same place out, `y_ref` `[P, hb]`. P lies on sublanes and N on
    lanes, so `d x` comes as a column a head (`dx_ref` `[P, hb]`), `B` and
    `C` as rows (`[gb, 1, N]`) and `y` leaves as a column a head."""
    i, j = pl.program_id(0), pl.program_id(1)
    n = n_ref[0]
    hb, P, _ = s_ref.shape
    per_group = hb // b_ref.shape[0]        # heads of the block a group

    @pl.when(i < n)
    def _():
        r = rows_ref[i]
        lane = jax.lax.broadcasted_iota(jnp.int32, (P, hb), 1)
        y = jnp.zeros((P, hb), jnp.float32)
        for h in range(hb):
            g = h // per_group
            s = s_ref[h].astype(jnp.float32)
            if has_fresh:
                s = jnp.where(fresh_ref[r] != 0, 0.0, s)
            new = a_ref[r, j * hb + h] * s + dx_ref[:, h:h + 1] * b_ref[g]
            o_ref[h] = new.astype(o_ref.dtype)
            y = jnp.where(lane == h, jnp.sum(new * c_ref[g], axis=-1,
                                             keepdims=True), y)
        y_ref[...] = y

    # no live row at all: the one block the pipeline still moves goes back
    # as it came
    @pl.when((n == 0) & (i == 0) & (j == 0))
    def _():
        o_ref[...] = s_ref[...]


def _heads_a_block(H: int, G: int, P: int, N: int) -> int:
    """The most heads whose states fit `_BLOCK_BYTES`: a divisor of H that
    divides a group's heads or is whole groups."""
    fits = [hb for hb in range(1, H + 1) if H % hb == 0
            and ((H // G) % hb == 0 or hb % (H // G) == 0)
            and hb * P * N * 4 <= _BLOCK_BYTES]
    return max(fits, default=1)


def ssm_update_in_place(s_stack, layer, row0, live, fresh, xs, dt, A, Bm, Cm,
                        *, heads_a_block: int | None = None,
                        interpret: bool | None = None):
    """`ssm_update` on rows `[row0, row0 + B)` of layer `layer` of the WHOLE
    state `s_stack` `[layers, rows, H, P, N]` float32, where it lies: `(y
    [B, H, P] float32, s_stack)`. `live` [B] bool or None (every row):
    a row not live is neither read nor written, its state is bit for bit
    what it was and its `y` is 0. `fresh` [B] bool or None: a fresh live
    row's incoming state counts as zeros. `xs`, `dt`, `A`, `Bm`, `Cm` as
    `ssm_update`'s.

    One Pallas call aliased to the stack (`ops/paged_cache_write.py`'s
    pattern): the live rows, first, ride in scalar prefetch with the layer;
    the grid is rows x head blocks, and a step past the live count repeats
    the last live step's block indices, so the pipeline moves nothing for
    it. A visited `(row, head)` reads `S` once, writes `a S + d x (outer) B`
    back and reduces `y = S_new C` from the values it holds; `B` and `C`
    stay a group's `[N]`. The arithmetic is `ssm_update`'s, in float32
    (`a = exp(d A)` and `d x` are made outside, as there); only the order
    of `y`'s sum over N is the kernel's own.

    On a TPU a state whose `[P, N]` is not whole tiles (P a multiple of 8
    for float32, N of 128) cannot be a block: the call is then `ssm_update`
    on the sliced layer and `dynamic_update_slice`, BY SHAPE. Off a TPU the
    kernel runs in interpret mode at any width. A stack of another type
    than float32 (`benchmark/tools/ssm_control.py`'s bfloat16 control) is
    read into float32 and rounded as it is written; `y` is reduced before
    the rounding, as `ssm_update`'s caller had it."""
    B_, H, P = xs.shape
    G, N = Bm.shape[1:]
    f32 = jnp.float32
    interpret = _interpret_default() if interpret is None else interpret
    dt = dt.astype(f32)
    size = s_stack.dtype.itemsize
    if not interpret and (P % (32 // size) or N % 128):
        if live is not None:
            dt = jnp.where(live[:, None], dt, 0)
        before = jax.lax.dynamic_slice(
            s_stack, (layer, row0, 0, 0, 0), (1, B_, H, P, N))[0]
        if fresh is not None:
            before = jnp.where(fresh[:, None, None, None], 0, before)
        y, after = ssm_update(xs, dt, A, Bm, Cm, before)
        if live is not None:
            y = jnp.where(live[:, None, None], y, 0)
        return y, jax.lax.dynamic_update_slice(
            s_stack, after[None].astype(s_stack.dtype),
            (layer, row0, 0, 0, 0))

    hb = heads_a_block or _heads_a_block(H, G, P, N)
    nblk, gb = H // hb, max(1, hb * G // H)
    n, rows = rows_first(jnp.ones((B_,), bool) if live is None else live)
    has_fresh = fresh is not None
    flags = (fresh.astype(jnp.int32) if has_fresh
             else jnp.zeros((1,), jnp.int32))
    a = jnp.exp(dt * A)                                          # [B, H]
    # [B, blocks, P, hb]: a head's `d x` as a column of its block
    dx = (xs.astype(f32) * dt[..., None]).reshape(
        B_, nblk, hb, P).transpose(0, 1, 3, 2)
    Bm, Cm = (m.astype(f32).reshape(B_, G, 1, N) for m in (Bm, Cm))

    def at(i, n_ref, rows_ref):
        return rows_ref[jnp.minimum(i, jnp.maximum(n_ref[0] - 1, 0))]

    def blk(i, j, n_ref):      # a step past the live count: the last block
        return jnp.where(i < n_ref[0], j, nblk - 1)

    def by_block(i, j, layer_ref, row0_ref, n_ref, rows_ref, fresh_ref):
        return at(i, n_ref, rows_ref), blk(i, j, n_ref), 0, 0

    def by_group(i, j, layer_ref, row0_ref, n_ref, rows_ref, fresh_ref):
        return (at(i, n_ref, rows_ref),
                blk(i, j, n_ref) * hb // (H // G * gb), 0, 0)

    def state(i, j, layer_ref, row0_ref, n_ref, rows_ref, fresh_ref):
        return (layer_ref[0], row0_ref[0] + at(i, n_ref, rows_ref),
                blk(i, j, n_ref), 0, 0)

    s_spec = pl.BlockSpec((None, None, hb, P, N), state)
    col_spec = pl.BlockSpec((None, None, P, hb), by_block)
    row_spec = pl.BlockSpec((None, gb, 1, N), by_group)
    y, s_stack = pl.pallas_call(
        functools.partial(_in_place_kernel, has_fresh=has_fresh),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(B_, nblk),
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), col_spec,
                      row_spec, row_spec, s_spec],
            out_specs=[col_spec, s_spec],
        ),
        out_shape=[jax.ShapeDtypeStruct((B_, nblk, P, hb), f32),
                   jax.ShapeDtypeStruct(s_stack.shape, s_stack.dtype)],
        # operands count from the scalar-prefetch ones: the stack is 9
        input_output_aliases={9: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=4 * hb * P * N * size + (16 << 20)),
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(1),
      jnp.asarray(row0, jnp.int32).reshape(1), n, rows, flags,
      a, dx, Bm, Cm, s_stack)
    y = y.transpose(0, 1, 3, 2).reshape(B_, H, P)
    if live is not None:    # a row not visited holds what the buffer held
        y = jnp.where(live[:, None, None], y, 0)
    return y, s_stack
