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
reference's, docs/SSM.md).

A token with `d_t = 0` neither decays nor feeds the state (`a_t = 1`, the
outer product 0), so the caller marks a pad, or a row nobody listens to, by
its `dt` alone and the state that leaves is the one after the last real
token. `A` is negative, so every exponent here is <= 0.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_EXACT = jax.lax.Precision.HIGHEST


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
