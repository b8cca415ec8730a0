"""Fused hidden→logprob scoring: chunked linear-cross-entropy.

Every non-sequence-parallel scoring and update pass used to materialize the
full `[B, T, V]` logits tensor (`padded_forward_logits` → `logprobs_from_
logits`), plus an extra f32 copy for the entropy stat. At Qwen2's 152k vocab
that buffer is the single largest HBM allocation in the train step — it caps
microbatch size, grad-accum shape, and reachable response length (RLAX and
LlamaRL both name trainer logits memory as the first-order bottleneck for
long-sequence RLHF).

This module fuses the unembedding matmul with the log-softmax gather (and the
entropy stat + optional top-k margin, in the same pass), chunked over the
flattened token rows so only one `[chunk, V]` logits block is ever live:

- **`fused_logprob_reference`** — the naive full-logits lax path (parity
  oracle, and the `fused_logprob=False` trainer fallback's math).
- **lax chunked path** (`impl="lax"`) — a `lax.scan` over row chunks; each
  chunk recomputes its logits block from `hidden @ W` and reduces it to
  per-token scalars. Chunk math goes through the SAME `logprobs_from_logits`
  / `entropy_from_logits` helpers as the naive path, so fused-vs-naive parity
  is exact up to matmul tiling noise.
- **Pallas kernel** (`impl="pallas"`, `interpret=True` CPU fallback) — a
  vocab-blocked online-logsumexp kernel (grid: row blocks × vocab blocks,
  vocab fastest) carrying running max / sumexp / Σp·z / label-logit in VMEM
  scratch, the same online-softmax recipe as ops/attention.py. The `[rows,
  V]` block never leaves VMEM.
- **`jax.custom_vjp`**: the backward RECOMPUTES each chunk's logits block
  from the saved `(hidden, W, labels)` instead of saving any logits — the
  flash-attention memory trade applied to the LM head. `dW` accumulates in
  f32 across chunks.

Gradient semantics: per-token logprobs are exact (the backward replays the
naive path's VJP chunk by chunk). The entropy and margin outputs carry
STOP-GRADIENT semantics — their cotangents are discarded, matching the
trainer's `stop_gradient(entropy)` stat (a differentiable entropy would have
to re-derive Σp·z in the backward; nothing in the repo wants that gradient).

`impl="auto"` resolves to the Pallas kernel on TPU and the lax chunk scan
elsewhere; `with_margin` forces the lax path (the kernel does not track
top-2). See docs/FUSED_LOGPROB.md for the chunk-size trade.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from nanorlhf_tpu.ops.masking import (
    entropy_from_logits,
    guard_temperature,
    logprobs_from_logits,
)

_VMEM = pltpu.VMEM
NEG_INF = -1e30
_LANES = 128
_SUBLANES = 8

# Default HBM budget for one recomputed logits chunk. Per row the forward
# holds the [1, V] logits strip in model dtype and the backward recompute
# additionally its f32 softmax (the vjp intermediate), so ~(itemsize + 4)
# bytes per vocab entry. 256 MB → 288 rows at a 152k bf16 vocab
# (256 MB // (151936·6 B), floored to a sublane multiple): two orders of
# magnitude under the multi-GB full-logits buffer, still far above the
# matmul-efficiency floor.
_FUSED_BYTES_BUDGET = 256 * 1024**2


def fused_chunk_rows(
    vocab_size: int,
    total_rows: int,
    dtype_bytes: int = 2,
    bytes_budget: int | None = None,
) -> int:
    """Rows (flattened B·T tokens) per recomputed logits chunk.

    Derived from a bytes budget the same way trainer.forward_token_budget
    bounds the scoring chunk — the knob that makes peak memory SUBLINEAR in
    V: as the vocabulary grows, the chunk shrinks so chunk×V stays ≈ budget.
    Rounded down to a sublane multiple (8) for TPU-friendly tiling; floored
    at 8 rows; capped at total_rows.
    """
    budget = _FUSED_BYTES_BUDGET if bytes_budget is None else bytes_budget
    per_row = max(1, vocab_size) * (dtype_bytes + 4)
    rows = max(8, int(budget) // per_row)
    rows = max(8, (rows // _SUBLANES) * _SUBLANES)
    return int(min(rows, max(1, total_rows)))


# ---------------------------------------------------------------------------
# lax reference (full logits — the parity oracle)
# ---------------------------------------------------------------------------


def _head_matmul(h: jnp.ndarray, w: jnp.ndarray,
                 transposed: bool) -> jnp.ndarray:
    """`h @ w` ([D, V] weight) or `h @ wᵀ` ([V, D] weight, `transposed`) as
    ONE dot_general — never a transposed weight copy. The transposed form
    is how tied embeddings reach the op: a `.Tᵀ` view feeding a Pallas
    custom call would make XLA materialize the full [D, V] transpose
    (custom-call operands are physical buffers; only XLA dots fold
    transposes), ~260 MB bf16 at Qwen2's 152k vocab, held live across the
    whole vocab sweep."""
    dims = (((1,), (1,)) if transposed else ((1,), (0,)), ((), ()))
    return jax.lax.dot_general(h, w, dims)


def fused_logprob_reference(
    hidden: jnp.ndarray,     # [..., D]
    unembed: jnp.ndarray,    # [D, V] ([V, D] when `transposed`)
    labels: jnp.ndarray,     # [...] int
    temperature: float = 1.0,
    with_entropy: bool = False,
    with_margin: bool = False,
    transposed: bool = False,
):
    """Naive full-logits path: `hidden @ unembed` → per-token logprobs
    (+ entropy, + top-1-vs-top-2 margin). Materializes [..., V] — the
    memory behavior the fused paths eliminate. Entropy/margin are emitted
    under stop_gradient, matching the fused op's semantics."""
    logits = hidden @ (unembed.T if transposed else unembed)
    t = guard_temperature(temperature)
    out = (logprobs_from_logits(logits, labels, temperature),)
    if with_entropy:
        out += (jax.lax.stop_gradient(
            entropy_from_logits(logits.astype(jnp.float32) / t)
        ),)
    if with_margin:
        top2 = jax.lax.top_k(logits.astype(jnp.float32) / t, 2)[0]
        out += (jax.lax.stop_gradient(top2[..., 0] - top2[..., 1]),)
    return out[0] if len(out) == 1 else out


def chunked_entropy(
    logits: jnp.ndarray, temperature: float = 1.0, chunk: int | None = None,
    bytes_budget: int | None = None,
) -> jnp.ndarray:
    """Per-position entropy of temperature-scaled logits WITHOUT the f32
    full-logits copy: blocks are cast f32 one slice at a time (the
    `fused_logprob=False` fallback's entropy stat — the fused path gets
    entropy from its own pass and never sees full logits at all).

    Chunks along the TIME axis (second-to-last), not flattened rows: time
    slices leave a batch-sharded tensor's sharding intact, whereas
    flattening batch×time into rows and re-chunking reshards the batch
    axis — GSPMD answered the ragged slice+concat form of that with a
    MISCOMPILED program (entropy exactly 2× on a 2-way-sharded batch;
    pinned by the sharded-mesh test in tests/test_fused_logprob.py), and
    the padded form with a second full-logits copy. The static python loop
    unrolls into one slice+reduce per block.
    """
    t = guard_temperature(temperature)
    T, V = logits.shape[-2], logits.shape[-1]
    rows = int(np.prod(logits.shape[:-1]))
    if chunk is None:
        # only the f32 copy + softmax intermediates count here — the source
        # logits already exist
        chunk = fused_chunk_rows(V, rows, dtype_bytes=4,
                                 bytes_budget=bytes_budget)
    # row budget → time-axis block width
    rows_per_t = max(1, rows // T)
    t_chunk = max(1, min(T, int(chunk) // rows_per_t))
    n_blocks = -(-T // t_chunk)
    if n_blocks == 1:
        return entropy_from_logits(logits.astype(jnp.float32) / t)

    # fori_loop keeps the traced graph O(1) in T (an unrolled python loop
    # is ~300 slice+reduce ops at 8k responses). A ragged final block is
    # handled by CLAMPING its start to T - t_chunk: dynamic_slice clamps
    # out-of-bounds starts the same way, and the overlapping positions are
    # recomputed to identical values, so the overlapping write is benign.
    def body(i, out):
        start = jnp.minimum(i * t_chunk, T - t_chunk)
        block = jax.lax.dynamic_slice_in_dim(logits, start, t_chunk, axis=-2)
        ent = entropy_from_logits(block.astype(jnp.float32) / t)
        return jax.lax.dynamic_update_slice_in_dim(out, ent, start, axis=-1)

    out0 = jnp.zeros(logits.shape[:-1], jnp.float32)
    return jax.lax.fori_loop(0, n_blocks, body, out0)


# ---------------------------------------------------------------------------
# lax chunked forward/backward (the default off-TPU fused path)
# ---------------------------------------------------------------------------


def _lax_forward(hidden, unembed, labels, temperature, chunk,
                 with_entropy, with_margin, transposed):
    """Scan over row chunks; each [chunk, V] logits block is a scan-local
    temporary. Chunk math reuses the exact naive helpers so fused == naive."""
    R, D = hidden.shape
    n = R // chunk
    t = guard_temperature(temperature)
    hs = hidden.reshape(n, chunk, D)
    ls = labels.reshape(n, chunk)

    def body(_, xs):
        h_c, l_c = xs
        z = _head_matmul(h_c, unembed, transposed)
        out = (logprobs_from_logits(z, l_c, temperature),)
        if with_entropy:
            out += (entropy_from_logits(z.astype(jnp.float32) / t),)
        if with_margin:
            top2 = jax.lax.top_k(z.astype(jnp.float32) / t, 2)[0]
            out += (top2[..., 0] - top2[..., 1],)
        return None, out

    _, outs = jax.lax.scan(body, None, (hs, ls))
    return tuple(o.reshape(R) for o in outs)


# ---------------------------------------------------------------------------
# Pallas kernel: vocab-blocked online logsumexp + label gather + Σp·z
# ---------------------------------------------------------------------------


def _fused_kernel(h_ref, w_ref, lab_ref, lp_ref, *refs,
                  inv_temp: float, block_v: int, vocab_size: int,
                  w_transposed: bool, with_entropy: bool):
    # the entropy accumulator (Σ exp(z−m)·z) costs ~2 VPU ops per logit
    # element across the whole vocab sweep — the entropy output, its u
    # scratch, and that work exist only when the caller asked (the hot
    # scoring path never does; only the update-pass entropy stat does)
    if with_entropy:
        ent_ref, m_ref, l_ref, u_ref, g_ref = refs
    else:
        ent_ref = u_ref = None
        m_ref, l_ref, g_ref = refs
    v_idx = pl.program_id(1)
    n_v = pl.num_programs(1)

    @pl.when(v_idx == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        if with_entropy:
            u_ref[:] = jnp.zeros_like(u_ref)
        g_ref[:] = jnp.zeros_like(g_ref)

    h = h_ref[...].astype(jnp.float32)                  # [Br, D]
    w = w_ref[...].astype(jnp.float32)                  # [D, Bv] / [Bv, D]
    s = jax.lax.dot_general(
        h, w,
        (((1,), (1,) if w_transposed else (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * inv_temp                                        # [Br, Bv]
    block_r = s.shape[0]
    col = v_idx * block_v + jax.lax.broadcasted_iota(
        jnp.int32, (block_r, block_v), 1
    )
    # vocab tail mask: V need not divide block_v — out-of-range columns are
    # neutralized here instead of padding a copy of the (huge) weight
    s = jnp.where(col < vocab_size, s, NEG_INF)

    lab = lab_ref[:, :1]                                # [Br, 1] int32
    # label gather: exactly one column matches across the whole vocab sweep
    g_new = g_ref[:, :1] + jnp.sum(
        jnp.where(col == lab, s, 0.0), axis=1, keepdims=True
    )

    m_prev = m_ref[:, :1]
    l_prev = l_ref[:, :1]
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)                              # masked cols → exp(-inf)=0
    l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
    if with_entropy:
        # Σ softmax·z carried unnormalized as Σ exp(z−m)·z (entropy
        # residual); 0 · NEG_INF = -0.0 for masked columns, never NaN
        # (NEG_INF is finite)
        u_new = alpha * u_ref[:, :1] + jnp.sum(p * s, axis=1, keepdims=True)
        u_ref[:] = jnp.broadcast_to(u_new, u_ref.shape)

    m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)
    g_ref[:] = jnp.broadcast_to(g_new, g_ref.shape)

    @pl.when(v_idx == n_v - 1)
    def _finalize():
        l = jnp.maximum(l_ref[:, :1], 1e-30)
        lse = m_ref[:, :1] + jnp.log(l)
        lp_ref[...] = jnp.broadcast_to(g_ref[:, :1] - lse, lp_ref.shape)
        if with_entropy:
            ent_ref[...] = jnp.broadcast_to(
                lse - u_ref[:, :1] / l, ent_ref.shape
            )


def _interpret_default() -> bool:
    from nanorlhf_tpu.ops.attention import _interpret_default as _att

    return _att()


def _pallas_forward(hidden, unembed, labels, temperature,
                    block_r: int = 256, block_v: int = 512,
                    interpret: bool | None = None, transposed: bool = False,
                    with_entropy: bool = False):
    """`(logprobs, entropy | None)` per row, [R] f32 — the [R, V] logits
    exist only as per-(row-block, vocab-block) VMEM tiles. With
    `transposed` the weight arrives [V, D] (tied embeddings) and the grid
    reads vocab-ROW blocks — the contraction flips inside the kernel, so no
    [D, V] transposed copy is staged for the custom call."""
    R, D = hidden.shape
    V = unembed.shape[0] if transposed else unembed.shape[1]
    inv_temp = 1.0 / guard_temperature(temperature)
    if interpret is None:
        interpret = _interpret_default()

    block_r = max(_SUBLANES, min(block_r, -(-R // _SUBLANES) * _SUBLANES))
    R_pad = -(-R // block_r) * block_r
    if R_pad != R:
        hidden = jnp.pad(hidden, ((0, R_pad - R), (0, 0)))
        labels = jnp.pad(labels, (0, R_pad - R))
    n_r = R_pad // block_r
    n_v = int(pl.cdiv(V, block_v))
    # labels ride lane-expanded [R, LANES] — a 1-D int vector is not a
    # Mosaic-liftable operand (same recipe as the attention kernels' mask)
    lab2 = jnp.broadcast_to(
        labels.astype(jnp.int32)[:, None], (R_pad, _LANES)
    )

    kernel = functools.partial(
        _fused_kernel, inv_temp=float(inv_temp), block_v=block_v,
        vocab_size=V, w_transposed=transposed, with_entropy=with_entropy,
    )
    w_spec = (
        pl.BlockSpec((block_v, D), lambda i, j: (j, 0), memory_space=_VMEM)
        if transposed else
        pl.BlockSpec((D, block_v), lambda i, j: (0, j), memory_space=_VMEM)
    )
    row_spec = pl.BlockSpec((block_r, _LANES), lambda i, j: (i, 0),
                            memory_space=_VMEM)
    row_shape = jax.ShapeDtypeStruct((R_pad, _LANES), jnp.float32)
    row_scratch = pltpu.VMEM((block_r, _LANES), jnp.float32)
    n_out = 2 if with_entropy else 1          # lp [, ent]
    n_scratch = 4 if with_entropy else 3      # m, l [, u], g
    outs = pl.pallas_call(
        kernel,
        grid=(n_r, n_v),
        in_specs=[
            pl.BlockSpec((block_r, D), lambda i, j: (i, 0),
                         memory_space=_VMEM),
            w_spec,
            row_spec,
        ],
        out_specs=[row_spec] * n_out,
        out_shape=[row_shape] * n_out,
        scratch_shapes=[row_scratch] * n_scratch,
        interpret=interpret,
    )(hidden, unembed, lab2)
    lp = outs[0][:R, 0]
    return lp, (outs[1][:R, 0] if with_entropy else None)


# ---------------------------------------------------------------------------
# custom_vjp core (2-D rows) + public entry
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _fused_core(hidden, unembed, labels, temperature, chunk, impl,
                with_entropy, with_margin, transposed):
    if impl == "pallas":
        lp, ent = _pallas_forward(hidden, unembed, labels, temperature,
                                  transposed=transposed,
                                  with_entropy=with_entropy)
        out = (lp,)
        if with_entropy:
            out += (ent,)
        return out
    return _lax_forward(
        hidden, unembed, labels, temperature, chunk, with_entropy,
        with_margin, transposed,
    )


def _core_fwd(hidden, unembed, labels, temperature, chunk, impl,
              with_entropy, with_margin, transposed):
    out = _fused_core(hidden, unembed, labels, temperature, chunk, impl,
                      with_entropy, with_margin, transposed)
    return out, (hidden, unembed, labels)


def _core_bwd(temperature, chunk, impl, with_entropy, with_margin,
              transposed, residuals, g):
    """Recompute each chunk's logits block and replay the naive VJP on it —
    no logits were saved in the forward. Entropy/margin cotangents (g[1:])
    are discarded: stop-gradient semantics, see module docstring. With
    `transposed` the vjp runs through `_head_matmul`'s flipped contraction,
    so dW lands in the weight's own [V, D] orientation — it accumulates
    straight into the tied embedding leaf, no transpose copy."""
    hidden, unembed, labels = residuals
    g_lp = g[0]
    R, D = hidden.shape
    n = R // chunk
    hs = hidden.reshape(n, chunk, D)
    ls = labels.reshape(n, chunk)
    gs = g_lp.reshape(n, chunk)

    def body(dw_acc, xs):
        h_c, l_c, g_c = xs

        def f(h_, w_):
            return logprobs_from_logits(
                _head_matmul(h_, w_, transposed), l_c, temperature
            )

        _, vjp = jax.vjp(f, h_c, unembed)
        dh_c, dw_c = vjp(g_c)
        return dw_acc + dw_c.astype(jnp.float32), dh_c

    dw, dh = jax.lax.scan(
        body, jnp.zeros(unembed.shape, jnp.float32), (hs, ls, gs)
    )
    # integer primal → float0 cotangent (jax's tangent type for int arrays)
    dlabels = np.zeros(labels.shape, jax.dtypes.float0)
    return dh.reshape(R, D), dw.astype(unembed.dtype), dlabels


_fused_core.defvjp(_core_fwd, _core_bwd)


def _resolve_impl(impl: str, with_margin: bool) -> str:
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "lax"
    if impl not in ("lax", "pallas"):
        raise ValueError(f"fused_logprob impl={impl!r}: auto | lax | pallas")
    if with_margin and impl == "pallas":
        return "lax"  # the kernel does not track top-2; lax path does
    return impl


@jax.named_scope("logprob")
def fused_logprob(
    hidden: jnp.ndarray,     # [..., D] final-normed hidden states
    unembed: jnp.ndarray,    # [D, V] weight ([V, D] when `transposed`)
    labels: jnp.ndarray,     # [...] int token ids to gather
    temperature: float = 1.0,
    *,
    chunk: int | None = None,
    impl: str = "auto",
    with_entropy: bool = False,
    with_margin: bool = False,
    bytes_budget: int | None = None,
    transposed: bool = False,
):
    """Per-token `log softmax(hidden @ unembed / T)[labels]` without ever
    materializing the [..., V] logits tensor.

    Returns `logprobs` (f32, shaped like `labels`), or a tuple
    `(logprobs[, entropy][, margin])` when the extra outputs are requested
    — entropy is the per-token logsumexp entropy of the temperature-scaled
    distribution, margin the top-1-vs-top-2 scaled-logit gap (both
    stop-gradient). `chunk=None` derives the rows-per-block from
    `bytes_budget` (`fused_chunk_rows`): peak memory then stays ≈ budget
    regardless of vocabulary size. Differentiable wrt `hidden` and
    `unembed`; the custom-VJP backward recomputes chunk logits instead of
    saving them.

    `transposed=True` takes the weight vocab-major ([V, D] — i.e. the tied
    `embed_tokens` leaf directly, see `core.model.unembedding`): every path
    contracts on the shared D axis (`_head_matmul`), dW comes back [V, D],
    and the Pallas grid reads vocab-row blocks — passing `embed.T` instead
    would stage a full [D, V] transposed copy for the custom call.
    """
    lead = hidden.shape[:-1]
    D = hidden.shape[-1]
    V = unembed.shape[0] if transposed else unembed.shape[-1]
    if labels.shape != lead:
        raise ValueError(f"labels shape {labels.shape} != hidden[:-1] {lead}")
    R = int(np.prod(lead)) if lead else 1
    impl = _resolve_impl(impl, with_margin)
    if chunk is None:
        chunk = fused_chunk_rows(
            V, R, dtype_bytes=jnp.dtype(hidden.dtype).itemsize,
            bytes_budget=bytes_budget,
        )
    chunk = max(1, min(int(chunk), R))
    h2 = hidden.reshape(R, D)
    l2 = labels.reshape(R).astype(jnp.int32)
    R_pad = -(-R // chunk) * chunk
    if R_pad != R:
        # pad rows so the scan sees equal chunks; the slice below zeroes the
        # pad rows' cotangents, so dW never sees them
        h2 = jnp.pad(h2, ((0, R_pad - R), (0, 0)))
        l2 = jnp.pad(l2, (0, R_pad - R))
    outs = _fused_core(h2, unembed, l2, float(temperature), int(chunk), impl,
                       bool(with_entropy), bool(with_margin),
                       bool(transposed))
    outs = tuple(o[:R].reshape(lead) for o in outs)
    if not with_entropy and not with_margin:
        return outs[0]
    return outs
