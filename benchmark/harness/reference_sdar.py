"""Plain reference: SDAR-MoE's forward pass and its generation by diffusion
over blocks, replayed, in `jax.numpy`, float32 (docs/BLOCKDIFF.md).

The layer (Qwen3-MoE's; `x` the residual stream, every layer alike):

    h = rmsnorm(x);  q, k, v = h W_q, h W_k, h W_v        no bias
    q, k <- rmsnorm_head(q), rmsnorm_head(k)               over each head's 128
    rotate-half RoPE on q, k (theta 1e6, unscaled)
    a = softmax(q k^T / sqrt(head_dim) + M) v              GQA
    x += a W_o
    h = rmsnorm(x);  p = softmax(h W_r) over the experts;  the top k by p,
        weights p_i / sum_topk p  (`norm_topk_prob`)
    x += sum_i w_i W_down_i( silu(h W_gate_i) * (h W_up_i) )
    final rmsnorm, untied head. Position i's logits predict position i's OWN
    token (no shift).

The mask M, B = `block_length`: the key at position j is visible to the query
at position i iff `j // B <= i // B` and j is a real token.

`forward(weights, cfg, ids, n_real, ...)` is ONE sequence `ids [T]` (no
padding on the left: positions are 0..T-1; the entries from `n_real` on are
not real: no query sees them) under M in one pass, attention in blocks of
queries and the head in blocks of columns, so that it fits beside a served
model. `replay(...)` rebuilds the denoise forwards of a served generation
from the tokens and the step of its block that unmasked each (what the
gateway's response carries): for the forward of step s on block b the
sequence as it stood is the prompt, the blocks before b as they ended, and
block b with the tokens of steps < s in place and `mask_token_id` elsewhere.

No kernel, no page, no sort and no grouped matmul: EVERY expert is computed
for EVERY token, one expert at a time, weighted by a [tokens, experts] matrix
that is zero off the top k. Nothing is imported from `nanorlhf_tpu`; the tree
is read by its leaf names (`embed_tokens [V, D]`; `layers.*` stacked on a
leading layer axis: `q_proj/k_proj/v_proj/o_proj.kernel [L, in, out]`,
`q_norm`, `k_norm [L, head_dim]`, `input_layernorm`,
`post_attention_layernorm`, `router.kernel [L, D, E]`,
`experts.{gate,up,down}_proj.kernel [L, E, in, out]`; `norm`; `lm_head [D,
V]`). Weights may arrive in bfloat16: each is cast to float32 as it is used
(exact). Callers wrap calls in `jax.default_matmul_precision("highest")`.

Departures, each for room or time and none for numerics:
- attention in blocks of `QUERY_BLOCK` queries, the head in `HEAD_BLOCKS`
  column blocks (reference_smallthinker.py's);
- `replay` does not run each denoise forward's whole sequence again. Under M
  a token's states depend on no later block, so the blocks before b have,
  in every forward on b or later, the states they have in the FINAL
  sequence. `replay` therefore computes the final sequence once and, beside
  it, every denoise forward's B tokens as one more row of queries whose keys
  are the final sequence's before the block's start and the block's own:
  the same sums over the same numbers (tests/test_sdar.py holds it to the
  literal rebuild, `literal=True`, which a negative control that changes the
  block structure takes anyway).

The NEGATIVE CONTROLS of the cell's comparison (a sound system must read as
wrong against each): `causal=True` (the usual causal mask), `shift=True` (the
logits at position i-1 predict position i), `block_length` 1 or 8 (another
block than the one generated with), `qk_norm=False` (no per-head norm),
`renorm=False` (the top-k weights not renormalised).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from harness.reference import F32, MASKED, _rms_norm, _rope

QUERY_BLOCK = 256
HEAD_BLOCKS = 8
HEAD_ROWS = 256     # rows of hidden states the reduced head takes at a time
T_PAD, N_PAD = 128, 64   # `replay` pads positions and forwards to multiples


def _widths(cfg: dict) -> tuple:
    D, H, KV = (cfg["hidden_size"], cfg["num_attention_heads"],
                cfg["num_key_value_heads"])
    return D, H, KV, cfg.get("head_dim") or D // H


def _qkv(h, p, cfg, positions, qk_norm: bool):
    """h [T, D] at `positions [T]` -> q [H, T, hd], k, v [KV, T, hd]."""
    _, H, KV, hd = _widths(cfg)
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    T = h.shape[0]
    heads = lambda name, n: (h @ p[name]["kernel"].astype(F32)).reshape(  # noqa: E731
        T, n, hd).transpose(1, 0, 2)
    q, k, v = heads("q_proj", H), heads("k_proj", KV), heads("v_proj", KV)
    if qk_norm:
        q, k = _rms_norm(q, p["q_norm"], eps), _rms_norm(k, p["k_norm"], eps)
    rope = lambda x: _rope(x[None], positions[None], theta)[0]  # noqa: E731
    return rope(q), rope(k), v


def _attend(q, k, v, allowed):
    """q [H, Tq, hd]; k, v [KV, Tk, hd]; allowed [Tq, Tk] -> [Tq, H * hd],
    in blocks of queries where there are many."""
    H, Tq, hd = q.shape
    k = jnp.repeat(k, H // k.shape[0], axis=0)
    v = jnp.repeat(v, H // v.shape[0], axis=0)

    def block(qb, mb):
        s = jnp.einsum("hqd,hkd->hqk", qb, k) / jnp.sqrt(F32(hd))
        s = jnp.where(mb[None], s, MASKED)
        return jnp.einsum("hqk,hkd->hqd", jax.nn.softmax(s, axis=-1), v)

    if Tq <= QUERY_BLOCK:
        out = block(q, allowed)
    else:
        n = -(-Tq // QUERY_BLOCK)
        pad = n * QUERY_BLOCK - Tq      # padded queries see nothing, cut off
        qs = jnp.pad(q, ((0, 0), (0, pad), (0, 0))).reshape(
            H, n, QUERY_BLOCK, hd).transpose(1, 0, 2, 3)
        ms = jnp.pad(allowed, ((0, pad), (0, 0))).reshape(n, QUERY_BLOCK, -1)
        out = jax.lax.map(lambda a: block(*a), (qs, ms))    # [n, H, bq, hd]
        out = out.transpose(1, 0, 2, 3).reshape(H, n * QUERY_BLOCK, hd)[:, :Tq]
    return out.transpose(1, 0, 2).reshape(Tq, H * hd)


def _experts(h, p, top_k: int, renorm: bool):
    """h [T, D] -> [T, D]: all experts for all tokens, one at a time."""
    probs = jax.nn.softmax(h @ p["router"]["kernel"].astype(F32), axis=-1)
    kth = jnp.sort(probs, axis=-1)[..., -top_k][..., None]
    dense_w = jnp.where(probs >= kth, probs, 0.0)          # zero off the top k
    if renorm:
        dense_w = dense_w / jnp.sum(dense_w, axis=-1, keepdims=True)

    def one(acc, ew):
        gate, up, down, w = ew
        out = (jax.nn.silu(h @ gate.astype(F32)) * (h @ up.astype(F32))) \
            @ down.astype(F32)
        return acc + w[:, None] * out, None

    ex = p["experts"]
    acc, _ = jax.lax.scan(
        one, jnp.zeros_like(h),
        (ex["gate_proj"]["kernel"], ex["up_proj"]["kernel"],
         ex["down_proj"]["kernel"], dense_w.T))
    return acc


def _visible(q_pos, k_pos, block_length: int, causal: bool):
    """M (or the causal control) between positions: [Tq, Tk] bool."""
    if causal:
        return k_pos[None, :] <= q_pos[:, None]
    return (k_pos // block_length)[None, :] <= (q_pos // block_length)[:, None]


def _head(weights, cfg, h):
    """h [n, D], final-normed -> logits [n, V], the head in column blocks."""
    head = (weights["embed_tokens"].T if cfg["tie_word_embeddings"]
            else weights["lm_head"])
    V = head.shape[1]
    if V % HEAD_BLOCKS or V < 65536:
        return h @ head.astype(F32)
    cols = jnp.moveaxis(head.reshape(head.shape[0], HEAD_BLOCKS, -1), 1, 0)
    out = jax.lax.map(lambda w: h @ w.astype(F32), cols)    # [n, rows, V / n]
    return jnp.moveaxis(out, 0, 1).reshape(h.shape[0], V)


def forward(weights, cfg: dict, ids, n_real, *, block_length: int | None = None,
            shift: bool = False, causal: bool = False, qk_norm: bool = True,
            renorm: bool = True, at=None):
    """Logits `[T, V]` of the FULL sequence `ids [T]` under M in one pass;
    entries from `n_real` on are not real (no query sees them; their own rows
    mean nothing). `at` [n] int: only those positions' rows. The keywords
    past `block_length` (the file's, where None) are the negative controls."""
    B_ = int(block_length or cfg["block_length"])
    eps, top_k = cfg["rms_norm_eps"], cfg["num_experts_per_tok"]
    T = ids.shape[0]
    pos = jnp.arange(T, dtype=jnp.int32)
    allowed = _visible(pos, pos, B_, causal) & (pos < n_real)[None, :]
    x = weights["embed_tokens"][ids].astype(F32)

    def layer(x, p):
        h = _rms_norm(x, p["input_layernorm"], eps)
        q, k, v = _qkv(h, p, cfg, pos, qk_norm)
        x = x + _attend(q, k, v, allowed) @ p["o_proj"]["kernel"].astype(F32)
        h = _rms_norm(x, p["post_attention_layernorm"], eps)
        return x + _experts(h, p, top_k, renorm), None

    x, _ = jax.lax.scan(layer, x, weights["layers"])
    h = _rms_norm(x, weights["norm"], eps)
    if shift:       # the control: position i is predicted from i - 1
        h = jnp.roll(h, 1, axis=0)
    return _head(weights, cfg, h if at is None else h[at])


def _reduced(weights, cfg, h, probes):
    """What the comparison needs of the logits of `h [n, D]`, without holding
    `[n, V]`: `top`, `lse`, `argmax` [n] and the logit at each of `probes`
    ([n] int each), `HEAD_ROWS` rows at a time."""
    n = h.shape[0]
    pad = -n % HEAD_ROWS
    hs = jnp.pad(h, ((0, pad), (0, 0))).reshape(-1, HEAD_ROWS, h.shape[1])
    ps = jnp.pad(jnp.stack(probes, axis=1) if probes else
                 jnp.zeros((n, 0), jnp.int32), ((0, pad), (0, 0))).reshape(
                     -1, HEAD_ROWS, len(probes))

    def rows(a):
        hb, pb = a
        lg = _head(weights, cfg, hb)
        return (lg.max(axis=-1), jax.nn.logsumexp(lg, axis=-1),
                jnp.argmax(lg, axis=-1).astype(jnp.int32),
                jnp.take_along_axis(lg, pb, axis=1))

    top, lse, arg, at = jax.lax.map(rows, (hs, ps))
    flat = lambda a: a.reshape((-1,) + a.shape[2:])[:n]     # noqa: E731
    return {"top": flat(top), "lse": flat(lse), "argmax": flat(arg),
            "probes": flat(at)}


def _two_streams(weights, cfg, final_ids, block_ids, starts, probes, *,
                 block_length, causal, qk_norm, renorm):
    """The final sequence `final_ids [T]` and, beside it, every denoise
    forward's block `block_ids [N, B]` at positions `starts[n] + i`, whose
    keys are the final sequence's before `starts[n]` and the block's own
    (module docstring). Returns `_reduced` of the blocks' N x B positions."""
    eps, top_k = cfg["rms_norm_eps"], cfg["num_experts_per_tok"]
    T, (N, B) = final_ids.shape[0], block_ids.shape
    pos_f = jnp.arange(T, dtype=jnp.int32)
    pos_b = (starts[:, None] + jnp.arange(B, dtype=jnp.int32)[None]).reshape(-1)
    own = jnp.repeat(jnp.arange(N), B)
    allowed_f = _visible(pos_f, pos_f, block_length, causal)
    # a block's queries: the final keys before its start, and its own keys
    # (all of them; under the causal control those up to the query)
    before = pos_f[None, :] < jnp.repeat(starts, B)[:, None]
    within = (own[None, :] == own[:, None]) & (
        _visible(pos_b, pos_b, block_length, causal))
    allowed_b = jnp.concatenate([before, within], axis=1)
    x_f = weights["embed_tokens"][final_ids].astype(F32)
    x_b = weights["embed_tokens"][block_ids.reshape(-1)].astype(F32)

    def layer(xs, p):
        x_f, x_b = xs
        out_w = p["o_proj"]["kernel"].astype(F32)
        h_f = _rms_norm(x_f, p["input_layernorm"], eps)
        h_b = _rms_norm(x_b, p["input_layernorm"], eps)
        q_f, k_f, v_f = _qkv(h_f, p, cfg, pos_f, qk_norm)
        q_b, k_b, v_b = _qkv(h_b, p, cfg, pos_b, qk_norm)
        x_f = x_f + _attend(q_f, k_f, v_f, allowed_f) @ out_w
        x_b = x_b + _attend(q_b, jnp.concatenate([k_f, k_b], axis=1),
                            jnp.concatenate([v_f, v_b], axis=1),
                            allowed_b) @ out_w
        both = jnp.concatenate([x_f, x_b])
        both = both + _experts(
            _rms_norm(both, p["post_attention_layernorm"], eps), p, top_k,
            renorm)
        return (both[:T], both[T:]), None

    (_, x_b), _ = jax.lax.scan(layer, (x_f, x_b), weights["layers"])
    return _reduced(weights, cfg, _rms_norm(x_b, weights["norm"], eps),
                    [p.reshape(-1) for p in probes])


def forwards_of(prompt, tokens, unmask_step, block_length: int,
                mask_token_id: int) -> dict:
    """The denoise forwards of one served generation, rebuilt on the host
    (numpy): `prompt` the request's tokens, `tokens` EVERY token its rows
    unmasked in order of position (the streamed ones, then the cut ones: whole
    blocks), `unmask_step` the denoise step of its block that unmasked each.
    Returns `final [T]` (prompt + tokens), and a forward n: `start [N]` (its
    block's first position), `step [N]`, `ids [N, B]` (the block as it stood),
    `masked [N, B]` (what was still masked), `chosen [N, B]` (what this forward
    unmasked), `token [N, B]` (the block's tokens as they ended)."""
    B = int(block_length)
    prompt = np.asarray(prompt, np.int64)
    tokens = np.asarray(tokens, np.int64)
    steps = np.asarray(unmask_step, np.int64)
    tail = len(prompt) % B
    if (tail + len(tokens)) % B or len(tokens) != len(steps):
        raise ValueError(
            f"{len(tokens)} tokens after a prompt of {len(prompt)} are no "
            f"whole blocks of {B} (streamed and cut tokens together are)")
    final = np.concatenate([prompt, tokens])
    first = len(prompt) - tail
    blocks = final[first:].reshape(-1, B)
    when = np.concatenate([np.full(tail, -1), steps]).reshape(-1, B)
    out = {k: [] for k in ("start", "step", "ids", "masked", "chosen", "token")}
    for b, (blk, at) in enumerate(zip(blocks, when)):
        for s in range(int(at.max()) + 1):
            out["start"].append(first + b * B)
            out["step"].append(s)
            out["ids"].append(np.where(at < s, blk, mask_token_id))
            out["masked"].append(at >= s)
            out["chosen"].append(at == s)
            out["token"].append(blk)
    out = {k: np.asarray(v) for k, v in out.items()}
    out["final"] = final
    return out


def replay(weights, cfg: dict, prompt, tokens, unmask_step, *,
           block_length: int | None = None, mask_token_id: int | None = None,
           probes: tuple = (), literal: bool = False, shift: bool = False,
           causal: bool = False, qk_norm: bool = True, renorm: bool = True,
           stale_commit: bool = False, fwd: dict | None = None,
           programs: dict | None = None) -> dict:
    """The reference's logits at every position of every denoise forward of
    one served generation (`forwards_of`), reduced to what the comparison
    reads (`_reduced`: `top`, `lse`, `argmax` `[N, B]`, and `probes [N, B,
    k]`, the logits at the k arrays `[N, B]` of token ids in `probes`; the
    token each position ended with is always the first probe), beside
    `forwards_of`'s arrays. `block_length` here is the REFERENCE's (a
    control: the file's is what was generated with); a control that changes
    the block structure (`block_length`, `shift`) or `literal=True` runs every
    forward's whole sequence through `forward`. `stale_commit` is the
    control for a program whose commit forward writes nothing: a generated
    block's K/V, as every later forward reads them, are those its LAST
    denoise forward left (the mask id where the block was then still
    masked). `programs`: a dict the
    caller keeps between calls; the jitted program of a set of flags lives
    there, and the shapes it sees are padded to `T_PAD` positions and `N_PAD`
    forwards (a padded position lies in a later block than any real one and
    a padded forward is cut off: neither is seen), so rows of like sizes
    share one compilation."""
    gen_B = int(cfg["block_length"])
    ref_B = int(block_length or gen_B)
    mask_id = int(cfg["mask_token_id"] if mask_token_id is None
                  else mask_token_id)
    if fwd is None:
        fwd = forwards_of(prompt, tokens, unmask_step, gen_B, mask_id)
    N = len(fwd["start"])
    probes = (fwd["token"],) + tuple(np.asarray(p) for p in probes)
    programs = {} if programs is None else programs
    key = (ref_B, literal, shift, causal, qk_norm, renorm)
    n_pad = -N % N_PAD

    def rows(a, fill=0):        # N forwards -> whole multiples of N_PAD
        a = np.asarray(a)
        return np.concatenate([a, np.full((n_pad,) + a.shape[1:], fill,
                                          a.dtype)])
    controls = dict(causal=causal, qk_norm=qk_norm, renorm=renorm)
    flat = not (literal or shift or ref_B != gen_B)
    if stale_commit and not flat:
        raise ValueError("stale_commit is a control of its own (the "
                         "sequence every forward sees is then no one's)")
    if not flat:
        width = int(fwd["start"].max()) + gen_B
        width += -width % T_PAD
        seqs = np.full((N, width), mask_id, np.int64)
        for n in range(N):
            s = int(fwd["start"][n])
            seqs[n, :s] = fwd["final"][:s]
            seqs[n, s:s + gen_B] = fwd["ids"][n]
        cols = fwd["start"][:, None] + np.arange(gen_B)[None]

        def one_of(w, a):
            ids, n_real, at, pr = a
            lg = forward(w, cfg, ids, n_real, block_length=ref_B,
                         shift=shift, at=at, **controls)
            return (lg.max(axis=-1), jax.nn.logsumexp(lg, axis=-1),
                    jnp.argmax(lg, axis=-1).astype(jnp.int32),
                    jnp.take_along_axis(lg, pr, axis=1))

        fn = programs.setdefault(key, jax.jit(lambda w, *a: jax.lax.map(
            lambda x: one_of(w, x), a)))
        top, lse, arg, at = fn(
            weights, jnp.asarray(rows(seqs, mask_id), jnp.int32),
            jnp.asarray(rows(fwd["start"] + gen_B, gen_B), jnp.int32),
            jnp.asarray(rows(cols), jnp.int32),
            jnp.asarray(rows(np.stack(probes, axis=-1)), jnp.int32))
        red = {"top": top[:N], "lse": lse[:N], "argmax": arg[:N],
               "probes": at[:N]}
    else:
        fn = programs.setdefault(key, jax.jit(lambda w, *a: _two_streams(
            w, cfg, *a, block_length=ref_B, **controls)))
        final = np.array(fwd["final"])
        if stale_commit:    # (a block's forwards stand in order of step)
            for start, ids in zip(fwd["start"], fwd["ids"]):
                final[start:start + gen_B] = ids
        final = np.concatenate([final, np.full(-len(final) % T_PAD, mask_id,
                                               final.dtype)])
        red = fn(weights, jnp.asarray(final, jnp.int32),
                 jnp.asarray(rows(fwd["ids"], mask_id), jnp.int32),
                 jnp.asarray(rows(fwd["start"]), jnp.int32),
                 [jnp.asarray(rows(p), jnp.int32) for p in probes])
        red = {k: v[:N * gen_B] for k, v in red.items()}
    out = dict(fwd)
    out.update({k: np.asarray(v).reshape((N, gen_B) + np.shape(v)[1:])
                if flat else np.asarray(v) for k, v in red.items()})
    return out
