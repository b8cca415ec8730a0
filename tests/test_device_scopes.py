"""The device's time has names: `utils/profiling.py::DEVICE_SCOPES`.

Every jitted body opens with its program's scope and the model's parts carry
theirs, all as `jax.named_scope`: metadata of the compiled program
(`op_name`), which a profiler trace carries in its `/host:metadata` plane and
`benchmark/harness/scope_trace.py` reduces to seconds by scope. Asked here
of XLA:CPU's compiled text at tiny sizes, for a dense, an expert, a
latent-attention and a pattern model: each scope that applies is there, every
matmul of a decode step sits under `attn*`, `mlp` / `moe.*` or `head`, and
the older scopes (`attn.global`, `attn.window`, `moe.experts`) are still the
innermost around their ops, since a kernel's custom call takes its name from
them (harness/attn_trace.py, moe_trace.py; the chip's compiler is asked in
tests/test_chip_compile.py). The last case writes a real profiler trace of a
tiny GRPO update and reads the trainer's scopes back out of the file.

The persistent compile cache is off around all of it: jax leaves metadata
out of the cache's key, so an executable cached before a scope was written is
loaded without it (docs/OBSERVABILITY.md section 4).
"""

import os
import re
import sys

import pytest

import jax
import jax.numpy as jnp

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmark"))

from harness import scope_trace  # noqa: E402

from nanorlhf_tpu.core import ModelConfig, init_params  # noqa: E402
from nanorlhf_tpu.core import model as M  # noqa: E402
from nanorlhf_tpu.utils.profiling import DEVICE_SCOPES  # noqa: E402

PAGE, ROWS, PROMPT, NEW = 8, 2, 16, 8
KINDS = {
    "dense": lambda: ModelConfig.qwen2_tiny(vocab_size=256),
    "expert": lambda: ModelConfig.olmoe_tiny(),
    "latent": lambda: ModelConfig.axk1_tiny(vocab_size=256),
    "pattern": lambda: ModelConfig.smallthinker_tiny(
        vocab_size=256, window=16, layers=4),
    "conv": lambda: ModelConfig.lfm2_tiny(vocab_size=256),
    "gated": lambda: ModelConfig.trinity_tiny(vocab_size=256, window=16),
    "hybrid": lambda: ModelConfig.falcon_h1_tiny(vocab_size=256),
    "mamba": lambda: ModelConfig.granite_h_tiny(vocab_size=256),
    "sala": lambda: ModelConfig.minicpm_sala_tiny(vocab_size=256),
}
# what a layer's attention is made of, by kind (the older families are the
# latent model's `mla.*` and the pattern model's `attn.global` / `.window`)
ATTENTION = {
    "dense": {"attn.qkv", "attn.write", "attn.read", "attn.out"},
    "expert": {"attn.qkv", "attn.write", "attn.read", "attn.out"},
    "latent": {"attn.write", "mla.q", "mla.latent", "mla.attend", "mla.out"},
    "pattern": {"attn.qkv", "attn.write", "attn.global", "attn.window",
                "attn.out"},
    "conv": {"attn.qkv", "attn.write", "attn.global", "attn.out", "attn.conv",
             "attn.conv.in", "attn.conv.mix", "attn.conv.out"},
    # afmoe: the pattern model's parts and the gate between read and out
    "gated": {"attn.qkv", "attn.write", "attn.global", "attn.window",
              "attn.gate", "attn.out"},
    # a state-space mixer BESIDE the attention (docs/SSM.md): a piece's
    # `attn.ssm.scan` and a step's `attn.ssm.update` have a test of their own
    "hybrid": {"attn.qkv", "attn.write", "attn.global", "attn.out",
               "attn.ssm", "attn.ssm.in", "attn.ssm.conv", "attn.ssm.gate",
               "attn.ssm.out"},
    # the same mixer ALONE in its layers beside attention layers without
    # rotary (docs/GRANITE_H.md): no scope of its own
    "mamba": {"attn.qkv", "attn.write", "attn.global", "attn.out",
              "attn.ssm", "attn.ssm.in", "attn.ssm.conv", "attn.ssm.gate",
              "attn.ssm.out"},
    # a lightning mixer in some layers, a sparse attention in the others
    # (docs/SALA.md): the recurrence's two forms and a piece that selects
    # have a test of their own
    "sala": {"attn.qkv", "attn.write", "attn.gate", "attn.out",
             "attn.compress", "attn.select", "attn.read", "attn.linear",
             "attn.linear.in", "attn.linear.gate", "attn.linear.out"},
}
MODEL = {"embed", "norm", "attn", "mlp", "head"}
MATMUL_HOMES = ("attn", "mlp", "head")
INNERMOST = ("attn.global", "attn.window", "moe.experts")
INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%?[\w.\-]+ = .*?[\])}] ([\w\-]+)\(")
OP_NAME = re.compile(r'op_name="([^"]*)"')


@pytest.fixture(autouse=True)
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache

    saved = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", saved)
    compilation_cache.reset_cache()


def spec(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def ops_of(lowered) -> list:
    """[(opcode, op_name, scope path)] of a compiled program's instructions
    that jax named (a reducer's inner `add` carries a bare `reduce_sum`)."""
    out = []
    for line in lowered.compile().as_text().splitlines():
        code, name = INSTRUCTION.match(line), OP_NAME.search(line)
        if code and name and name.group(1).startswith("jit("):
            out.append((code.group(1), name.group(1),
                        scope_trace.scope_of(name.group(1))))
    return out


def parts_of(ops, first: str) -> set:
    """Every step of the scope paths that start with `first`."""
    return {part for _, _, scope in ops if scope_trace.under(scope, first)
            for part in scope.removesuffix(scope_trace.BACKWARD).split("/")}


def check_matmuls_and_innermost(ops, first: str):
    matmuls = [(name, scope) for code, name, scope in ops
               if code in scope_trace.MATMULS
               and scope_trace.under(scope, first)]
    assert matmuls
    homeless = [name for name, scope in matmuls
                if not any(scope_trace.has(scope, h) for h in MATMUL_HOMES)]
    assert not homeless, homeless
    # nothing of the vocabulary between an older scope and a matmul in it
    for name, scope in matmuls:
        steps = scope.split("/")
        for old in INNERMOST:
            if old in steps:
                assert steps[-1] == old, name


def params_of(cfg):
    return jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0),
                                         jnp.float32))


def key_shape():
    return jax.eval_shape(lambda: jax.random.PRNGKey(0))


def test_the_harness_reads_the_programs_vocabulary():
    """scope_trace.py keeps a copy (it also runs over a commit without
    `DEVICE_SCOPES`); the families' members are the program's."""
    assert len(set(DEVICE_SCOPES)) == len(DEVICE_SCOPES)
    for scope in DEVICE_SCOPES:
        if scope == "sample.unmask":
            # the harness (not PR 46's to edit) keeps no `sample.` family:
            # the scope sits inside `sample` and reads as it
            # (docs/BLOCKDIFF.md; layer_metrics/unmask_share.py reads it apart)
            assert scope_trace.scope_of(
                f"jit(f)/sample/{scope}/add") == "sample"
            continue
        assert scope_trace.scope_of(f"jit(f)/{scope}/add") == scope
    assert scope_trace.SCOPES == {s for s in DEVICE_SCOPES if "." not in s}
    assert scope_trace.scope_of(
        "jit(f)/decode/while/body/closed_call/attn/attn.read/dot_general"
    ) == "decode/attn/attn.read"
    assert scope_trace.scope_of(
        "jit(f)/update/transpose(jvp(mlp))/moe.experts/mul"
    ) == "update/mlp/moe.experts bwd"
    assert scope_trace.scope_of("jit(f)/jit(cumsum)/reduce_window_sum") == ""


@pytest.mark.parametrize("kind", list(KINDS))
def test_the_one_jit_rollout_names_its_prefill_and_its_decode_loops(kind):
    from nanorlhf_tpu.sampler.sampler import generate_tokens

    cfg = KINDS[kind]()
    ops = ops_of(generate_tokens.lower(
        params_of(cfg), cfg, spec((ROWS, PROMPT), jnp.int32),
        spec((ROWS, PROMPT), jnp.bool_), key_shape(), max_tokens=NEW,
        eos_token_id=3, pad_token_id=0, temperature=0.9,
        capture_logprobs=True, prompt_fanout=2))
    want = MODEL | ATTENTION[kind] | {"sample", "logprob"}
    for first in ("prefill", "decode"):
        missing = want - parts_of(ops, first)
        assert not missing, (first, missing)
    check_matmuls_and_innermost(ops, "decode")
    if kind not in ("dense", "hybrid", "sala"):
        assert {"moe.router", "moe.dispatch", "moe.experts",
                "moe.combine"} <= parts_of(ops, "decode")
    # a scope of the model is never the first of a path: some program's is
    top = {scope.split("/")[0].removesuffix(scope_trace.BACKWARD)
           for _, _, scope in ops if scope}
    assert top == {"prefill", "decode"}, top


def session_programs(kind, **config):
    """{name: lowered} of a serving session's programs over a page pool."""
    import dataclasses

    from nanorlhf_tpu.sampler.paged import session
    from nanorlhf_tpu.sampler.paged.pages import ring_blocks
    from nanorlhf_tpu.serving.radix import copy_page, suffix_logits

    cfg = dataclasses.replace(KINDS[kind](), **config)
    params = params_of(cfg)
    nb = (PROMPT + NEW) // PAGE
    pages, table, row_table = ROWS * nb + nb, spec((ROWS, nb), jnp.int32), \
        spec((nb,), jnp.int32)
    if kind in ("pattern", "gated"):
        pages = (pages, ROWS * ring_blocks(cfg.sliding_window, PAGE, PAGE))
        table, row_table = (table,) * 2, (row_table,) * 2
    state_rows = {}
    if kind in ("conv", "hybrid", "mamba", "sala"):  # no window layer; the state's rows ride third
        pages, state_rows = (pages, ROWS), {"state_rows": ROWS}
        table = (table,) * 2 + (spec((ROWS, 1), jnp.int32),)
        row_table = (row_table,) * 2 + (spec((1,), jnp.int32),)
    cache = jax.eval_shape(lambda: M.init_paged_kv_cache(
        cfg, pages, PAGE, jnp.float32, **state_rows))
    T = PROMPT + NEW
    state = (spec((), jnp.int32), spec((ROWS, NEW), jnp.int32),
             spec((ROWS, NEW), jnp.float32), cache,
             spec((ROWS, T), jnp.bool_), spec((ROWS,), jnp.bool_),
             spec((ROWS,), jnp.int32), spec((ROWS,), jnp.int32),
             spec((ROWS,), jnp.int32), key_shape())
    sampling = dict(temperature=1.0, top_p=1.0, greedy=False, top_k=8,
                    approx_top_k=True)
    one = (spec((1, PAGE), jnp.int32), spec((1, PAGE), jnp.int32),
           spec((1,), jnp.int32))
    return {
        "chunk": session._serving_chunk.lower(
            params, cfg, state, table, spec((ROWS,), jnp.float32),
            spec((ROWS,), jnp.float32), spec((ROWS,), jnp.bool_),
            spec((ROWS,), jnp.int32), Tp=PROMPT, max_tokens=NEW,
            page_size=PAGE, sync_every=4, eos_token_id=1, pad_token_id=0,
            lora_scale=1.0, capture_logprobs=False, **sampling),
        "piece": session._prefill_chunk_fwd.lower(
            params, cfg, *one, spec((1, T), jnp.bool_), cache, row_table,
            page_size=PAGE, lora_scale=1.0),
        "suffix": suffix_logits.lower(
            params, cfg, *one, spec((), jnp.int32), spec((1, T), jnp.bool_),
            cache, row_table, page_size=PAGE, lora_scale=1.0),
        "admit": session._admit_one.lower(
            params, cfg, spec((1, PROMPT), jnp.int32),
            spec((1, PROMPT), jnp.bool_), cache, row_table, key_shape(),
            page_size=PAGE, T_max=T, lora_scale=1.0, **sampling),
        "copy": copy_page.lower(cache, spec((), jnp.int32),
                                spec((), jnp.int32)),
    }


@pytest.mark.parametrize("kind", list(KINDS))
def test_a_serving_sessions_programs_name_their_steps(kind):
    lowered = session_programs(kind)
    chunk = ops_of(lowered["chunk"])
    missing = (MODEL | ATTENTION[kind] | {"sample"}) - parts_of(chunk, "decode")
    assert not missing, missing
    check_matmuls_and_innermost(chunk, "decode")
    for name, body in (("piece", MODEL - {"head"}), ("suffix", MODEL),
                       ("admit", MODEL | {"sample", "logprob"})):
        ops = ops_of(lowered[name])
        scopes = {scope for _, _, scope in ops if scope}
        assert scopes and all(scope_trace.under(s, "prefill") for s in scopes), \
            (name, scopes)
        assert body - {"attn.read"} <= parts_of(ops, "prefill"), name
        check_matmuls_and_innermost(ops, "prefill")
    assert {scope for _, _, scope in ops_of(lowered["copy"]) if scope} == {
        "install"}


def test_a_hybrid_layers_recurrence_is_a_scan_in_a_piece_an_update_in_a_step():
    """docs/SSM.md: the mixer's recurrence runs under `attn.ssm.scan` in a
    prefill piece and a suffix forward, under `attn.ssm.update` in a decode
    chunk, both inside `attn/attn.ssm`, and both state leaves are written
    under `attn.write` there."""
    lowered = session_programs("hybrid")
    chunk = {scope for _, _, scope in ops_of(lowered["chunk"])}
    assert "decode/attn/attn.ssm/attn.ssm.update" in chunk
    assert "decode/attn/attn.ssm/attn.write" in chunk
    assert not any("attn.ssm.scan" in s for s in chunk)
    for name in ("piece", "suffix"):
        scopes = {scope for _, _, scope in ops_of(lowered[name])}
        assert "prefill/attn/attn.ssm/attn.ssm.scan" in scopes, name
        assert "prefill/attn/attn.ssm/attn.write" in scopes, name
        assert not any("attn.ssm.update" in s for s in scopes), name


def test_a_lightning_layer_scans_a_piece_and_updates_a_step_a_sparse_one_selects():
    """docs/SALA.md: the lightning recurrence runs under `attn.linear.scan`
    in a prefill piece and a suffix forward, under `attn.linear.update` in a
    decode chunk, both inside `attn/attn.linear`; a sparse layer writes its
    compressed keys under `attn.compress`, selects under `attn.select` and
    reads under `attn.read` in every program that has a cache, with no
    `attn.global` around them (its steps are named as a model of one kind
    names them)."""
    lowered = session_programs("sala")
    chunk = {scope for _, _, scope in ops_of(lowered["chunk"])}
    assert "decode/attn/attn.linear/attn.linear.update" in chunk
    assert not any("attn.linear.scan" in s for s in chunk)
    for part in ("attn.compress", "attn.select", "attn.read"):
        assert f"decode/attn/{part}" in chunk, part
    assert not any("attn.global" in s for s in chunk)
    for name in ("piece", "suffix"):
        scopes = {scope for _, _, scope in ops_of(lowered[name])}
        assert "prefill/attn/attn.linear/attn.linear.scan" in scopes, name
        assert "prefill/attn/attn.linear/attn.write" in scopes, name
        assert not any("attn.linear.update" in s for s in scopes), name
        for part in ("attn.compress", "attn.select", "attn.read"):
            assert any(s.startswith("prefill/attn/" + part)
                       for s in scopes), (name, part)
    assert {"attn.linear", "attn.linear.scan", "attn.linear.update",
            "attn.compress", "attn.select"} <= set(DEVICE_SCOPES)


def test_a_pattern_models_piece_reads_its_pages_under_a_scope_of_its_own():
    """ISSUE 37: under the decode read's rule (`"pallas"`; interpret mode
    here, so the kernel's body is XLA ops that carry its scope) a piece's
    and a suffix forward's paged read is `ops/paged_prefill_attention`,
    under `attn.paged_flash` INSIDE its kind's scope: its time stays
    `prefill/attn/attn.global|window` for the prefill readers, and its custom
    call is not named `attn.global*` / `attn.window*`, which
    harness/attn_trace.py takes for a decode read. The decode chunk has no
    such scope, and XLA's walk (the default off the TPU) none either."""
    assert "attn.paged_flash" in DEVICE_SCOPES
    flash = session_programs("pattern", attention_impl="pallas")
    for name in ("piece", "suffix"):
        scopes = {scope for _, _, scope in ops_of(flash[name])}
        for kind in ("attn.global", "attn.window"):
            assert f"prefill/attn/{kind}/attn.paged_flash" in scopes, (name, kind)
        assert not any(s.endswith("/attn.paged_flash")
                       and s.split("/")[-2] not in ("attn.global", "attn.window")
                       for s in scopes)
    assert "attn.paged_flash" not in parts_of(ops_of(flash["chunk"]), "decode")
    walk = session_programs("pattern")
    assert "attn.paged_flash" not in parts_of(ops_of(walk["piece"]), "prefill")


@pytest.mark.parametrize("kind", ["dense", "latent", "pattern", "conv",
                                  "gated", "hybrid", "sala"])
def test_a_pieces_write_by_page_carries_attn_write(kind):
    """ISSUE 41: a piece of a page or more writes its K and V by PAGE
    (`core/model._paged_page_write`: the touched pages gathered, patched and
    scattered back whole), all of it under `attn.write`, inside its kind's
    scope in a pattern model: the gather of the old pages, the select and
    the scatter of `[KV, P, hd]` windows. The row scatter the compiler
    rewrote over a `[rows, 128]` view of the leaf lost its `op_name`, and
    sixteen of them a piece read as no scope at all (PERF.md PR 37)."""
    piece = session_programs(kind)["piece"]
    ops = ops_of(piece)
    both = {"prefill/attn/attn.global/attn.write",
            "prefill/attn/attn.window/attn.write"}
    homes = {"pattern": both, "gated": both,
             "conv": {"prefill/attn/attn.global/attn.write"},
             "hybrid": {"prefill/attn/attn.global/attn.write"},
             "sala": {"prefill/attn/attn.write"}}.get(
        kind, {"prefill/attn/attn.write"})
    for part in ("gather", "select_n", "scatter"):
        found = {scope for _, name, scope in ops
                 if name.endswith("/attn.write/" + part)}
        # (the conv layers' state write is a dynamic-update-slice, also
        # under `attn.write`, in `attn.conv`)
        assert homes <= found, (part, found)
        assert all(s.endswith("attn.write") for s in found), (part, found)
    # and the scatters into the pool move pages: windows over (KV, P, hd),
    # where the row scatter's were one row of hd
    windows = [len(m.group(1).split(",")) for line in
               piece.compile().as_text().splitlines()
               if "/attn.write/scatter" in line
               for m in [re.search(r"update_window_dims=\{([\d,]*)\}", line)]
               if m]
    assert windows and min(windows) >= 3, windows


def test_what_an_admission_does_beside_its_forward_is_install():
    from nanorlhf_tpu.sampler.paged import session

    R, V = ROWS, 64
    sampling = dict(top_k=8, approx_top_k=True)
    programs = {
        "_first_token": session._first_token.lower(
            spec((V,), jnp.float32), key_shape(), spec((), jnp.float32),
            spec((), jnp.float32), spec((), jnp.bool_), **sampling),
        "_admit_sample": session._admit_sample.lower(
            spec((V,), jnp.float32), key_shape(), temperature=1.0, top_p=1.0,
            greedy=False, **sampling),
        "_beat_report": session._beat_report.lower(
            spec((), jnp.int32), spec((R, NEW), jnp.int32),
            spec((R,), jnp.bool_), spec((R,), jnp.int32), spec((2,), jnp.int32),
            width=4),
        "_end_row": session._end_row.lower(spec((R,), jnp.bool_),
                                           spec((), jnp.int32)),
    }
    for name, lowered in programs.items():
        scopes = {scope for _, _, scope in ops_of(lowered) if scope}
        assert scopes and all(scope_trace.under(s, "install")
                              for s in scopes), (name, scopes)
    assert "install/sample" in {
        scope for _, _, scope in ops_of(programs["_first_token"])}


def test_a_speculative_chunk_is_verify():
    from nanorlhf_tpu.sampler.speculative import generate_tokens_spec

    cfg = KINDS["dense"]()
    ops = ops_of(generate_tokens_spec.lower(
        params_of(cfg), cfg, spec((ROWS, PROMPT), jnp.int32),
        spec((ROWS, PROMPT), jnp.bool_), key_shape(), max_tokens=NEW,
        eos_token_id=3, pad_token_id=0, greedy=True, spec_k=2))
    assert MODEL | {"attn.qkv", "attn.write", "attn.read",
                    "attn.out"} <= parts_of(ops, "verify")
    check_matmuls_and_innermost(ops, "verify")


def test_the_weight_hand_off_is_sync():
    from nanorlhf_tpu.core.quant import quantize_layers

    layers = params_of(KINDS["dense"]())["layers"]
    assert {scope for _, _, scope in ops_of(quantize_layers.lower(layers))
            if scope} == {"sync"}


def test_a_profiler_trace_of_an_update_gives_the_trainers_scopes_back(
        tmp_path):
    """The decoder against the installed jax's own file: a tiny GRPO update
    under `jax.profiler`, then `op_scopes()` of the `.xplane.pb` it wrote
    (on the CPU the trace has the metadata plane and no device plane)."""
    from test_trainer_smoke import make_trainer

    from harness import xplane
    from nanorlhf_tpu.trainer import AlgoName

    trainer = make_trainer(AlgoName.GRPO, tmp_path, total_episodes=16,
                           save_steps=0)
    jax.profiler.start_trace(str(tmp_path / "trace"))
    try:
        trainer.train()
    finally:
        jax.profiler.stop_trace()
    table = scope_trace.op_scopes(xplane.newest_xplane(str(tmp_path / "trace")))

    def scopes_of(program):
        found = [v for name, v in table.items()
                 if name.startswith(f"jit_{program}(")]
        assert found, (program, sorted(table))
        return {scope_trace.scope_of(s) for v in found
                for s in v["op_name"].values() if s.startswith("jit(")} - {""}

    update = scopes_of("update_minibatch")
    assert all(scope_trace.under(s, "update") for s in update), update
    parts = {p for s in update
             for p in s.removesuffix(scope_trace.BACKWARD).split("/")}
    assert {"update", "loss", "optim", "logprob", "mlp", "attn.read",
            "head"} <= parts, parts
    assert any(s.endswith(scope_trace.BACKWARD) for s in update)
    assert "update/optim" in update and "update/loss" in update
    score = scopes_of("score")
    assert all(scope_trace.under(s, "score") for s in score), score
    assert "score/logprob" in score
    rollout = scopes_of("generate_tokens")
    assert {"prefill/head", "decode/head", "decode/sample"} <= rollout
    # a fusion's instructions come with it, opcode and op_name
    fused = [pair for name, v in table.items()
             if name.startswith("jit_generate_tokens(")
             for pairs in v["fused"].values() for pair in pairs]
    assert any(scope_trace.scope_of(name) == "decode/sample"
               for _, name in fused), fused[:8]
