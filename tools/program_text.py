"""What a change to the model layer did to the programs, without a chip.

    python tools/program_text.py <tree>                 # one hash a program
    python tools/program_text.py <parent> <change>      # what differs
    python tools/program_text.py <tree> > a.json; ... a.json <change>
    ... [--only REGEX] [--dump DIR] [--v5e]

Lowers, on the CPU, the programs each of the six model kinds runs (the tiny
configurations under benchmark/tests/rehearsal/configs/: Qwen2, OLMoE,
A.X-K1, SmallThinker, LFM2, Trinity) from the tree at a path, and writes two
hashes a program:

- `text`: the StableHLO without locations: the operations, in order;
- `scopes`: the same text with every operation's name stack
  (`jit(f)/attn/attn.qkv/dot_general`) in place of its location: which
  `jax.named_scope` wraps which operation (what utils/profiling.DEVICE_SCOPES
  and the benchmark's scope reduction read), without files and lines.

The model-level programs: `prefill` (contiguous, paged), `decode_step`
(contiguous, with `extent`, paged with and without `live`, `count_experts`),
`decode_verify` (contiguous and paged, with and without `want_logits`),
`padded_forward_logits` plain and under `jax.grad` with `remat`, each under
`attention_impl` "auto" (XLA's forms on the CPU) and "pallas" (the kernels'
forms, interpreted), and a few that only one kind has (the int8 cache, LoRA
leaves, a mesh hint, the sequence-parallel hooks). Then the one-jit rollout
(`generate_tokens`) and every module a tiny `ServingEngine` compiles while it
serves a chunked admission, a re-used prefix and a decode (the session's
chunk, prefill-piece and admission programs), caught at
`jax._src.compiler.compile_or_get_cached`.

`--v5e` lowers instead the decode loops of Qwen2.5-1.5B and OLMoE and the
pattern models' session programs at their cells' widths for a DESCRIBED v5e
(the TPU's compiler is installed here; `tests/test_chip_compile.py`'s
`_decode_loop`, `_smallthinker_session_program`, `_lfm2_session_program`
and `_trinity_session_program` of each tree, with
`jax.default_backend()` answering "tpu"): what the CPU lowering cannot reach,
the branches on the backend and the kernels as Mosaic compiles them. A Pallas
kernel's body carries the file and line of every frame that led to it, so two
checkouts never agree on it: each body is hashed as its assembly WITHOUT
locations.

Each tree is lowered in a process of its own with that tree first on
`sys.path`, so a parent unpacked with `git archive` beside the working tree
compares against it. A program that cannot be lowered hashes its exception's
type: it still has to fail the same way on both sides. No hash is stored
anywhere: the next jax prints another text.
"""

from __future__ import annotations

import argparse
import functools
import hashlib
import itertools
import json
import os
import re
import subprocess
import sys
import tempfile

KINDS = {"qwen2": "tiny.json", "olmoe": "tiny-olmoe.json",
         "axk1": "tiny-axk1.json", "smallthinker": "tiny-smallthinker.json",
         "lfm2": "tiny-lfm2.json", "trinity": "tiny-trinity.json"}
PAD, EOS = 0, 3
_LOC_DEF = re.compile(r'^#loc(\d+) = loc\((?:"([^"]*)")?')
_LOC_USE = re.compile(r"#loc(\d+)")


def _digest(text: str) -> str:
    return hashlib.sha256(text.encode()).hexdigest()[:16]


def _with_name_stacks(text: str) -> str:
    """The module's text with every `#locN` replaced by the name that
    location carries (a file-and-line or call-site location carries none)
    and the table of locations cut off."""
    names, body = {}, []
    for line in text.splitlines():
        m = _LOC_DEF.match(line)
        if m:
            names[m.group(1)] = m.group(2) or ""
        elif not line.startswith("#loc"):
            body.append(line)
    return "\n".join(_LOC_USE.sub(lambda m: f'"{names.get(m.group(1), "")}"',
                                  line) for line in body)


def _module_hashes(module) -> tuple:
    """(text hash, scopes hash, text, text with name stacks) of an
    `ir.Module`."""
    from jax._src.interpreters import mlir

    plain = mlir.module_to_string(module, enable_debug_info=False)
    named = _with_name_stacks(
        mlir.module_to_string(module, enable_debug_info=True))
    return _digest(plain), _digest(named), plain, named


# --------------------------------------------------------------------- #
# the child: lower one tree
# --------------------------------------------------------------------- #

def _lower_tree(tree: str, only: str | None, dump: str | None) -> dict:
    sys.path.insert(0, tree)
    os.chdir(tree)
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from nanorlhf_tpu.core import ModelConfig, init_params
    from nanorlhf_tpu.core import model as M

    assert os.path.realpath(M.__file__).startswith(os.path.realpath(tree)), (
        M.__file__, tree)
    out: dict = {}
    wanted = re.compile(only) if only else None

    def record(name, make_module):
        if wanted is not None and not wanted.search(name):
            return
        try:
            text, scopes, plain, named = _module_hashes(make_module())
        except Exception as e:  # noqa: BLE001 - has to fail alike on both
            text = scopes = f"{type(e).__name__}"
            plain = named = f"{type(e).__name__}: {e}"
        out[name] = {"text": text, "scopes": scopes}
        if dump:
            safe = re.sub(r"[^\w.\-]+", "_", name)
            with open(os.path.join(dump, safe + ".mlir"), "w") as f:
                f.write(plain)
            with open(os.path.join(dump, safe + ".scopes.mlir"), "w") as f:
                f.write(named)

    def lowered(fn, *args, **kw):
        return lambda: jax.jit(functools.partial(fn, **kw)).lower(
            *args).compiler_ir("stablehlo")

    def config_of(kind, **replace):
        with open(os.path.join(tree, "benchmark", "tests", "rehearsal",
                               "configs", KINDS[kind])) as f:
            cfg = ModelConfig.from_hf_config({**json.load(f),
                                              "vocab_size": 128})
        return dataclasses.replace(cfg, **replace)

    B, T, P, T_max, K1 = 2, 12, 4, 16, 4
    nb = T_max // P
    rng = np.random.default_rng(3)
    ids_np = rng.integers(4, 128, (B, T_max)).astype(np.int32)
    ids_np[0, :5] = PAD
    ids = jnp.asarray(ids_np)
    valid = ids != PAD
    pos = jnp.cumsum(valid, 1) - 1
    table = jnp.arange(B * nb, dtype=jnp.int32).reshape(B, nb)

    def paged_kw(cfg):
        """(page_table=, page_size=) and a fresh pool for `cfg`."""
        if cfg.attention_pattern is None:
            tabs, pages, more = table, B * nb, {}
        else:
            tabs = (table, table) + (
                (jnp.arange(B, dtype=jnp.int32)[:, None],)
                if cfg.conv_layers else ())
            pages = (B * nb, B * nb)
            more = {"state_rows": B} if cfg.conv_layers else {}
        pool = M.init_paged_kv_cache(cfg, pages, P, jnp.float32, **more)
        return {"page_table": tabs, "page_size": P}, pool

    def model_programs(tag, cfg, params, paged=True, uncached=True):
        """The model-level list for one configuration."""
        slab = M.init_kv_cache(cfg, B, T_max, jnp.float32)
        prompt = (ids[:, :T], valid[:, :T])
        km = jnp.zeros((B, T_max), bool).at[:, :T + 1].set(valid[:, :T + 1])
        step = (ids[:, T], pos[:, T])
        rows = jnp.full((B,), T, jnp.int32)
        live = jnp.asarray([True, False])
        cand = (ids[:, T:T + K1], pos[:, T:T + K1], rows,
                jnp.zeros((B, T_max), bool).at[:, :T].set(valid[:, :T]))
        tv = ({"token_valid": jnp.ones((B, K1), bool).at[1, -1].set(False)}
              if cfg.conv_layers else {})
        record(f"{tag}/prefill", lowered(
            lambda p, *a: M.prefill(p, cfg, *a), params, *prompt, slab))
        record(f"{tag}/decode_step", lowered(
            lambda p, *a: M.decode_step(p, cfg, *a), params, *step, T, km,
            slab))
        record(f"{tag}/decode_step.per_row", lowered(
            lambda p, *a: M.decode_step(p, cfg, *a), params, *step, rows, km,
            slab))
        if len(M.decode_read_extents(cfg, 0, T_max - 1, T_max)) > 1:
            record(f"{tag}/decode_step.extent", lowered(
                lambda p, *a: M.decode_step(p, cfg, *a, extent=14), params,
                *step, T, km, slab))
        for want in (True, False):
            record(f"{tag}/decode_verify.logits={int(want)}", lowered(
                lambda p, *a, want=want: M.decode_verify(
                    p, cfg, *a, want_logits=want, **tv), params, *cand, slab))
        if cfg.num_experts:
            record(f"{tag}/decode_step.count_experts", lowered(
                lambda p, *a: M.decode_step(
                    p, cfg, *a, live=live, count_experts=True), params, *step,
                rows, km, slab))
        if paged:
            kw, pool = paged_kw(cfg)
            record(f"{tag}/paged.prefill", lowered(
                lambda p, *a: M.prefill(p, cfg, *a, logical_len=T_max, **kw),
                params, *prompt, pool))
            record(f"{tag}/paged.decode_step", lowered(
                lambda p, *a: M.decode_step(p, cfg, *a, **kw), params, *step,
                rows, km, pool))
            record(f"{tag}/paged.decode_step.live", lowered(
                lambda p, *a: M.decode_step(p, cfg, *a, live=live, **kw),
                params, *step, rows, km, pool))
            if cfg.num_experts:
                record(f"{tag}/paged.decode_step.count_experts", lowered(
                    lambda p, *a: M.decode_step(
                        p, cfg, *a, live=live, count_experts=True, **kw),
                    params, *step, rows, km, pool))
            for want in (True, False):
                record(f"{tag}/paged.decode_verify.logits={int(want)}",
                       lowered(lambda p, *a, want=want: M.decode_verify(
                           p, cfg, *a, want_logits=want, **kw, **tv),
                           params, *cand, pool))
        if not uncached:
            return

        def loss(p, remat):
            logits = M.padded_forward_logits(p, cfg, ids, PAD, remat=remat)
            return jnp.mean(jax.nn.logsumexp(logits, -1) * valid)

        record(f"{tag}/forward.response", lowered(
            lambda p: M.padded_forward_logits(
                p, cfg, ids, PAD, response_context_length=8), params))
        record(f"{tag}/grad.remat", lowered(
            jax.grad(lambda p: loss(p, True)), params))
        record(f"{tag}/grad", lowered(
            jax.grad(lambda p: loss(p, False)), params))
        if cfg.num_experts:
            record(f"{tag}/forward.router_stats", lowered(
                lambda p: M.padded_forward_logits(
                    p, cfg, ids, PAD, router_stats=True), params))

    def adapters(cfg, params):
        from nanorlhf_tpu.core.lora import LoraConfig, init_lora_params

        return {**params, "lora": init_lora_params(
            cfg, LoraConfig(r=4, alpha=8), jax.random.PRNGKey(2),
            jnp.float32)}

    all_params = {}
    for kind in KINDS:
        for impl in ("auto", "pallas"):
            cfg = config_of(kind, attention_impl=impl)
            params = all_params.setdefault(kind, init_params(
                cfg, jax.random.PRNGKey(1), jnp.float32))
            model_programs(f"{kind}/{impl}", cfg, params)

    # what only one kind has
    q = config_of("qwen2")
    for impl in ("auto", "pallas"):
        model_programs(f"qwen2.int8/{impl}", dataclasses.replace(
            q, kv_cache_quant="int8", attention_impl=impl),
            all_params["qwen2"], uncached=False)
    for kind in ("qwen2", "smallthinker", "trinity"):
        cfg = config_of(kind)
        model_programs(f"{kind}.lora/auto", cfg,
                       adapters(cfg, all_params[kind]))
    model_programs("qwen2.dots/auto", dataclasses.replace(
        q, remat_policy="dots"), all_params["qwen2"], paged=False)
    if len(jax.devices()) >= 4:
        from jax.sharding import Mesh

        mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(2, 2),
                    ("data", "tensor"))
        for kind in ("qwen2", "smallthinker"):
            for impl in ("auto", "pallas"):
                cfg = config_of(kind, attention_impl=impl, spmd_mesh=mesh,
                                spmd_batch_axes=("data",),
                                spmd_head_axis="tensor")
                with mesh:
                    model_programs(f"{kind}.mesh/{impl}", cfg,
                                   all_params[kind])
    # the sequence-parallel path's hooks into the one runner
    for kind in ("qwen2", "olmoe"):
        cfg = config_of(kind)
        pids = jnp.maximum(pos, 0)
        record(f"{kind}/hooks.attn_fn", lowered(
            lambda p: M._hidden_from_inputs(
                p, cfg, ids, valid, pids, 1.0, True,
                attn_fn=lambda q_, k_, v_: q_ * 2), all_params[kind]))
        record(f"{kind}/hooks.layer_transform", lowered(
            lambda p: M._hidden_from_inputs(
                p, cfg, ids, valid, pids, 1.0, False,
                layer_transform=lambda lp, lo: (jax.tree.map(
                    lambda a: a * 2, lp), lo)), all_params[kind]))
    cfg = config_of("smallthinker")
    record("smallthinker/hooks.layer_transform", lowered(
        lambda p: M._hidden_from_inputs(
            p, cfg, ids, valid, jnp.maximum(pos, 0), 1.0, False,
            layer_transform=lambda lp, lo: (jax.tree.map(
                lambda a: a * 2, lp), lo)), all_params["smallthinker"]))

    # the one-jit rollout
    from nanorlhf_tpu.sampler.sampler import generate_tokens

    for kind, impl, page_size in itertools.product(
            KINDS, ("auto", "pallas"), (0, 4)):
        if page_size and config_of(kind).attention_pattern is not None:
            continue    # the monolithic paged rollout is not built for it
        cfg = config_of(kind, attention_impl=impl)
        record(f"{kind}/{impl}/generate.page_size={page_size}",
               lambda cfg=cfg, page_size=page_size: generate_tokens.lower(
                   all_params[kind], cfg, ids[:, :T], valid[:, :T],
                   jax.random.PRNGKey(0), max_tokens=6, eos_token_id=EOS,
                   pad_token_id=PAD, temperature=0.9, capture_logprobs=True,
                   prompt_fanout=2,
                   page_size=page_size).compiler_ir("stablehlo"))

    # every module a serving engine compiles
    from jax._src import compiler

    from nanorlhf_tpu.serving.engine import ServingEngine

    compile_module = compiler.compile_or_get_cached
    for kind, impl in itertools.product(KINDS, ("auto", "pallas")):
        if wanted is not None and not wanted.search(f"{kind}/{impl}/serve"):
            continue
        seen: dict = {}

        def catch(backend, computation, *a, **kw):
            text, scopes, plain, named = _module_hashes(computation)
            name = re.search(r"module @(\S+)", plain).group(1)
            seen.setdefault(name, set()).add((text, scopes))
            if dump:
                safe = re.sub(r"[^\w.\-]+", "_", f"{kind}_{impl}_serve_{name}_{text}")
                with open(os.path.join(dump, safe + ".mlir"), "w") as f:
                    f.write(plain)
            return compile_module(backend, computation, *a, **kw)

        compiler.compile_or_get_cached = catch
        try:
            cfg = config_of(kind, attention_impl=impl)
            with ServingEngine(all_params[kind], cfg, eos_token_id=EOS,
                               pad_token_id=PAD, page_size=4, prompt_len=16,
                               max_new_tokens=8, rows=2, headroom=1.0,
                               prefill_chunk=8) as engine:
                prompt = np.arange(4, 18) % 100 + 4
                for real in (prompt[:14], prompt[:13], prompt[:5]):
                    req, _ = engine.submit(real, greedy=True, max_tokens=4)
                    list(engine.stream(req))
        except Exception as e:  # noqa: BLE001
            seen["ERROR"] = {(type(e).__name__, type(e).__name__)}
        finally:
            compiler.compile_or_get_cached = compile_module
        for name, hashes in seen.items():
            ordered = sorted(hashes)
            out[f"{kind}/{impl}/serve/{name}"] = {
                "text": "+".join(t for t, _ in ordered),
                "scopes": "+".join(s for _, s in ordered)}
    return out


def _without_kernel_locations(text: str) -> str:
    """A lowered TPU module's text with the serialized body of every Mosaic
    kernel replaced by a hash of its assembly without locations."""
    import base64

    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir

    def body(m):
        from jax._src.interpreters import mlir

        ctx = mlir.make_ir_context()
        tpu.register_dialect(ctx)
        ctx.allow_unregistered_dialects = True
        with ctx:
            asm = ir.Module.parse(base64.b64decode(m.group(1))).operation.get_asm(
                enable_debug_info=False)
        return f"BODY<{_digest(asm)}>"

    return re.sub(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22', body, text)


def _lower_tree_for_v5e(tree: str, only: str | None, dump: str | None) -> dict:
    os.environ["NANORLHF_PALLAS_INTERPRET"] = "0"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")
    sys.path.insert(0, tree)
    os.chdir(tree)
    import jax
    from jax._src import stages
    from jax.experimental import topologies

    jax.config.update("jax_enable_compilation_cache", False)
    devices = list(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices)
    # the helpers compile; the text is what is wanted
    stages.Lowered.compile = lambda self, *a, **k: self
    jax.default_backend = lambda: "tpu"
    from tests import test_chip_compile as C

    assert os.path.realpath(C.__file__).startswith(os.path.realpath(tree))
    from jax.sharding import SingleDeviceSharding

    programs = {
        # Qwen2.5-1.5B's and OLMoE's decode loops, two layers deep
        **{f"v5e/{case}": functools.partial(
            C._decode_loop, case, SingleDeviceSharding(devices[0]))
           for case in ("rollout", "rollout_int8", "rollout_olmoe",
                        "serving_chunk", "serving_admission")},
        **{f"v5e/smallthinker.{case}": functools.partial(
            C._smallthinker_session_program, case, devices, layers=8)
           for case in ("decode_chunk", "prefill_chunk", "suffix")},
        **{f"v5e/lfm2.{case}": functools.partial(
            C._lfm2_session_program, case, devices, layers=10)
           for case in ("decode_chunk", "prefill_piece", "suffix")},
        **{f"v5e/trinity.{case}": functools.partial(
            C._trinity_session_program, case, devices)
           for case in ("decode_chunk", "prefill_piece", "admission")}}
    out = {}
    for name, lower in programs.items():
        if only and not re.search(only, name):
            continue
        try:
            text = _without_kernel_locations(lower()[0].as_text())
            digest = _digest(text)
        except Exception as e:  # noqa: BLE001 - has to fail alike on both
            text = digest = type(e).__name__
        out[name] = {"text": digest, "scopes": digest}
        if dump:
            with open(os.path.join(dump, name.replace("/", "_") + ".mlir"),
                      "w") as f:
                f.write(text)
    return out


# --------------------------------------------------------------------- #
# the parent: one process a tree, then the comparison
# --------------------------------------------------------------------- #

def hashes_of(tree: str, only: str | None = None, dump: str | None = None,
              v5e: bool = False) -> dict:
    """One tree's hashes; a `.json` path is a run's saved output."""
    if tree.endswith(".json"):
        with open(tree) as f:
            return json.load(f)
    tree = os.path.abspath(tree)
    if dump:
        os.makedirs(dump, exist_ok=True)
    with tempfile.TemporaryDirectory() as tmp:
        result = os.path.join(tmp, "hashes.json")
        env = {**os.environ, "JAX_PLATFORMS": "cpu", "NANORLHF_CACHE_DIR": "0",
               "PYTHONPATH": tree, "PYTHONDONTWRITEBYTECODE": "1",
               "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
        env.pop("JAX_COMPILATION_CACHE_DIR", None)
        cmd = [sys.executable, os.path.abspath(__file__), "--lower", tree,
               "--out", result]
        if only:
            cmd += ["--only", only]
        if dump:
            cmd += ["--dump", os.path.abspath(dump)]
        if v5e:
            cmd += ["--v5e"]
        subprocess.run(cmd, check=True, env=env, cwd=tree)
        with open(result) as f:
            return json.load(f)


def compare(a: dict, b: dict) -> list:
    """Lines that say what differs between two trees' hashes."""
    lines = []
    for name in sorted(set(a) | set(b)):
        if name not in a or name not in b:
            lines.append(f"{name}: only in the "
                         f"{'first' if name in a else 'second'}")
            continue
        for what in ("text", "scopes"):
            if a[name][what] != b[name][what]:
                lines.append(f"{name}: {what} {a[name][what]} -> "
                             f"{b[name][what]}")
                break
    return lines


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trees", nargs="*")
    ap.add_argument("--only", help="programs whose name matches")
    ap.add_argument("--dump", help="write every program's text under DIR")
    ap.add_argument("--v5e", action="store_true",
                    help="the session programs at the cells' sizes, lowered "
                         "for a described v5e")
    ap.add_argument("--lower", help=argparse.SUPPRESS)
    ap.add_argument("--out", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.lower:
        got = (_lower_tree_for_v5e if args.v5e else _lower_tree)(
            args.lower, args.only, args.dump)
        with open(args.out, "w") as f:
            json.dump(got, f, indent=1, sort_keys=True)
        return 0
    if len(args.trees) not in (1, 2):
        ap.error("one tree to hash, or two to compare")
    got = [hashes_of(t, args.only,
                     args.dump and os.path.join(args.dump, str(i)), args.v5e)
           for i, t in enumerate(args.trees)]
    if args.only:   # (a saved output holds every program)
        got = [{n: h for n, h in g.items() if re.search(args.only, n)}
               for g in got]
    if len(got) == 1:
        json.dump(got[0], sys.stdout, indent=1, sort_keys=True)
        print()
        return 0
    lines = compare(*got)
    errors = sorted(n for n, h in got[1].items() if not re.fullmatch(
        r"[0-9a-f+]+", h["text"]))
    print("\n".join(lines) or "no program differs")
    print(f"{len(got[1])} programs, {len(lines)} differ, "
          f"{len(errors)} not lowered{': ' if errors else ''}"
          + ", ".join(f"{n} ({got[1][n]['text']})" for n in errors))
    return 1 if lines else 0


if __name__ == "__main__":
    raise SystemExit(main())
