"""Driver for mixes of kind `serve`: open-loop load on `ServingEngine` behind
`ServingGateway`, over HTTP, from a jax-free child process.

Set-up: weights from the seed (the base model, as a deployment serves it: no
training adapter), the engine with the mix's shapes, one warm-up request per
suffix-prefill bucket the mix's prompt lengths can produce plus a
shared-prefix pair (the decode chunk, the copy-on-write page copy, the
first-token and install programs compile with them), the greedy comparison
against the reference, a reset of the engine's latency hub (a hub that has
seen set-up's compiling requests must not be what sheds), then the child's
ramp at the cell's rate. The window opens `ramp_s` after the child's t0.

On the client's clock (harness/loadgen_child.py), over the requests DUE inside
the window that completed:
  tpot_p95_ms     (last - first token time) / (tokens - 1), per request
  tokens_per_s    their output tokens over the window's seconds
  ttft_p*_ms      due instant -> first streamed token (per-layer metrics)
A shed, failed, unfinished or short request counts in `failed` and in no
latency sample.

`correct`: every completed stream ends `{"done": true, "n": n}` with n = its
budget (or its last token is EOS); no program new to the process inside the
window; and the seeded greedy requests of set-up follow the float32
reference's greedy continuation (harness/agreement.follows_greedy), one of
them through a radix hit.
"""

from __future__ import annotations

import dataclasses
import json
import os
import subprocess
import sys
import time
import urllib.request

import numpy as np

from harness import agreement, client, model, trafficgen
from harness.window import Meter, RunResult, TraceWindow, annotate, percentile

HERE = os.path.dirname(os.path.abspath(__file__))
CHILD = os.path.join(os.path.dirname(HERE), "harness", "loadgen_child.py")
CHILD_LEAD_S = 2.0      # child start-up before its t0
TRACE_AFTER_S = 3.0     # the traced part starts this far into the window


def post(port: int, spec: dict, timeout: float = 900.0) -> list:
    body = urllib.request.urlopen(urllib.request.Request(
        f"http://127.0.0.1:{port}/generate", data=json.dumps(spec).encode(),
        headers={"Content-Type": "application/json"}), timeout=timeout).read()
    return json.loads(body)["tokens"]


def buckets(lo: int, hi: int) -> list:
    """Powers of two that `radix.bucket_len` can round a suffix of lo..hi
    real tokens up to."""
    out, b = [], 1
    while b < lo:
        b *= 2
    while b < hi:
        out.append(b)
        b *= 2
    return out + [hi]


def warm_up(port: int, mix: dict, seed: int, vocab: int) -> int:
    """Every shape the window's traffic can use, and no other."""
    rng = np.random.default_rng([seed, 77])
    draw = lambda n: rng.integers(trafficgen.FIRST_TOKEN_ID, vocab, n).tolist()  # noqa: E731
    p = mix["prompt_len"]
    n = 0
    for length in buckets(p["min"], p["max"]):
        post(port, {"tokens": draw(length), "greedy": True, "max_tokens": 6})
        n += 1
    if mix.get("tenants"):      # a hit that ends inside a page: the COW copy
        prefix = draw(int(mix["tenant_prompt_len"]))
        for _ in range(2):
            post(port, {"tokens": prefix + draw(int(mix["tenant_turn"]["min"]) + 7),
                        "temperature": 0.8, "top_p": 0.95, "max_tokens": 6})
            n += 1
    return n


def check_greedy(port: int, engine, params, mcfg, cell, seed: int) -> tuple:
    """Four seeded greedy requests through the gateway (the fourth repeats
    the third's first tokens at equal length, so it is a radix hit), then the
    served tokens against the float32 reference, teacher-forced."""
    import jax
    import jax.numpy as jnp

    from harness import reference
    from nanorlhf_tpu.core.model import padded_forward_logits

    mix = cell.traffic
    chk = mix["greedy_check"]
    vocab, pad = mcfg.vocab_size, int(mix["pad_token_id"])
    rng = np.random.default_rng([seed, 78])
    draw = lambda n: rng.integers(trafficgen.FIRST_TOKEN_ID, vocab, n).tolist()  # noqa: E731
    shared = draw(int(chk["shared_prefix"]))
    prompts = [draw(int(n)) for n in chk["cold_lengths"]]
    prompts += [shared + draw(int(chk["turn"])), shared + draw(int(chk["turn"]))]
    n_new = int(chk["max_tokens"])
    hits0 = engine.metrics()["serving/prefix_hit_tokens"]
    served = [post(port, {"tokens": p, "greedy": True, "max_tokens": n_new})
              for p in prompts]
    hit = engine.metrics()["serving/prefix_hit_tokens"] - hits0
    eos = int(mix["eos_token_id"])
    if not all(len(s) == n_new or (s and s[-1] == eos) for s in served):
        return False, {"error": "a greedy answer is short",
                       "lengths": [len(s) for s in served]}
    if any(len(s) != n_new for s in served):    # an EOS: nothing to compare
        return True, {"skipped": "a greedy answer ended in EOS"}
    width = max(len(p) for p in prompts) + n_new
    seqs = np.full((len(prompts), width), pad, np.int32)
    real = np.zeros(seqs.shape, bool)   # by length: a served token may be the pad id
    for i, (p, s) in enumerate(zip(prompts, served)):
        seqs[i, width - len(p) - n_new:] = p + s
        real[i, width - len(p) - n_new:] = True
    seqs, real = jnp.asarray(seqs), jnp.asarray(real)
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(jax.jit(lambda p, x, m: reference.logits(
            p, cell.config, x, pad, last=n_new + 1, mask=m))(
                params, seqs, real))[:, :-1]
    plain_mcfg = dataclasses.replace(mcfg, attention_impl="xla")
    plain = np.asarray(jax.jit(lambda p, x: padded_forward_logits(
        p, plain_mcfg, x, pad, response_context_length=width - n_new))(
            params, seqs)).astype(np.float32)
    tokens = np.asarray(served)
    ok, detail = agreement.follows_greedy(
        ref.reshape(-1, ref.shape[-1]), tokens.reshape(-1),
        plain.reshape(-1, plain.shape[-1]))
    detail.update(radix_hit_tokens=int(hit))
    if hit < int(chk["shared_prefix"]) - 1:
        ok = False
        detail["error"] = "the equal-length repeat was not a radix hit"
    return ok, detail


def reset_hub(hub) -> None:
    """Empty every histogram through the hub's own journal interface."""
    from nanorlhf_tpu.telemetry.hist import StreamingHistogram

    hub.restore({"hists": {name: StreamingHistogram().state()
                           for name in hub.names()}})


@dataclasses.dataclass
class Served:
    """The system under test, warmed up: what `start` hands to `measure`."""
    engine: object
    gateway: object
    meter: Meter
    vocab_size: int
    setup_compile: dict
    greedy_ok: bool
    greedy: dict

    def close(self) -> None:
        self.gateway.close()
        self.engine.close()


def start(cell, opts) -> Served:
    """Set-up up to the child's ramp: weights, engine, gateway, warm-up, the
    greedy comparison, the hub's reset."""
    from nanorlhf_tpu.serving.engine import ServingEngine
    from nanorlhf_tpu.serving.gateway import ServingGateway
    from nanorlhf_tpu.telemetry.hist import LatencyHub

    mix, seed = cell.traffic, int(opts["seed"])
    meter = Meter()
    mark0 = meter.mark()
    mcfg = model.model_config(cell.config)
    params = model.init_weights(mcfg, seed, model.dtype_of(cell.config))
    hub = LatencyHub(enabled=True)
    e = mix["engine"]
    engine = ServingEngine(
        params, mcfg, eos_token_id=int(mix["eos_token_id"]),
        pad_token_id=int(mix["pad_token_id"]), page_size=int(e["page_size"]),
        prompt_len=int(e["prompt_len"]), max_new_tokens=int(e["max_new_tokens"]),
        rows=int(e["rows"]), headroom=float(e["headroom"]),
        sync_every=int(e["sync_every"]), max_queue=int(e["max_queue"]),
        latency=hub, seed=seed)
    gateway = ServingGateway(engine, port=-1)
    try:
        with annotate("bench.warmup"):
            n_warm = warm_up(gateway.port, mix, seed, mcfg.vocab_size)
            greedy_ok, greedy = check_greedy(gateway.port, engine, params, mcfg,
                                             cell, seed)
    except BaseException:
        gateway.close()
        engine.close()
        raise
    reset_hub(hub)
    setup_compile = Meter.delta(mark0, meter.mark())
    use_kernel = _use_decode_kernel(mcfg, engine)
    print(json.dumps({"phase": "setup", "warmup_requests": n_warm,
                      "greedy_check": greedy, "num_pages": engine.num_pages,
                      "decode_attention": "pallas-paged" if use_kernel
                      else "xla-gathered-view", **setup_compile}), flush=True)
    return Served(engine, gateway, meter, mcfg.vocab_size, setup_compile,
                  greedy_ok, greedy)


def _use_decode_kernel(mcfg, engine) -> bool:
    from nanorlhf_tpu.core.model import use_decode_kernel

    return use_decode_kernel(mcfg.attention_impl, engine.T_max)


def measure(served: Served, cell, opts, tracer: TraceWindow,
            rate: float | None = None) -> dict:
    """One ramp + window + drain of the child against the warmed-up engine:
    the client's records and what the harness polled and counted meanwhile."""
    mix, seed, seconds = cell.traffic, int(opts["seed"]), float(opts["seconds"])
    ramp, drain = float(mix["ramp_s"]), float(mix["drain_s"])
    engine, meter = served.engine, served.meter
    records_file = os.path.join(opts["out_dir"], "requests.jsonl")
    t0 = time.time() + CHILD_LEAD_S
    argv = [sys.executable, CHILD, "--port", str(served.gateway.port),
            "--traffic", opts["traffic_file"], "--seed", str(seed),
            "--vocab", str(served.vocab_size), "--t0", repr(t0),
            "--ramp", str(ramp), "--seconds", str(seconds),
            "--drain", str(drain), "--out", records_file]
    if rate is not None:
        argv += ["--rate", str(rate)]
    child = subprocess.Popen(argv, stdout=subprocess.PIPE)
    try:
        w_start, w_end = t0 + ramp, t0 + ramp + seconds
        time.sleep(max(0.0, w_start - time.time()))
        trace_s = float(mix["trace_s"])
        counters0, mark0 = engine.metrics(), meter.mark()
        snapshots = []
        traced = "before" if tracer.enabled else "after"
        with annotate("bench.loadgen_window"):
            while time.time() < w_end:
                snap = engine.snapshot()
                into = time.time() - w_start
                snapshots.append({"t": into, "active": snap["active"],
                                  "pending": snap["pending"]})
                if traced == "before" and into >= TRACE_AFTER_S:
                    tracer.start()
                    traced, t_trace = "on", time.time()
                elif traced == "on" and time.time() - t_trace >= trace_s:
                    tracer.stop()
                    traced = "after"
                time.sleep(max(0.0, min(0.5, w_end - time.time())))
            if traced == "on":
                tracer.stop()
        counters1, mark1 = engine.metrics(), meter.mark()
        at_end = engine.snapshot()
        out, _ = child.communicate(timeout=drain + 60.0)
    finally:
        if child.poll() is None:
            child.kill()
            child.wait()
    with open(records_file) as f:
        records = [json.loads(line) for line in f]
    statuses: dict = {}
    for r in records:
        statuses[r["status"]] = statuses.get(r["status"], 0) + 1
    return {"records": records, "statuses": statuses, "snapshots": snapshots,
            "window_start": w_start, "seconds": seconds,
            "child": json.loads(out.decode().strip().splitlines()[-1]),
            "counters": {"start": counters0, "end": counters1},
            "backlog_end": {"active": at_end["active"],
                            "pending": at_end["pending"]},
            "window_compile": Meter.delta(mark0, mark1)}


def client_metrics(records: list, seconds: float) -> dict:
    """What the client saw of the requests that completed. Judged end to end:
    `tokens_per_s`, `tpot_p95_ms`; the TTFT percentiles are per-layer metrics
    (layer_metrics/ttft_p*_ms.py) and ride along here for the detail line and
    the knee sweep."""
    done = client.completed(records)
    if not done:
        return {}
    ttft, tpot = client.ttft_ms(records), client.tpot_ms(records)
    return {"tokens_per_s": sum(r["n"] for r in done) / seconds,
            "tpot_p95_ms": percentile(tpot, 95),
            "ttft_p50_ms": percentile(ttft, 50),
            "ttft_p90_ms": percentile(ttft, 90),
            "ttft_p95_ms": percentile(ttft, 95)}


def run(cell, opts) -> RunResult:
    import jax

    served = start(cell, opts)
    tracer = TraceWindow(opts["out_dir"], bool(opts["trace"]),
                         inside="bench.loadgen_window")
    try:
        m = measure(served, cell, opts, tracer)
    finally:
        served.close()
    records, statuses = m["records"], m["statuses"]
    done = statuses.get("ok", 0)
    why_not = []
    if not served.greedy_ok:
        why_not.append(f"served greedy tokens leave the reference: {served.greedy}")
    if m["window_compile"]["compiles"]:
        why_not.append(f"{m['window_compile']['compiles']} programs new to the "
                       "process inside the window")
    if statuses.get("short"):
        why_not.append(f"{statuses['short']} streams ended short of their budget")
    if not done:
        why_not.append("no request completed")
    end_to_end = {"setup_s": m["window_start"] - opts["t_process_start"],
                  **client_metrics(records, m["seconds"])}
    artefacts = {
        "kind": "serve", "cell": cell.name, "seed": int(opts["seed"]),
        "chips": cell.chips, "config": cell.config, "traffic": cell.traffic,
        "seconds": m["seconds"], "records": records, "statuses": statuses,
        "snapshots": m["snapshots"], "child": m["child"],
        "rows_total": int(cell.traffic["engine"]["rows"]),
        "counters": m["counters"], "backlog_end": m["backlog_end"],
        "greedy_check": served.greedy, "samples": done,
        "compile": {"setup": served.setup_compile, "window": m["window_compile"]},
        "trace": tracer.reduce(), "device_kind": jax.devices()[0].device_kind,
    }
    return RunResult(correct=not why_not, attempted=len(records),
                     failed=len(records) - done, end_to_end=end_to_end,
                     run=artefacts, why_not=why_not)
