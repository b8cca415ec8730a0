"""Open-loop load generator: a jax-free child process of the serve driver.

One process, one thread (asyncio): every request is a task that sleeps until
its DUE instant, opens a connection to the gateway, posts `/generate` with
`"stream": true` and stamps each token line on arrival. Repairs over
`nanorlhf_tpu/loadgen/driver.py`: latency is timed from when the request was
due, not from when it was sent; how late it was sent is reported; every
token is stamped, so the gap between tokens includes every stall between
chunks; no thread per request, and none inside the engine's process.

The parent fixes `--t0` (epoch seconds). Arrivals run from t0 for
ramp + window + drain seconds at the mix's rate; the requests DUE inside
[t0 + ramp, t0 + ramp + window) are the measured ones. Traffic goes on after
the window, unmeasured, so that the last measured requests finish under the
same load; the child ends when they all have, or at the drain limit
(unfinished = failed), closes every connection and writes one JSON line per
measured request to `--out`, then one summary line to stdout.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from harness import trafficgen  # noqa: E402


class Clock:
    """perf_counter readings as offsets from the parent's t0 (epoch)."""

    def __init__(self, t0_epoch: float):
        self._pc_at_t0 = time.perf_counter() + (t0_epoch - time.time())

    def now(self) -> float:
        return time.perf_counter() - self._pc_at_t0


async def fire(req: dict, port: int, clock: Clock, eos_id: int) -> dict:
    rec = {"index": req["index"], "due": req["t"], "tenant": req["tenant"],
           "prompt_len": len(req["tokens"]), "budget": req["max_tokens"],
           "status": "unfinished", "n": 0}
    delay = req["t"] - clock.now()
    if delay > 0:
        await asyncio.sleep(delay)
    rec["sent"] = clock.now()
    writer = None
    try:
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
        body = json.dumps({
            "tokens": req["tokens"], "max_tokens": req["max_tokens"],
            "greedy": req["greedy"], "temperature": req["temperature"],
            "top_p": req["top_p"], "stream": True}).encode()
        writer.write(b"POST /generate HTTP/1.1\r\nHost: 127.0.0.1\r\n"
                     b"Content-Type: application/json\r\nContent-Length: "
                     + str(len(body)).encode() + b"\r\n\r\n" + body)
        await writer.drain()
        code = int((await reader.readline()).split()[1])
        length = 0
        while True:
            line = await reader.readline()
            if line in (b"\r\n", b"\n", b""):
                break
            if line.lower().startswith(b"content-length:"):
                length = int(line.split(b":")[1])
        if code != 200:
            payload = await reader.readexactly(length) if length else b"{}"
            reason = json.loads(payload or b"{}").get("reason", "")
            rec["status"] = (f"shed:{reason}" if code == 429 else f"http_{code}")
            return rec
        last_tok = None
        while True:
            size = int((await reader.readline()).strip() or b"0", 16)
            if size == 0:
                break
            data = await reader.readexactly(size)
            await reader.readexactly(2)
            now = clock.now()
            for text in data.splitlines():
                obj = json.loads(text)
                if "token" in obj:
                    if rec["n"] == 0:
                        rec["first"] = now
                    rec["last"] = now
                    last_tok = obj["token"]
                    rec["n"] += 1
                elif obj.get("done"):
                    whole = rec["n"] == req["max_tokens"] or last_tok == eos_id
                    rec["status"] = ("ok" if obj.get("n") == rec["n"] and whole
                                     and rec["n"] > 0 else "short")
                    rec["eos"] = last_tok == eos_id
        return rec
    except asyncio.CancelledError:
        raise
    except Exception as e:  # a failed request is a record, not a crash
        if rec["status"] == "unfinished":
            rec["status"] = f"error:{type(e).__name__}"
        return rec
    finally:
        if writer is not None:
            writer.close()


async def run(args) -> dict:
    with open(args.traffic) as f:
        mix = json.load(f)
    reqs = trafficgen.serve_requests(
        mix, args.seed, [args.ramp, args.seconds, args.drain], args.vocab,
        rate_rps=args.rate)
    clock = Clock(args.t0)
    started_late = max(0.0, clock.now())
    lo, hi = args.ramp, args.ramp + args.seconds
    eos_id = int(mix.get("eos_token_id", 1))
    tasks = {r["index"]: asyncio.ensure_future(fire(r, args.port, clock, eos_id))
             for r in reqs}
    measured = [r for r in reqs if lo <= r["t"] < hi]
    pending = {tasks[r["index"]] for r in measured}
    limit = hi + args.drain
    while pending and clock.now() < limit:
        _, pending = await asyncio.wait(
            pending, timeout=max(0.0, min(0.5, limit - clock.now())))
    records = []
    for r in measured:
        t = tasks[r["index"]]
        records.append(t.result() if t.done() and not t.cancelled() else {
            "index": r["index"], "due": r["t"], "tenant": r["tenant"],
            "prompt_len": len(r["tokens"]), "budget": r["max_tokens"],
            "status": "unfinished", "n": 0})
    others = [t for i, t in tasks.items() if not t.done()]
    for t in others:
        t.cancel()
    await asyncio.gather(*others, return_exceptions=True)
    with open(args.out, "w") as f:
        for rec in records:
            f.write(json.dumps(rec) + "\n")
    return {"scheduled": len(reqs), "measured": len(records),
            "ended_at": clock.now(), "started_late_s": started_late,
            "digest": trafficgen.digest(reqs), "rate_rps":
            float(mix["rate_rps"] if args.rate is None else args.rate)}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--vocab", type=int, required=True)
    ap.add_argument("--t0", type=float, required=True)
    ap.add_argument("--ramp", type=float, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--drain", type=float, required=True)
    ap.add_argument("--rate", type=float, default=None)
    ap.add_argument("--out", required=True)
    summary = asyncio.run(run(ap.parse_args()))
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
