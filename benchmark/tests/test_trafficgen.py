"""The generator replays bit-identically from a seed, keeps to its mix's
parameters, and the load generator times from the DUE instant."""

import json
import os
import subprocess
import sys
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import numpy as np

from harness import trafficgen

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MIX = json.load(open(os.path.join(BENCH, "traffic", "chat-steady.json")))
RL = json.load(open(os.path.join(BENCH, "traffic", "grpo-r512.json")))


SEGMENTS = [10.0, 45.0, 30.0]


def test_schedule_replays_bit_identically():
    a = trafficgen.serve_requests(MIX, 7, SEGMENTS, 151936)
    b = trafficgen.serve_requests(MIX, 7, SEGMENTS, 151936)
    assert a == b and trafficgen.digest(a) == trafficgen.digest(b)
    assert trafficgen.digest(a) != trafficgen.digest(
        trafficgen.serve_requests(MIX, 8, SEGMENTS, 151936))
    # a longer drain does not move the ramp's and the window's requests
    c = trafficgen.serve_requests(MIX, 7, [10.0, 45.0, 60.0], 151936)
    n = sum(r["t"] < 55.0 for r in a)
    assert c[:n] == a[:n]


def window(reqs, lo=10.0, hi=55.0):
    return [r for r in reqs if lo <= r["t"] < hi]


def test_a_mix_with_a_schedule_seed_keeps_its_shape():
    """chat-steady fixes its shape: seeds change the tokens, nothing else."""
    assert "schedule_seed" in MIX
    a = trafficgen.serve_requests(MIX, 1, SEGMENTS, 151936)
    b = trafficgen.serve_requests(MIX, 2, SEGMENTS, 151936)
    shape = lambda r: {k: v for k, v in r.items() if k != "tokens"} | {  # noqa: E731
        "n": len(r["tokens"])}
    assert [shape(r) for r in a] == [shape(r) for r in b]
    assert all(x["tokens"] != y["tokens"] for x, y in zip(a, b))


FREE = {k: v for k, v in MIX.items() if k != "schedule_seed"}


def test_every_seed_offers_the_same_work():
    """A fixed amount of work drawn from the seed: the window's request
    count is exact and its token totals barely move, so `tokens_per_s` of a
    cell below its knee reads the same on every seed."""
    runs = [window(trafficgen.serve_requests(FREE, seed, SEGMENTS, 151936))
            for seed in range(1, 9)]
    assert {len(w) for w in runs} == {round(MIX["rate_rps"] * 45.0)}
    out = np.array([sum(r["max_tokens"] for r in w) for w in runs])
    prompt = np.array([sum(len(r["tokens"]) for r in w) for w in runs])
    assert out.std() / out.mean() < 0.01 and prompt.std() / prompt.mean() < 0.02
    assert {sum(r["greedy"] for r in w) for w in runs} == {len(runs[0]) // 2}
    assert {sum(r["tenant"] >= 0 for r in w) for w in runs} == {len(runs[0]) // 2}
    assert [r["t"] for r in runs[0]] != [r["t"] for r in runs[1]]


def test_schedule_keeps_to_its_parameters():
    reqs = trafficgen.serve_requests(MIX, 1, [400.0], 151936)
    p, o = MIX["prompt_len"], MIX["max_tokens"]
    cold = np.array([len(r["tokens"]) for r in reqs if r["tenant"] < 0])
    outs = np.array([r["max_tokens"] for r in reqs])
    assert cold.min() >= p["min"] and cold.max() <= p["max"]
    assert outs.min() >= o["min"] and outs.max() <= o["max"]
    assert len(reqs) == round(400 * MIX["rate_rps"])
    assert abs(np.median(cold) - p["median"]) < 25
    assert abs(np.median(outs) - o["median"]) < 10
    gaps = np.diff([r["t"] for r in reqs])
    assert np.all(gaps >= 0)
    assert abs(gaps.std() / gaps.mean() - 1.0) < 0.15    # memoryless gaps
    tenants = trafficgen.tenant_prompts(MIX, 1, 151936)
    joined = [r for r in reqs if r["tenant"] >= 0]
    for r in joined:
        t = tenants[r["tenant"]]
        assert r["tokens"][: len(t)] == t
        assert len(r["tokens"]) >= len(t) + MIX["tenant_turn"]["min"]
    # no length carries a pile of tenant requests (equal lengths are what the
    # radix cache shares today: the mix must not bend towards them)
    tl = [len(r["tokens"]) for r in joined if len(r["tokens"]) < p["max"]]
    assert max(tl.count(x) for x in set(tl)) < 0.03 * len(tl)
    assert all(min(r["tokens"]) >= trafficgen.FIRST_TOKEN_ID for r in reqs)


def test_bursty_arrivals_keep_the_count_and_bunch_up():
    mix = dict(MIX, arrival="bursty", burst_factor=4.0, burst_frac=0.25)
    t = trafficgen.arrival_offsets(mix, 3, 0.0, 2000.0)
    assert len(t) == round(2000 * MIX["rate_rps"]) and np.all(np.diff(t) >= 0)
    assert t.min() >= 0 and t.max() < 2000.0
    gaps = np.diff(t)
    assert gaps.std() / gaps.mean() > 1.2       # burstier than Poisson


def test_rl_corpus_is_left_padded_and_replays():
    a = trafficgen.rl_prompts(RL, 5, 151936)
    assert np.array_equal(a, trafficgen.rl_prompts(RL, 5, 151936))
    assert a.shape == (RL["dataset_prompts"], RL["prompt_len_max"])
    real = (a != 0).sum(1)
    assert real.min() >= RL["prompt_len_min"] and real.max() == RL["prompt_len_max"]
    first = (a != 0).argmax(1)
    assert all((row[f:] != 0).all() for row, f in zip(a, first))


class _Gateway(BaseHTTPRequestHandler):
    """Streams `max_tokens` tokens, 10 ms apart, the gateway's wire format."""
    protocol_version = "HTTP/1.1"

    def do_POST(self):
        spec = json.loads(self.rfile.read(int(self.headers["Content-Length"])))
        self.send_response(200)
        self.send_header("Transfer-Encoding", "chunked")
        self.end_headers()
        n = spec["max_tokens"]
        for i in range(n):
            time.sleep(0.01)
            self._chunk(json.dumps({"token": 5 + i}) + "\n")
        self._chunk(json.dumps({"done": True, "n": n}) + "\n")
        self.wfile.write(b"0\r\n\r\n")

    def _chunk(self, text):
        data = text.encode()
        self.wfile.write(f"{len(data):x}\r\n".encode() + data + b"\r\n")
        self.wfile.flush()

    def log_message(self, *a):
        pass


def test_child_times_from_the_due_instant(tmp_path):
    server = ThreadingHTTPServer(("127.0.0.1", 0), _Gateway)
    server.daemon_threads = True
    threading.Thread(target=server.serve_forever, daemon=True).start()
    mix = dict(MIX, rate_rps=20.0,
               max_tokens={"median": 4, "sigma": 0.1, "min": 2, "max": 6})
    mix_file, out = tmp_path / "mix.json", tmp_path / "records.jsonl"
    mix_file.write_text(json.dumps(mix))
    late_by = 0.4   # the child is told a t0 that has already passed
    argv = [sys.executable, os.path.join(BENCH, "harness", "loadgen_child.py"),
            "--port", str(server.server_address[1]), "--traffic", str(mix_file),
            "--seed", "1", "--vocab", "1000", "--t0", repr(time.time() - late_by),
            "--ramp", "0.0", "--seconds", "1.0", "--drain", "5.0", "--out", str(out)]
    summary = json.loads(subprocess.run(
        argv, capture_output=True, check=True, timeout=60).stdout)
    server.shutdown()
    records = [json.loads(x) for x in out.read_text().splitlines()]
    assert summary["measured"] == len(records) > 5
    assert all(r["status"] == "ok" and r["n"] == r["budget"] for r in records)
    for r in records:
        assert r["first"] - r["due"] >= r["sent"] - r["due"] >= 0
        # 10 ms between tokens at the server: the gap is inside the record
        assert (r["last"] - r["first"]) / max(r["n"] - 1, 1) >= 0.009
    # requests that were due before the child existed were sent late, and
    # their latency is counted from when they were DUE
    early = [r for r in records if r["due"] < 0.1]
    assert early and all(r["sent"] - r["due"] > 0.2 for r in early)
    assert all(r["first"] - r["due"] > 0.2 for r in early)
