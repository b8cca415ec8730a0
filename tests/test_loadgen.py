"""Traffic harness + autoscaling control loop (nanorlhf_tpu/loadgen/,
docs/TRAFFIC.md, ISSUE 16).

Pins the acceptance contract:

- workload replay: same seed + spec is a BIT-identical request sequence
  (requests_digest equality, plus a hard-coded digest pin — the sampler
  is pure 64-bit integer math, so the digest is platform-stable); seed
  and spec sensitivity; Poisson and bursty arrivals monotone from 0;
  prefix groups actually share prefixes;
- autoscaler hysteresis under a fake clock: no flapping on an
  oscillating verdict, cooldown respected, min/max bounds enforced,
  queue-depth leading trigger;
- drain-then-remove on a real (jax-free, fake-dispatch) fleet: a
  drained worker's in-flight lease completes on that worker (nothing
  stranded, nothing reassigned) while abrupt removal still reassigns;
- the open-loop driver against the real in-process ServingEngine:
  request conservation, per-reason shed counters, client-TTFT hub rows,
  `traffic`/`traffic_run` lineage events;
- end-to-end: saturate the engine -> CRIT SLO verdict -> autoscaler
  add_worker on the fleet -> sustained recovery -> drain-remove back to
  the floor, every decision a lineage `autoscale` event;
- `tools/inspect_run.py --traffic` rebuilds offered/goodput/shed + the
  autoscale decision list from the ledger alone (CLI, jax-free).

CI runs this file as the `traffic-smoke` tier-1 step under
NANORLHF_LOCK_CHECK=1 — loadgen.driver/loadgen.autoscaler rank at the
front of the declared LOCK_ORDER, so every actuate-under-lock call is
order-checked live.
"""

import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from nanorlhf_tpu.core import ModelConfig, init_params
from nanorlhf_tpu.loadgen import (
    Autoscaler,
    AutoscalerConfig,
    TrafficDriver,
    WorkloadSpec,
    requests_digest,
    sample_requests,
    slo_level_from_monitor,
    spec_digest,
)
from nanorlhf_tpu.orchestrator import FleetConfig, FleetOrchestrator
from nanorlhf_tpu.serving.engine import ServingEngine
from nanorlhf_tpu.telemetry.health import (
    CRIT,
    OK,
    HealthConfig,
    HealthMonitor,
    HealthRule,
)
from nanorlhf_tpu.telemetry.hist import LatencyHub
from nanorlhf_tpu.telemetry.lineage import LineageLedger, read_ledger

EOS, PAD = 3, 0

TOOLS = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                     "tools", "inspect_run.py")


# --------------------------------------------------------------------- #
# workload replay discipline (jax-free)
# --------------------------------------------------------------------- #

def test_replay_bit_identical_and_digest_pinned():
    spec = WorkloadSpec(seed=7, n_requests=32, rate_rps=20.0,
                        arrival="bursty")
    a, b = sample_requests(spec), sample_requests(spec)
    assert a == b                       # frozen dataclasses: full equality
    assert requests_digest(a) == requests_digest(b)
    # pure splitmix64 integer math end to end — the digest is stable
    # across platforms and sessions, so pin it (a drift here means the
    # sampling stream changed and every recorded spec_digest is invalid)
    assert requests_digest(a) == "94ae405ac382b949"
    assert spec_digest(spec) == "acbbd7d142cfcba1"


def test_replay_sensitivity():
    base = WorkloadSpec(seed=7, n_requests=32, rate_rps=20.0)
    assert (requests_digest(sample_requests(base))
            != requests_digest(sample_requests(
                WorkloadSpec(seed=8, n_requests=32, rate_rps=20.0))))
    # any spec field participates in the digest (rate changes arrivals)
    assert (spec_digest(base)
            != spec_digest(WorkloadSpec(seed=7, n_requests=32,
                                        rate_rps=21.0)))


@pytest.mark.parametrize("arrival", ["poisson", "bursty"])
def test_arrival_offsets_monotone_from_zero(arrival):
    spec = WorkloadSpec(seed=3, n_requests=64, rate_rps=50.0,
                        arrival=arrival)
    reqs = sample_requests(spec)
    assert len(reqs) == 64
    offs = [r.t_offset for r in reqs]
    assert offs[0] >= 0.0
    assert offs == sorted(offs)
    assert all(reqs[i].index == i for i in range(len(reqs)))


def test_prefix_groups_share_prefixes():
    spec = WorkloadSpec(seed=5, n_requests=64, rate_rps=50.0,
                        prefix_groups=3, prefix_frac=0.6, prefix_len=4,
                        prompt_len_min=5, prompt_len_max=10)
    reqs = sample_requests(spec)
    grouped = [r for r in reqs if r.prefix_group >= 0]
    # ~60% of 64 requests join a tenant group
    assert len(grouped) >= 20
    by_group: dict = {}
    for r in grouped:
        by_group.setdefault(r.prefix_group, []).append(r)
    for members in by_group.values():
        prefixes = {m.tokens[:4] for m in members}
        assert len(prefixes) == 1       # group members share the prefix
        for m in members:
            assert len(m.tokens) >= 5   # at least one unique tail token
    # loners don't all collapse onto one group's prefix
    assert len(by_group) >= 2


def test_spec_validation():
    with pytest.raises(ValueError):
        WorkloadSpec(rate_rps=0.0).validate()
    with pytest.raises(ValueError):
        WorkloadSpec(arrival="uniform").validate()
    with pytest.raises(ValueError):
        WorkloadSpec(prompt_len_min=8, prompt_len_max=4).validate()


# --------------------------------------------------------------------- #
# autoscaler hysteresis (fake clock, fake actuators)
# --------------------------------------------------------------------- #

class _FakeFleet:
    """Actuator stub: monotonic ids like FleetOrchestrator's."""

    def __init__(self, n=1):
        self.ids = list(range(n))
        self.next_id = n
        self.removed: list = []

    def add(self):
        wid = self.next_id
        self.next_id += 1
        self.ids.append(wid)
        return wid

    def remove(self, wid):
        self.ids.remove(wid)
        self.removed.append(wid)


def _controller(fleet, level_fn, cfg, clock, depth_fn=None, lineage=None):
    return Autoscaler(
        add_worker=fleet.add, remove_worker=fleet.remove,
        worker_ids=lambda: list(fleet.ids), slo_level=level_fn,
        queue_depth=depth_fn, config=cfg, clock=clock, lineage=lineage)


def test_no_flap_under_oscillating_verdict():
    """A verdict that alternates crit/ok every tick never accumulates
    `breach_evals=2` consecutive breaches NOR `recovery_evals=4`
    consecutive healthy ticks from above the floor — zero actions."""
    fleet = _FakeFleet(n=1)
    t = [0.0]
    tick = [0]

    def level():
        return CRIT if tick[0] % 2 == 0 else OK

    asc = _controller(
        fleet, level,
        AutoscalerConfig(min_workers=1, max_workers=3, breach_evals=2,
                         recovery_evals=4, cooldown_s=0.0),
        clock=lambda: t[0])
    for _ in range(50):
        asc.evaluate()
        tick[0] += 1
        t[0] += 1.0
    m = asc.metrics()
    assert m["loadgen/scale_ups"] == 0
    assert m["loadgen/scale_downs"] == 0
    assert fleet.ids == [0]


def test_cooldown_respected_and_counted():
    fleet = _FakeFleet(n=1)
    t = [0.0]
    asc = _controller(
        fleet, lambda: CRIT,
        AutoscalerConfig(min_workers=1, max_workers=3, breach_evals=1,
                         recovery_evals=1, cooldown_s=10.0),
        clock=lambda: t[0])
    actions = []
    for _ in range(15):
        actions.append(asc.evaluate())
        t[0] += 1.0
    # one up immediately, then held until the cooldown elapses, then the
    # second up, then bounded at max_workers
    assert actions[0] == "scale_up"
    assert actions.count("scale_up") == 2
    first, second = (i for i, a in enumerate(actions) if a == "scale_up")
    assert second - first >= 10
    assert "hold_cooldown" in actions[first + 1:second]
    assert asc.metrics()["loadgen/holds_cooldown"] >= 1


def test_min_max_bounds_enforced():
    fleet = _FakeFleet(n=1)
    t = [0.0]
    level = [CRIT]
    asc = _controller(
        fleet, lambda: level[0],
        AutoscalerConfig(min_workers=1, max_workers=2, breach_evals=1,
                         recovery_evals=1, cooldown_s=0.0),
        clock=lambda: t[0])
    for _ in range(10):
        asc.evaluate()
        t[0] += 1.0
    assert fleet.ids == [0, 1]          # capped at max_workers
    level[0] = OK
    for _ in range(10):
        asc.evaluate()
        t[0] += 1.0
    assert fleet.ids == [0]             # floored at min_workers
    # scale-in removed the NEWEST worker (monotonic ids)
    assert fleet.removed == [1]


def test_queue_depth_leading_trigger():
    """Queue depth over `queue_high` counts as a breach while the SLO
    still reads OK — the leading indicator scales before TTFT degrades."""
    fleet = _FakeFleet(n=1)
    t = [0.0]
    depth = [100]
    asc = _controller(
        fleet, lambda: OK,
        AutoscalerConfig(min_workers=1, max_workers=2, breach_evals=2,
                         recovery_evals=99, cooldown_s=0.0, queue_high=8),
        clock=lambda: t[0], depth_fn=lambda: depth[0])
    a1, a2 = asc.evaluate(), asc.evaluate()
    assert (a1, a2) == ("hold", "scale_up")
    assert fleet.ids == [0, 1]


def test_config_validation():
    with pytest.raises(ValueError):
        AutoscalerConfig(min_workers=0).validate()
    with pytest.raises(ValueError):
        AutoscalerConfig(min_workers=3, max_workers=2).validate()
    with pytest.raises(ValueError):
        AutoscalerConfig(breach_level="fatal").validate()


# --------------------------------------------------------------------- #
# drain-then-remove on a real fake-dispatch fleet (jax-free)
# --------------------------------------------------------------------- #

def _fleet(n_workers=2, dispatch_s=0.05, n_batches=1000, hold=None):
    """`hold`: `(index, event)`, every dispatch of that index waits for the
    event."""
    batches = iter(range(n_batches))

    def dispatch(index, queries, tree, worker_id):
        if hold is not None and index == hold[0]:
            hold[1].wait(timeout=30.0)
        time.sleep(dispatch_s)
        return {"index": index, "worker": worker_id}

    return FleetOrchestrator(
        dispatch_fn=dispatch, batch_fn=lambda: next(batches),
        initial_params={}, n_workers=n_workers, max_staleness=8,
        fleet=FleetConfig(poll_interval=0.02, lease_size=2),
    )


def test_drain_remove_never_strands_a_lease():
    orch = _fleet(n_workers=2, dispatch_s=0.05)
    try:
        orch.publish({})
        first = orch.get()              # both workers warmed + leased
        victim = first.payload["worker"]
        t0 = time.monotonic()
        drained = orch.remove_worker(victim, drain=True,
                                     drain_timeout_s=10.0)
        assert drained is True
        assert time.monotonic() - t0 < 10.0
        assert victim not in orch.coordinator.live_worker_ids()
        # the drained worker's in-flight lease COMPLETED on that worker:
        # nothing was revoked into the reassignment pool
        assert orch.coordinator.counters["reassigned_leases"] == 0
        assert orch.coordinator.counters["expired_leases"] == 0
        # the fleet still makes progress on the survivor, in index order
        seen = [orch.get().index for _ in range(4)]
        assert seen == sorted(seen)
    finally:
        orch.close()


def test_abrupt_remove_still_reassigns():
    # index 1 is on the lease of index 0 (lease_size=2): its holder is in
    # the middle of that lease when it is removed, and stays there until
    # the lease has been granted again. A revoked holder that finished
    # BEFORE the new grant would commit, and nothing would be reassigned.
    release = threading.Event()
    orch = _fleet(n_workers=2, dispatch_s=0.02, hold=(1, release))
    coord = orch.coordinator
    try:
        orch.publish({})
        first = orch.get()
        assert first.index == 0
        victim = first.payload["worker"]
        orch.remove_worker(victim)      # default: abrupt, revoke + reassign
        with coord._cond:               # every grant notifies it
            assert coord._cond.wait_for(
                lambda: coord.counters["reassigned_leases"] >= 1,
                timeout=30.0)
        release.set()
        got = [orch.get() for _ in range(4)]
        assert [g.index for g in got] == [1, 2, 3, 4]
        # the revoked holder's own result of index 1 lost to the new grant
        assert got[0].payload["worker"] != victim
    finally:
        release.set()
        orch.close()


def test_draining_worker_gets_no_new_lease():
    orch = _fleet(n_workers=2, dispatch_s=0.02)
    try:
        orch.publish({})
        orch.get()
        victim = orch.coordinator.live_worker_ids()[0]
        assert orch.coordinator.drain_worker(victim)
        assert orch.coordinator.wait_drained(victim, timeout=10.0)
        # the victim's PRE-drain leases are still queued (delivery is
        # index-ordered) — but draining stopped new grants, so its
        # backlog is bounded by the staleness window; past it, every
        # sample is the survivor's
        survivor = [w for w in orch.coordinator.live_worker_ids()
                    if w != victim]
        assert len(survivor) == 1
        tail = []
        for _ in range(24):
            orch.publish({})    # keep the staleness gate open
            tail.append(orch.get().payload["worker"])
        last_victim = max(
            (i for i, w in enumerate(tail) if w == victim), default=-1)
        assert last_victim < 20
        assert all(w == survivor[0] for w in tail[last_victim + 1:])
    finally:
        orch.close()


# --------------------------------------------------------------------- #
# open-loop driver against the real in-process engine
# --------------------------------------------------------------------- #

@pytest.fixture(scope="module")
def tiny():
    config = ModelConfig.qwen2_tiny(vocab_size=128)
    params = init_params(config, jax.random.PRNGKey(7), jnp.float32)
    return config, params


def _engine(tiny, rows=2, max_queue=2, hub=None, slo_warn=1e9):
    config, params = tiny
    return ServingEngine(
        params, config, eos_token_id=EOS, pad_token_id=PAD, page_size=4,
        prompt_len=12, max_new_tokens=8, rows=rows, max_queue=max_queue,
        latency=hub, slo_warn_ttft_s=slo_warn, seed=0)


def test_driver_open_loop_inprocess(tiny, tmp_path):
    hub = LatencyHub()
    led = LineageLedger(str(tmp_path))
    spec = WorkloadSpec(seed=1, n_requests=16, rate_rps=500.0,
                        prompt_len_min=4, prompt_len_max=12,
                        token_lo=10, token_hi=50, greedy_frac=1.0,
                        prefix_groups=2, prefix_frac=0.5, prefix_len=4,
                        max_tokens_min=8, max_tokens_max=8)
    eng = _engine(tiny, rows=2, max_queue=2)
    try:
        driver = TrafficDriver(engine=eng, latency=hub, lineage=led,
                               stream_timeout_s=120.0)
        summary = driver.run(spec)
    finally:
        eng.close()
    # open loop conserves requests: offered = completed + shed + errors
    assert summary.offered == 16
    assert summary.completed + summary.shed + summary.errors == 16
    assert summary.errors == 0
    # 16 near-simultaneous arrivals into 2 rows + queue bound 2 MUST shed
    assert summary.shed >= 1
    assert set(summary.shed_reasons) <= {"queue_full", "slo_ttft_p95",
                                         "engine_abort"}
    # client-side hub rows: one TTFT and one total per completion
    assert hub.count("latency/client_ttft_s") == summary.completed
    assert hub.count("latency/client_total_s") == summary.completed
    # the engine's per-reason counters agree with the client's view
    m = eng.metrics()
    assert m["serving/shed"] == summary.shed
    assert sum(v for k, v in m.items()
               if k.startswith("serving/shed_total{")) == summary.shed
    dm = driver.metrics()
    assert dm["loadgen/offered"] == 16
    assert dm["loadgen/completed"] == summary.completed
    assert dm["loadgen/goodput_rps"] > 0
    # lineage: one run header + one event per request
    evs = list(read_ledger(str(tmp_path)))
    runs = [e for e in evs if e["type"] == "traffic_run"]
    fired = [e for e in evs if e["type"] == "traffic"]
    assert len(runs) == 1 and runs[0]["spec_digest"] == spec_digest(spec)
    assert len(fired) == 16
    assert ({e["request_index"] for e in fired} == set(range(16)))


def test_driver_requires_exactly_one_target():
    with pytest.raises(ValueError):
        TrafficDriver()
    with pytest.raises(ValueError):
        TrafficDriver(engine=object(), base_url="http://127.0.0.1:1")


# --------------------------------------------------------------------- #
# end-to-end: saturation -> CRIT -> scale up -> recovery -> drain down
# --------------------------------------------------------------------- #

def test_e2e_saturate_crit_scale_up_recover_drain_down(tiny, tmp_path):
    """The acceptance loop (ISSUE 16): drive the in-process engine past
    saturation, watch the SLO rule go CRIT on CLIENT TTFT, see the
    autoscaler add a fleet worker, then — after sustained recovery —
    drain-remove back to the floor, with every decision a lineage event."""
    led = LineageLedger(str(tmp_path))
    hub = LatencyHub()
    # client-TTFT SLO sized for the CPU rig: saturated queue waits are
    # tens of ms, healthy ones sub-ms synthetic
    rule = HealthRule("slo_ttft_p95", "latency/client_ttft_s",
                      "quantile_above", warn=0.002, crit=0.005,
                      warmup=4, quantile=0.95)
    monitor = HealthMonitor(
        HealthConfig(rules=(rule,), recovery_rows=2), latency=hub)

    orch = _fleet(n_workers=1, dispatch_s=0.01)
    asc = Autoscaler(
        add_worker=orch.add_worker,
        remove_worker=lambda wid: orch.remove_worker(
            wid, drain=True, drain_timeout_s=10.0),
        worker_ids=orch.coordinator.live_worker_ids,
        slo_level=lambda: slo_level_from_monitor(
            monitor, rules=("slo_ttft_p95",)),
        config=AutoscalerConfig(min_workers=1, max_workers=2,
                                breach_evals=2, recovery_evals=3,
                                cooldown_s=0.0),
        lineage=led)

    eng = _engine(tiny, rows=2, max_queue=4)
    try:
        # phase 1: saturate. 24 arrivals at 500 rps into 2 rows: queue
        # waits push client p95 TTFT far over crit=5ms
        spec = WorkloadSpec(seed=2, n_requests=24, rate_rps=500.0,
                            prompt_len_min=4, prompt_len_max=12,
                            token_lo=10, token_hi=50, greedy_frac=1.0,
                            max_tokens_min=8, max_tokens_max=8)
        driver = TrafficDriver(engine=eng, latency=hub, lineage=led,
                               stream_timeout_s=120.0)
        summary = driver.run(spec)
        assert summary.completed >= rule.warmup  # enough SLO samples
        for step in range(4):
            monitor.observe(step, {})
        assert slo_level_from_monitor(
            monitor, rules=("slo_ttft_p95",)) == CRIT

        actions = [asc.evaluate() for _ in range(3)]
        assert "scale_up" in actions
        assert len(orch.coordinator.live_worker_ids()) == 2

        # phase 2: recovery. Histograms are cumulative, so the verdict
        # recovers through the documented hub-swap seam: attach a fresh
        # hub (a new measurement window) carrying healthy client TTFTs.
        fresh = LatencyHub()
        for _ in range(rule.warmup + 2):
            fresh.record("latency/client_ttft_s", 0.0005)
        monitor.attach_latency(fresh)
        for step in range(4, 4 + monitor.cfg.recovery_rows + 2):
            monitor.observe(step, {})
        assert slo_level_from_monitor(
            monitor, rules=("slo_ttft_p95",)) == OK

        for _ in range(5):
            asc.evaluate()
        assert len(orch.coordinator.live_worker_ids()) == 1  # the floor
        assert asc.metrics()["loadgen/scale_downs"] == 1
        # the drained fleet never revoked a lease into reassignment
        assert orch.coordinator.counters["reassigned_leases"] == 0
    finally:
        eng.close()
        orch.close()

    # every scaling decision is a lineage event, in order
    evs = list(read_ledger(str(tmp_path)))
    scale = [e for e in evs if e["type"] == "autoscale"]
    assert [e["action"] for e in scale] == ["scale_up", "scale_down"]
    up, down = scale
    assert (up["workers_before"], up["workers_after"]) == (1, 2)
    assert (down["workers_before"], down["workers_after"]) == (2, 1)
    assert down["worker_id"] == up["worker_id"]  # newest drains out
    assert up["level"] == CRIT and down["level"] == OK


# --------------------------------------------------------------------- #
# offline reconstruction: inspect_run --traffic from the ledger alone
# --------------------------------------------------------------------- #

def test_inspect_run_traffic_from_ledger_alone(tiny, tmp_path):
    led = LineageLedger(str(tmp_path))
    spec = WorkloadSpec(seed=4, n_requests=12, rate_rps=200.0,
                        prompt_len_min=4, prompt_len_max=12,
                        token_lo=10, token_hi=50, greedy_frac=1.0,
                        max_tokens_min=8, max_tokens_max=8)
    eng = _engine(tiny, rows=2, max_queue=4)
    try:
        summary = TrafficDriver(engine=eng, lineage=led,
                                stream_timeout_s=120.0).run(spec)
    finally:
        eng.close()
    led.event("autoscale", action="scale_up", worker_id=1,
              workers_before=1, workers_after=2, level="crit", eval=3)

    out = subprocess.run(
        [sys.executable, TOOLS, str(tmp_path), "--traffic", "--json"],
        capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert out.returncode == 0, out.stderr
    rep = json.loads(out.stdout)
    assert rep["offered"] == 12
    assert rep["outcomes"].get("completed", 0) == summary.completed
    assert rep["outcomes"].get("shed", 0) == summary.shed
    assert rep["client_ttft_s"]["count"] == summary.completed
    assert sum(b["offered"] for b in rep["timeline"]) == 12
    assert rep["runs"][0]["spec_digest"] == spec_digest(spec)
    assert rep["autoscale"] == [{
        "action": "scale_up", "worker_id": 1, "workers_before": 1,
        "workers_after": 2, "level": "crit", "queue_depth": None,
        "eval": 3}]
    # the human printer renders without error too
    out2 = subprocess.run(
        [sys.executable, TOOLS, str(tmp_path), "--traffic"],
        capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert out2.returncode == 0, out2.stderr
    assert "autoscale decisions" in out2.stdout
