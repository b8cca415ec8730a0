"""trainer loop: `time/rollout_s` over the sum of the trainer's phases, over
the window's updates (the `PhaseTimer` rows of metrics.jsonl), in %."""


def phase_seconds(row):
    return {k: v for k, v in row.items()
            if k.startswith("time/") and k.endswith("_s")
            and isinstance(v, (int, float))}


def read(run):
    rows = run.get("rows")
    if not rows:
        return None
    rollout = sum(r.get("time/rollout_s", 0.0) for r in rows)
    total = sum(sum(phase_seconds(r).values()) for r in rows)
    return 100.0 * rollout / total if total else None
