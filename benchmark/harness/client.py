"""Reductions of the load generator's per-request records (client clock)."""

from __future__ import annotations

from harness.window import percentile


def completed(records) -> list:
    return [r for r in records if r["status"] == "ok"]


def ttft_ms(records) -> list:
    """Due instant -> first streamed token, per completed request."""
    return [(r["first"] - r["due"]) * 1e3 for r in completed(records)]


def tpot_ms(records) -> list:
    """(last - first token time) / (tokens - 1), per completed request."""
    return [(r["last"] - r["first"]) / (r["n"] - 1) * 1e3
            for r in completed(records) if r["n"] > 1]


def ttft_percentile(run: dict, q: float):
    values = ttft_ms(run.get("records", ()))
    return percentile(values, q) if values else None
