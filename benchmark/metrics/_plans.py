"""Helpers shared by the span and timing readers."""

from __future__ import annotations


def mean(values) -> float | None:
    values = [v for v in values if v is not None]
    return sum(values) / len(values) if values else None


def span_s(plan, prefix: str) -> float | None:
    """Total seconds of a plan's spans whose name starts with `prefix`."""
    hits = [e - s for name, s, e in plan.spans if name.startswith(prefix)]
    return sum(hits) if hits else None
