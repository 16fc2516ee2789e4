"""Helpers the span readers share: durations of spans by name, and a span's
self time (its duration less that of its direct children)."""
from __future__ import annotations


def spans(ctx: dict, name: str):
    for trace in ctx["traces"]:
        for s in trace["spans"]:
            if s["name"] == name and s["t1"] is not None:
                yield trace, s


def total_s(ctx: dict, name: str) -> float:
    return sum(s["t1"] - s["t0"] for _, s in spans(ctx, name))


def self_s(ctx: dict, name: str) -> float:
    """Summed self time of every span called ``name``."""
    total = 0.0
    for trace, s in spans(ctx, name):
        children = sum(c["t1"] - c["t0"] for c in trace["spans"]
                       if c["parent_id"] == s["span_id"]
                       and c["t1"] is not None and c is not s)
        total += (s["t1"] - s["t0"]) - children
    return total


def per_request_ms(ctx: dict, seconds: float):
    """Milliseconds per due request, or None when no request was due."""
    n = ctx["n_requests"]
    return 1e3 * seconds / n if n else None
