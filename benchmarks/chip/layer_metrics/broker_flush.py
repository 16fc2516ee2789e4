"""Broker and oracle pool (``core/broker.py``, ``core/oracle_pool.py``):
``broker.flush`` spans, total per due request, in ms."""
from benchmarks.chip.layer_metrics._spans import per_request_ms, total_s

NAME = "broker.flush_ms"


def read(ctx: dict):
    return per_request_ms(ctx, total_s(ctx, "broker.flush"))
