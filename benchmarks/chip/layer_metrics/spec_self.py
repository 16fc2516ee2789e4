"""Executors (``core/queries/``): ``spec.execute`` self time, that is less
its broker, proxy and scheduler children, summed and divided by the due
requests, in ms."""
from benchmarks.chip.layer_metrics._spans import per_request_ms, self_s

NAME = "spec.self_ms"


def read(ctx: dict):
    return per_request_ms(ctx, self_s(ctx, "spec.execute"))
