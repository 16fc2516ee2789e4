"""Sessions (``core/session.py``): ``session.plan`` spans, where a session
groups its specs and builds the stratified sample order of its
aggregations over the whole corpus, total per due request, in ms."""
from benchmarks.chip.layer_metrics._spans import per_request_ms, total_s

NAME = "session.plan_ms"


def read(ctx: dict):
    return per_request_ms(ctx, total_s(ctx, "session.plan"))
