"""Scheduler (``serve/scheduler.py``): ``sched.hold``, the part of the
``sched.queue`` wait in which the admission window held the request for
co-travelers, mean per due request, in ms.  Nothing is read from a program
that does not split the queue span."""
from benchmarks.chip.layer_metrics._spans import per_request_ms, spans, total_s

NAME = "sched.hold_ms"


def read(ctx: dict):
    if next(spans(ctx, "sched.hold"), None) is None:
        return None
    return per_request_ms(ctx, total_s(ctx, "sched.hold"))
