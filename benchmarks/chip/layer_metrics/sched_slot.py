"""Scheduler (``serve/scheduler.py``): ``sched.slot``, the part of the
``sched.queue`` wait in which the request was ready but every worker slot
was taken, mean per due request, in ms; with ``sched.hold_ms`` it sums to
``sched.queue_ms``.  Nothing is read from a program that does not split the
queue span."""
from benchmarks.chip.layer_metrics._spans import per_request_ms, spans, total_s

NAME = "sched.slot_ms"


def read(ctx: dict):
    if next(spans(ctx, "sched.hold"), None) is None:
        return None
    return per_request_ms(ctx, total_s(ctx, "sched.slot"))
