"""Scheduler (``serve/scheduler.py``): ``sched.queue`` span, admission to
grant, mean per due request, in ms."""
from benchmarks.chip.layer_metrics._spans import per_request_ms, total_s

NAME = "sched.queue_ms"


def read(ctx: dict):
    return per_request_ms(ctx, total_s(ctx, "sched.queue"))
