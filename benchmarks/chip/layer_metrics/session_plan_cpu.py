"""Sessions (``core/session.py``): the CPU time of the ``session.plan``
spans' threads (``cpu_s``), total per due request, in ms.  Beside
``session.plan_ms`` it splits planning into computing and waiting (the GIL,
locks).  Nothing is read from a program whose spans carry no ``cpu_s``."""
from benchmarks.chip.layer_metrics._spans import per_request_ms, spans

NAME = "session.plan_cpu_ms"


def read(ctx: dict):
    cpu = [s["attrs"]["cpu_s"] for _, s in spans(ctx, "session.plan")
           if "cpu_s" in s["attrs"]]
    return per_request_ms(ctx, sum(cpu)) if cpu else None
