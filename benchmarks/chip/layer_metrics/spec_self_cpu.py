"""Executors (``core/queries/``): the CPU time of ``spec.execute`` less
that of its children that record one (``cpu_s``), summed and divided by the
due requests, in ms.  Beside ``spec.self_ms`` it splits the executors' own
time into computing and waiting.  Nothing is read from a program whose
spans carry no ``cpu_s``."""
from benchmarks.chip.layer_metrics._spans import per_request_ms, spans

NAME = "spec.self_cpu_ms"


def read(ctx: dict):
    total, found = 0.0, False
    for trace, s in spans(ctx, "spec.execute"):
        if "cpu_s" not in s["attrs"]:
            continue
        found = True
        total += s["attrs"]["cpu_s"] - sum(
            c["attrs"].get("cpu_s", 0.0) for c in trace["spans"]
            if c["parent_id"] == s["span_id"] and c is not s)
    return per_request_ms(ctx, total) if found else None
