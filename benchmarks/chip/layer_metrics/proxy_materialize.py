"""Engine and resident state (``core/engine.py``, ``core/resident.py``):
``proxy.materialize`` spans (rep scores on the host, the device call and
the copy back), total per due request, in ms."""
from benchmarks.chip.layer_metrics._spans import per_request_ms, total_s

NAME = "proxy.materialize_ms"


def read(ctx: dict):
    return per_request_ms(ctx, total_s(ctx, "proxy.materialize"))
