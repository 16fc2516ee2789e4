"""Device: the share of the traced window in which no operation ran on the
chip while some host thread was inside a ``session.plan`` or
``session.execute`` event, in percent: idle time during which the host was
working on a request (:mod:`benchmarks.chip.idle`).  ``device.idle`` less
this is idle time with no request in work.  Read from the trace's host
plane (``ctx["xplane"]``, the trace file), on the trace's own clock;
nothing is read from a trace without the program's events."""
from benchmarks.chip import idle

NAME = "device.idle_in_request"


def read(ctx: dict):
    device, path = ctx["device"], ctx.get("xplane")
    if device is None or path is None:
        return None
    return idle.idle_in_request(device, path)[0]
