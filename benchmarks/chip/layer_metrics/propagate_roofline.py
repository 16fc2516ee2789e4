"""Kernels (``kernels/propagate``): the least time of the window's
propagation calls, from the work counts in ``kernels/propagate.py`` and the
chip's peaks, over the device time of the whole jitted calls (padding
copies included), in percent.  Each ``proxy.materialize`` span answered by
the device names one call's mode; nothing is read unless the trace holds
exactly as many calls."""
NAME = "propagate_roofline"


def read(ctx: dict):
    device = ctx["device"]
    if device is None:
        return None
    counts = ctx["kernels"]["propagate"]
    times = device.module_calls(counts.MODULE)
    calls = ctx["propagate_calls"]
    if not calls or len(times) != len(calls):
        return None
    peaks = ctx["peaks"]
    least = 0.0
    for call in calls:
        w = counts.work(**call)
        least += max(w["flops"] / peaks["flops_per_s"],
                     w["bytes"] / peaks["hbm_bytes_per_s"])
    share = 100.0 * least / sum(times)
    if share > 100.0:
        raise ValueError(f"propagate_roofline {share}% is above 100%: the "
                         "work is counted too high or the time too low")
    return share
