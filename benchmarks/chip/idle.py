"""Put the device's idle time down to what the host was doing in it.

The program mirrors its nested ``repro.obs`` spans into the profiler's
trace as annotations that carry the request's ``trace_id``; that argument
tells them from JAX's own host events.  They sit on the trace's host plane,
one line per thread, on the same clock as the device's operations, so no
offset is involved.

:func:`account` takes the idle gaps and the program's events of each host
line and says how much of the idle time fell

* inside request work: some thread inside a ``session.plan`` or
  ``session.execute`` event (``device.idle_in_request``);
* under each span name: at each idle instant, the innermost event open on
  each line (the latest started) shares the instant equally with those of
  the other lines; an instant with no event open on any line is
  ``no request in work``.  The shares sum to the idle time.
"""
from __future__ import annotations

import collections
import json

#: the spans in which a thread works on a request
REQUEST_WORK = ("session.plan", "session.execute")
#: the name of idle time in which no program event is open on any line
NO_WORK = "no request in work"


def program_lines(path: str) -> list:
    """The program's events on the trace's host plane: per line that holds
    any, [(start, end, name)] in seconds on the trace's clock."""
    from jax.profiler import ProfileData
    lines = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            events = [(e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9,
                       e.name) for e in line.events
                      if any(key == "trace_id" for key, _ in e.stats)]
            if events:
                lines.append(events)
    return lines


def account(gaps: list, lines: list, work=REQUEST_WORK) -> dict:
    """``idle_s`` (the gaps' seconds), ``in_request_s`` (of which some line
    was inside a ``work`` event) and ``by_span`` (name -> seconds, summing
    to ``idle_s``) for ``gaps`` [(start, end)], which may overlap where they
    come from several chips, and ``lines`` as :func:`program_lines` gives
    them."""
    points = []
    for a, b in gaps:
        if b > a:
            points += [(a, 1, None, None), (b, -1, None, None)]
    for i, events in enumerate(lines):
        for ev in events:
            if ev[1] > ev[0]:
                points += [(ev[0], 1, i, ev), (ev[1], -1, i, ev)]
    points.sort(key=lambda p: p[0])
    open_ = [[] for _ in lines]
    depth = 0                   # gaps open at this instant
    idle = in_request = 0.0
    by_span = collections.defaultdict(float)
    prev = None
    for t, step, i, ev in points:
        if depth and t > prev:
            dt = (t - prev) * depth
            idle += dt
            inner = [max(evs, key=lambda e: (e[0], -e[1]))[2]
                     for evs in open_ if evs]
            for name in inner:
                by_span[name] += dt / len(inner)
            if not inner:
                by_span[NO_WORK] += dt
            if any(e[2] in work for evs in open_ for e in evs):
                in_request += dt
        if i is None:
            depth += step
        elif step > 0:
            open_[i].append(ev)
        else:
            open_[i].remove(ev)
        prev = t
    return {"idle_s": idle, "in_request_s": in_request,
            "by_span": dict(sorted(by_span.items(), key=lambda kv: -kv[1]))}


def idle_in_request(device, path: str):
    """``device.idle_in_request`` (%) and the account it comes from, or
    (None, account) where the trace holds no program event."""
    lines = program_lines(path)
    acct = account(device.idle_gaps(), lines)
    if not lines:
        return None, acct
    share = 100.0 * acct["in_request_s"] / (device.window_s * len(device.ops))
    return share, acct


def report_line(acct: dict, window_s: float) -> str:
    """The ``[idle]`` line: idle seconds by span name."""
    return "[idle] " + json.dumps({"idle_s": acct["idle_s"],
                                   "window_s": window_s,
                                   "in_request_s": acct["in_request_s"],
                                   "by_span": acct["by_span"]})
