"""Reduce a profiler trace (``.xplane.pb``) to device busy time, per-module
device time and idle gaps.

The traced window runs between two ``bench.sync`` host annotations.  Device
planes are those named ``/device:TPU:<n>``; on each, an operation's interval
comes from its ``XLA Ops`` line and a jitted call's from its ``XLA Modules``
line.  Busy time is the union of the operations' intervals inside the
window, averaged over the chips; an idle gap is a stretch of the window in
which no operation ran on that chip.

Host spans (``time.perf_counter`` seconds) are put on the trace's clock by
the first marker: the harness reads ``perf_counter`` inside it, so the
offset between the two clocks is known to within the annotation's few
microseconds.
"""
from __future__ import annotations

import collections
import glob
import pathlib
import re

SYNC = "bench.sync"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


class TraceError(RuntimeError):
    """The trace lacks what the reduction needs."""


def find_xplane(directory) -> str:
    paths = sorted(glob.glob(str(pathlib.Path(directory) / "**" /
                                 "*.xplane.pb"), recursive=True))
    if not paths:
        raise TraceError(f"no .xplane.pb under {directory}")
    return paths[-1]


class Reduction:
    """What one trace says, in seconds on the trace's clock."""

    def __init__(self, path: str):
        from jax.profiler import ProfileData
        profile = ProfileData.from_file(path)
        self.ops = []        # per chip: sorted [(start, end, name)]
        self.modules = []    # per chip: [(start, end, name)]
        marks = []
        for plane in profile.planes:
            if DEVICE_PLANE.match(plane.name):
                lines = {line.name: line for line in plane.lines}
                self.ops.append(sorted(_events(lines.get(OPS_LINE))))
                self.modules.append(sorted(_events(lines.get(MODULES_LINE))))
            elif plane.name.startswith("/host"):
                for line in plane.lines:
                    marks += [e for e in _events(line) if e[2] == SYNC]
        if not self.ops:
            raise TraceError("no /device:TPU plane in the trace")
        if len(marks) < 2:
            raise TraceError(f"{len(marks)} {SYNC} markers in the trace, "
                             "want 2")
        marks.sort()
        self.lo, self.hi = marks[0][0], marks[-1][1]

    @property
    def window_s(self) -> float:
        return self.hi - self.lo

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the chips."""
        total = 0.0
        for ops in self.ops:
            total += sum(b - a for a, b in _union(ops, self.lo, self.hi))
        return total / len(self.ops)

    def idle_gaps(self) -> list:
        """[(start, end)] of every stretch with no operation, on any chip."""
        gaps = []
        for ops in self.ops:
            t = self.lo
            for a, b in _union(ops, self.lo, self.hi):
                if a > t:
                    gaps.append((t, a))
                t = max(t, b)
            if t < self.hi:
                gaps.append((t, self.hi))
        return gaps

    def module_calls(self, fragment: str) -> list:
        """Device seconds of each jitted call whose module name holds
        ``fragment``, in the window, in time order."""
        out = []
        for modules in self.modules:
            out += [(a, b - a) for a, b, name in modules
                    if fragment in name and self.lo <= a and b <= self.hi]
        return [d for _, d in sorted(out)]

    def top_ops(self, n: int = 10) -> list:
        """[(operation name, device seconds)] of the n costliest names."""
        by_name = collections.Counter()
        for ops in self.ops:
            for a, b, name in ops:
                lo, hi = max(a, self.lo), min(b, self.hi)
                if hi > lo:
                    by_name[name] += hi - lo
        return [[name, s / len(self.ops)] for name, s in
                by_name.most_common(n)]


def _events(line):
    if line is None:
        return []
    return [(e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9, e.name)
            for e in line.events]


def _union(events, lo: float, hi: float) -> list:
    """Merged [(start, end)] of the events' intervals, clipped to [lo, hi]."""
    out = []
    for a, b, _ in events:
        a, b = max(a, lo), min(b, hi)
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def label_gaps(gaps: list, offset: float, spans: list, n: int = 10) -> list:
    """The n longest gaps as [name, seconds], each named by the innermost
    host span open at its middle (``spans``: (t0, t1, name) in
    ``perf_counter`` seconds; ``offset`` = perf_counter - trace clock), or
    ``no span open`` where none was."""
    out = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        mid = 0.5 * (a + b) + offset
        open_ = [s for s in spans if s[0] <= mid <= s[1]]
        name = max(open_)[2] if open_ else "no span open"
        out.append([name, b - a])
    return out
