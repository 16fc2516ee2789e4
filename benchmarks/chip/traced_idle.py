"""One traced run of a cell, as ``run.py --trace 1`` makes it, that also
puts the device's idle time down to the program's host work.

    python3 benchmarks/chip/traced_idle.py --workload <cell> --seed <n> \
        --seconds <s>

Prints ``run.py``'s lines and result line, the result line's metrics
joined by ``device.idle_in_request`` (:mod:`idle`), and before it one
``[idle]`` line (idle seconds by the span the host was in) and one
``[traced]`` line with the traced window's ``p50_ms``, to set beside a
``--trace 0`` run of the same seed as the cost of tracing.  ``check.py``
gives its readers no trace file, so this reads the trace that its
reduction reads, before the run's directory is removed.
"""
from __future__ import annotations

import json
import sys

import run  # also puts the checkout on sys.path

from benchmarks.chip import check, devtrace, idle, manifest
from repro.launch.compile_cache import enable_compile_cache

METRIC = "device.idle_in_request"


def main(argv=None) -> int:
    args = run.parse_args(list(argv if argv is not None else sys.argv[1:])
                          + ["--trace", "1"])
    enable_compile_cache()
    layer_metrics = check.layer_metrics
    extra = {}

    def traced(loaded, readers, layer, trace_dir, *rest):
        metrics, out = layer_metrics(loaded, readers, layer, trace_dir, *rest)
        path = devtrace.find_xplane(trace_dir)
        share, acct = idle.idle_in_request(devtrace.Reduction(path), path)
        print(idle.report_line(acct, out["device"]["window_s"]), flush=True)
        run.report("traced", **run.end_to_end(["p50_ms"], 0.0, loaded))
        if share is not None:
            extra[METRIC] = {"value": share, "unit": "%"}
        return metrics, out

    check.layer_metrics = traced
    line = run.run_cell(args, manifest.benchmark(), run.HERE,
                        run.require_chips)
    line["metrics"].update(extra)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except run.Refused as e:
        print(f"traced_idle.py: {e}", file=sys.stderr)
        sys.exit(2)
