"""Run one benchmark cell once on the chip this process finds.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Set-up (``setup_s``, from process start until the first request can be
answered): the corpus from the seed, the index, the serving stack, the
compile warm-up and the load generator's start.  Then the load generator,
a child process, sends the cell's traffic for ``--seconds`` and waits up to
``GRACE_S`` more for answers.  With ``--trace 0`` the result line carries
the cell's end-to-end metrics; with ``--trace 1`` the profiler records the
window and the result line carries its per-layer metrics, the device's busy
and window seconds, and a breakdown.  Either way, once the window has
closed, every answer is checked against the plain reference
(:mod:`reference`) and the numbers compared are printed beside their limits
as the last lines of standard error and as the ``checks`` key, last in the
result line.

Refuses (non-zero exit, no result line) any platform but a TPU and fewer
chips than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
for _p in (ROOT / "src", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from benchmarks.chip import check, latency, manifest, mount, schedule  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

#: seconds past the window the load generator waits for answers
GRACE_S = 60.0
#: flight-recorder capacity: every request of a window is kept
TRACE_BUFFER = 1 << 16


class Refused(SystemExit):
    """The run cannot be made on this machine."""


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)
    if args.seed < 0 or args.seconds <= 0:
        ap.error("--seed must be >= 0 and --seconds > 0")
    return args


def require_chips(n: int):
    """The TPU devices JAX sees; refuses anything else."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise Refused(f"needs a TPU; JAX reports {devices[0].platform!r} "
                      f"({len(devices)} device(s)); no fallback")
    if len(devices) < n:
        raise Refused(f"the cell needs {n} chips, JAX sees {len(devices)}")
    return devices[:n]


class CompileClock:
    """Backend compiles and their seconds, from ``jax.monitoring``."""

    def __init__(self):
        import jax.monitoring as mon
        self.seconds = 0.0
        self.compiles = 0
        self.cache = {"hits": 0, "misses": 0}
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.compiles += 1

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache["misses"] += 1

    def read(self) -> dict:
        return {"backend_compile_s": self.seconds, "compiles": self.compiles,
                "cache": dict(self.cache)}


def report(phase: str, **facts) -> None:
    print(f"[{phase}] " + json.dumps(facts, default=str), flush=True)


def start_loadgen(plan: dict, workdir: pathlib.Path):
    """Start the child; returns it once it has read its plan."""
    plan_path, out_path = workdir / "plan.json", workdir / "loadgen.json"
    plan_path.write_text(json.dumps(plan))
    child = subprocess.Popen(
        [sys.executable, str(HERE / "loadgen.py"), str(plan_path),
         str(out_path)], stdin=subprocess.PIPE, stdout=subprocess.PIPE,
        text=True)
    line = child.stdout.readline().strip()
    if line != "ready":
        child.kill()
        child.wait()
        raise RuntimeError(f"load generator did not start: {line!r}")
    return child, out_path


def run_window(child, seconds: float, trace_dir):
    """Start the window, wait for the child; returns the window's start and
    end and, when tracing, the first ``bench.sync`` marker's time, all in
    ``perf_counter`` seconds."""
    import jax
    sync = None
    if trace_dir is not None:
        # no Python function tracing and no HLO protos: the window's host
        # spans come from the program's own tracer
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.enable_hlo_proto = False
        jax.profiler.start_trace(str(trace_dir), profiler_options=options)
        with jax.profiler.TraceAnnotation("bench.sync"):
            sync = time.perf_counter()
    t0 = time.perf_counter()
    child.stdin.write("go\n")
    child.stdin.flush()
    try:
        out, _ = child.communicate(timeout=seconds + GRACE_S + 30)
    except subprocess.TimeoutExpired:
        child.kill()
        child.communicate()
        raise RuntimeError("load generator overran its wait")
    t1 = time.perf_counter()
    if trace_dir is not None:
        with jax.profiler.TraceAnnotation("bench.sync"):
            pass
        jax.profiler.stop_trace()
    for line in out.splitlines():
        print(line, flush=True)
    if child.returncode != 0:
        raise RuntimeError(f"load generator exited {child.returncode}")
    return t0, t1, sync


def end_to_end(names: list, setup_s: float, loaded) -> dict:
    lat = latency.latencies(loaded.due, loaded.rows, loaded.wait_end)
    values = {
        "setup_s": (setup_s, "s"),
        "p50_ms": (1e3 * latency.percentile(lat, 50), "ms"),
        # counted at the oracle by the benchmark, not read from the program
        "labels_per_query": (loaded.oracle_labels / len(loaded.due),
                             "labels/query"),
    }
    return {n: {"value": values[n][0], "unit": values[n][1]} for n in names}


def main(argv=None) -> int:
    args = parse_args(argv)
    # $JAX_COMPILATION_CACHE_DIR, else the fixed <checkout>/.jax_cache
    enable_compile_cache()
    line = run_cell(args, manifest.benchmark(), HERE, require_chips)
    print(json.dumps(line), flush=True)
    return 0


def run_cell(args, bench: dict, base: pathlib.Path, require) -> dict:
    """One run of the cell ``args.workload``; returns the result line.
    ``base`` holds the benchmark's files; ``require(n)`` returns the n
    devices to run on, or refuses."""
    cell = manifest.cell(bench, args.workload)
    cfg = manifest.config(cell["config"], base)
    mix = manifest.traffic(cell["traffic"], base)
    truth = manifest.truth_module(cell["config"], base)
    limits = manifest.limits(base)
    e2e = [m for m in bench["end_to_end"]
           if args.workload in m.get("workloads", [args.workload])]
    layer = [m for m in bench["per_layer"]
             if args.workload in m.get("workloads", [args.workload])]
    readers = manifest.layer_metrics([m["name"] for m in layer], base)

    devices = require(cell["chips"])
    device = devices[0]
    peaks = manifest.peaks(device.device_kind, base)
    compile_clock = CompileClock()
    clock = mount.Clock()
    clock.phases["start"] = time.perf_counter() - T_START
    report("device", platform=device.platform, kind=device.device_kind,
           count=len(devices), cell=args.workload, seed=args.seed)

    workdir = pathlib.Path(tempfile.mkdtemp(prefix="chip-bench-"))
    server = child = None
    try:
        corpus, index = mount.build(cfg, args.seed, clock)
        oracle = mount.OracleCounter(corpus)
        server, engine = mount.serve(cfg, corpus, index,
                                     str(workdir / "labels"), clock,
                                     TRACE_BUFFER)
        mount.warm_up(engine, mix, clock)
        plan = {"requests": schedule.open_loop_plan(mix, args.seed,
                                                    args.seconds),
                "url": server.url, "seconds": args.seconds,
                "grace_s": GRACE_S}
        child, out_path = start_loadgen(plan, workdir)
        clock.lap("loadgen")
        setup_s = time.perf_counter() - T_START
        compiles0 = compile_clock.read()
        report("setup", setup_s=setup_s, phases=clock.phases,
               compile=compiles0)

        trace_dir = workdir / "trace" if args.trace else None
        t0, t1, sync = run_window(child, args.seconds, trace_dir)
        compiles1 = compile_clock.read()
        in_window = compiles1["compiles"] - compiles0["compiles"]
        stats = device.memory_stats() or {}
        peak = stats.get("peak_bytes_in_use")
        result = json.loads(out_path.read_text())
        loaded = check.collect(server, engine, corpus, result, plan, args,
                               mix, cfg, truth, t0, oracle.labels)
        server.shutdown()
        server = None
        engine.resident.invalidate()
        report("window", seconds=t1 - t0, compiles_in_window=in_window,
               compile=compiles1, peak_bytes_in_use=peak,
               requests=len(loaded.rows), unfinished=result["unfinished"])

        t_check = time.perf_counter()
        checks = check.compare(loaded, limits)
        report("check", seconds=time.perf_counter() - t_check)
        metrics, extra = {}, {}
        if args.trace:
            metrics, extra = check.layer_metrics(
                loaded, readers, layer, trace_dir, sync, in_window, peaks,
                base)
        else:
            metrics = end_to_end([m["name"] for m in e2e], setup_s, loaded)
    finally:
        if child is not None and child.poll() is None:
            child.kill()
            child.wait()
        if server is not None:
            server.shutdown()
        shutil.rmtree(workdir, ignore_errors=True)

    correct = all(c["value"] <= c["limit"] for c in checks.values())
    for name, c in checks.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    line = {"correct": correct, "attempted": len(loaded.due),
            "failed": loaded.n_unanswered, "metrics": metrics,
            "device": {"platform": device.platform,
                       "kind": device.device_kind, "count": len(devices),
                       "memory_peak_bytes": peak, **extra.get("device", {})}}
    if "breakdown" in extra:
        line["breakdown"] = extra["breakdown"]
    line["checks"] = checks
    return line


if __name__ == "__main__":
    try:
        sys.exit(main())
    except Refused as e:
        print(f"run.py: {e}", file=sys.stderr)
        sys.exit(2)
