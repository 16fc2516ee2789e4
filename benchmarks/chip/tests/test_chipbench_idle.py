"""Device idle time put down to the program's host work: the interval
arithmetic on synthetic intervals, the program's events found on a trace's
host plane, and the reader with nothing to read."""
import glob
import json

import pytest

from _bench import HERE

from benchmarks.chip import devtrace, idle, manifest  # noqa: E402

FIXTURE = HERE / "tests" / "fixtures"


def test_two_threads_share_an_idle_instant_and_the_rest_is_no_work():
    # idle from 0 to 10; thread 0 plans from 1 to 4 with a flush from 2 to
    # 3 inside; thread 1 executes from 3 to 6
    lines = [[(1.0, 4.0, "session.plan"), (2.0, 3.0, "broker.flush")],
             [(3.0, 6.0, "session.execute")]]
    acct = idle.account([(0.0, 10.0)], lines)
    assert acct["idle_s"] == pytest.approx(10.0)
    assert acct["in_request_s"] == pytest.approx(5.0)
    assert acct["by_span"] == pytest.approx({
        idle.NO_WORK: 1.0 + 4.0,           # before 1 and after 6
        "session.plan": 1.0 + 0.5,         # alone, then shared with 3-4
        "broker.flush": 1.0,               # the innermost on its line
        "session.execute": 0.5 + 2.0})
    assert sum(acct["by_span"].values()) == pytest.approx(acct["idle_s"])


def test_only_idle_time_counts_and_gaps_of_several_chips_add():
    lines = [[(0.0, 10.0, "session.execute"), (4.0, 5.0, "spec.execute")]]
    # chips idle over [1, 3] and [2, 5]: 2 + 3 seconds of chip idle time
    acct = idle.account([(1.0, 3.0), (2.0, 5.0)], lines)
    assert acct["idle_s"] == pytest.approx(5.0)
    assert acct["in_request_s"] == pytest.approx(5.0)
    assert acct["by_span"] == pytest.approx({"session.execute": 4.0,
                                             "spec.execute": 1.0})


def test_idle_time_outside_request_work():
    lines = [[(0.0, 1.0, "broker.flush")], []]
    acct = idle.account([(0.0, 2.0)], lines)
    # a program event that is not planning or executing a session
    assert acct["in_request_s"] == 0.0
    assert acct["by_span"] == pytest.approx({"broker.flush": 1.0,
                                             idle.NO_WORK: 1.0})
    assert idle.account([], lines) == {"idle_s": 0.0, "in_request_s": 0.0,
                                       "by_span": {}}


def test_the_report_line_is_one_json_object():
    acct = idle.account([(0.0, 2.0)], [[(0.5, 1.0, "session.plan")]])
    line = idle.report_line(acct, 4.0)
    assert line.startswith("[idle] ")
    doc = json.loads(line[len("[idle] "):])
    assert doc["window_s"] == 4.0 and doc["idle_s"] == 2.0
    assert sum(doc["by_span"].values()) == pytest.approx(doc["idle_s"])


def test_program_events_are_told_from_jax_events_by_their_trace_id(
        tmp_path):
    import threading

    import jax
    from jax.profiler import TraceAnnotation
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        def work():
            with TraceAnnotation("session.plan", trace_id="a"):
                with TraceAnnotation("spec.execute", trace_id="a"):
                    pass
        worker = threading.Thread(target=work)
        worker.start()
        worker.join(timeout=30)
        with TraceAnnotation("bench.sync"):
            pass
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    lines = idle.program_lines(path)
    assert len(lines) == 1
    assert sorted(name for _, _, name in lines[0]) == ["session.plan",
                                                      "spec.execute"]
    assert all(a <= b for a, b, _ in lines[0])


def test_the_reader_reads_nothing_without_the_programs_events():
    reader = manifest.layer_metrics(["device.idle_in_request"])[
        "device.idle_in_request"]
    red = devtrace.Reduction(str(FIXTURE / "small.xplane.pb"))
    assert reader.read({"device": red}) is None
    assert reader.read({"device": None, "xplane": "x"}) is None
    # the fixture was recorded without the program's spans
    path = str(FIXTURE / "small.xplane.pb")
    assert reader.read({"device": red, "xplane": path}) is None
    share, acct = idle.idle_in_request(red, path)
    idle_s = sum(b - a for a, b in red.idle_gaps())
    assert acct["idle_s"] == pytest.approx(idle_s)
    assert acct["by_span"] == pytest.approx({idle.NO_WORK: idle_s})


def test_the_fixture_still_reads_as_before():
    """``device.idle``, ``propagate_roofline`` and the gaps' labels on the
    committed trace, as they read before the program's spans reached it."""
    meta = json.loads((FIXTURE / "small.json").read_text())
    red = devtrace.Reduction(str(FIXTURE / "small.xplane.pb"))
    readers = manifest.layer_metrics(["device.idle", "propagate_roofline"])
    p = meta["propagate"]
    ctx = {"device": red, "peaks": manifest.peaks(meta["device_kind"]),
           "kernels": {"propagate": manifest.kernel_counts("propagate")},
           "propagate_calls": [{"records": p["records"], "k": p["k"],
                                "reps": p["reps"], "mode": m}
                               for m in p["modes"]] * p["calls_per_mode"]}
    assert readers["device.idle"].read(ctx) == pytest.approx(
        71.9645029219089, rel=1e-12)
    assert readers["propagate_roofline"].read(ctx) == pytest.approx(
        0.4567433442899004, rel=1e-12)
    named = devtrace.label_gaps(red.idle_gaps(), 100.0, [], n=3)
    assert [name for name, _ in named] == ["no span open"] * 3
    assert [s for _, s in named] == pytest.approx(
        [0.0018550079999999983, 0.0011839319999999987,
         0.0011596029999999952], rel=1e-12)
