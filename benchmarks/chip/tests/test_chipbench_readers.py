"""The per-layer readers on a request's spans as the program records them,
and as a program records them that has no hold and slot split, no CPU time
on its spans and no front-end spans: the readers of those return nothing
there, and every other reader reads the same from both."""
import pytest

from _bench import HERE  # noqa: F401  (puts the checkout on sys.path)

from benchmarks.chip import manifest  # noqa: E402

OLD = ["http.self_ms", "sched.queue_ms", "session.plan_ms", "spec.self_ms",
       "broker.flush_ms", "proxy.materialize_ms", "compile.in_window"]
NEW = ["sched.hold_ms", "sched.slot_ms", "session.plan_cpu_ms",
       "spec.self_cpu_ms", "http.handler_ms"]
READERS = manifest.layer_metrics(OLD + NEW)

#: (span id, parent id, name, t0, t1, cpu_s or None); one request that
#: waited 50 ms in the admission window and 10 ms for a slot
SPANS = [
    (0, None, "request", 10.0, 10.5, None),
    (1, 0, "sched.queue", 10.0, 10.06, None),
    (2, 1, "sched.hold", 10.0, 10.05, None),
    (3, 1, "sched.slot", 10.05, 10.06, None),
    (4, 0, "session.plan", 10.06, 10.16, 0.04),
    (5, 0, "session.execute", 10.16, 10.46, 0.2),
    (6, 5, "spec.execute", 10.17, 10.45, 0.15),
    (7, 6, "broker.flush", 10.2, 10.25, 0.01),
    (8, 6, "proxy.materialize", 10.3, 10.32, 0.02),
    (9, 7, "oracle.subbatch", 10.2, 10.24, None),
    (10, 0, "http.read", 9.998, 10.0, None),
    (11, 0, "http.write", 10.5, 10.503, None),
]
#: what a program without this tracing records of the same request
NOT_RECORDED = {"sched.hold", "sched.slot", "http.read", "http.write"}


def ctx_of(split: bool) -> dict:
    spans = []
    for sid, parent, name, t0, t1, cpu in SPANS:
        if not split and name in NOT_RECORDED:
            continue
        attrs = {"cpu_s": cpu} if split and cpu is not None else {}
        spans.append({"name": name, "span_id": sid, "parent_id": parent,
                      "t0": t0, "t1": t1, "thread": 1, "attrs": attrs})
    trace = {"trace_id": "r0", "name": "request", "duration_s": 0.5,
             "spans": spans}
    requests = [{"trace_id": "r0", "fired": 9.99, "done": 10.51,
                 "answered": True},
                {"trace_id": "r1", "fired": 11.0, "done": 12.0,
                 "answered": False}]
    return {"traces": [trace], "requests": requests, "n_requests": 2,
            "compiles": 3}


#: ms per due request (two are due), as the spans above give them
EXPECTED_OLD = {"http.self_ms": 20.0, "sched.queue_ms": 30.0,
                "session.plan_ms": 50.0, "spec.self_ms": 105.0,
                "broker.flush_ms": 25.0, "proxy.materialize_ms": 10.0,
                "compile.in_window": 3}
EXPECTED_NEW = {"sched.hold_ms": 25.0, "sched.slot_ms": 5.0,
                "session.plan_cpu_ms": 20.0, "spec.self_cpu_ms": 60.0,
                "http.handler_ms": 5.0}


@pytest.mark.parametrize("split", [True, False])
@pytest.mark.parametrize("name", OLD)
def test_the_older_readers_read_the_same_with_the_new_spans(split, name):
    assert READERS[name].read(ctx_of(split)) == pytest.approx(
        EXPECTED_OLD[name], rel=1e-9)


@pytest.mark.parametrize("name", NEW)
def test_the_new_readers(name):
    assert READERS[name].read(ctx_of(True)) == pytest.approx(
        EXPECTED_NEW[name], rel=1e-9)


@pytest.mark.parametrize("name", NEW)
def test_the_new_readers_read_nothing_from_an_older_program(name):
    assert READERS[name].read(ctx_of(False)) is None


def test_hold_and_slot_sum_to_the_queue_and_cpu_stays_under_wall():
    ctx = ctx_of(True)
    value = {n: READERS[n].read(ctx) for n in OLD + NEW}
    assert value["sched.hold_ms"] + value["sched.slot_ms"] == pytest.approx(
        value["sched.queue_ms"])
    assert value["session.plan_cpu_ms"] <= value["session.plan_ms"]
    assert value["spec.self_cpu_ms"] <= value["spec.self_ms"]
    assert value["http.handler_ms"] <= value["http.self_ms"]


def test_no_slot_wait_reads_zero_not_nothing():
    ctx = ctx_of(True)
    for trace in ctx["traces"]:
        trace["spans"] = [s for s in trace["spans"]
                          if s["name"] != "sched.slot"]
    assert READERS["sched.slot_ms"].read(ctx) == 0.0
