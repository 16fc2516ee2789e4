"""Observability subsystem tests: metrics registry round-trip (render ->
parse), tracer span nesting + the bounded flight recorder, Chrome trace
export, disabled-path no-ops, CPU time on nested spans, their export to the
JAX profiler, backend compiles on the trace, loadgen error-kind
classification and trace stamping, the scheduler wait split into hold and
slot, the front end's own spans, the sample-order memo's counters, and the
end-to-end attribution guarantee over a live server — every fresh oracle
label of a traced request lands in exactly one span chain, and the
``/metrics`` exposition agrees with the request's own accounting."""
import glob
import threading
import time

import pytest

from repro.core.engine import QueryEngine, QuerySpec
from repro.core.index import TastiIndex
from repro.core.schema import make_workload
from repro.core.session import QuerySession
from repro.loadgen import ArrivalProcess, OpenLoopGenerator, SpecClass, SpecMix
from repro.loadgen.generator import _accepts_kwarg, _classify_error
from repro.obs import (
    NULL_SPAN,
    NULL_TRACE,
    MetricsRegistry,
    Observability,
    Sample,
    activate,
    active_trace,
    chrome_trace,
    parse_prometheus_text,
    series_key,
    span,
    start_span,
)
from repro.obs.trace import FlightRecorder, Trace, Tracer
from repro.serve import (
    QueryClient,
    QueryServer,
    WorkloadRegistry,
    WorkloadSpec,
)
from repro.serve.client import ServerError

pytestmark = pytest.mark.tier1


@pytest.fixture(scope="module")
def wl():
    return make_workload("night-street", n_frames=1200)


@pytest.fixture(scope="module")
def index(wl):
    return TastiIndex.build(wl.features, 120, wl.target_dnn_batch, k=4,
                            random_fraction=0.0, seed=0)


SPEC_DICTS = [
    {"kind": "aggregation", "score": "score_count", "err": 0.2, "seed": 0},
    {"kind": "selection", "score": "score_has_object", "budget": 80,
     "seed": 0},
    {"kind": "limit", "score": "score_has_object", "k_results": 3},
]


# -- metrics registry ------------------------------------------------------
def test_metrics_render_parse_roundtrip():
    reg = MetricsRegistry()
    reg.counter("oracle_fresh_total", help="fresh labels",
                workload="video").inc(7)
    reg.counter("oracle_fresh_total", workload="text").inc(3)
    reg.gauge("queue_depth", workload="video").set(5)
    h = reg.histogram("flush_seconds", buckets=(0.1, 1.0), workload="video")
    for v in (0.05, 0.5, 2.0):
        h.observe(v)
    text = reg.render()
    parsed = parse_prometheus_text(text)
    assert parsed[series_key("oracle_fresh_total", workload="video")] == 7
    assert parsed[series_key("oracle_fresh_total", workload="text")] == 3
    assert parsed[series_key("queue_depth", workload="video")] == 5
    # histogram: cumulative buckets, +Inf == count, sum preserved
    assert parsed[series_key("flush_seconds_bucket", workload="video",
                             le="0.1")] == 1
    assert parsed[series_key("flush_seconds_bucket", workload="video",
                             le="1")] == 2
    assert parsed[series_key("flush_seconds_bucket", workload="video",
                             le="+Inf")] == 3
    assert parsed[series_key("flush_seconds_count", workload="video")] == 3
    assert parsed[series_key("flush_seconds_sum",
                             workload="video")] == pytest.approx(2.55)
    # HELP/TYPE lines are present for the exposition to be well-formed
    assert "# TYPE oracle_fresh_total counter" in text
    assert "# TYPE flush_seconds histogram" in text


def test_metric_name_cannot_change_type():
    reg = MetricsRegistry()
    reg.counter("requests_total")
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("requests_total")


def test_collectors_emit_samples_and_isolate_failures():
    reg = MetricsRegistry()
    reg.add_collector(lambda: [
        Sample("derived_total", 42, labels={"workload": "v"}, help="derived"),
        Sample("derived_depth", 3, mtype="gauge"),
    ])
    reg.add_collector(lambda: (_ for _ in ()).throw(RuntimeError("boom")))
    parsed = parse_prometheus_text(reg.render())
    assert parsed[series_key("derived_total", workload="v")] == 42
    assert parsed["derived_depth"] == 3
    # one broken snapshot is counted, not fatal to the whole exposition
    assert parsed["metrics_collector_errors_total"] == 1


# -- tracing ---------------------------------------------------------------
def test_span_nesting_follows_the_activation_stack():
    trace = Trace("request", trace_id="a" * 16)
    with activate(trace):
        assert active_trace() is trace
        with span("outer") as outer:
            with span("inner", n=2) as inner:
                assert inner.parent_id == outer.span_id
            timed = trace.find_spans  # keep a handle before deactivation
            loose = start_span("loose")   # manual-end span, same parent
            assert loose.parent_id == outer.span_id
            loose.end()
        after = span("sibling")
        assert after.parent_id == 0       # back under the root
        after.end()
    assert active_trace() is None
    trace.finish()
    assert inner.attrs["n"] == 2
    assert all(s.t1 is not None for s in timed("inner"))


def test_trace_finish_clamps_leaked_spans_and_is_idempotent():
    trace = Trace("request")
    with activate(trace):
        leaked = start_span("never.ended")
    trace.finish()
    assert leaked.t1 is not None
    t1 = trace.t1
    trace.finish()
    assert trace.t1 == t1                 # second finish is a no-op


def test_flight_recorder_is_a_bounded_ring():
    rec = FlightRecorder(capacity=4)
    tracer = Tracer(rec)
    ids = []
    for _ in range(10):
        t = tracer.start("request")
        ids.append(t.trace_id)
        tracer.finish(t)
    assert len(rec) == 4
    assert rec.recorded == 10
    kept = [t.trace_id for t in rec.traces()]
    assert kept == ids[-4:]               # oldest dropped, order preserved
    assert rec.find(ids[0]) is None
    assert rec.find(ids[-1]).trace_id == ids[-1]
    assert [s["trace_id"] for s in rec.summaries()] == kept


def test_chrome_trace_export_shape():
    trace = Trace("request", trace_id="b" * 16, workload="video")
    with activate(trace):
        with span("session.execute", fresh=5):
            time.sleep(0.001)
    trace.finish()
    doc = chrome_trace(trace)
    assert doc["otherData"]["trace_id"] == "b" * 16
    assert doc["otherData"]["attr_workload"] == "video"
    events = doc["traceEvents"]
    assert len(events) == 2               # root + session.execute
    for ev in events:
        assert ev["ph"] == "X"
        assert ev["ts"] >= 0 and ev["dur"] >= 0
        assert "span_id" in ev["args"] and "parent_id" in ev["args"]
    exe = next(ev for ev in events if ev["name"] == "session.execute")
    assert exe["args"]["fresh"] == 5


def test_disabled_observability_is_all_noops():
    obs = Observability(enabled=False)
    t = obs.tracer.start("request", trace_id="c" * 16)
    assert t is NULL_TRACE and t.trace_id == ""
    assert t.new_span("x") is NULL_SPAN
    obs.tracer.finish(t)                  # records nothing
    assert obs.recorder is None
    obs.counter("n_total").inc()
    obs.histogram("h").observe(1.0)
    assert obs.metrics.render() == "# observability disabled\n"
    # activating the null trace leaves the thread trace-free
    with activate(t):
        assert active_trace() is None
        assert span("anything") is NULL_SPAN


def test_scoped_labels_fold_into_instruments():
    obs = Observability()
    scope = obs.scoped(workload="video")
    scope.counter("oracle_fresh_total").inc(4)
    scope.scoped(replica=1).counter("subbatches_total").inc()
    parsed = parse_prometheus_text(obs.metrics.render())
    assert parsed[series_key("oracle_fresh_total", workload="video")] == 4
    assert parsed[series_key("subbatches_total", workload="video",
                             replica=1)] == 1


def test_nested_spans_record_the_threads_cpu_time():
    trace = Trace("request")
    with activate(trace):
        with span("busy") as busy:
            t_end = time.perf_counter() + 0.05
            while time.perf_counter() < t_end:
                pass
        with span("asleep") as asleep:
            time.sleep(0.05)
    trace.finish()
    # a busy loop holds the CPU for its whole wall time; a sleep for none
    assert abs(busy.attrs["cpu_s"] - busy.duration_s) < 0.01
    assert asleep.attrs["cpu_s"] < 0.005 < 0.045 < asleep.duration_s
    # spans not entered as context managers stay wall-time only
    loose = trace.new_span("loose")
    loose.end()
    assert "cpu_s" not in loose.attrs


class _RecordingAnnotation:
    """Stands in for ``jax.profiler.TraceAnnotation``: counts the checks
    and records every annotation opened."""

    enabled = False
    checks = 0
    opened: list = []

    @classmethod
    def is_enabled(cls):
        cls.checks += 1
        return cls.enabled

    def __init__(self, name, **kwargs):
        self.opened.append((name, kwargs))

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        pass


def test_with_the_profiler_off_a_span_only_checks_it(monkeypatch):
    fake = type("Fake", (_RecordingAnnotation,), {"opened": []})
    monkeypatch.setattr("repro.obs.trace.TraceAnnotation", fake)
    trace = Trace("request", trace_id="d" * 16)
    with activate(trace):
        with span("session.plan"):
            with span("spec.execute"):
                pass
    assert fake.checks == 2 and fake.opened == []
    fake.enabled = True
    with activate(trace):
        with span("session.execute"):
            pass
    assert fake.opened == [("session.execute", {"trace_id": "d" * 16})]
    # disabled observability: no span object, no check at all
    with activate(NULL_TRACE):
        with span("session.plan"):
            pass
    assert fake.checks == 3


def test_a_served_requests_spans_land_on_the_profilers_host_plane(
        wl, index, tmp_path):
    import jax
    from jax.profiler import ProfileData
    server = QueryServer(QueryEngine(index, wl), port=0,
                         admission_window=0.0, max_workers=2).start()
    try:
        specs = [QuerySpec.from_dict(dict(d)) for d in SPEC_DICTS]
        quiet = server.submit(specs, trace_id="e" * 16)
        assert quiet.done.wait(60) and quiet.error is None
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            sub = server.submit(specs, trace_id="f" * 16)
            assert sub.done.wait(60) and sub.error is None
        finally:
            jax.profiler.stop_trace()
    finally:
        server.shutdown()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    found = {}
    for plane in ProfileData.from_file(path).planes:
        for i, line in enumerate(plane.lines):
            for e in line.events:
                tid = dict(e.stats).get("trace_id")
                if tid is not None:
                    found.setdefault(tid, set()).add(
                        (plane.name, i, e.name))
    # only the request traced while the profiler ran, on the host plane,
    # each nested span once per time it ran
    assert set(found) == {"f" * 16}
    names = {name for _, _, name in found["f" * 16]}
    assert {"session.plan", "session.execute", "spec.execute"} <= names
    assert all(plane.startswith("/host") for plane, _, _ in found["f" * 16])
    # timed and manually ended spans stay off the profiler
    assert not names & {"request", "sched.queue", "sched.hold",
                        "oracle.subbatch"}


def test_compiles_land_on_the_active_trace_and_in_metrics():
    import jax
    import jax.numpy as jnp
    obs = Observability()
    key = series_key("jax_backend_compiles_total", fun="jit(tripled)")
    x = jnp.ones(7)

    def tripled(v):
        return v * 3.0 + 1.0

    before = parse_prometheus_text(obs.metrics.render()).get(key, 0.0)
    trace = Trace("request")
    t0 = time.perf_counter()
    with activate(trace):
        with span("proxy.materialize") as parent:
            jax.jit(tripled)(x).block_until_ready()
    trace.finish()
    (compile_span,) = trace.find_spans("jax.compile")
    assert compile_span.attrs["fun"] == "jit(tripled)"
    assert compile_span.parent_id == parent.span_id
    # put on the spans' clock: inside the span that compiled
    assert t0 - 0.01 < compile_span.t0 <= compile_span.t1 \
        < trace.t1 + 0.01
    after = parse_prometheus_text(obs.metrics.render())
    assert after[key] == before + 1
    # a compile outside any trace is counted, and lands on no trace
    jax.jit(tripled)(jnp.ones(9)).block_until_ready()
    assert parse_prometheus_text(obs.metrics.render())[key] == before + 2


# -- loadgen error kinds + trace stamping ----------------------------------
def test_error_kind_classification():
    assert _classify_error(ServerError("bad spec", status=400)) == "http_4xx"
    assert _classify_error(ServerError("shedding", status=503)) == "http_5xx"
    assert _classify_error(ConnectionRefusedError("refused")) == "connect"
    assert _classify_error(TimeoutError("slow")) == "connect"
    assert _classify_error(RuntimeError("?")) == "other"

    # an HTTP-status-carrying error subclassing OSError is a server answer
    class StatusOSError(OSError):
        status = 502
    assert _classify_error(StatusOSError()) == "http_5xx"


def test_loadgen_counts_error_kinds_and_stamps_trace_ids():
    lock = threading.Lock()
    seen = []

    def post(specs, budget=None, priority=None, deadline_ms=None,
             name=None, trace_id=None):
        with lock:
            seen.append(trace_id)
            i = len(seen)
        if i % 3 == 1:
            raise ServerError("overloaded", status=503)
        if i % 3 == 2:
            raise ConnectionRefusedError("refused")
        return {"ok": True}

    assert _accepts_kwarg(post, "trace_id")
    mix = SpecMix([SpecClass("c", SPEC_DICTS[:1])], seed=0)
    gen = OpenLoopGenerator(post, mix, ArrivalProcess(rate=150.0, seed=0),
                            duration_s=0.3)
    report = gen.run()
    assert report.offered == len(seen) > 5
    by_kind = {k: sum(o.error_kind == k for o in report.outcomes)
               for k in ("connect", "http_4xx", "http_5xx", "other")}
    assert report.http_errors == by_kind["http_4xx"] + by_kind["http_5xx"] > 0
    assert report.connect_errors == by_kind["connect"] > 0
    assert report.errors == report.offered - report.completed
    assert (report.errors
            == report.connect_errors + report.http_errors + by_kind["other"])
    row = report.classes["c"]
    assert row["connect_errors"] == report.connect_errors
    assert row["http_errors"] == report.http_errors
    # every fired request got a fresh 16-hex trace id
    tids = [o.trace_id for o in report.outcomes]
    assert all(t and len(t) == 16 for t in tids)
    assert len(set(tids)) == len(tids)
    assert sorted(t for t in seen if t) == sorted(tids)


def test_loadgen_skips_trace_ids_for_legacy_post_callables():
    def post(specs, budget=None, priority=None, deadline_ms=None, name=None):
        return {"ok": True}

    assert not _accepts_kwarg(post, "trace_id")
    mix = SpecMix([SpecClass("c", SPEC_DICTS[:1])], seed=0)
    report = OpenLoopGenerator(post, mix, ArrivalProcess(rate=100.0, seed=1),
                               duration_s=0.2).run()
    assert report.completed == report.offered > 0
    assert all(o.trace_id is None for o in report.outcomes)


# -- broker observe(): totals + accounts in one lock pass ------------------
def test_broker_observe_is_consistent_under_concurrent_flush(wl, index):
    engine = QueryEngine(index, wl)
    stop = threading.Event()
    snaps = []

    def scrape():
        while not stop.is_set():
            snaps.append(engine.broker.observe(recent_accounts=0))

    scraper = threading.Thread(target=scrape, daemon=True)
    scraper.start()
    threads = [threading.Thread(
        target=lambda s: QuerySession(
            engine, [QuerySpec.from_dict(dict(s))]).execute(),
        args=(s,), daemon=True) for s in SPEC_DICTS for _ in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    stop.set()
    scraper.join(timeout=10)
    snaps.append(engine.broker.observe(recent_accounts=0))
    assert len(snaps) >= 2
    prev_fresh = -1
    for snap in snaps:
        stats, accounts = snap["stats"], snap["accounts"]
        # an account increment is only ever visible together with the total
        # increment it belongs to (both land in one lock hold)
        assert sum(a["fresh"] for a in accounts) <= stats["fresh"]
        assert sum(a["cached"] for a in accounts) <= stats["cached"]
        assert stats["fresh"] >= prev_fresh
        prev_fresh = stats["fresh"]
    # quiescent: every fresh label is attributed to some account
    final = snaps[-1]
    assert sum(a["fresh"] for a in final["accounts"]) \
        == final["stats"]["fresh"] > 0


# -- live server: end-to-end attribution -----------------------------------
def _finished_trace(client, tid: str, timeout: float = 10.0) -> dict:
    """The request's trace once its handler has added ``http.write``, which
    happens just after the client has read the answer."""
    deadline = time.monotonic() + timeout
    while True:
        doc = client.traces(trace_id=tid)
        if any(s["name"] == "http.write" for s in doc["spans"]):
            return doc
        assert time.monotonic() < deadline, "no http.write span"
        time.sleep(0.01)


def test_traced_request_attributes_every_fresh_label(wl, index):
    """The acceptance invariant: with a replicated oracle pool, a traced
    request's fresh count, the sum over its ``broker.flush`` spans, the sum
    over its ``oracle.subbatch`` spans, and the scraped
    ``oracle_fresh_total`` delta are all the same number — and every span
    chains back to the request root."""
    engine = QueryEngine(index, wl, oracle_replicas=2)
    server = QueryServer(engine, port=0, admission_window=0.0,
                         max_workers=2).start()
    try:
        client = QueryClient(server.url)
        client.wait_ready(30)
        before = parse_prometheus_text(client.metrics())
        tid = "feedfacecafe0001"
        out = client.query(SPEC_DICTS, trace_id=tid)
        req = out["request"]
        assert req["trace_id"] == tid
        fresh = req["fresh"]
        assert fresh > 0

        doc = _finished_trace(client, tid)
        assert doc["trace_id"] == tid
        spans = doc["spans"]
        by_id = {s["span_id"]: s for s in spans}
        names = {s["name"] for s in spans}
        assert {"request", "sched.queue", "session.plan",
                "session.execute", "broker.flush",
                "oracle.subbatch"} <= names
        # every span reaches the root through its parents: one chain each
        for s in spans:
            hops, cur = 0, s
            while cur["span_id"] != 0:
                cur = by_id[cur["parent_id"]]
                hops += 1
                assert hops < len(spans)
        flushes = [s for s in spans if s["name"] == "broker.flush"]
        flush_fresh = sum(s["attrs"].get("fresh", 0) for s in flushes)
        subs = [s for s in spans if s["name"] == "oracle.subbatch"]
        assert all(by_id[s["parent_id"]]["name"] == "broker.flush"
                   for s in subs)
        sub_n = sum(s["attrs"]["n"] for s in subs)
        # which replica served each sub-batch is load-dependent (work
        # stealing); that it's recorded and valid is the invariant
        assert {s["attrs"]["replica"] for s in subs} <= {0, 1}
        assert flush_fresh == sub_n == fresh

        after = parse_prometheus_text(client.metrics())
        key = series_key("oracle_fresh_total", workload=req["workload"])
        assert after[key] - before.get(key, 0.0) == fresh
        lat = series_key("request_latency_seconds_count",
                         workload=req["workload"])
        assert after[lat] - before.get(lat, 0.0) == 1
        assert after.get(series_key("sched_grants_total",
                                    reason="first"), 0) >= 1

        # flight-recorder listing + chrome export + 404 on unknown id
        listing = client.traces()
        assert listing["recorded"] >= 1
        assert any(s["trace_id"] == tid for s in listing["traces"])
        cdoc = client.traces(trace_id=tid, fmt="chrome")
        assert cdoc["otherData"]["trace_id"] == tid
        assert len(cdoc["traceEvents"]) == len(spans)
        with pytest.raises(ServerError) as ei:
            client.traces(trace_id="0" * 16)
        assert ei.value.status == 404
    finally:
        server.shutdown()


def test_metrics_count_sample_order_computes_and_hits(wl, index):
    server = QueryServer(QueryEngine(index, wl), port=0,
                         admission_window=0.0, max_workers=2).start()
    try:
        client = QueryClient(server.url)
        client.wait_ready(30)
        for _ in range(2):
            out = client.query(SPEC_DICTS[:1])
        metrics = parse_prometheus_text(client.metrics())
    finally:
        server.shutdown()
    workload = out["request"]["workload"]
    # the first aggregation over the score builds its order; every later
    # plan of it (the server plans, then the session re-plans) reuses it
    assert metrics[series_key("engine_sample_order_computes_total",
                              workload=workload)] == 1
    assert metrics[series_key("engine_sample_order_hits_total",
                              workload=workload)] >= 1


def test_front_end_spans_sit_outside_the_root(wl, index):
    server = QueryServer(QueryEngine(index, wl), port=0,
                         admission_window=0.0, max_workers=2).start()
    try:
        client = QueryClient(server.url)
        client.wait_ready(30)
        tid = "feedfacecafe0002"
        client.query(SPEC_DICTS[:1], trace_id=tid)
        doc = _finished_trace(client, tid)
    finally:
        server.shutdown()
    by_name = {s["name"]: s for s in doc["spans"]}
    root, read, write = (by_name[n] for n in
                         ("request", "http.read", "http.write"))
    assert read["parent_id"] == write["parent_id"] == 0
    # reading ends where the root begins and writing starts where it ends,
    # so the root keeps its admission-to-answer duration
    assert read["t0"] < read["t1"] == root["t0"]
    assert root["t1"] == write["t0"] < write["t1"]
    assert doc["duration_s"] == pytest.approx(root["t1"] - root["t0"])


def _queue_split(trace):
    (queue,) = trace.find_spans("sched.queue")
    (hold,) = trace.find_spans("sched.hold")
    slots = trace.find_spans("sched.slot")
    assert hold.parent_id == queue.span_id
    assert all(s.parent_id == queue.span_id for s in slots)
    assert len(slots) <= 1
    slot = slots[0].duration_s if slots else 0.0
    assert abs(hold.duration_s + slot - queue.duration_s) < 1e-3
    return hold.duration_s, slot


def test_the_queue_wait_splits_into_hold_and_slot(wl, index):
    window = 0.2
    server = QueryServer(QueryEngine(index, wl), port=0,
                         admission_window=window, max_workers=2).start()
    try:
        specs = [QuerySpec.from_dict(dict(SPEC_DICTS[0]))]
        first = server.submit(specs)
        time.sleep(0.05)
        rider = server.submit(specs)
        for sub in (first, rider):
            assert sub.done.wait(60) and sub.error is None
    finally:
        server.shutdown()
    # the first request is held for the whole window, then granted at once
    hold, slot = _queue_split(first.trace)
    assert window - 1e-3 < hold < window + 0.05
    # the rider is absorbed at that grant, before its own window is over:
    # held until then, never waiting for a slot
    assert rider.trace.attrs["coalesced_into"] == first.trace.trace_id
    hold, slot = _queue_split(rider.trace)
    assert 0.0 < hold < window and slot == 0.0


def test_a_ready_request_waiting_for_a_slot_shows_sched_slot(wl, index):
    server = QueryServer(QueryEngine(index, wl), port=0,
                         admission_window=0.0, max_workers=1).start()
    run_batch = server._run_batch

    def slow_run_batch(task, entry):
        time.sleep(0.1)
        run_batch(task, entry)

    server._run_batch = slow_run_batch
    server._scheduler._run = slow_run_batch
    try:
        specs = [QuerySpec.from_dict(dict(SPEC_DICTS[0]))]
        first, second = server.submit(specs), server.submit(specs)
        for sub in (first, second):
            assert sub.done.wait(60) and sub.error is None
    finally:
        server.shutdown()
    assert _queue_split(first.trace)[0] < 0.05
    # ready at once, but the one slot is held by the first request's run
    hold, slot = _queue_split(second.trace)
    assert hold < 0.05 and slot > 0.05


def test_server_with_observability_disabled_still_serves(wl, index):
    server = QueryServer(QueryEngine(index, wl), port=0,
                         admission_window=0.0, max_workers=2,
                         obs=False).start()
    try:
        client = QueryClient(server.url)
        client.wait_ready(30)
        out = client.query(SPEC_DICTS)
        assert out["request"]["fresh"] > 0
        assert out["request"]["trace_id"] is None
        assert client.metrics() == "# observability disabled\n"
        with pytest.raises(ServerError) as ei:
            client.traces()
        assert ei.value.status == 404
        stats = client.stats()
        assert stats["server"]["observability"]["enabled"] is False
    finally:
        server.shutdown()


# -- introspection never triggers or waits on a lazy load ------------------
def test_scrapes_respond_while_a_lazy_load_is_in_flight(wl, index):
    """/healthz, /workloads, /metrics and /stats must answer while a
    workload's first-load is blocked mid-build — and must not themselves
    trigger the load."""
    registry = WorkloadRegistry()
    entry = registry.declare(WorkloadSpec(name="lazy", dataset="night-street",
                                          n_records=1200))
    started, gate = threading.Event(), threading.Event()

    def slow_load():
        started.set()
        assert gate.wait(timeout=30)
        entry.store = None
        entry.engine = QueryEngine(index, wl)
    entry._load = slow_load

    server = QueryServer(registry, port=0, admission_window=0.0,
                         max_workers=2).start()
    try:
        client = QueryClient(server.url)
        client.wait_ready(30)
        # scraping an unloaded mount is free: no load started
        assert client.healthy()
        assert not started.is_set()

        result = {}

        def post():
            result["out"] = client.query(SPEC_DICTS[:1], workload="lazy")
        poster = threading.Thread(target=post, daemon=True)
        poster.start()
        assert started.wait(timeout=30)

        t0 = time.monotonic()
        health = client._call("/healthz")
        wls = client.workloads()
        metrics = parse_prometheus_text(client.metrics())
        stats = client.stats()
        elapsed = time.monotonic() - t0
        assert elapsed < 10.0  # answered while the load was still blocked
        assert not gate.is_set()
        assert health["ok"] is True
        assert health["workloads"]["lazy"]["loaded"] is False
        (row,) = [w for w in wls["workloads"] if w["name"] == "lazy"]
        assert row["loaded"] is False
        # the collector skipped the unloaded entry instead of loading it
        assert series_key("oracle_fresh_total", workload="lazy") not in metrics
        assert stats["workloads"]["lazy"]["loaded"] is False

        gate.set()
        poster.join(timeout=60)
        assert result["out"]["request"]["fresh"] > 0
        assert client._call("/healthz")["workloads"]["lazy"]["loaded"] is True
    finally:
        server.shutdown()
