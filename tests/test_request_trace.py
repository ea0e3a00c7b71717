"""One span tree per request: sampled at the HTTP front, carried through
the scheduler (solo, batched and coalesced flights), the registry and the
engine, and closed by the handler after the last byte is written; spans
carry the thread's CPU time and land in a ``jax.profiler`` trace as
``repro/<span>`` host events."""

import json
import re
import threading
import time
import urllib.request
from urllib.parse import urlencode

import jax
import pytest

from benchmarks.chip import devtrace
from repro.obs import Trace
from repro.obs import trace as trace_mod
from repro.serve.metrics import ServeMetrics
from repro.serve.scheduler import Scheduler
from repro.serve.server import DatasetRegistry, make_server, serve_in_thread

# no constant: the solo path; one constant: the parameterized batch path
SOLO = "SELECT ?x WHERE { ?x rdf:type ub:GraduateStudent . }"
COURSE = """SELECT ?x WHERE {{
  ?x rdf:type ub:GraduateStudent .
  ?x ub:takesCourse {c} .
}}"""
REQUEST_SPANS = ["parse", "fingerprint", "queue_wait", "plan", "execute",
                 "decode", "serialize", "write"]


def _names(span):
    yield span.name
    for c in span.children:
        yield from _names(c)


@pytest.fixture(scope="module")
def courses(lubm_graph):
    _, maps = lubm_graph
    out = [t for t in maps.dict.terms.to_str
           if re.match(r"ub:GraduateCourse\d", t)]
    assert len(out) >= 2
    return out


@pytest.fixture
def sampled_service(lubm_graph):
    """An HTTP service tracing every request; yields ``(server,
    recorded)``, where ``recorded`` lists ``(trace, span names at record
    time, write span closed)`` for each trace the metrics were given."""
    g, maps = lubm_graph
    registry = DatasetRegistry(ServeMetrics(), trace_sample=1.0)
    registry.register("lubm", g, maps)
    server = make_server(registry, port=0, workers=2, default_timeout_s=60.0)
    serve_in_thread(server)
    recorded = []
    orig = registry.metrics.record_trace

    def record(trace):
        writes = trace.find("write")
        recorded.append((trace, set(_names(trace.root)),
                         bool(writes) and writes[0].dur > 0))
        orig(trace)

    registry.metrics.record_trace = record
    yield server, recorded
    server.shutdown()
    server.scheduler.stop()


def _get(server, query):
    host, port = server.server_address[:2]
    url = f"http://{host}:{port}/sparql?" + urlencode({"query": query})
    with urllib.request.urlopen(url, timeout=60) as r:
        return json.loads(r.read())


def _wait_for(cond, timeout=10.0):
    end = time.monotonic() + timeout
    while not cond() and time.monotonic() < end:
        time.sleep(0.01)
    return cond()


@pytest.mark.parametrize("path", ["solo", "batched"])
def test_sampled_http_request_one_tree_recorded_after_write(
        sampled_service, courses, path):
    server, recorded = sampled_service
    query = SOLO if path == "solo" else COURSE.format(c=courses[0])
    out = _get(server, query)
    assert _wait_for(lambda: recorded)
    time.sleep(0.1)  # a second record would land by now
    assert len(recorded) == 1
    trace, names, write_closed = recorded[0]
    assert set(REQUEST_SPANS) <= names
    assert write_closed  # recorded once the response was written
    assert trace.sampled and not trace.profile_steps
    assert "trace" not in out  # only a forced trace goes in the response
    # the request's spans share its query id, which the response carries
    assert trace.query_id == out["query_id"]
    # the answer is encoded by one table take, then one assembly
    top = [s.name for s in trace.root.children]
    assert top[top.index("decode"):] == ["decode", "serialize", "write"]
    (decode,), (serialize,) = trace.find("decode"), trace.find("serialize")
    assert decode.meta == {"rows": out["stats"]["returned"], "extended": 0}
    assert serialize.meta["bytes"] == trace.find("write")[0].meta["bytes"]
    assert trace.find("write")[0].meta["bytes"] > 0
    # queue_wait comes from the flight's own times, before planning
    qw, plan = trace.find("queue_wait")[0], trace.find("plan")[0]
    assert qw.dur >= 0 and qw.t0 <= plan.t0
    assert qw.cpu is None and plan.cpu is not None


def test_batched_pair_links_members_to_leader(lubm_graph, courses):
    g, maps = lubm_graph
    registry = DatasetRegistry(ServeMetrics())
    registry.register("lubm", g, maps)
    traces = [Trace(sampled=True), Trace(sampled=True)]
    counts = {}
    # one worker holds its batch open long enough for the second member
    with Scheduler(registry, workers=1, batch_max=4,
                   batch_window_ms=1000.0) as sched:
        def go(i):
            counts[i] = sched.submit("lubm", COURSE.format(c=courses[i]),
                                     trace=traces[i]).count

        threads = [threading.Thread(target=go, args=(i,)) for i in (0, 1)]
        threads[0].start()
        time.sleep(0.05)
        threads[1].start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    assert len(counts) == 2
    assert registry.metrics.coalesced_queries.total() == 2  # one batch
    leader = traces[0].query_id
    for tr in traces:
        for name in ("plan", "execute"):
            (span,) = tr.find(name)
            assert span.meta["batch"] == 2
            assert span.meta["leader"] == leader
        assert tr.find("queue_wait")
        assert not tr.find("step")  # the batch path adds no step spans
    lead_exec = traces[0].find("execute")[0]
    assert "shared" not in lead_exec.meta and lead_exec.cpu is not None
    assert set(_names(lead_exec)) & {"dispatch", "compile"}
    assert lead_exec.children and traces[0].find("device_wait")
    member_exec = traces[1].find("execute")[0]
    assert member_exec.meta["shared"] is True
    assert member_exec.cpu is None and not member_exec.children
    assert traces[1].find("plan")[0].meta["shared"] is True


def test_coalesced_waiter_names_the_flight_it_joined(lubm_graph):
    g, maps = lubm_graph
    registry = DatasetRegistry(ServeMetrics())
    registry.register("lubm", g, maps)
    sched = Scheduler(registry, workers=1).start()
    entered, release = threading.Event(), threading.Event()
    execute = registry.execute_canonical

    def held(*a, **kw):
        entered.set()
        release.wait(30)
        return execute(*a, **kw)

    registry.execute_canonical = held
    traces = [Trace(sampled=True), Trace(sampled=True)]
    results = {}

    def go(i):
        results[i] = sched.submit("lubm", SOLO, trace=traces[i])

    try:
        first = threading.Thread(target=go, args=(0,))
        first.start()
        assert entered.wait(30)
        second = threading.Thread(target=go, args=(1,))
        second.start()
        assert _wait_for(lambda: sched.metrics.coalesced.total() == 1)
        release.set()
        for t in (first, second):
            t.join(timeout=60)
            assert not t.is_alive()
    finally:
        release.set()
        sched.stop()
    leader, waiter = traces
    flight_qid = results[0].stats["query_id"]
    assert leader.query_id == flight_qid
    assert results[1].stats["query_id"] == flight_qid
    (ex,) = waiter.find("execute")
    assert ex.meta == {"coalesced_into": flight_qid, "shared": True}
    assert ex.dur > 0 and ex.cpu is None  # a wait, with no CPU figure
    assert waiter.query_id != flight_qid
    assert not waiter.find("plan") and not waiter.find("queue_wait")
    assert "shared" not in leader.find("execute")[0].meta


def test_untraced_requests_build_no_trace_and_open_no_annotation(
        lubm_graph, monkeypatch, courses):
    def refuse(*a, **kw):
        raise AssertionError("tracing is off")

    monkeypatch.setattr(trace_mod.Trace, "__init__", refuse)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", refuse)
    g, maps = lubm_graph
    registry = DatasetRegistry(ServeMetrics(), trace_sample=0.0)
    registry.register("lubm", g, maps)
    server = make_server(registry, port=0, workers=2, default_timeout_s=60.0)
    serve_in_thread(server)
    try:
        for q in (SOLO, COURSE.format(c=courses[0])):
            out = _get(server, q)
            assert out["stats"]["count"] > 0 and "trace" not in out
            assert server.scheduler.submit("lubm", q).count > 0
            assert registry.execute("lubm", q).count > 0
    finally:
        server.shutdown()
        server.scheduler.stop()
    assert registry.metrics.traces.total() == 0


def test_span_cpu_time_tells_work_from_waiting():
    t = Trace()
    with t.span("work"):
        # 50 ms of this thread's CPU, however busy the machine's cores are
        cpu0 = time.thread_time()
        while time.thread_time() - cpu0 < 0.05:
            pass
        spent = time.thread_time() - cpu0
    with t.span("sleep"):
        time.sleep(0.05)
    t.add("posthoc", 0.01)
    t.finish()
    work, sleep = t.find("work")[0], t.find("sleep")[0]
    # a CPU-bound span's CPU time is its wall time less what other
    # processes took from it: all the work it did, and no more than wall
    assert spent <= work.cpu <= work.dur
    assert work.cpu == pytest.approx(spent, abs=0.005)
    assert sleep.dur >= 0.05 and sleep.cpu < 0.2 * sleep.dur
    assert t.find("posthoc")[0].cpu is None
    d = {c["name"]: c for c in t.to_dict()["root"]["children"]}
    assert d["work"]["cpu_ms"] == pytest.approx(work.cpu * 1e3, abs=1e-3)
    assert "cpu_ms" not in d["posthoc"]
    from repro.obs import chrome_trace
    events = {e["name"]: e for e in chrome_trace(t)["traceEvents"]
              if e["ph"] == "X"}
    assert events["sleep"]["args"]["cpu_ms"] < events["sleep"]["dur"] / 1e3


def test_spans_land_in_the_profiler_trace_in_order(sampled_service,
                                                   tmp_path):
    server, recorded = sampled_service
    jax.profiler.start_trace(str(tmp_path))
    try:
        _get(server, SOLO)
        assert _wait_for(lambda: recorded)
    finally:
        jax.profiler.stop_trace()
    host = devtrace.load(tmp_path)["host"]
    first: dict[str, int] = {}
    for name, start, _dur in sorted(host, key=lambda e: e[1]):
        if name.startswith("repro/"):
            first.setdefault(name[len("repro/"):], start)
    order = ["parse", "fingerprint", "plan", "execute", "decode",
             "serialize", "write"]
    assert set(order) <= set(first)
    assert [first[n] for n in order] == sorted(first[n] for n in order)
