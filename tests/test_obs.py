"""Observability tests: trace span trees (incl. a hypothesis nesting
property), Chrome export, slow-query log bounds/eviction, labeled metrics +
Prometheus exposition-format validity, roofline kernel cost models, the
benchmark regression gate, and end-to-end forced tracing through the
engine, the scheduler, and the HTTP debug endpoints."""

import json
import re
import threading
import urllib.error
import urllib.request
from urllib.parse import urlencode

import pytest

from benchmarks import check
from conftest import given, settings, st
from repro.analysis.roofline import (KERNEL_MODELS, estimate_step_ms,
                                     kernel_cost)
from repro.core import SparqlEngine
from repro.obs import SlowQueryLog, Trace, chrome_trace
from repro.rdf.workloads import LUBM_QUERIES
from repro.serve.cache import PlanCache, ResultCache
from repro.serve.metrics import (FINE_BUCKETS_S, LabeledGauge,
                                 LabeledHistogram, MetricsRegistry,
                                 ServeMetrics)
from repro.serve.scheduler import Scheduler
from repro.serve.server import (DatasetRegistry, make_server,
                                serve_in_thread)


# ------------------------------------------------------------------ traces
def test_trace_nesting_and_find():
    t = Trace("q", profile_steps=True)
    with t.span("execute"):
        with t.span("branch", index=0):
            t.add("step", 0.001, step=0, kernel="ragged_expand")
            t.add("step", 0.002, step=1, kernel="expand_filter")
        t.event("plan_cache", hit=True)
    t.finish()
    assert [c.name for c in t.root.children] == ["execute"]
    branch = t.find("branch")[0]
    assert [c.name for c in branch.children] == ["step", "step"]
    assert branch.meta["index"] == 0
    assert t.find("plan_cache")[0].meta["hit"] is True
    assert len(t.find("step")) == 2
    d = t.to_dict()
    assert d["profiled"] and not d["sampled"]
    assert d["dur_ms"] >= d["root"]["children"][0]["dur_ms"] > 0


def test_trace_finish_is_idempotent_and_closes_stack():
    t = Trace()
    cm = t.span("left_open")
    cm.__enter__()  # deliberately never exited
    t.finish()
    first = t.dur_ms
    assert len(t._stack) == 1  # stack tail cleared down to the root
    t.finish()
    assert t.dur_ms >= first


@given(st.recursive(st.just([]),
                    lambda ch: st.lists(ch, max_size=3), max_leaves=12))
@settings(max_examples=25, deadline=None)
def test_trace_span_tree_mirrors_nesting(shape):
    t = Trace("prop")

    def build(children):
        for sub in children:
            with t.span("s"):
                build(sub)

    build(shape)
    t.finish()

    def verify(span, children_shape):
        assert len(span.children) == len(children_shape)
        end = span.t0 + span.dur
        prev_t0 = span.t0
        for child, sub in zip(span.children, children_shape):
            # siblings open in order; children lie within the parent
            assert child.t0 >= prev_t0 - 1e-9
            assert child.t0 + child.dur <= end + 1e-6
            prev_t0 = child.t0
            verify(child, sub)

    verify(t.root, shape)
    # top-level spans are disjoint, so they can't sum past the wall time
    assert t.span_sum_ms() <= t.dur_ms + 1e-3


def test_chrome_trace_export():
    t = Trace("q")
    with t.span("execute", branches=1):
        t.add("step", 0.001, kernel="ragged_expand")
    t.finish()
    doc = chrome_trace(t)
    names = [e["name"] for e in doc["traceEvents"]]
    assert "thread_name" in names and "execute" in names and "step" in names
    ex = next(e for e in doc["traceEvents"] if e["name"] == "execute")
    assert ex["ph"] == "X" and ex["args"]["branches"] == 1
    step = next(e for e in doc["traceEvents"] if e["name"] == "step")
    assert step["dur"] == 1000  # 0.001s in microseconds
    text = chrome_trace([t], as_text=True)
    assert json.loads(text)["displayTimeUnit"] == "ms"


def _fake_trace(name="query"):
    t = Trace(name, profile_steps=True)
    with t.span("execute"):
        pass
    return t.finish()


# ---------------------------------------------------------- slow-query log
def test_slowlog_keeps_worst_per_fingerprint():
    log = SlowQueryLog(capacity=4)
    t1, t2 = _fake_trace(), _fake_trace()
    assert log.record("fpA", 10.0, t1)
    assert not log.record("fpA", 5.0, _fake_trace())   # faster: ignored
    assert log.record("fpA", 50.0, t2)                  # slower: replaces
    assert len(log) == 1
    assert log.entries()[0]["wall_ms"] == 50.0
    assert log.get(t2.trace_id) is not None
    assert log.get(t1.trace_id) is None  # replaced entry is gone


def test_slowlog_bounded_evicts_fastest():
    log = SlowQueryLog(capacity=3)
    for i, ms in enumerate([30.0, 10.0, 20.0]):
        assert log.record(f"fp{i}", ms, _fake_trace())
    assert not log.record("fp_new", 5.0, _fake_trace())  # faster than all
    assert log.record("fp_new", 25.0, _fake_trace())     # evicts the 10ms
    assert len(log) == 3
    walls = [e["wall_ms"] for e in log.entries()]
    assert walls == [30.0, 25.0, 20.0]  # slowest first, 10ms gone


def test_slowlog_disabled_and_render():
    assert not SlowQueryLog(capacity=0).record("fp", 99.0, _fake_trace())
    log = SlowQueryLog(capacity=2)
    t = _fake_trace()
    log.record("fp", 7.0, t, dataset="lubm", count=3,
               explain={"order": ["u0"]})
    (entry,) = log.entries()
    digest = log.summaries()[0]
    assert digest["count"] == 3 and "explain" not in digest
    full = SlowQueryLog.render_entry(entry)
    assert full["trace"]["id"] == t.trace_id
    assert full["explain"] == {"order": ["u0"]}
    chrome = SlowQueryLog.render_entry(entry, fmt="chrome")
    assert "traceEvents" in chrome


# ----------------------------------------------------------------- metrics
def test_labeled_histogram_and_gauge_render():
    h = LabeledHistogram("x_seconds", "spans", label="span",
                         buckets=FINE_BUCKETS_S)
    h.observe("compile", 0.5)
    h.observe("compile", 2e-6)
    h.observe("dispatch", 1e-3)
    lines = h.render()
    assert '# TYPE x_seconds histogram' in lines
    assert any('span="compile"' in ln and 'le="+Inf"' in ln and
               ln.endswith(" 2") for ln in lines)
    assert 'x_seconds_count{span="dispatch"} 1' in lines
    g = LabeledGauge("x_inflight", "per dataset", label="dataset")
    g.inc("lubm")
    g.inc("lubm")
    g.dec("lubm")
    g.set("bsbm", 5)
    assert g.value("lubm") == 1.0
    assert 'x_inflight{dataset="bsbm"} 5' in g.render()


def test_fine_buckets_ladder():
    assert list(FINE_BUCKETS_S) == sorted(FINE_BUCKETS_S)
    assert FINE_BUCKETS_S[0] == 1e-6
    assert FINE_BUCKETS_S[-1] == float("inf")
    assert 10.0 in FINE_BUCKETS_S


# grammar of the Prometheus text exposition format (v0.0.4, subset we emit)
_HELP_RE = re.compile(r"^# HELP [a-zA-Z_:][a-zA-Z0-9_:]* .*$")
_TYPE_RE = re.compile(
    r"^# TYPE ([a-zA-Z_:][a-zA-Z0-9_:]*) (counter|gauge|histogram)$")
_SAMPLE_RE = re.compile(
    r"^([a-zA-Z_:][a-zA-Z0-9_:]*)"
    r'(\{[a-zA-Z_][a-zA-Z0-9_]*="[^"\\]*"'
    r'(,[a-zA-Z_][a-zA-Z0-9_]*="[^"\\]*")*\})?'
    r" (NaN|[-+]?Inf|[-+]?[0-9]*\.?[0-9]+([eE][-+]?[0-9]+)?)$")


def test_prometheus_exposition_validity():
    m = ServeMetrics(MetricsRegistry())
    m.record("lubm", "ok", 12.5)
    m.record_plan_search(3.0)
    m.record_cardinality(10.0, 12)
    m.compile_events.inc(2)
    m.span_seconds.observe("execute", 0.01)
    m.dataset_inflight.inc("lubm")
    m.record_trace(_fake_trace())
    m.attach_cache_gauges("lubm", PlanCache(4), ResultCache(4))
    text = m.registry.render()
    assert text.endswith("\n")
    typed = set()
    for ln in text.splitlines():
        if ln.startswith("# HELP"):
            assert _HELP_RE.match(ln), ln
        elif ln.startswith("# TYPE"):
            mt = _TYPE_RE.match(ln)
            assert mt, ln
            typed.add(mt.group(1))
        else:
            ms = _SAMPLE_RE.match(ln)
            assert ms, f"invalid sample line: {ln!r}"
            base = re.sub(r"_(bucket|sum|count)$", "", ms.group(1))
            assert base in typed or ms.group(1) in typed, ln
    # the new series exist alongside the original names
    for name in ("repro_requests_total", "repro_span_seconds_bucket",
                 "repro_compile_events_total", "repro_traces_total",
                 "repro_dataset_inflight_queries",
                 "repro_plan_cache_hit_ratio_lubm"):
        assert name in text, name


def test_histogram_buckets_cumulative_in_render():
    m = ServeMetrics(MetricsRegistry())
    for s in (1e-6, 1e-3, 1e-3, 0.2, 5.0):
        m.span_seconds.observe("execute", s)
    lines = [ln for ln in m.registry.render().splitlines()
             if ln.startswith("repro_span_seconds_bucket")]
    counts = [int(ln.rsplit(" ", 1)[1]) for ln in lines]
    assert counts == sorted(counts)  # cumulative
    assert counts[-1] == 5           # +Inf bucket sees everything


# ---------------------------------------------------------------- roofline
def test_kernel_cost_models_cover_all_kernels():
    for kernel in KERNEL_MODELS:
        cost = kernel_cost(kernel, expanded=1e4, rows=1e3, capacity=2048,
                           nq=4, bitmap_words=2, n_iters=16)
        assert cost["flops"] > 0 and cost["bytes"] > 0, kernel
    with pytest.raises(ValueError):
        kernel_cost("not_a_kernel", expanded=1.0)


def test_estimate_step_ms_is_roofline():
    kw = dict(expanded=1e6, rows=1e4, capacity=4096)
    est = estimate_step_ms("ragged_expand", "TPU v5 lite", **kw)
    cost = kernel_cost("ragged_expand", **kw)
    # v5e's published peaks: 197 TFLOP/s bf16, 819 GB/s HBM
    assert est["model_ms"] == pytest.approx(
        max(cost["flops"] / 197e12, cost["bytes"] / 819e9) * 1e3)
    assert est["dominant"] == "memory"
    # a device with no published peaks gets no estimate: no peak is assumed
    assert estimate_step_ms("ragged_expand", "cpu", **kw) is None


# ---------------------------------------------------------- regression gate
_EXEC_BASE = {"lubm.Q2": {"count": 10, "speedup": 2.0,
                          "legacy_us": 100.0, "pipelined_us": 50.0}}


def test_check_exec_count_mismatch_is_regression():
    fresh = {"lubm.Q2": {**_EXEC_BASE["lubm.Q2"], "count": 11}}
    bad = check.compare("exec", _EXEC_BASE, fresh)
    assert bad and "correctness" in bad[0]


def test_check_exec_speedup_regression_and_tolerance():
    ok = {"lubm.Q2": {**_EXEC_BASE["lubm.Q2"], "speedup": 1.9}}
    assert check.compare("exec", _EXEC_BASE, ok) == []
    slow = {"lubm.Q2": {**_EXEC_BASE["lubm.Q2"], "speedup": 1.0}}
    assert check.compare("exec", _EXEC_BASE, slow)
    # faster-than-baseline never fails the gate
    fast = {"lubm.Q2": {**_EXEC_BASE["lubm.Q2"], "speedup": 4.0}}
    assert check.compare("exec", _EXEC_BASE, fast) == []


def test_check_exec_missing_query_is_regression():
    assert check.compare("exec", _EXEC_BASE, {})


def test_check_store_speedup_ratio():
    # store's floor is widened to 60% (quick-scale ingest ratios are
    # noisy) — a halved ratio passes, an order-of-magnitude loss gates
    base = {"speedup_ingest": 20.0, "speedup_wall": 1.1}
    assert check.compare("update", base,
                         {"speedup_ingest": 10.0, "speedup_wall": 1.1}) == []
    bad = check.compare("update", base,
                        {"speedup_ingest": 2.0, "speedup_wall": 1.1})
    assert bad and "speedup_ingest" in bad[0]


def test_check_planner_counts():
    base = {"lubm.dp.Q1": {"count": 4, "us_per_call": 10.0}}
    assert check.compare("planner", base,
                         {"lubm.dp.Q1": {"count": 4}}) == []
    assert check.compare("planner", base, {"lubm.dp.Q1": {"count": 5}})


def test_check_unknown_suite_passes():
    assert check.compare("kernels", {"a": 1}, {"a": 2}) == []


# ------------------------------------------------------------- end to end
def test_engine_forced_trace_spans_account_for_wall(lubm_graph):
    g, maps = lubm_graph
    engine = SparqlEngine(g, maps)
    plain = engine.query(LUBM_QUERIES["Q2"])
    res = engine.query(LUBM_QUERIES["Q2"], trace=True)
    assert res.count == plain.count  # tracing must not change answers
    t = res.stats["trace_obj"]
    d = res.stats["trace"]
    names = {s["name"] for s in _walk(d["root"])}
    assert {"parse", "fingerprint", "plan_cache", "execute",
            "branch", "step"} <= names
    # dispatch or compile depending on jit-cache state; one must exist
    assert names & {"compile", "dispatch"}
    steps = t.find("step")
    assert steps and all("kernel" in s.meta for s in steps)
    # the span tree accounts for the end-to-end wall time (20% tolerance)
    assert d["span_sum_ms"] >= 0.8 * d["dur_ms"]
    # second traced run: plan cache hit, no fresh compiles
    res2 = engine.query(LUBM_QUERIES["Q2"], trace=True)
    t2 = res2.stats["trace_obj"]
    assert t2.find("plan_cache")[0].meta["hit"] is True
    assert not t2.find("compile")
    assert t2.find("dispatch")


def _walk(span_dict):
    yield span_dict
    for c in span_dict.get("children", ()):
        yield from _walk(c)


def test_untraced_query_carries_no_trace(lubm_graph):
    g, maps = lubm_graph
    engine = SparqlEngine(g, maps)
    res = engine.query(LUBM_QUERIES["Q1"])
    assert "trace" not in res.stats


def test_scheduler_forced_trace_executes_and_logs(lubm_graph):
    g, maps = lubm_graph
    registry = DatasetRegistry(ServeMetrics(), result_cache_size=16)
    registry.register("lubm", g, maps)
    with Scheduler(registry, workers=2) as sched:
        r1 = sched.submit("lubm", LUBM_QUERIES["Q1"], trace=True)
        r2 = sched.submit("lubm", LUBM_QUERIES["Q1"], trace=True)
    t1, t2 = r1.stats["trace"], r2.stats["trace"]
    assert t1["id"] != t2["id"]  # no coalescing, each run observed
    assert r1.count == r2.count
    names = {s["name"] for s in _walk(t1["root"])}
    assert {"parse", "fingerprint", "execute"} <= names
    # worst Q1 execution is in the slow log, findable by trace id
    ds = registry.get("lubm")
    assert len(ds.slow_log) == 1
    entry = ds.slow_log.entries()[0]
    assert entry["id"] in (t1["id"], t2["id"])
    assert registry.find_trace(entry["id"]) is entry
    assert "order" in entry["explain"]["branches"][0]
    # traced runs bypass the result cache: nothing was stored
    assert ds.result_cache.stats.inserts == 0
    # span histograms + trace counter fed
    assert registry.metrics.traces.value(mode="forced") == 2
    assert registry.metrics.dataset_inflight.value("lubm") == 0


def test_registry_trace_sampling(lubm_graph):
    g, maps = lubm_graph
    registry = DatasetRegistry(ServeMetrics(), trace_sample=1.0)
    registry.register("lubm", g, maps)
    res = registry.execute("lubm", LUBM_QUERIES["Q1"])
    ds = registry.get("lubm")
    assert len(ds.slow_log) == 1
    assert registry.metrics.traces.value(mode="sampled") == 1
    # sampled traces keep the fast path: no per-step profiling
    assert ds.slow_log.entries()[0]["trace"].profile_steps is False
    assert res.count == registry.get("lubm").slow_log.entries()[0]["count"]


@pytest.fixture(scope="module")
def obs_http_service(lubm_graph):
    g, maps = lubm_graph
    registry = DatasetRegistry(ServeMetrics())
    registry.register("lubm", g, maps)
    server = make_server(registry, port=0, workers=2,
                         default_timeout_s=60.0)
    serve_in_thread(server)
    yield server
    server.shutdown()
    server.scheduler.stop()


def _get(server, path, **params):
    host, port = server.server_address[:2]
    url = f"http://{host}:{port}{path}"
    if params:
        url += "?" + urlencode(params)
    with urllib.request.urlopen(url, timeout=60) as r:
        return json.loads(r.read())


def test_http_trace_roundtrip_and_debug_endpoints(obs_http_service):
    server = obs_http_service
    plain = _get(server, "/sparql", query=LUBM_QUERIES["Q2"])
    out = _get(server, "/sparql", query=LUBM_QUERIES["Q2"], trace=1)
    assert out["stats"]["count"] == plain["stats"]["count"]
    tr = out["trace"]
    names = {s["name"] for s in _walk(tr["root"])}
    assert {"parse", "fingerprint", "execute", "branch", "step"} <= names
    assert tr["span_sum_ms"] >= 0.8 * tr["dur_ms"]

    slow = _get(server, "/debug/slow")["slow"]
    assert any(e["id"] == tr["id"] for e in slow["lubm"])

    full = _get(server, "/debug/trace", id=tr["id"])
    assert full["trace"]["id"] == tr["id"]
    assert "explain" in full and full["dataset"] == "lubm"

    chrome = _get(server, "/debug/trace", id=tr["id"], format="chrome")
    assert any(e["name"] == "step" for e in chrome["traceEvents"])

    # span histograms show up on /metrics
    host, port = server.server_address[:2]
    with urllib.request.urlopen(f"http://{host}:{port}/metrics",
                                timeout=60) as r:
        text = r.read().decode()
    assert 'repro_span_seconds_bucket{span="execute"' in text
    assert "repro_dataset_inflight_queries" in text


def test_http_debug_trace_unknown_id_404(obs_http_service):
    server = obs_http_service
    host, port = server.server_address[:2]
    with pytest.raises(urllib.error.HTTPError) as ei:
        urllib.request.urlopen(
            f"http://{host}:{port}/debug/trace?id=999999999", timeout=60)
    assert ei.value.code == 404
    with pytest.raises(urllib.error.HTTPError) as ei:
        urllib.request.urlopen(
            f"http://{host}:{port}/debug/trace", timeout=60)
    assert ei.value.code == 400


def test_http_concurrent_forced_traces_are_distinct(obs_http_service):
    server = obs_http_service
    ids, errors = [], []

    def client():
        try:
            out = _get(server, "/sparql", query=LUBM_QUERIES["Q1"], trace=1)
            ids.append(out["trace"]["id"])
        except Exception as e:  # pragma: no cover
            errors.append(e)

    threads = [threading.Thread(target=client) for _ in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120.0)
    assert not errors
    assert len(set(ids)) == 3  # forced traces never coalesce
