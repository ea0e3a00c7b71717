"""Resilience subsystem: cooperative cancellation, fault injection,
degraded-mode execution, scheduler shutdown, and serve-path error mapping.

The load-bearing guarantees under test:

- the degradation ladder returns *bit-identical* results under injected
  RESOURCE_EXHAUSTED at every query-path fault site;
- deadline expiry mid-query stops within one chunk boundary and surfaces
  partial stats (HTTP 504, not 500);
- scheduler shutdown fails every unfinished flight with SchedulerShutdown
  and no waiter blocks past it;
- a store_commit fault leaves the versioned store unmutated.
"""

import threading
import time
import urllib.error
import urllib.parse
import urllib.request

import json

import numpy as np
import pytest

from conftest import given, settings, st
from repro.core import ExecOpts, SparqlEngine
from repro.core.sparql_exec import QueryResult
from repro.resilience import faults
from repro.resilience.cancel import CancelToken, QueryCancelled
from repro.resilience.faults import FaultInjector, FaultSpec, InjectedFault, parse_fault_spec
from repro.resilience.policy import (MAX_LEVEL, DegradationBreaker, RetryPolicy,
                                     degrade_opts, is_transient_fault)
from repro.serve.scheduler import (DeadlineExceeded, Overloaded, Scheduler,
                                   SchedulerShutdown, SchedulerStopped)
from repro.serve.server import DatasetRegistry, make_server, serve_in_thread
from repro.store import VersionedStore

Q_ADVISOR = "SELECT ?x ?y WHERE { ?x <ub:advisor> ?y . }"
Q_COURSE = "SELECT ?x ?y WHERE { ?x <ub:takesCourse> ?y . }"


# ------------------------------------------------------------------ units
def test_cancel_token_deadline_and_extend():
    tok = CancelToken()
    assert not tok.expired and tok.remaining() is None
    tok.check()  # no deadline, not cancelled -> no-op

    tok = CancelToken(time.monotonic() + 60)
    assert not tok.expired and tok.remaining() > 50
    tok.extend(time.monotonic() + 120)
    assert tok.remaining() > 100
    tok.extend(time.monotonic() - 1)  # never moves earlier
    assert tok.remaining() > 100

    past = CancelToken(time.monotonic() - 0.001)
    assert past.expired and past.reason == "deadline exceeded"
    with pytest.raises(QueryCancelled):
        past.check({"chunks": 3})
    try:
        past.check({"chunks": 3})
    except QueryCancelled as e:
        assert e.partial_stats == {"chunks": 3}

    tok = CancelToken()
    tok.cancel("client went away")
    assert tok.expired and tok.reason == "client went away"


def test_fault_spec_parsing_and_validation():
    specs = parse_fault_spec("dispatch:oom:0.5;compile:latency:1.0:20")
    assert specs == (FaultSpec("dispatch", "oom", rate=0.5),
                     FaultSpec("compile", "latency", rate=1.0, latency_ms=20.0))
    # comma works as separator too, blanks ignored
    assert len(parse_fault_spec("dispatch:poison, store_commit:oom")) == 2
    with pytest.raises(ValueError):
        parse_fault_spec("nowhere:oom")
    with pytest.raises(ValueError):
        parse_fault_spec("dispatch:frobnicate")
    with pytest.raises(ValueError):
        parse_fault_spec("dispatch")
    with pytest.raises(ValueError):
        FaultSpec("dispatch", "oom", rate=1.5)


def test_injector_is_deterministic_and_bounded():
    def run(seed):
        inj = FaultInjector(
            [FaultSpec("dispatch", "poison", rate=0.5)], seed=seed)
        return [inj.fire("dispatch") for _ in range(64)]

    assert run(7) == run(7)          # same seed -> same firing sequence
    assert run(7) != run(8)          # different seed -> different sequence

    inj = FaultInjector([FaultSpec("dispatch", "oom", times=2)], seed=0)
    for _ in range(2):
        with pytest.raises(InjectedFault):
            inj.fire("dispatch")
    inj.fire("dispatch")             # exhausted: no-op
    assert inj.counters[("dispatch", "oom")] == 2
    assert inj.snapshot()["fired"] == {"dispatch:oom": 2}
    assert inj.fire("compile") is False  # unwired site: no-op


def test_transient_classification():
    assert is_transient_fault(InjectedFault("dispatch", "oom"))
    assert is_transient_fault(InjectedFault("compile", "compile_error"))
    assert not is_transient_fault(InjectedFault("dispatch", "poison"))
    assert is_transient_fault(MemoryError())
    assert is_transient_fault(RuntimeError("RESOURCE_EXHAUSTED: whatever"))
    assert not is_transient_fault(ValueError("bad query"))


def test_degrade_opts_ladder_shape():
    base = ExecOpts(chunk=4096, init_cap=1 << 16, async_chunks=2)
    assert degrade_opts(base, 0) is base
    l1 = degrade_opts(base, 1)
    assert l1.chunk == 2048 and l1.init_cap == (1 << 15)
    assert l1.async_chunks == 1 and l1.cap_slack == base.cap_slack * 0.5
    assert l1.use_fused == base.use_fused
    l2 = degrade_opts(base, 2)
    assert l2.use_fused is False and l2.chunk == 2048
    l3 = degrade_opts(base, MAX_LEVEL)
    assert l3.cap_schedule is False and l3.suffix_resume is False
    assert l3.use_fused is False and l3.async_chunks == 1
    # floors hold even from tiny configs
    tiny = degrade_opts(ExecOpts(chunk=64, init_cap=256), 1)
    assert tiny.chunk == 512 and tiny.init_cap == 1024


def test_breaker_escalates_and_reprobes():
    br = DegradationBreaker(cooldown_s=10.0)
    sig = "plan-a"
    assert br.level(sig, now=0.0) == 0
    assert br.record_failure(sig, 0, now=0.0) == 1
    assert br.level(sig, now=1.0) == 1        # inside cooldown: stay put
    assert br.level(sig, now=10.0) == 0       # cooldown over: probe lower
    assert br.record_failure(sig, 1, now=11.0) == 2
    br.record_success(sig, 2, now=12.0)
    assert br.level(sig, now=13.0) == 2       # success pins the level
    assert br.level(sig, now=22.0) == 1       # ...until the next re-probe
    br.record_success(sig, 0, now=23.0)       # success at 0 clears the entry
    assert br.snapshot()["degraded_plans"] == 0
    assert br.record_failure(sig, MAX_LEVEL, now=0.0) == MAX_LEVEL  # capped

    assert RetryPolicy(backoff_s=0.01, backoff_max_s=0.05).backoff(10) == 0.05


# --------------------------------------------- degradation ladder (engine)
def _rows_equal(a: QueryResult, b: QueryResult) -> bool:
    return (a.count == b.count and list(a.variables) == list(b.variables)
            and np.array_equal(np.asarray(a.rows), np.asarray(b.rows)))


@pytest.mark.parametrize("site", ["dispatch", "compile"])
def test_ladder_bit_identical_under_oom(lubm_graph, site):
    """RESOURCE_EXHAUSTED injected at a query-path site: the retry ladder
    must still produce bit-identical bindings for every query."""
    g, maps = lubm_graph
    expected = {q: SparqlEngine(g, maps, ExecOpts(chunk=64)).query(q)
                for q in (Q_ADVISOR, Q_COURSE)}
    for q, exp in expected.items():
        eng = SparqlEngine(g, maps, ExecOpts(chunk=64))
        with faults.inject(f"{site}:oom", times=4, seed=7) as inj:
            res = eng.query(q)
        assert inj.counters[(site, "oom")] >= 1
        assert _rows_equal(res, exp), f"results diverged under {site} oom"
        snap = eng.executor.resilience_snapshot()
        assert snap["fault_retries"] >= 1


def test_ladder_escalation_and_breaker_memory(lubm_graph):
    """Enough same-level failures escalate one ladder level; the breaker
    remembers, so the next run starts degraded without re-failing."""
    g, maps = lubm_graph
    exp = SparqlEngine(g, maps).query(Q_ADVISOR)
    eng = SparqlEngine(g, maps)
    # default policy: max_retries=2 -> 3 attempts at L0; 4 faults push the
    # 4th attempt to L1 where the injector is exhausted
    with faults.inject("dispatch:oom", times=4, seed=7):
        res = eng.query(Q_ADVISOR)
    assert _rows_equal(res, exp)
    snap = eng.executor.resilience_snapshot()
    assert snap["escalations"] >= 1 and snap["degraded_runs"] >= 1
    assert snap["degraded_plans"] == 1 and snap["max_level"] >= 1
    assert res.stats["exec"]["branches"][0]["base"]["degraded_level"] >= 1
    # breaker memory: the same plan now runs degraded and fault-free
    res2 = eng.query(Q_ADVISOR)
    assert _rows_equal(res2, exp)
    assert eng.executor.resilience_snapshot()["fault_retries"] == snap["fault_retries"]


def test_ladder_exhaustion_reraises(lubm_graph):
    """A fault that persists through every ladder level must surface, not
    loop forever."""
    g, maps = lubm_graph
    eng = SparqlEngine(g, maps)
    with faults.inject("dispatch:oom", seed=0):  # unlimited fires
        with pytest.raises(InjectedFault):
            eng.query(Q_ADVISOR)
    snap = eng.executor.resilience_snapshot()
    assert snap["max_level"] == MAX_LEVEL


def test_tpu_compile_refusal_raises_without_degrading(lubm_graph,
                                                     monkeypatch):
    """A kernel the TPU compiler refuses (Mosaic lowering, VMEM overflow)
    fails the query: no retry, no escalation to a config that avoids it."""
    vmem = RuntimeError("RESOURCE_EXHAUSTED: Ran out of memory in memory "
                        "space vmem while allocating on stack")
    mosaic = RuntimeError("INTERNAL: Mosaic failed to compile TPU kernel: "
                          "Only 2D gather is supported")
    assert not is_transient_fault(vmem)
    assert not is_transient_fault(mosaic)
    g, maps = lubm_graph
    eng = SparqlEngine(g, maps)

    def refuse(*args, **kwargs):
        raise vmem

    monkeypatch.setattr(eng.executor, "_run_impl", refuse)
    with pytest.raises(RuntimeError, match="memory space vmem"):
        eng.query(Q_ADVISOR)
    snap = eng.executor.resilience_snapshot()
    assert snap["fault_retries"] == 0 and snap["escalations"] == 0
    assert snap["degraded_plans"] == 0


def test_nontransient_errors_bypass_ladder(lubm_graph):
    g, maps = lubm_graph
    eng = SparqlEngine(g, maps)
    # unlimited poison: the executor's small-plan probe also visits the
    # dispatch site, so a one-shot spec can be consumed before the real run
    with faults.inject("dispatch:poison", seed=0):
        res = eng.query(Q_ADVISOR)
    # poison is a *silent* corruption, not a retryable fault: the run
    # completes, the chunk's counts are zeroed, and the stats say so
    assert res.count < SparqlEngine(g, maps).query(Q_ADVISOR).count
    parts = [br["base"] for br in res.stats["exec"]["branches"]]
    assert any(p.get("poisoned") for p in parts)
    assert eng.executor.resilience_snapshot()["fault_retries"] == 0


def test_delta_merge_fault_retries_to_identical_result(lubm_graph):
    g, maps = lubm_graph
    store = VersionedStore(g, maps, auto_compact=False)
    store.apply_update("INSERT DATA { ub:RZed ub:advisor ub:ROther . }")
    exp = SparqlEngine(store.snapshot(), maps).query(Q_ADVISOR)
    eng = SparqlEngine(store.snapshot(), maps)
    with faults.inject("delta_merge:oom", times=1, seed=0) as inj:
        res = eng.query(Q_ADVISOR)
    assert inj.counters[("delta_merge", "oom")] == 1
    assert _rows_equal(res, exp)
    assert eng.executor.resilience_snapshot()["fault_retries"] >= 1


def test_store_commit_fault_leaves_store_unmutated(lubm_graph):
    g, maps = lubm_graph
    store = VersionedStore(g, maps, auto_compact=False)
    v0, d0 = store.version, store.delta_size()
    upd = "INSERT DATA { ub:FaultS ub:advisor ub:FaultO . }"
    with faults.inject("store_commit:oom", seed=0):
        with pytest.raises(InjectedFault):
            store.apply_update(upd)
    assert store.version == v0 and store.delta_size() == d0
    eng = SparqlEngine(store.snapshot(), maps)
    assert eng.count("SELECT ?x WHERE { ub:FaultS ub:advisor ?x . }") == 0
    store.apply_update(upd)  # retried commit applies cleanly
    assert store.version > v0


# -------------------------------------------------- cancellation (engine)
def test_deadline_stops_within_one_chunk(lubm_graph):
    g, maps = lubm_graph
    eng = SparqlEngine(g, maps, ExecOpts(chunk=4))
    full = eng.query(Q_COURSE)  # warm compile so only dispatch costs count
    total_chunks = full.stats["exec"]["branches"][0]["base"]["chunks"]
    assert total_chunks >= 4, "fixture must yield a multi-chunk query"
    with faults.inject("dispatch:latency:1.0:25", seed=0):
        with pytest.raises(QueryCancelled) as ei:
            eng.query(Q_COURSE, timeout_ms=60)
    part = ei.value.partial_stats["exec"]["branches"][-1]["base"]
    # stopped at a chunk boundary: some progress, but nowhere near done —
    # 25ms injected per dispatch vs a 60ms budget bounds it to <=4 chunks
    assert 0 <= part["chunks"] < total_chunks
    assert part["wall_ms"] >= 0.0


def test_timeout_ms_zero_budget_cancels_before_dispatch(lubm_graph):
    g, maps = lubm_graph
    eng = SparqlEngine(g, maps, ExecOpts(chunk=4))
    eng.query(Q_COURSE)  # warm
    with pytest.raises(QueryCancelled):
        eng.query(Q_COURSE, timeout_ms=0)


def test_explicit_cancel_token(lubm_graph):
    g, maps = lubm_graph
    eng = SparqlEngine(g, maps, ExecOpts(chunk=4))
    eng.query(Q_COURSE)
    tok = CancelToken()
    tok.cancel("caller aborted")
    with pytest.raises(QueryCancelled) as ei:
        eng.query(Q_COURSE, cancel=tok)
    assert "caller aborted" in str(ei.value)


# ------------------------------------------------------- scheduler + HTTP
class _StubRegistry:
    """Duck-typed registry: version + execute_canonical only.  ``exec_s``
    simulates device occupancy; ``cooperative`` adds a cancel kwarg and
    polls it like the real executor does."""

    def __init__(self, exec_s: float = 0.2, cooperative: bool = False):
        self.exec_s = exec_s
        self.calls = 0
        if cooperative:
            self.execute_canonical = self._execute_cancellable

    def version(self, name: str) -> int:
        return 0

    def _result(self) -> QueryResult:
        return QueryResult(["v0"], np.empty((0, 1), np.int64), ["vertex"],
                           count=0, stats={})

    def execute_canonical(self, name, canon, version):
        self.calls += 1
        time.sleep(self.exec_s)
        return self._result()

    def _execute_cancellable(self, name, canon, version, cancel=None):
        self.calls += 1
        t_end = time.monotonic() + self.exec_s
        while time.monotonic() < t_end:
            if cancel is not None:
                cancel.check()
            time.sleep(0.005)
        return self._result()


def _submit_bg(sched, query, timeout_s, out, key):
    try:
        out[key] = sched.submit("ds", query, timeout_s=timeout_s)
    except Exception as e:  # noqa: BLE001 — the outcome *is* the assertion
        out[key] = e


def test_scheduler_shutdown_fails_unfinished_flights():
    reg = _StubRegistry(exec_s=1.0)
    sched = Scheduler(reg, workers=1, default_timeout_s=30.0).start()
    out: dict = {}
    t1 = threading.Thread(target=_submit_bg, args=(
        sched, "SELECT ?a WHERE { ?a <p:one> ?b . }", 30.0, out, 1))
    t2 = threading.Thread(target=_submit_bg, args=(
        sched, "SELECT ?a WHERE { ?a <p:two> ?b . }", 30.0, out, 2))
    t1.start()
    time.sleep(0.15)  # worker now busy on flight 1
    t2.start()
    time.sleep(0.15)  # flight 2 queued behind it
    t0 = time.monotonic()
    sched.stop()
    t1.join(8.0)
    t2.join(8.0)
    assert not t1.is_alive() and not t2.is_alive(), \
        "a waiter blocked past shutdown"
    assert time.monotonic() - t0 < 6.0
    # flight 1 may have finished inside the join window; flight 2 never
    # started and must carry the shutdown error
    assert isinstance(out[2], SchedulerShutdown)
    assert isinstance(out[1], (QueryResult, SchedulerShutdown, QueryCancelled))
    snap = sched.snapshot()
    assert snap["inflight"] == 0 and snap["running"] is False
    with pytest.raises(SchedulerStopped):
        sched.submit("ds", "SELECT ?a WHERE { ?a <p:one> ?b . }")


def test_scheduler_shutdown_cancels_cooperative_execution():
    """A cancel-aware registry exits at the next poll, so stop() returns
    well inside the join timeout instead of riding out the execution."""
    reg = _StubRegistry(exec_s=10.0, cooperative=True)
    sched = Scheduler(reg, workers=1, default_timeout_s=30.0).start()
    out: dict = {}
    t = threading.Thread(target=_submit_bg, args=(
        sched, "SELECT ?a WHERE { ?a <p:one> ?b . }", 30.0, out, 1))
    t.start()
    time.sleep(0.2)
    t0 = time.monotonic()
    sched.stop()
    assert time.monotonic() - t0 < 3.0
    t.join(3.0)
    assert not t.is_alive()
    assert isinstance(out[1], (QueryCancelled, SchedulerShutdown))


def test_waiter_abandonment_cancels_flight():
    """When the only waiter times out, the flight's token flips so the
    execution stops occupying the worker."""
    reg = _StubRegistry(exec_s=5.0, cooperative=True)
    sched = Scheduler(reg, workers=1, default_timeout_s=30.0).start()
    try:
        with pytest.raises(DeadlineExceeded) as ei:
            sched.submit("ds", "SELECT ?a WHERE { ?a <p:one> ?b . }",
                         timeout_s=0.2)
        assert ei.value.queue_wait_ms is not None
        # the cooperative stub polls every 5ms: the cancel lands long
        # before the 5s sleep would have finished
        t0 = time.monotonic()
        while sched.snapshot()["inflight"] and time.monotonic() - t0 < 2.0:
            time.sleep(0.01)
        assert sched.snapshot()["inflight"] == 0
        assert sched.metrics.cancelled.total() >= 1
    finally:
        sched.stop()


def test_http_resilience_status_codes(lubm_graph):
    g, maps = lubm_graph
    registry = DatasetRegistry()
    registry.register("lubm", g, maps, ExecOpts(chunk=4))
    server = make_server(registry, port=0, workers=1)
    serve_in_thread(server)
    host, port = server.server_address[:2]
    base = f"http://{host}:{port}"
    try:
        # warm the plan so injected latency dominates the timed run
        url = f"{base}/sparql?query=" + urllib.parse.quote(Q_COURSE)
        with urllib.request.urlopen(url, timeout=60) as r:
            assert json.load(r)["stats"]["count"] > 0

        # 504 with queue-wait/execution split, distinct from 500
        with faults.inject("dispatch:latency:1.0:30", seed=0):
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(f"{url}&timeout_ms=60", timeout=60)
        assert ei.value.code == 504
        body = json.load(ei.value)
        assert "queue_wait_ms" in body and "exec_ms" in body
        assert "error" in body

        # /healthz carries resilience + scheduler + fault state
        with urllib.request.urlopen(f"{base}/healthz", timeout=30) as r:
            h = json.load(r)
        assert "resilience" in h["datasets"]["lubm"]
        assert h["scheduler"]["workers_alive"] == 1
        assert "faults" in h

        # /metrics exposes the new counters
        with urllib.request.urlopen(f"{base}/metrics", timeout=30) as r:
            text = r.read().decode()
        assert "repro_cancelled_total" in text
        assert "repro_degraded_dispatch_total" in text
        assert "repro_degraded_plans_lubm" in text
    finally:
        server.shutdown()
        server.scheduler.stop()


def test_http_overload_sends_retry_after(lubm_graph):
    g, maps = lubm_graph
    registry = DatasetRegistry()
    registry.register("lubm", g, maps)
    # max_queue=0: every submission trips admission control, making the
    # 503 deterministic without racing worker threads
    server = make_server(registry, port=0, workers=1, max_queue=0)
    serve_in_thread(server)
    host, port = server.server_address[:2]
    try:
        url = (f"http://{host}:{port}/sparql?query="
               + urllib.parse.quote(Q_ADVISOR))
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(url, timeout=30)
        assert ei.value.code == 503
        assert int(ei.value.headers["Retry-After"]) >= 1
        body = json.load(ei.value)
        assert body["retry_after_s"] >= 0.5
    finally:
        server.shutdown()
        server.scheduler.stop()


def test_overloaded_retry_after_tracks_backlog():
    reg = _StubRegistry(exec_s=0.01, cooperative=True)
    sched = Scheduler(reg, workers=2, max_queue=4,
                      default_timeout_s=30.0)
    # empty queue: floor
    assert sched.retry_after_s() == 0.5
    sched._ema_exec_ms = 10_000.0
    sched._queue.put(object())
    try:
        assert 0.5 <= sched.retry_after_s() <= 30.0
    finally:
        sched._queue.get()


# -------------------------------------------------------- chaos (property)
@given(st.lists(st.sampled_from(["advisor", "course", "tight", "jitter"]),
                min_size=1, max_size=6),
       st.integers(0, 2**31 - 1))
@settings(max_examples=8, deadline=None)
def test_chaos_interleavings_property(lubm_graph, ops, seed):
    """Random submit/fault/shutdown interleavings: every flight reaches
    exactly one terminal state, no waiter blocks past its deadline plus
    slack, and stop() leaves no inflight/pending residue."""
    g, maps = lubm_graph
    registry = DatasetRegistry()
    registry.register("ds", g, maps, ExecOpts(chunk=16))
    sched = Scheduler(registry, workers=2, max_queue=16,
                      default_timeout_s=10.0,
                      metrics=registry.metrics).start()
    out: dict = {}
    elapsed: dict = {}
    threads: list[threading.Thread] = []
    spec = ("dispatch:latency:0.3:3" if "jitter" in ops else None)
    injector = faults.install(
        FaultInjector(parse_fault_spec(spec), seed=seed)) if spec else None

    def run(i, query, timeout_s):
        t0 = time.monotonic()
        _submit_bg(sched, query, timeout_s, out, i)
        elapsed[i] = time.monotonic() - t0

    budgets = {}
    try:
        for i, op in enumerate(ops):
            if op == "jitter":
                continue
            q, timeout_s = {
                "advisor": (Q_ADVISOR, 10.0),
                "course": (Q_COURSE, 10.0),
                "tight": (Q_COURSE, 0.002),
            }[op]
            budgets[i] = timeout_s
            th = threading.Thread(target=run, args=(i, q, timeout_s))
            threads.append(th)
            th.start()
            time.sleep(0.002)
        time.sleep(0.01)
    finally:
        sched.stop()
        if spec:
            faults.install(injector)
    for th in threads:
        th.join(15.0)
        assert not th.is_alive(), "a waiter never reached a terminal state"
    for i, budget in budgets.items():
        assert i in out, f"flight {i} has no terminal state"
        assert elapsed[i] <= budget + 8.0, \
            f"flight {i} blocked {elapsed[i]:.1f}s past its deadline"
        assert isinstance(
            out[i], (QueryResult, DeadlineExceeded, QueryCancelled,
                     SchedulerShutdown, SchedulerStopped, Overloaded))
    assert sched._inflight == {} and sched._pending == {}
