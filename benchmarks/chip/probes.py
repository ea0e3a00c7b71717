"""The benchmark's own probes at the program's layer boundaries.

They wrap calls between layers of the served path, in this process and
from the benchmark's side, so no program file changes:

- JAX's compile events (``jax.monitoring``), always on: programs compiled
  or fetched from the persistent cache, with their time;
- the scheduler's finish of each flight, which carries the flight's own
  submit and start times (queue wait);
- parse and fingerprint on the HTTP thread, plan-cache lookup or plan
  search on the worker (planning time);
- each execution's result counters (chunk dispatches);
- each sampled trace the registry records (step spans: rows, kept,
  non-tree checks);
- SPARQL JSON encoding of each answer.

Layer probes are installed only for a traced run (``--trace 1``); each
call they wrap also opens a ``bench:<layer>`` profiler annotation, so the
device trace can say what the host was doing during an idle gap.
:meth:`Probes.close` restores everything it wrapped.
"""

from __future__ import annotations

import time
from contextlib import contextmanager

import jax

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class Probes:
    def __init__(self) -> None:
        self.compiles: list[tuple[float, float]] = []   # (t, seconds)
        self.cache_hits: list[float] = []               # t
        self.flights: list[tuple[float, float]] = []    # (t, queue ms)
        self.planning: list[tuple[float, float]] = []   # (t, ms)
        self.dispatches: list[tuple[float, int]] = []   # (t, chunks)
        self.traces: list[tuple[float, object]] = []    # (t, Trace)
        self.encode: list[tuple[float, float]] = []     # (t, ms)
        self._undo: list = []
        jax.monitoring.register_event_duration_secs_listener(self._on_dur)
        jax.monitoring.register_event_listener(self._on_event)

    # ------------------------------------------------------------ compiles
    def _on_dur(self, event: str, duration: float, **kw) -> None:
        if event == COMPILE_EVENT:
            self.compiles.append((time.monotonic(), duration))

    def _on_event(self, event: str, **kw) -> None:
        if event == CACHE_HIT_EVENT:
            self.cache_hits.append(time.monotonic())

    def compile_counts(self, t0: float, t1: float) -> dict:
        """Compiles, their seconds and persistent-cache hits in
        ``[t0, t1)`` (``time.monotonic()``)."""
        c = [d for t, d in self.compiles if t0 <= t < t1]
        return {"compiles": len(c), "compile_s": sum(c),
                "cache_hits": sum(1 for t in self.cache_hits if t0 <= t < t1)}

    # -------------------------------------------------------------- layers
    def _patch(self, obj, name: str, wrapper) -> None:
        orig = getattr(obj, name)
        setattr(obj, name, wrapper(orig))
        self._undo.append((obj, name, orig))

    @staticmethod
    @contextmanager
    def _timed(sink: list, label: str):
        t = time.monotonic()
        with jax.profiler.TraceAnnotation(label):
            try:
                yield
            finally:
                now = time.monotonic()
                sink.append((now, (now - t) * 1e3))

    def install_layers(self, server, dataset: str) -> None:
        import repro.serve.scheduler as sched_mod
        import repro.serve.server as server_mod

        registry, scheduler = server.registry, server.scheduler
        engine = registry.get(dataset).engine
        timed = self._timed

        def timing(sink, label):
            def wrap(fn):
                def call(*a, **kw):
                    with timed(sink, label):
                        return fn(*a, **kw)
                return call
            return wrap

        for name in ("parse_sparql", "parameterize_query",
                     "canonicalize_query"):
            self._patch(sched_mod, name, timing(self.planning, "bench:parse"))
        for name in ("compile_canonical", "compile_param"):
            self._patch(engine, name, timing(self.planning, "bench:plan"))
        self._patch(server_mod, "_bindings_json",
                    timing(self.encode, "bench:encode"))

        def finish(fn):
            def call(flight, *a, **kw):
                if flight.t_start is not None and not flight.done.is_set():
                    self.flights.append(
                        (time.monotonic(),
                         (flight.t_start - flight.t_submit) * 1e3))
                return fn(flight, *a, **kw)
            return call
        self._patch(scheduler, "_finish_locked", finish)

        seen: set[int] = set()

        def count(results) -> None:
            """Chunk dispatches of finished executions; a vmapped batch is
            one dispatch however many members it answered."""
            batches = 0
            for res in results:
                if isinstance(res, Exception) or id(res) in seen:
                    continue
                seen.add(id(res))
                for br in (res.stats.get("exec") or {}).get("branches", ()):
                    for part in [br.get("base") or {},
                                 *(br.get("optionals") or ())]:
                        if part.get("batched"):
                            batches = 1
                        else:
                            self.dispatches.append(
                                (time.monotonic(), int(part.get("chunks", 0))))
            if batches:
                self.dispatches.append((time.monotonic(), batches))

        def execute(fn):
            def call(*a, **kw):
                with jax.profiler.TraceAnnotation("bench:execute"):
                    res = fn(*a, **kw)
                count([res])
                return res
            return call

        def execute_batch(fn):
            def call(*a, **kw):
                with jax.profiler.TraceAnnotation("bench:execute"):
                    out = fn(*a, **kw)
                count(out)
                return out
            return call

        self._patch(registry, "execute_canonical", execute)
        self._patch(registry, "execute_canonical_batch", execute_batch)

        def record(fn):
            def call(trace, *a, **kw):
                self.traces.append((time.monotonic(), trace))
                return fn(trace, *a, **kw)
            return call
        self._patch(registry.metrics, "record_trace", record)

    def close(self) -> None:
        for obj, name, orig in reversed(self._undo):
            setattr(obj, name, orig)
        self._undo.clear()
        jax.monitoring.unregister_event_duration_listener(self._on_dur)
        jax.monitoring.unregister_event_listener(self._on_event)


def step_records(trace) -> list[dict]:
    """The ``step`` spans of one sampled trace, in order, as dicts of
    their counters."""
    out: list[dict] = []

    def walk(span) -> None:
        if span.name == "step":
            out.append(dict(span.meta))
        for c in span.children:
            walk(c)

    walk(trace.root)
    return out
