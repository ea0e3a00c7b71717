"""Structured tracing for the query path (PR: end-to-end observability).

``Trace`` collects a tree of spans — parse → fingerprint → plan →
compile → per-chunk dispatch → per-step kernel — cheaply enough to stay
in the serving hot path (off by default, sampled or forced per request).
``SlowQueryLog`` keeps the N worst traces per dataset for the
``/debug/slow`` endpoint; ``chrome_trace`` renders a trace as Chrome's
``trace_event`` JSON for one-click flamegraph viewing.

``repro.obs.workload`` aggregates *across* queries: per-plan-shape
``WorkloadProfile`` q-error accounting, a ``DecisionJournal`` of engine
choices, and the observed-fanout feedback loop into the planner; the
offline ``python -m repro.obs.report`` CLI merges profiles, slow-log
entries, and bench traces into one report.
"""

from repro.obs.slowlog import SlowQueryLog
from repro.obs.trace import Span, Trace, chrome_trace, maybe_span
from repro.obs.workload import (DecisionJournal, WorkloadProfile,
                                WorkloadProfiler, qerror, qerror_log10)

__all__ = ["Span", "Trace", "SlowQueryLog", "chrome_trace", "maybe_span",
           "WorkloadProfile", "WorkloadProfiler", "DecisionJournal",
           "qerror", "qerror_log10"]
