"""Least device time of the step work in the traced stretch, at the
chip's peaks (``benchmarks/chip/cost.py``), as a share of the device's
busy time there.  The step work is read from the step spans of the
sampled traces finished inside the traced stretch."""

from benchmarks.chip.cost import least_seconds
from benchmarks.chip.probes import step_records


def read(run):
    if run.probes is None or not run.device_trace:
        return None
    busy = run.device_trace["busy_s"]
    lo, hi = run.trace_span
    steps = [step_records(tr) for t, tr in run.probes.traces
             if lo <= t < hi]
    steps = [s for s in steps if s]
    if not steps or busy <= 0:
        return None
    least = sum(least_seconds(s, run.max_degree, run.peak) for s in steps)
    return 100.0 * least / busy
