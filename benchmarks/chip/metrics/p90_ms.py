"""90th percentile latency of every request due in the window, from its
due time to the last byte of its response; a failed request counts as
beyond every answered one."""

from benchmarks.chip.metrics import percentile


def read(run):
    return percentile(run.latencies_ms(), 90)
