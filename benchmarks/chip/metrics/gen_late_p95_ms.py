"""95th percentile of how late the load generator sent each window
request (send time minus due time)."""

from benchmarks.chip.metrics import percentile


def read(run):
    return percentile([(r.sent - r.due) * 1e3 for r in run.window], 95)
