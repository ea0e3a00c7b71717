"""95th percentile of scheduler queue wait (a flight's start minus its
submit, the scheduler's own times) of the flights finished in the
window."""

from benchmarks.chip.metrics import percentile


def read(run):
    if run.probes is None:
        return None
    return percentile([ms for t, ms in run.probes.flights
                       if run.in_window(t)], 95)
