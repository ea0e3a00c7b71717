"""Parse, fingerprint and plan time (plan-cache lookup or plan search)
spent in the window, per request due in the window."""


def read(run):
    if run.probes is None or not run.window:
        return None
    total = sum(ms for t, ms in run.probes.planning if run.in_window(t))
    return total / len(run.window)
