"""Chunk-program dispatches of the executions finished in the window (a
vmapped batch counts once), per request answered in the window."""


def read(run):
    if run.probes is None:
        return None
    answered = sum(1 for r in run.window if r.status == 200)
    if not answered:
        return None
    return sum(n for t, n in run.probes.dispatches
               if run.in_window(t)) / answered
