"""Host time of the executor: the wall time of each execution's
``execute`` span less its ``device_wait`` and ``compile`` descendants,
over the traces recorded in the window, per request answered in the
window.  A batched or coalesced execution counts once: its copies on the
other members' traces are marked ``shared`` and skipped."""

DEVICE = ("device_wait", "compile")


def _spans(span):
    yield span
    for c in span.children:
        yield from _spans(c)


def _executes(span):
    """The outermost ``execute`` spans under ``span``."""
    for c in span.children:
        if c.name == "execute":
            yield c
        else:
            yield from _executes(c)


def read(run):
    if run.probes is None:
        return None
    answered = sum(1 for r in run.window if r.status == 200)
    host = [ex.dur - sum(d.dur for d in _spans(ex) if d.name in DEVICE)
            for t, tr in run.probes.traces if run.in_window(t)
            for ex in _executes(tr.root) if not ex.meta.get("shared")]
    if not answered or not host:
        return None
    return 1e3 * sum(host) / answered
