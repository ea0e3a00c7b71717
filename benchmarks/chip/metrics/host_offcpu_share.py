"""Share of the host's own work in which its thread did not run: over the
window's ``parse``, ``fingerprint``, ``plan``, ``execute``, ``host_ops``,
``decode`` and ``serialize`` spans, and the sub-spans that divide the same
host work (``plan_search`` in ``plan``; ``branch`` and ``optional`` in
``execute``), wall minus the thread's CPU seconds over wall.  Each span
counts only its own time, without its child spans.  For pure Python work
that is time spent waiting for the interpreter lock or the OS.  Left out
are spans that wait by nature (``queue_wait``, ``device_wait``,
``write``), spans with no CPU figure, and spans marked ``shared``: a batch
member's copy of its leader's work, and a coalesced waiter's wait for the
flight it joined, whose work the flight's own trace holds."""

HOST = ("parse", "fingerprint", "plan", "plan_search", "execute", "branch",
        "optional", "host_ops", "decode", "serialize")


def _spans(span):
    yield span
    for c in span.children:
        yield from _spans(c)


def read(run):
    if run.probes is None:
        return None
    wall = off = 0.0
    for t, tr in run.probes.traces:
        if not run.in_window(t):
            continue
        for s in _spans(tr.root):
            cpu = getattr(s, "cpu", None)
            if s.name not in HOST or cpu is None or s.meta.get("shared"):
                continue
            w = s.dur - sum(c.dur for c in s.children)
            c = cpu - sum(k.cpu for k in s.children if k.cpu is not None)
            wall += w
            off += w - c
    if wall <= 0:
        return None
    return 100.0 * off / wall
