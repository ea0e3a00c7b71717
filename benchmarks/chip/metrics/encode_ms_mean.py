"""Wall time of HTTP response encoding, the program's ``decode`` and
``serialize`` spans (``serve/server.py`` ``_bindings_json`` and the
handler's ``json.dumps``), over the traces recorded in the window, per
request answered in the window."""

NAMES = ("decode", "serialize")


def _spans(span):
    yield span
    for c in span.children:
        yield from _spans(c)


def read(run):
    if run.probes is None:
        return None
    answered = sum(1 for r in run.window if r.status == 200)
    durs = [s.dur for t, tr in run.probes.traces if run.in_window(t)
            for s in _spans(tr.root) if s.name in NAMES]
    if not answered or not durs:
        return None
    return 1e3 * sum(durs) / answered
