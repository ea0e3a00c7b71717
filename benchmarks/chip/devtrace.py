"""Device trace: record a window with ``jax.profiler`` and reduce it.

:func:`load` turns the profiler's ``.xplane.pb`` into plain data,
``{"devices": {plane: [(name, start_ns, dur_ns), ...]}, "host":
[(name, start_ns, dur_ns), ...]}``: for each device plane the events of
its ``XLA Ops`` line (the operations as the device ran them), and every
host event.  :func:`reduce` works on that plain data only, so a small
recorded trace checks it without a chip.
"""

from __future__ import annotations

from collections import defaultdict
from pathlib import Path

DEVICE_PREFIX = "/device:"
OPS_LINE = "XLA Ops"


def load(trace_dir: Path) -> dict:
    import jax

    files = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = jax.profiler.ProfileData.from_file(str(files[-1]))
    devices: dict[str, list] = {}
    host: list = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[plane.name] = [
                        (op_name(e.name), int(e.start_ns),
                         int(e.duration_ns))
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, int(e.start_ns), int(e.duration_ns))
                            for e in line.events)
    return {"devices": devices, "host": host}


def op_name(text: str) -> str:
    """An operation's name without its HLO text (``%fusion.112 = s32[...]
    fusion(...)`` gives ``%fusion.112``)."""
    return text.split(" = ", 1)[0]


def _union(events) -> list[tuple[int, int]]:
    """Merged ``[start, end)`` intervals of ``(name, start, dur)`` events."""
    spans = sorted((s, s + d) for _, s, d in events if d > 0)
    out: list[list[int]] = []
    for s, e in spans:
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _label(host, t: int) -> str:
    """What the host was doing at ``t``: the innermost host event around
    it, preferring the benchmark's own ``bench:`` annotations."""
    around = [(d, name) for name, s, d in host if s <= t < s + d]
    bench = [x for x in around if x[1].startswith("bench:")]
    pick = min(bench or around, default=None)
    return pick[1] if pick else "no host event"


def reduce(trace: dict, n_gaps: int = 10, n_ops: int = 10) -> dict:
    """``busy_s`` (union of device op intervals, averaged over devices),
    the ``n_ops`` device operations that took most time, and the
    ``n_gaps`` longest idle gaps between device operations, each named by
    what the host was doing in its middle."""
    devices = {k: v for k, v in trace["devices"].items() if v}
    if not devices:
        return {"busy_s": 0.0, "device_ops": [], "idle_gaps": []}
    busy, by_op, gaps = 0.0, defaultdict(float), []
    for events in devices.values():
        merged = _union(events)
        busy += sum(e - s for s, e in merged) / 1e9
        for name, _s, d in events:
            by_op[name] += d / 1e9
        gaps += [(s2 - e1, e1, s2) for (_, e1), (s2, _) in
                 zip(merged, merged[1:])]
    gaps.sort(reverse=True)
    return {
        "busy_s": busy / len(devices),
        "device_ops": sorted(([k, v] for k, v in by_op.items()),
                             key=lambda kv: -kv[1])[:n_ops],
        "idle_gaps": [[_label(trace["host"], (a + b) // 2), g / 1e9]
                      for g, a, b in gaps[:n_gaps]],
    }
