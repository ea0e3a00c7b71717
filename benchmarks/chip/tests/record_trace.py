"""Record the small device trace that ``test_trace_cost.py`` reduces.

    python3 benchmarks/chip/tests/record_trace.py OUT.json

Runs one traced ``lubm-mix`` run on the chip and keeps, from its device
trace, the first 3,000 operations of the device plane with the host
events that overlap them, in the plain form ``devtrace.load`` returns,
together with the busy time ``devtrace.reduce`` gives for that excerpt.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[3]))

from benchmarks.chip import devtrace  # noqa: E402
from benchmarks.chip import run as R  # noqa: E402

N_OPS = 3000


def main(out: str) -> int:
    load = devtrace.load

    def keep(trace_dir):
        full = load(trace_dir)
        plane, events = max(full["devices"].items(), key=lambda kv: len(kv[1]))
        events = sorted(events, key=lambda e: e[1])[:N_OPS]
        lo, hi = events[0][1], events[-1][1] + events[-1][2]
        host = [e for e in full["host"] if e[1] < hi and e[1] + e[2] > lo]
        host.sort(key=lambda e: -e[2])
        excerpt = {"devices": {plane: events}, "host": host[:N_OPS]}
        red = devtrace.reduce(excerpt)
        Path(out).write_text(json.dumps({
            "about": "excerpt of a traced lubm-mix run on one TPU v5e",
            "busy_s": red["busy_s"], "window_s": (hi - lo) / 1e9,
            "trace": excerpt}))
        return full

    devtrace.load = keep
    res = R.run("lubm-mix", 2**31 + 77, 10.0, True)
    print(json.dumps({k: res[k] for k in ("correct", "metrics", "device")}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
