"""One reader per metric, ``<name>.py`` exposing ``read(run) -> float |
None``, found by the metric's name in ``BENCHMARK.json``.  ``run`` is the
:class:`benchmarks.chip.run.RunData` of one run.  A reader that finds
nothing to read returns ``None`` and the metric is left out of the line.
"""

from __future__ import annotations

import importlib
import math
import re

_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def reader(name: str):
    if not _NAME.match(name):
        raise ValueError(f"bad metric name {name!r}")
    module = name.replace(".", "_").replace("-", "_")
    return importlib.import_module(f"{__name__}.{module}").read


def percentile(values, q: float) -> float | None:
    """Nearest-rank percentile (``q`` in percent) of ``values``."""
    vals = sorted(values)
    if not vals:
        return None
    return vals[max(0, math.ceil(q / 100.0 * len(vals)) - 1)]
