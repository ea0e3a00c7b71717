"""CPU rehearsal of the chip benchmark at a tiny scale.

    JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/tests

Runs drive the real served path (data generator, the program's load path,
HTTP server, scheduler, load generator child, reference check) with the
chip check skipped, one university of three departments and a few
requests a second.
"""

from __future__ import annotations

import json
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")

ROOT = Path(__file__).resolve().parents[3]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

import pytest  # noqa: E402

SEED = 2**31 + 11


def tiny(cfg_name: str = "lubm-50") -> dict:
    cfg = json.loads((ROOT / "benchmarks/chip/configs" /
                      f"{cfg_name}.json").read_text())
    return {"universities": 1,
            "ranges": {**cfg["ranges"], "departments": [3, 3]}}


@pytest.fixture
def tiny_run(tmp_path, monkeypatch):
    """``tiny_run(cell, trace=False, rate=3.0, seconds=6.0, server={})``
    runs a cell on the CPU at a tiny scale, with ``server`` settings over
    the configuration's, and returns its result dict."""
    from benchmarks.chip import run as R

    monkeypatch.setattr(R, "CACHE_DIR", tmp_path / "jax_cache")
    monkeypatch.setattr(R, "WORK_DIR", tmp_path / "work")

    def go(cell: str, trace: bool = False, rate: float = 3.0,
           seconds: float = 6.0, root: Path = ROOT, log=None,
           server: dict | None = None):
        cfg = R.load_cell(cell, root)[2]
        over = {"config": {**tiny(),
                           "server": {**cfg["server"], **(server or {})}},
                "traffic": {"rate_qps": rate, "warmup_per_template": 1}}
        return R.run(cell, SEED, seconds, trace, require_tpu=False,
                     root=root, overrides=over,
                     log=log or (lambda *a, **k: None))

    return go


@pytest.fixture
def anchored_root(tmp_path_factory):
    """A checkout root whose BENCHMARK.json also lists ``lubm-anchored``
    (out of the benchmark while the program's batched path fails on the
    chip, PERF.md section 7), so its faults and control stay tested."""
    root = tmp_path_factory.mktemp("anchored")
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["workloads"].append({
        "name": "lubm-anchored", "config": "lubm-50",
        "traffic": "lubm-anchored", "chips": 1, "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    (root / "benchmarks").symlink_to(ROOT / "benchmarks")
    return root
