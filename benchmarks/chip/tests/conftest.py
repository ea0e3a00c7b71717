"""CPU rehearsal of the chip benchmark at a tiny scale.

    JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/tests

Runs drive the real served path (data generator, the program's load path,
HTTP server, scheduler, load generator child, reference check) with the
chip check skipped, at the size each configuration file's ``rehearsal``
block gives (one university of three departments for ``lubm-50``) and a
few requests a second.
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


def rehearsal(cfg_file: Path) -> dict:
    """The overrides that shrink a configuration to a size a CPU test run
    holds: its file's ``rehearsal`` block, merged over the file one level
    deep (``run.merge``).  The chip run never reads the block."""
    cfg = json.loads(Path(cfg_file).read_text())
    if "rehearsal" not in cfg:
        raise ValueError(f"{cfg_file}: no 'rehearsal' key; every "
                         "configuration file gives the size a CPU test run "
                         "holds")
    return cfg["rehearsal"]


def rehearsed(name: str) -> dict:
    """Configuration ``configs/<name>.json`` at its rehearsal size."""
    from benchmarks.chip.run import merge

    path = ROOT / "benchmarks/chip/configs" / f"{name}.json"
    return merge(json.loads(path.read_text()), rehearsal(path))


def config_file(cell: str, root: Path = ROOT) -> Path:
    """The configuration file of a cell of ``root``'s BENCHMARK.json."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    name = {w["name"]: w for w in bench["workloads"]}[cell]["config"]
    return root / {c["name"]: c for c in bench["configs"]}[name]["file"]


@pytest.fixture
def tiny_run(tmp_path, monkeypatch):
    """``tiny_run(cell, trace=False, rate=3.0, seconds=6.0, server={})``
    runs a cell on the CPU at its configuration's rehearsal size, with
    ``server`` settings over the configuration's, and returns its result
    dict."""
    from benchmarks.chip import run as R

    monkeypatch.setattr(R, "CACHE_DIR", tmp_path / "jax_cache")
    monkeypatch.setattr(R, "WORK_DIR", tmp_path / "work")

    def go(cell: str, trace: bool = False, rate: float = 3.0,
           seconds: float = 6.0, root: Path = ROOT, log=None,
           server: dict | None = None):
        size = rehearsal(config_file(cell, root))
        over = {"config": R.merge(size, {"server": server or {}}),
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
