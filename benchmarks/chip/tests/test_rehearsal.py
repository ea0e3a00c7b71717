"""Each configuration file carries its own CPU rehearsal size, laid over the
file one level deep; a file without one fails before any data is made."""

from __future__ import annotations

import json

import pytest

from benchmarks.chip.run import merge
from benchmarks.chip.tests.conftest import ROOT, rehearsal

CONFIGS = sorted((ROOT / "benchmarks/chip/configs").glob("*.json"))


@pytest.mark.parametrize("path", CONFIGS, ids=lambda p: p.stem)
def test_every_config_names_its_rehearsal(path):
    cfg = json.loads(path.read_text())
    size = rehearsal(path)
    assert size
    for key, value in size.items():
        assert key in cfg, key
        if isinstance(value, dict):
            assert isinstance(cfg[key], dict), key
            assert set(value) <= set(cfg[key]), key
    assert merge(cfg, {}) == cfg


def test_merge_keeps_the_other_ranges():
    path = ROOT / "benchmarks/chip/configs/lubm-50.json"
    cfg = json.loads(path.read_text())
    out = merge(cfg, rehearsal(path))
    assert out["universities"] == 1
    assert out["ranges"] == {**cfg["ranges"], "departments": [3, 3]}
    assert out["server"] == cfg["server"]
    out = merge(cfg, {"server": {"batch_window_ms": 100.0}})
    assert out["server"] == {**cfg["server"], "batch_window_ms": 100.0}
    assert out["ranges"] == cfg["ranges"]


def test_config_without_rehearsal_fails_by_name(tiny_run, tmp_path):
    cfg = json.loads((ROOT / "benchmarks/chip/configs/bsbm-20k.json")
                     .read_text())
    del cfg["rehearsal"]
    rel = "benchmarks/chip/configs/bsbm-bare-test.json"
    (tmp_path / rel).parent.mkdir(parents=True)
    (tmp_path / rel).write_text(json.dumps(cfg))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "bsbm-bare-test", "source": "test",
                             "file": rel, "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "bsbm-bare-test",
                               "config": "bsbm-bare-test",
                               "traffic": "bsbm-explore", "chips": 1,
                               "why": "test"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    with pytest.raises(ValueError, match=r"bsbm-bare-test\.json: no "
                                         r"'rehearsal' key"):
        tiny_run("bsbm-bare-test", root=tmp_path)
