"""The device-trace reduction, the step cost function and the peaks."""

from __future__ import annotations

import json
from pathlib import Path

import pytest

from benchmarks.chip import devtrace
from benchmarks.chip.cost import least_seconds, step_cost
from benchmarks.chip.peaks import UnknownDevice, peaks

DATA = Path(__file__).resolve().parent / "data"


def test_reduce_synthetic_trace():
    trace = {"devices": {"/device:TPU:0": [
                ("fusion.1", 0, 100), ("fusion.2", 50, 100),  # overlap
                ("gather", 400, 100), ("fusion.1", 1000, 50)]},
             "host": [("bench:execute", 100, 900),
                      ("bench:encode", 600, 100), ("other", 0, 2000)]}
    out = devtrace.reduce(trace)
    assert out["busy_s"] == pytest.approx(300e-9)
    assert out["device_ops"][0] == ["fusion.1", pytest.approx(150e-9)]
    # gaps: 150..400 (mid 275 -> bench:execute) and 500..1000 (mid 750 ->
    # the innermost bench annotation there, bench:execute; 650..700 is
    # bench:encode only up to 700)
    assert [g for _, g in out["idle_gaps"]] == [
        pytest.approx(500e-9), pytest.approx(250e-9)]
    assert out["idle_gaps"][1][0] == "bench:execute"


def test_reduce_recorded_trace():
    """A stretch recorded on one TPU v5e by a traced lubm-mix run."""
    rec = json.loads((DATA / "trace_v5e.json").read_text())
    out = devtrace.reduce(rec["trace"])
    assert out["busy_s"] == pytest.approx(rec["busy_s"])
    assert 0 < out["busy_s"] <= rec["window_s"]
    assert len(out["device_ops"]) <= 10 and len(out["idle_gaps"]) <= 10
    assert all(s > 0 for _, s in out["device_ops"])


def test_empty_trace_reads_nothing():
    out = devtrace.reduce({"devices": {}, "host": []})
    assert out["busy_s"] == 0.0 and out["device_ops"] == []


def test_step_cost_counts_reported_work():
    # step 1: 100 rows in, 1000 expanded, 10 kept, one non-tree check,
    # max degree 1000 (10 binary-search probes)
    ops, nbytes = step_cost(1, 100, 1000, 10, 1, 1000)
    assert ops == 1000 + 10 * 10
    assert nbytes == 8 * 100 + 8 * 1000 + 4 * 3 * 10 + 4 * 10 * 10


def test_least_seconds_is_bytes_bound_on_v5e():
    pk = peaks("TPU v5 lite")
    steps = [{"step": 0, "rows": 1000, "kept": 500},
             {"step": 1, "rows": 2000, "kept": 100, "nontree_checks": 1}]
    want = sum(max(o / pk["flops_per_s"], b / pk["hbm_bytes_per_s"])
               for o, b in (step_cost(0, 0, 1000, 500, 0, 64),
                            step_cost(1, 500, 2000, 100, 1, 64)))
    assert least_seconds(steps, 64, pk) == pytest.approx(want)


def test_unknown_device_is_an_error():
    assert peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(UnknownDevice):
        peaks("cpu")
