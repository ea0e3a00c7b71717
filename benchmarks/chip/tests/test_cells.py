"""Every cell of BENCHMARK.json, end to end on the CPU at a tiny scale."""

from __future__ import annotations

import json

import pytest

from benchmarks.chip.tests.conftest import ROOT

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_end_to_end(tiny_run, cell):
    res = tiny_run(cell)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0
    assert res["attempted"] > 0
    want = {m["name"] for m in BENCH["end_to_end"]
            if cell in m.get("workloads", [cell])}
    assert set(res["metrics"]) == want
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert list(res)[-1] == "checks"


def test_traced_run_reports_layer_metrics(tiny_run):
    res = tiny_run("lubm-mix", trace=True)
    assert res["correct"], res["checks"]
    names = set(res["metrics"])
    # the CPU has no device plane: the device metrics find nothing there
    assert {"gen_late_p95_ms", "queue_wait_p95_ms", "plan_ms_mean",
            "dispatches_per_query", "compiles_in_window"} <= names
    assert "window_s" in res["device"] and "busy_s" in res["device"]
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
