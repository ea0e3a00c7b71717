"""A cell, configuration, traffic mix or metric comes as new files and a
new entry of BENCHMARK.json; no existing file is edited."""

from __future__ import annotations

import json
import shutil

from benchmarks.chip.tests.conftest import ROOT

CHIP = ROOT / "benchmarks/chip"
NEW = {
    "configs/lubm-tiny-test.json": None,        # filled below
    "traffic/lubm-pair-test.json": {
        "rate_qps": 2.0, "arrivals": "poisson", "schedule_seed": 5,
        "warmup_per_template": 1,
        "templates": [
            {"name": "Q6", "weight": 1,
             "query": "SELECT ?x WHERE { ?x rdf:type ub:Student . }"},
            {"name": "Q1c", "weight": 2,
             "query": "SELECT ?x WHERE { ?x rdf:type ub:GraduateStudent . "
                      "?x ub:takesCourse {course} . }",
             "params": {"course": {"population": "graduate_course",
                                   "zipf_s": 1.5}}}]},
    "metrics/answered_share_test.py":
        '"""Share of window requests answered correctly."""\n\n\n'
        'def read(run):\n'
        '    return 100.0 * sum(r.correct for r in run.window) / '
        'len(run.window)\n',
}


def test_new_files_make_a_new_cell(tiny_run, tmp_path):
    before = {p: p.read_bytes() for p in CHIP.rglob("*")
              if p.is_file() and "__pycache__" not in p.parts}
    cfg = json.loads((CHIP / "configs/lubm-50.json").read_text())
    NEW["configs/lubm-tiny-test.json"] = {**cfg, "name": "lubm-tiny-test"}
    made = []
    try:
        for rel, body in NEW.items():
            path = CHIP / rel
            path.write_text(body if isinstance(body, str)
                            else json.dumps(body))
            made.append(path)
        bench = json.loads((ROOT / "BENCHMARK.json").read_text())
        bench["configs"].append({
            "name": "lubm-tiny-test", "source": "test",
            "file": "benchmarks/chip/configs/lubm-tiny-test.json",
            "reduced": [], "why": "test"})
        bench["workloads"].append({
            "name": "lubm-pair-test", "config": "lubm-tiny-test",
            "traffic": "lubm-pair-test", "chips": 1, "why": "test"})
        bench["end_to_end"].append({
            "name": "answered_share_test", "unit": "%", "better": "higher",
            "bound": 0.01, "source": "host_clock",
            "workloads": ["lubm-pair-test"]})
        (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
        (tmp_path / "benchmarks").symlink_to(ROOT / "benchmarks")
        res = tiny_run("lubm-pair-test", root=tmp_path, rate=2.0)
    finally:
        for path in made:
            path.unlink()
        shutil.rmtree(CHIP / "metrics/__pycache__", ignore_errors=True)
    assert res["correct"], res["checks"]
    assert res["metrics"]["answered_share_test"]["value"] == 100.0
    assert {"qps", "setup_s"} <= set(res["metrics"])
    after = {p: p.read_bytes() for p in CHIP.rglob("*")
             if p.is_file() and "__pycache__" not in p.parts}
    assert after == before
