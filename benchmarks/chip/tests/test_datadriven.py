"""A cell, configuration, traffic mix or metric comes as new files and a
new entry of BENCHMARK.json; no existing file is edited.  Each case copies
one configuration of the benchmark under a new name and gives it a new
traffic mix, so a configuration of any generator joins at the size its own
``rehearsal`` block gives."""

from __future__ import annotations

import contextlib
import json
import shutil

import pytest

from benchmarks.chip import traffic
from benchmarks.chip.tests.conftest import ROOT
from benchmarks.chip.tests.test_faults import _drop_row

CHIP = ROOT / "benchmarks/chip"
METRIC = ("metrics/answered_share_test.py",
          '"""Share of window requests answered correctly."""\n\n\n'
          'def read(run):\n'
          '    return 100.0 * sum(r.correct for r in run.window) / '
          'len(run.window)\n')
LUBM_PAIR = {
    "rate_qps": 2.0, "arrivals": "poisson", "schedule_seed": 5,
    "warmup_per_template": 1,
    "templates": [
        {"name": "Q6", "weight": 1,
         "query": "SELECT ?x WHERE { ?x rdf:type ub:Student . }"},
        {"name": "Q1c", "weight": 2,
         "query": "SELECT ?x WHERE { ?x rdf:type ub:GraduateStudent . "
                  "?x ub:takesCourse {course} . }",
         "params": {"course": {"population": "graduate_course",
                               "zipf_s": 1.5}}}]}


def _bsbm_mix() -> dict:
    # B1-B10 and B12 of the explore mix: OPTIONAL, UNION, regex FILTERs
    # and Zipf constants.  B11 is left out: the program drops rdf:type
    # triples under a variable predicate, and its wrong answers are the
    # program fault of PERF.md section 7 item 2.
    mix = traffic.load("bsbm-explore")
    return {**mix, "schedule_seed": 5, "templates": [
        t for t in mix["templates"] if t["name"] != "B11"]}


# new configuration name: (copied configuration, new traffic mix, its body)
CASES = {
    "lubm-tiny-test": ("lubm-50", "lubm-pair-test", lambda: LUBM_PAIR),
    "bsbm-tiny-test": ("bsbm-20k", "bsbm-explore-test", _bsbm_mix),
}


def _files() -> dict:
    return {p: p.read_bytes() for p in CHIP.rglob("*")
            if p.is_file() and "__pycache__" not in p.parts}


@contextlib.contextmanager
def new_cell(name: str, tmp_path):
    """Write the case's configuration, traffic and metric as new files, and
    yield ``(root, cell)``: a checkout root whose BENCHMARK.json lists them
    beside the benchmark's own entries.  The files go again on exit."""
    source, mix, body = CASES[name]
    cfg = json.loads((CHIP / f"configs/{source}.json").read_text())
    new = {f"configs/{name}.json": {**cfg, "name": name},
           f"traffic/{mix}.json": body(), METRIC[0]: METRIC[1]}
    made = []
    try:
        for rel, content in new.items():
            path = CHIP / rel
            path.write_text(content if isinstance(content, str)
                            else json.dumps(content))
            made.append(path)
        bench = json.loads((ROOT / "BENCHMARK.json").read_text())
        bench["configs"].append({
            "name": name, "source": "test",
            "file": f"benchmarks/chip/configs/{name}.json",
            "reduced": [], "why": "test"})
        bench["workloads"].append({
            "name": mix, "config": name, "traffic": mix, "chips": 1,
            "why": "test"})
        bench["end_to_end"].append({
            "name": "answered_share_test", "unit": "%", "better": "higher",
            "bound": 0.01, "source": "host_clock", "workloads": [mix]})
        (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
        (tmp_path / "benchmarks").symlink_to(ROOT / "benchmarks")
        yield tmp_path, mix
    finally:
        for path in made:
            path.unlink()
        shutil.rmtree(CHIP / "metrics/__pycache__", ignore_errors=True)


@pytest.mark.parametrize("name", sorted(CASES, reverse=True))
def test_new_files_make_a_new_cell(tiny_run, tmp_path, name):
    before = _files()
    with new_cell(name, tmp_path) as (root, cell):
        res = tiny_run(cell, root=root, rate=2.0)
    assert res["correct"], res["checks"]
    assert res["metrics"]["answered_share_test"]["value"] == 100.0
    assert {"qps", "setup_s"} <= set(res["metrics"])
    assert _files() == before


def test_altered_answer_is_not_correct_on_bsbm(tiny_run, tmp_path,
                                               monkeypatch):
    """The reference bites on BSBM: an answer altered where the engine
    produces it (``test_faults.py``'s fault) makes the run not correct."""
    from repro.core.sparql_exec import SparqlEngine

    monkeypatch.setattr(SparqlEngine, "execute_compiled",
                        _drop_row(SparqlEngine.execute_compiled))
    monkeypatch.setattr(SparqlEngine, "_finish_param",
                        _drop_row(SparqlEngine._finish_param))
    with new_cell("bsbm-tiny-test", tmp_path) as (root, cell):
        res = tiny_run(cell, root=root, rate=2.0)
    assert not res["correct"]
    assert res["checks"]["wrong_answers"]["value"] > 0
