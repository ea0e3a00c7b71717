"""The plain reference against the program's engine, template by template,
and the answer digest catching an altered answer."""

from __future__ import annotations

import json

import numpy as np
import pytest

from benchmarks.chip import traffic
from benchmarks.chip.answers import digest_response
from benchmarks.chip.reference import Reference, parse
from benchmarks.chip.run import generate
from benchmarks.chip.tests.conftest import SEED, rehearsed


def _engine(ds):
    from repro.rdf.transform import type_aware_transform
    from repro.rdf.triples import TripleStore
    from repro.serve.server import DatasetRegistry

    st = TripleStore()
    for s, p, o in ds.term_strings():
        st.add(s, p, o)
    st.finalize()
    reg = DatasetRegistry()
    reg.register("d", *type_aware_transform(st))
    return reg


def _served(reg, query: str) -> bytes:
    from repro.serve.server import _bindings_json

    return json.dumps(_bindings_json(reg, "d", reg.execute("d", query),
                                     None)).encode()


def _rehearsed(name: str):
    ds = generate(rehearsed(name), SEED)
    return ds, Reference(ds), _engine(ds)


@pytest.fixture(scope="module")
def lubm():
    return _rehearsed("lubm-50")


@pytest.fixture(scope="module")
def bsbm():
    return _rehearsed("bsbm-20k")


def _cases(mix_name: str, skip=()):
    mix = traffic.load(mix_name)
    return [(mix_name, t["name"]) for t in mix["templates"]
            if t["name"] not in skip]


def _query(ds, mix_name: str, name: str) -> str:
    mix = traffic.load(mix_name)
    tpl = next(t for t in mix["templates"] if t["name"] == name)
    return traffic._Drawer(ds, np.random.default_rng(3)).fill(tpl)


@pytest.mark.parametrize("mix,name", _cases("lubm-mix")
                         + _cases("lubm-anchored"))
def test_reference_agrees_with_engine_lubm(lubm, mix, name):
    ds, ref, reg = lubm
    q = _query(ds, mix, name)
    assert digest_response(_served(reg, q)) == ref.answer(q)


# B11 binds a variable predicate; the engine leaves out the subject's
# rdf:type triples there (PERF.md, Open questions), so it is not compared
@pytest.mark.parametrize("mix,name", _cases("bsbm-explore", skip=("B11",)))
def test_reference_agrees_with_engine_bsbm(bsbm, mix, name):
    ds, ref, reg = bsbm
    q = _query(ds, mix, name)
    assert digest_response(_served(reg, q)) == ref.answer(q)


def test_variable_predicate_matches_types(bsbm):
    ds, ref, _ = bsbm
    head, rows = ref.solve("SELECT ?p ?v WHERE { b:Offer0.0 ?p ?v . }")
    preds = {ds.terms[x] if x < len(ds.terms) else None for x in rows[:, 0]}
    assert head == ["p", "v"]
    vals = {ds.terms[v] for p, v in rows.tolist()
            if ds.preds[p] == "rdf:type"}
    assert vals == {"b:Offer"}, preds


def test_entailment_and_its_control(lubm):
    ds, ref, _ = lubm
    plain = Reference(ds, entail=False)
    q = "SELECT ?x WHERE { ?x rdf:type ub:Student . }"
    n_ent, n_plain = ref.answer(q)[0], plain.answer(q)[0]
    assert n_plain == 0 < n_ent


@pytest.mark.parametrize("alter", ["drop", "duplicate", "value"])
def test_digest_catches_an_altered_answer(lubm, alter):
    ds, ref, reg = lubm
    q = _query(ds, "lubm-mix", "Q4")
    doc = json.loads(_served(reg, q))
    rows = doc["results"]["bindings"]
    if alter == "drop":
        rows.pop()
    elif alter == "duplicate":
        rows.append(rows[0])
    else:
        rows[0]["y1"]["value"] += "x"
    assert digest_response(json.dumps(doc).encode()) != ref.answer(q)


def test_parser_reads_the_fragment():
    q = parse('SELECT DISTINCT ?a WHERE { { ?a b:p ub:X.Y . } UNION '
              '{ ?a a b:C } OPTIONAL { ?a b:q ?v . } '
              'FILTER (?v > 3 && ?v != 4) FILTER regex(?a, "x") }')
    assert q.distinct and q.select == ["a"]
    kinds = [k for k, _ in q.where.elements]
    assert kinds == ["union", "optional"]
    assert len(q.where.filters) == 3
