"""The served answer body (``serve/server.py:_answer_body``, fragments
taken from a dataset's ``AnswerTable``) against the plain dict encoder
``_bindings_json`` and ``json.dumps``, byte for byte."""

import json

import numpy as np
import pytest

from conftest import given, settings, st
from repro.core.sparql_exec import QueryResult
from repro.obs import Trace
from repro.rdf.transform import type_aware_transform
from repro.rdf.triples import TripleStore
from repro.serve.metrics import ServeMetrics
from repro.serve.scheduler import Scheduler
from repro.serve.server import (DatasetRegistry, _answer_body,
                                _bindings_json, _fragments)

# URIs and literals whose JSON needs escaping: quotes, backslashes,
# control characters, non-ASCII, a typed and a language-tagged literal
TRIPLES = [
    ("ub:a", "ub:p", '"plain"'),
    ("ub:a", "ub:q\"", '"with \\"quote\\" and \\\\ back"'),
    ("ub:b\\c", "ub:p", '"ctl \x01\x1f\t\n\r end"'),
    ("<http://example.org/naïve/☃>", "ub:ü",
     '"été \U0001d11e"'),
    ("ub:a", "ub:n", '"5"^^xsd:integer'),
    ("ub:b\\c", "ub:n", '"chat"@fr'),
    ("ub:end\"", "ub:p", "ub:a"),
    ("ub:a", "ub:p", "ub:b\\c"),
]


class _FrozenTrace(Trace):
    """A trace whose clock stands still, so its span tree serializes the
    same way twice."""

    def _now(self) -> float:
        return 0.25


@pytest.fixture(scope="module")
def registry():
    st_ = TripleStore()
    st_.add_many(TRIPLES)
    g, maps = type_aware_transform(st_.finalize())
    reg = DatasetRegistry(ServeMetrics())
    reg.register("d", g, maps)
    return reg


def _expected(reg, res, limit, qid=None, trace=None) -> bytes:
    out = _bindings_json(reg, "d", res, limit)
    if qid:
        out["query_id"] = qid
    if trace is not None:
        out["trace"] = trace.finish().to_dict()
    return json.dumps(out).encode()


@st.composite
def _answers(draw, n_vertices: int, n_elabels: int):
    k = draw(st.integers(0, 4))
    # a name may repeat: its row keeps the first place and the last column
    names = draw(st.lists(st.sampled_from(["x", "y", "z", "é", 'q"']),
                          min_size=k, max_size=k))
    kinds = draw(st.lists(st.sampled_from(["vertex", "predicate"]),
                          min_size=k, max_size=k))
    n = draw(st.integers(0, 24))
    rows = np.empty((n, k), dtype=np.int32)
    for c, kind in enumerate(kinds):
        hi = (n_vertices if kind == "vertex" else n_elabels) - 1
        null_share = draw(st.sampled_from([0.0, 0.3, 1.0]))
        for i in range(n):
            rows[i, c] = (-1 if draw(st.floats(0, 1)) < null_share
                          else draw(st.integers(0, hi)))
    count = draw(st.integers(n, n + 5))
    limit = draw(st.one_of(st.none(), st.integers(-1, n + 2)))
    return QueryResult(names, rows, kinds, count=count), limit


@given(data=st.data())
@settings(max_examples=200, deadline=None)
def test_body_is_json_dumps_of_the_dict_encoder(registry, data):
    maps = registry.get("d").maps
    res, limit = data.draw(_answers(len(maps.vertex_to_term),
                                    len(maps.elabel_to_pred)))
    qid = data.draw(st.one_of(st.none(), st.just(""),
                              st.text(max_size=8)))
    trace = None
    if data.draw(st.booleans()):
        trace = _FrozenTrace(profile_steps=True)
        trace.add("execute", 0.125, rows=res.rows.shape[0])
        trace.query_id = qid or None
    body = _answer_body(registry, "d", res, limit, qid, inline=trace)
    assert body == _expected(registry, res, limit, qid, trace)


@pytest.mark.parametrize("limit", [None, 0, 1, 3, 4, 100])
def test_nulls_first_column_and_limits(registry, limit):
    # a null first column, a null middle and last column, a row all null
    rows = np.array([[-1, 0, 1], [2, -1, -1], [-1, -1, -1], [3, 4, 0]],
                    dtype=np.int32)
    res = QueryResult(["a", "b", "p"], rows, ["vertex", "vertex",
                                              "predicate"], count=4)
    body = _answer_body(registry, "d", res, limit, "q-7")
    assert body == _expected(registry, res, limit, "q-7")
    out = json.loads(body)
    assert out["stats"]["returned"] == min(4, 4 if limit is None else limit)
    if limit != 0:  # no ", " before the first present key
        assert b'"bindings": [{"b": {"type": ' in body


@pytest.mark.parametrize("query", [
    "SELECT ?s ?o WHERE { ?s ub:p ?o . }",
    "SELECT ?s ?p ?o WHERE { ?s ?p ?o . }",
    "SELECT ?s ?o WHERE { ?s ub:absent ?o . }",
])
@pytest.mark.parametrize("limit", [None, 0, 2])
def test_executed_answers(registry, query, limit):
    res = registry.execute("d", query)
    assert _answer_body(registry, "d", res, limit, "q-1") == \
        _expected(registry, res, limit, "q-1")


def test_no_rows_of_no_width(registry):
    # what SparqlEngine returns when no branch ran
    res = QueryResult(["s", "o"], np.zeros((0, 0), np.int32),
                      ["vertex", "vertex"], count=0)
    for limit in (None, 0, 3):
        assert _answer_body(registry, "d", res, limit) == \
            _expected(registry, res, limit)


def test_every_term_and_predicate_as_the_dict_encoder_writes_it(registry):
    maps = registry.get("d").maps
    nv, ne = len(maps.vertex_to_term), len(maps.elabel_to_pred)
    for kind, n in (("vertex", nv), ("predicate", ne)):
        res = QueryResult(["v"], np.arange(n, dtype=np.int32)[:, None],
                          [kind], count=n)
        assert _answer_body(registry, "d", res, None) == \
            _expected(registry, res, None)
    literals = [t for t in maps.dict.terms.to_str if t.startswith('"')]
    assert '"5"^^xsd:integer' in literals  # a typed literal is served
    assert any(ord(ch) < 0x20 for t in literals for ch in t)
    assert any(ord(ch) > 0x7f for t in literals for ch in t)


@given(st.text())
@settings(max_examples=300, deadline=None)
def test_fragment_of_any_term(term):
    kind = "literal" if term.startswith('"') else "uri"
    assert _fragments([term]) == [json.dumps(
        {"type": kind, "value": term.strip('"')}).encode()]


def test_updatable_dataset_extends_its_table(lubm_graph):
    g, maps = lubm_graph
    registry = DatasetRegistry(ServeMetrics())
    registry.register("live", g, maps, updatable=True)
    ds = registry.get("live")
    before = len(ds.answers.vertices)
    query = "SELECT ?s ?o WHERE { ?s ub:answerTableProbe ?o . }"
    registry.update("live", """INSERT DATA {
        ub:NewStudent0 ub:answerTableProbe "new \\"one\\" é" .
        ub:NewStudent1 ub:answerTableProbe ub:NewStudent0 . }""")
    with Scheduler(registry, workers=1) as sched:
        traces = [Trace(sampled=True), Trace(sampled=True)]
        results = [sched.submit("live", query, trace=t) for t in traces]
    for i, (res, trace) in enumerate(zip(results, traces)):
        qid = res.stats["query_id"]
        body = _answer_body(registry, "live", res, None, qid)
        (decode,) = trace.find("decode")
        assert decode.meta["rows"] == res.count == 2
        if i == 0:  # the first answer that names the new vertices
            # two subjects, a literal and a predicate
            assert decode.meta["extended"] == 4
        else:
            assert decode.meta["extended"] == 0
        out = _bindings_json(registry, "live", res, None)
        out["query_id"] = qid
        assert body == json.dumps(out).encode()
    extended = traces[0].find("decode")[0].meta["extended"]
    assert len(ds.answers.vertices) == before + 3
    assert registry.metrics.answer_table_extended.value(
        dataset="live") == extended
    assert "repro_answer_table_extended_total" in \
        registry.metrics.registry.render()
