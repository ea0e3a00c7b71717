"""Adaptive-capacity pipelined executor tests.

Covers: overflow/suffix-resume exactness (forced tiny capacities must give
the same multiset as an overflow-free run), suffix-resume locality (retries
land on the overflowing step only, earlier steps are not re-executed —
asserted via the Result.stats step counters), count-only vs bindings
equivalence across every ExecOpts toggle, the int32 cumsum widening on a
high-degree star graph, async double-buffering, profiled stats, and the
engine-level OPTIONAL/analyze paths.
"""

import numpy as np
import pytest

from conftest import (given, random_labeled_graph, random_query_graph,
                      settings, st)

from repro.core import ExecOpts, Executor, build_plan
from repro.core.reference import enumerate_matches


def _tiny_plan(g, q):
    """Plan with presizing estimates stripped: tiny caps force resumes."""
    plan = build_plan(g, q)
    plan.est_fanout = []
    plan.est_expand = []
    return plan


def _multiset(res, n_pvars):
    return sorted(
        (tuple(b), tuple(p[:n_pvars]))
        for b, p in zip(res.bindings.tolist(), res.pvar_bindings.tolist()))


# ------------------------------------------------------- suffix resume
@given(st.integers(0, 10_000), st.integers(1, 5), st.booleans(),
       st.booleans())
@settings(max_examples=10, deadline=None)
def test_suffix_resume_exactness(seed, chunk, use_fused, count_mode):
    """Forced-overflow runs (init_cap=8, tiny chunks) return exactly the
    no-retry run's results, bindings and count alike."""
    rng = np.random.default_rng(seed)
    g = random_labeled_graph(rng, n_vertices=11, p_edge=0.4)
    q = random_query_graph(rng, g, n_qv=3)
    want = Executor(g, ExecOpts()).run(build_plan(g, q))
    ex = Executor(g, ExecOpts(init_cap=8, chunk=chunk, use_fused=use_fused))
    if count_mode:
        got = ex.run(_tiny_plan(g, q), collect="count")
        assert got.count == want.count
        assert got.bindings is None
    else:
        got = ex.run(_tiny_plan(g, q))
        assert _multiset(got, len(q.pvars)) == _multiset(want, len(q.pvars))


def test_suffix_resume_reexecutes_only_overflowing_step():
    """When step k overflows, steps < k must not run again: their expansion
    totals match the overflow-free run exactly (no double counting), and
    the retry counters sit on step k alone."""
    rng = np.random.default_rng(7)
    g = random_labeled_graph(rng, n_vertices=14, p_edge=0.6)
    q = random_query_graph(rng, g, n_qv=4, with_labels=False, with_id=False)
    want = Executor(g, ExecOpts()).run(build_plan(g, q))
    ex = Executor(g, ExecOpts(init_cap=8, chunk=4))
    got = ex.run(_tiny_plan(g, q))
    assert got.count == want.count
    st_ = got.stats
    assert st_["resumes"] > 0
    # exactness of the per-step totals proves no step was re-executed
    assert st_["step_rows"] == want.stats["step_rows"]
    assert st_["step_kept"] == want.stats["step_kept"]
    # every resume is attributed to exactly one overflowing step
    assert sum(st_["step_retries"]) == st_["resumes"]


def test_legacy_mode_still_exact():
    """cap_schedule=False + suffix_resume=False reproduces the old
    whole-chunk-retry executor, bit-for-bit results."""
    rng = np.random.default_rng(7)
    g = random_labeled_graph(rng, n_vertices=14, p_edge=0.6)
    q = random_query_graph(rng, g, n_qv=4, with_labels=False, with_id=False)
    want = Executor(g, ExecOpts()).run(build_plan(g, q))
    ex = Executor(g, ExecOpts(init_cap=8, chunk=4, cap_schedule=False,
                              suffix_resume=False, async_chunks=1,
                              use_fused=False))
    got = ex.run(_tiny_plan(g, q))
    assert _multiset(got, len(q.pvars)) == _multiset(want, len(q.pvars))
    assert got.chunks_retried > 0


# ------------------------------------------- count == bindings, all toggles
@pytest.mark.parametrize("toggles", [
    {},
    {"use_fused": False},
    {"cap_schedule": False},
    {"suffix_resume": False},
    {"async_chunks": 1},
    {"async_chunks": 3, "chunk": 3},
    {"semantics": "iso"},
    {"use_int": False},
    {"use_nlf": True, "use_deg": True},
    {"init_cap": 8, "chunk": 2},
])
def test_count_matches_bindings(toggles):
    rng = np.random.default_rng(99)
    g = random_labeled_graph(rng, n_vertices=12, p_edge=0.4)
    opts = ExecOpts(**toggles)
    for seed in range(3):
        rngq = np.random.default_rng(700 + seed)
        q = random_query_graph(rngq, g, n_qv=3, with_pvar=True)
        plan = build_plan(g, q, use_nlf=opts.use_nlf, use_deg=opts.use_deg)
        ex = Executor(g, opts)
        res_b = ex.run(plan, collect="bindings")
        res_c = ex.run(plan, collect="count")
        assert res_c.count == res_b.count
        assert res_c.bindings is None
        ref = enumerate_matches(g, q, semantics=opts.semantics)
        assert res_b.count == len(ref)


# --------------------------------------------------- int32 cumsum widening
def test_int32_cumsum_widening_star_graph():
    """A wide chunk expanding a 40k-degree star hub makes cap * max_degree
    exceed 2**31 — the widened total check must keep the count exact
    instead of wrapping into silent truncation."""
    from repro.rdf.graph import LabeledGraph

    n, hub_deg = 70_000, 40_000
    src = np.concatenate([np.arange(1, n, dtype=np.int64),
                          np.zeros(hub_deg, np.int64)])
    dst = np.concatenate([np.zeros(n - 1, np.int64),
                          np.arange(1, hub_deg + 1, dtype=np.int64)])
    el = np.zeros(src.shape[0], np.int64)
    g = LabeledGraph.build(n, src, el, dst, 1, [()] * n, 1)

    from repro.core.query import QEdge, QueryGraph, QVertex
    q = QueryGraph()
    q.vertices = [QVertex("x"), QVertex("y")]
    q.var_to_vertex = {"x": 0, "y": 1}
    q.edges = [QEdge(0, 1, 0)]

    opts = ExecOpts(chunk=1 << 16, init_cap=1 << 16)
    plan = build_plan(g, q, estimate="static")
    # the hazard condition the widening guards: chunk rows × max degree
    assert (1 << 16) * hub_deg >= 2**31
    res = Executor(g, opts).run(plan, collect="count")
    assert res.count == (n - 1) + hub_deg


# ----------------------------------------------------------- stats & async
def test_stats_populated_and_async_invariance():
    rng = np.random.default_rng(3)
    g = random_labeled_graph(rng, n_vertices=13, p_edge=0.45)
    q = random_query_graph(rng, g, n_qv=3, with_labels=False, with_id=False)
    plan = build_plan(g, q)
    n_src = plan.start_candidates.shape[0]
    assert n_src > 1  # label-free start: several candidates -> several chunks
    base = Executor(g, ExecOpts(chunk=1, async_chunks=1)).run(plan)
    deep = Executor(g, ExecOpts(chunk=1, async_chunks=4)).run(plan)
    assert _multiset(base, len(q.pvars)) == _multiset(deep, len(q.pvars))
    st_ = deep.stats
    n_steps = len(plan.steps)
    assert len(st_["step_rows"]) == n_steps
    assert len(st_["caps"]) == n_steps
    assert st_["chunks"] == n_src  # one dispatch per single-row chunk
    assert st_["wall_ms"] > 0
    assert st_["step_kept"][-1] == deep.count


def test_profile_mode_wall_times():
    rng = np.random.default_rng(5)
    g = random_labeled_graph(rng, n_vertices=12, p_edge=0.4)
    q = random_query_graph(rng, g, n_qv=3)
    plan = build_plan(g, q)
    want = Executor(g, ExecOpts()).run(plan)
    got = Executor(g, ExecOpts()).run(plan, profile=True)
    assert _multiset(got, len(q.pvars)) == _multiset(want, len(q.pvars))
    wall = got.stats["step_wall_ms"]
    assert wall is not None and len(wall) == len(plan.steps)
    assert all(w > 0 for w in wall)


def test_profile_mode_resumes_exact():
    """Profiled execution with forced overflow still returns exact rows."""
    rng = np.random.default_rng(7)
    g = random_labeled_graph(rng, n_vertices=14, p_edge=0.6)
    q = random_query_graph(rng, g, n_qv=4, with_labels=False, with_id=False)
    want = Executor(g, ExecOpts()).run(build_plan(g, q))
    got = Executor(g, ExecOpts(init_cap=8, chunk=4)).run(
        _tiny_plan(g, q), profile=True)
    assert _multiset(got, len(q.pvars)) == _multiset(want, len(q.pvars))
    assert got.stats["resumes"] > 0


def test_grow_caps_to_observed_need():
    """An overflow grows the step to the power of two that holds what the
    dispatch counted (at least doubling, at most max_cap), and later steps
    stay monotone."""
    from repro.core.exec import _grow_caps

    assert _grow_caps([8, 8, 16], 1, 1 << 10, need=100) == [8, 128, 128]
    assert _grow_caps([8, 8], 0, 1 << 10, need=3) == [16, 16]
    assert _grow_caps([8, 8], 0, 1 << 10) == [16, 16]
    assert _grow_caps([8, 8], 0, 64, need=1000) == [64, 64]
    with pytest.raises(RuntimeError, match="max capacity"):
        _grow_caps([64], 0, 64, need=1000)


@pytest.mark.parametrize("profile", [False, True])
def test_overflow_resumes_once_per_step(profile):
    """With one chunk, each step overflows at most once: the first
    overflow grows its capacity to the whole expansion it counted."""
    rng = np.random.default_rng(3)
    g = random_labeled_graph(rng, n_vertices=40, p_edge=0.5)
    q = random_query_graph(rng, g, n_qv=3, with_labels=False, with_id=False)
    want = Executor(g, ExecOpts()).run(build_plan(g, q))
    got = Executor(g, ExecOpts(init_cap=8, chunk=64)).run(
        _tiny_plan(g, q), profile=profile)
    assert _multiset(got, len(q.pvars)) == _multiset(want, len(q.pvars))
    assert got.stats["resumes"] > 0
    assert max(got.stats["step_retries"]) == 1


def test_small_probe_keeps_pipeline_for_plans_that_are_not_small(
        lubm_graph, monkeypatch):
    """A plan shortlisted by a wrong estimate runs once: its first run
    shows it is not small, so no legacy run is timed against it."""
    import repro.core.exec as ex
    from repro.core import build_query_graph
    from repro.rdf.sparql import parse_sparql
    from repro.rdf.workloads import LUBM_QUERIES

    g, maps = lubm_graph
    q = build_query_graph(parse_sparql(LUBM_QUERIES["Q2"]).where.triples, maps)
    plan = build_plan(g, q)
    exe = Executor(g, ExecOpts())
    monkeypatch.setattr(ex, "_SMALL_PLAN_ROWS", max(1.0, plan.estimated_rows()))
    assert ex._small_plan(plan, exe.opts)
    runs = []
    real = exe._run_impl
    monkeypatch.setattr(exe, "_run_impl",
                        lambda *a, **kw: runs.append(a[-1]) or real(*a, **kw))
    res = exe.run(plan)
    assert res.count == len(enumerate_matches(g, q)) > 4
    assert "small_probe" not in res.stats
    assert exe._small_mode[plan.signature()] is False
    assert runs == [None, exe.opts]  # the served call and one pipelined run


def _step_spans(trace: dict) -> list[dict]:
    out, todo = [], [trace["root"]]
    while todo:
        span = todo.pop()
        if span.get("name") == "step":
            out.append(span["meta"])
        todo.extend(reversed(span.get("children", ())))
    return sorted(out, key=lambda m: m["step"])


@pytest.mark.parametrize("mode", ["", "pallas_interpret"])
def test_step_spans_name_the_traced_kernels(lubm_graph, monkeypatch, mode):
    """Step spans carry what each step's program was traced with, and its
    implementation; the expansion kernel is the one ``kernel`` names."""
    from repro.core import SparqlEngine
    from repro.kernels import ops
    from repro.rdf.workloads import LUBM_QUERIES

    g, maps = lubm_graph
    monkeypatch.setenv("REPRO_KERNELS", mode)
    res = SparqlEngine(g, maps, ExecOpts()).query(LUBM_QUERIES["Q2"],
                                                  trace=True)
    steps = _step_spans(res.stats["trace"])
    assert [m["kernel"] for m in steps] == ["expand_filter", "ragged_expand"]
    impl = ops.PALLAS_INTERPRET if mode else ops.XLA
    assert steps[0]["kernels"] == ["expand_filter:xla"]
    assert steps[1]["kernels"][:3] == [
        "ragged_expand:xla", f"bitmap_superset:{impl}",
        "signature_filter:xla"]
    base = res.stats["exec"]["branches"][0]["base"]
    assert base["step_kernels"] == ["expand_filter", "ragged_expand"]


# --------------------------------------------------- engine-level coverage
def test_engine_optional_under_tiny_caps(lubm_graph):
    g, maps = lubm_graph
    from repro.core import SparqlEngine

    q = """SELECT ?x ?e WHERE { ?x rdf:type ub:GraduateStudent .
           OPTIONAL { ?x ub:emailAddress ?e } }"""
    want = SparqlEngine(g, maps, ExecOpts()).query(q)
    got = SparqlEngine(g, maps, ExecOpts(init_cap=8, chunk=4)).query(q)
    assert sorted(map(tuple, want.rows.tolist())) == \
        sorted(map(tuple, got.rows.tolist()))


def test_engine_count_only_and_analyze(lubm_graph):
    g, maps = lubm_graph
    from repro.core import SparqlEngine

    eng = SparqlEngine(g, maps, ExecOpts())
    q = """SELECT ?x ?y WHERE { ?x rdf:type ub:GraduateStudent .
           ?x ub:memberOf ?y . }"""
    full = eng.query(q)
    cnt = eng.query(q, collect="count")
    assert cnt.count == full.count
    assert cnt.rows.shape[0] == 0
    assert full.stats["exec"]["branches"][0]["base"]["step_kept"][-1] \
        == full.count
    ex = eng.explain(q, analyze=True)
    assert ex["actual_rows"] == full.count
    steps = ex["branches"][0]["steps"]
    assert all("actual_rows" in s and "wall_ms" in s for s in steps)
    assert steps[-1]["actual_rows"] == full.count
