"""The timed path broken underneath: ``correct`` comes out false.

Each fault is planted in the program's answer path for one run on the CPU
at a tiny scale, with the chip check skipped:

- an answer altered where it is produced (the engine drops a row of each
  non-empty result, on the plain path and the parameterized path);
- half of a same-shape batch left out (the registry returns the second
  half of each batched dispatch's members empty).  Only ``lubm-anchored``
  forms such batches; ``lubm-mix`` sends fixed constants, whose identical
  requests coalesce instead.  The run holds under-full batches open for
  a while, as ``batch_window_ms`` lets the server do, so that the CPU's
  short flights still meet in batches.
"""

from __future__ import annotations

import numpy as np
import pytest

from repro.core.sparql_exec import QueryResult, SparqlEngine
from repro.serve.server import DatasetRegistry


def _drop_row(fn):
    def call(*a, **kw):
        res = fn(*a, **kw)
        if res.rows.shape[0]:
            res.rows = res.rows[:-1]
            res.count = int(res.rows.shape[0])
        return res
    return call


@pytest.mark.parametrize("cell", ["lubm-mix", "lubm-anchored"])
def test_altered_answer_is_not_correct(tiny_run, anchored_root, monkeypatch,
                                       cell):
    monkeypatch.setattr(SparqlEngine, "execute_compiled",
                        _drop_row(SparqlEngine.execute_compiled))
    monkeypatch.setattr(SparqlEngine, "_finish_param",
                        _drop_row(SparqlEngine._finish_param))
    res = tiny_run(cell, rate=6.0, root=anchored_root)
    assert not res["correct"]
    assert res["checks"]["wrong_answers"]["value"] > 0


def test_half_batch_left_out_is_not_correct(tiny_run, anchored_root,
                                            monkeypatch):
    sizes = []
    orig = DatasetRegistry.execute_canonical_batch

    def half(self, name, pqs, version, **kw):
        out = orig(self, name, pqs, version, **kw)
        sizes.append(len(out))
        for i in range(len(out) // 2, len(out)):
            r = out[i]
            if isinstance(r, QueryResult):
                out[i] = QueryResult(r.variables,
                                     np.zeros((0, r.rows.shape[1]),
                                              np.int32), r.kinds, count=0,
                                     stats=r.stats)
        return out

    monkeypatch.setattr(DatasetRegistry, "execute_canonical_batch", half)
    res = tiny_run("lubm-anchored", rate=60.0, seconds=4.0,
                   root=anchored_root, server={"batch_window_ms": 100.0})
    assert max(sizes) >= 2
    assert not res["correct"]
    assert res["checks"]["wrong_answers"]["value"] > 0
