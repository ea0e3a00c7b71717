"""The benchmark's own raw RDF data: an integer triple table and its terms.

A data generator (``datagen/<name>.py``) fills a :class:`Builder` and
returns the :class:`Dataset` it makes.  The same dataset feeds the program
under test (as term strings, through its own load path) and the plain
reference (as integers), so the reference never reads anything the program
has built.  Terms are written as the repository's datasets write them:
prefixed names (``ub:Dept0.Univ0``) for IRIs and double-quoted lexical
forms (``"Research12"``) for literals.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

RDF_TYPE = "rdf:type"
RDFS_SUBCLASSOF = "rdf:subClassOf"


@dataclass
class Dataset:
    terms: list[str]          # term id -> term string
    preds: list[str]          # predicate id -> predicate name
    s: np.ndarray             # int64 [n] subject term ids
    p: np.ndarray             # int64 [n] predicate ids
    o: np.ndarray             # int64 [n] object term ids
    populations: dict[str, list[int]] = field(default_factory=dict)

    @property
    def n_triples(self) -> int:
        return int(self.s.shape[0])

    def term_strings(self):
        """``(s, p, o)`` string triples, in table order."""
        terms, preds = self.terms, self.preds
        for s, p, o in zip(self.s.tolist(), self.p.tolist(),
                           self.o.tolist()):
            yield terms[s], preds[p], terms[o]


class Builder:
    """Append-only triple table.  ``entity`` gives a fresh id to a term the
    caller knows to be new; ``shared`` interns a term that recurs (classes,
    repeated literals, entities referred to before they are made)."""

    def __init__(self) -> None:
        self.terms: list[str] = []
        self._shared: dict[str, int] = {}
        self.preds: list[str] = []
        self._pred: dict[str, int] = {}
        self.S: list[int] = []
        self.P: list[int] = []
        self.O: list[int] = []
        self.populations: dict[str, list[int]] = {}

    def entity(self, term: str) -> int:
        self.terms.append(term)
        return len(self.terms) - 1

    def shared(self, term: str) -> int:
        tid = self._shared.get(term)
        if tid is None:
            tid = self._shared[term] = self.entity(term)
        return tid

    def pred(self, name: str) -> int:
        pid = self._pred.get(name)
        if pid is None:
            pid = self._pred[name] = len(self.preds)
            self.preds.append(name)
        return pid

    def add(self, s: int, p: int, o: int) -> None:
        self.S.append(s)
        self.P.append(p)
        self.O.append(o)

    def subclasses(self, pairs) -> None:
        sc = self.pred(RDFS_SUBCLASSOF)
        for sub, sup in pairs:
            self.add(self.shared(sub), sc, self.shared(sup))

    def population(self, name: str, tid: int) -> None:
        self.populations.setdefault(name, []).append(tid)

    def build(self) -> Dataset:
        return Dataset(self.terms, self.preds,
                       np.asarray(self.S, dtype=np.int64),
                       np.asarray(self.P, dtype=np.int64),
                       np.asarray(self.O, dtype=np.int64),
                       self.populations)
