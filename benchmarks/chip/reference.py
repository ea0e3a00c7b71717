"""Plain SPARQL reference over the benchmark's own raw triples.

It evaluates the fragment the traffic mixes use, straight from the SPARQL
1.1 algebra and independent of the program under test: a group pattern is
``Filter(conditions, ... LeftJoin(Join(Join(BGP, Union), ...), Optional))``
in the order its elements are written, under bag semantics, with
``SELECT`` projection and ``DISTINCT``.

Entailment is RDFS ``subClassOf`` on ``rdf:type`` only (rules rdfs9 and
rdfs11): the entailed ``rdf:type`` and ``rdf:subClassOf`` triples are
materialized once, so ``?x rdf:type ub:Student`` matches every graduate
student and a variable predicate also matches a subject's types.

Tables are numpy columns of term ids (``-1`` unbound); joins sort one
side and expand ranges, so a triangle over millions of triples stays in
numpy.  FILTER follows SPARQL's error semantics: a comparison whose
operand is unbound or not numeric is false, ``regex`` applies Python's
``re.search`` to a literal's lexical form and is false on an IRI.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field

import numpy as np

from benchmarks.chip.answers import digest_rows, term_value
from benchmarks.chip.triples import RDF_TYPE, RDFS_SUBCLASSOF, Dataset

# ------------------------------------------------------------------ syntax


@dataclass(frozen=True)
class Var:
    name: str


@dataclass(frozen=True)
class Const:
    term: str        # as in the data: prefixed IRI or '"lexical"'


@dataclass(frozen=True)
class Cmp:
    lhs: object
    op: str
    rhs: object


@dataclass(frozen=True)
class Regex:
    var: Var
    pattern: str


@dataclass
class Group:
    # ("bgp", [(s, p, o), ...]) | ("union", [Group, ...]) |
    # ("optional", Group) | ("group", Group)
    elements: list = field(default_factory=list)
    filters: list = field(default_factory=list)


@dataclass
class Query:
    select: list[str]      # empty: SELECT *
    distinct: bool
    where: Group


_TOKEN = re.compile(r"""
    (?P<ws>\s+|\#[^\n]*)
  | (?P<iri><[^>\s]*>)
  | (?P<lit>"(?:[^"\\]|\\.)*")
  | (?P<var>[?$][A-Za-z_]\w*)
  | (?P<num>[+-]?\d+(?:\.\d+)?)
  | (?P<op><=|>=|!=|&&|[{}().,=<>*])
  | (?P<name>[A-Za-z_][\w.\-]*(?::[\w.\-]*)?)
""", re.VERBOSE)


class ParseError(ValueError):
    pass


def _tokens(text: str) -> list[tuple[str, str]]:
    out, pos = [], 0
    while pos < len(text):
        m = _TOKEN.match(text, pos)
        if m is None:
            raise ParseError(f"cannot read {text[pos:pos + 20]!r}")
        pos = m.end()
        if m.lastgroup != "ws":
            tok = m.group()
            if m.lastgroup == "name" and tok.endswith("."):
                # 'ub:Univ0 .' is a term followed by the triple's dot
                out.append(("name", tok[:-1]))
                out.append(("op", "."))
                continue
            out.append((m.lastgroup, tok))
    out.append(("eof", ""))
    return out


class _Parser:
    def __init__(self, text: str):
        self.toks = _tokens(text)
        self.i = 0

    def peek(self, k: int = 0) -> tuple[str, str]:
        return self.toks[self.i + k]

    def take(self, want: str | None = None) -> tuple[str, str]:
        tok = self.toks[self.i]
        self.i += 1
        if want is not None and tok[1].upper() != want:
            raise ParseError(f"expected {want}, got {tok[1]!r}")
        return tok

    def query(self) -> Query:
        while self.peek()[1].upper() == "PREFIX":
            self.take()
            self.take()
            self.take()
        self.take("SELECT")
        distinct = self.peek()[1].upper() == "DISTINCT"
        if distinct:
            self.take()
        select = []
        if self.peek()[1] == "*":
            self.take()
        while self.peek()[0] == "var":
            select.append(self.take()[1][1:])
        self.take("WHERE")
        where = self.group()
        if self.peek()[0] != "eof":
            raise ParseError(f"unexpected {self.peek()[1]!r} after WHERE")
        return Query(select, distinct, where)

    def group(self) -> Group:
        self.take("{")
        g = Group()
        bgp: list = []

        def flush() -> None:
            if bgp:
                g.elements.append(("bgp", list(bgp)))
                bgp.clear()

        while True:
            kind, tok = self.peek()
            word = tok.upper()
            if tok == "}":
                self.take()
                flush()
                return g
            if word == "OPTIONAL":
                self.take()
                flush()
                g.elements.append(("optional", self.group()))
            elif word == "FILTER":
                self.take()
                g.filters.extend(self.condition())
            elif tok == "{":
                flush()
                branches = [self.group()]
                while self.peek()[1].upper() == "UNION":
                    self.take()
                    branches.append(self.group())
                g.elements.append(("union", branches) if len(branches) > 1
                                  else ("group", branches[0]))
            elif kind == "eof":
                raise ParseError("unexpected end inside a group")
            else:
                bgp.append((self.term(), self.term(pred=True), self.term()))
                if self.peek()[1] == ".":
                    self.take()

    def term(self, pred: bool = False):
        kind, tok = self.take()
        if kind == "var":
            return Var(tok[1:])
        if kind == "lit":
            return Const(tok)
        if kind == "num":
            return Const(f'"{tok}"')
        if kind == "name":
            if pred and tok == "a":
                return Const(RDF_TYPE)
            if tok in ("rdfs:subClassOf", "rdf:subClassOf"):
                return Const(RDFS_SUBCLASSOF)
            return Const(tok)
        if kind == "iri":
            return Const(tok[1:-1])
        raise ParseError(f"bad term {tok!r}")

    def condition(self) -> list:
        if self.peek()[1].upper() == "REGEX":
            self.take()
            self.take("(")
            var = self.term()
            self.take(",")
            kind, pat = self.take()
            if not isinstance(var, Var) or kind != "lit":
                raise ParseError("regex(?var, \"pattern\") expected")
            self.take(")")
            return [Regex(var, pat[1:-1])]
        self.take("(")
        out = []
        while True:
            lhs = self.term()
            op = self.take()[1]
            if op not in ("<", "<=", ">", ">=", "=", "!="):
                raise ParseError(f"bad comparison {op!r}")
            out.append(Cmp(lhs, op, self.term()))
            if self.peek()[1] != "&&":
                break
            self.take()
        self.take(")")
        return out


def parse(text: str) -> Query:
    return _Parser(text).query()


# ------------------------------------------------------------------ tables


class Table:
    """Bag of solutions: one int64 column of term ids per variable."""

    def __init__(self, cols: dict[str, np.ndarray], n: int):
        self.cols = cols
        self.n = n

    @staticmethod
    def unit() -> "Table":
        return Table({}, 1)

    def take(self, idx: np.ndarray) -> "Table":
        return Table({v: c[idx] for v, c in self.cols.items()},
                     int(idx.shape[0]))


def _keys(a: Table, b: Table, shared: list[str]):
    """Integer join keys of both sides over the shared variables."""
    if len(shared) == 1:
        return a.cols[shared[0]], b.cols[shared[0]]
    stacked = np.concatenate([np.stack([t.cols[v] for v in shared], axis=1)
                              for t in (a, b)])
    _, inv = np.unique(stacked, axis=0, return_inverse=True)
    inv = inv.reshape(-1)
    return inv[: a.n], inv[a.n:]


def _match(a: Table, b: Table) -> tuple[np.ndarray, np.ndarray]:
    """Row pairs ``(ia, ib)`` of compatible solutions."""
    shared = [v for v in a.cols if v in b.cols]
    for v in shared:
        if (a.cols[v] < 0).any() or (b.cols[v] < 0).any():
            raise NotImplementedError("join on a possibly unbound variable")
    if not shared:
        return (np.repeat(np.arange(a.n), b.n),
                np.tile(np.arange(b.n), a.n))
    ka, kb = _keys(a, b, shared)
    order = np.argsort(kb, kind="stable")
    sk = kb[order]
    lo = np.searchsorted(sk, ka, "left")
    hi = np.searchsorted(sk, ka, "right")
    cnt = hi - lo
    ia = np.repeat(np.arange(a.n), cnt)
    start = np.repeat(lo - (np.cumsum(cnt) - cnt), cnt)
    ib = order[np.arange(int(cnt.sum())) + start]
    return ia, ib


def join(a: Table, b: Table) -> Table:
    ia, ib = _match(a, b)
    cols = {v: c[ia] for v, c in a.cols.items()}
    for v, c in b.cols.items():
        if v not in cols:
            cols[v] = c[ib]
    return Table(cols, int(ia.shape[0]))


def left_join(a: Table, b: Table) -> Table:
    ia, ib = _match(a, b)
    joined = join(a, b) if ia.size else None
    lone = np.setdiff1d(np.arange(a.n), ia)
    cols = {}
    for v in dict.fromkeys([*a.cols, *b.cols]):
        parts = [joined.cols[v]] if joined is not None else []
        parts.append(a.cols[v][lone] if v in a.cols
                     else np.full(lone.shape[0], -1, np.int64))
        cols[v] = np.concatenate(parts)
    return Table(cols, (joined.n if joined is not None else 0)
                 + int(lone.shape[0]))


def union(tables: list[Table]) -> Table:
    names = list(dict.fromkeys(v for t in tables for v in t.cols))
    cols = {v: np.concatenate([t.cols[v] if v in t.cols
                               else np.full(t.n, -1, np.int64)
                               for t in tables]) for v in names}
    return Table(cols, sum(t.n for t in tables))


# ------------------------------------------------------------------ engine


class Reference:
    """Indexes ``ds`` once (with the entailed triples) and answers queries."""

    def __init__(self, ds: Dataset, entail: bool = True):
        self.terms = ds.terms
        self.term_id = {t: i for i, t in enumerate(ds.terms)}
        self.pred_id = {p: i for i, p in enumerate(ds.preds)}
        s, p, o = ds.s, ds.p, ds.o
        t_type = self.pred_id.get(RDF_TYPE, -1)
        t_sc = self.pred_id.get(RDFS_SUBCLASSOF, -1)
        if entail and t_sc >= 0:
            s, p, o = self._entail(s, p, o, t_type, t_sc)
        order = np.lexsort((o, s, p))
        p, s, o = p[order], s[order], o[order]
        new = np.ones(p.shape[0], bool)
        new[1:] = (np.diff(p) != 0) | (np.diff(s) != 0) | (np.diff(o) != 0)
        self.p, self.s, self.o = p[new], s[new], o[new]
        bounds = np.searchsorted(self.p, np.arange(len(ds.preds) + 1))
        self.slice = {i: (int(bounds[i]), int(bounds[i + 1]))
                      for i in range(len(ds.preds))}
        self._num: dict[int, float] = {}

    @staticmethod
    def _entail(s, p, o, t_type: int, t_sc: int):
        """Add rdfs11 (subClassOf is transitive) and rdfs9 (an instance of
        a class is an instance of its superclasses)."""
        sc = p == t_sc
        sup: dict[int, set[int]] = {}
        for a, b in zip(s[sc].tolist(), o[sc].tolist()):
            sup.setdefault(a, set()).add(b)
        closure: dict[int, set[int]] = {}
        for c in list(sup):
            seen, stack = set(), [c]
            while stack:
                for d in sup.get(stack.pop(), ()):
                    if d not in seen:
                        seen.add(d)
                        stack.append(d)
            closure[c] = seen
        pairs = [(c, d) for c, sups in closure.items() for d in sups]
        xs = [np.asarray([c for c, _ in pairs], np.int64)]
        xo = [np.asarray([d for _, d in pairs], np.int64)]
        xp = [np.full(len(pairs), t_sc, np.int64)]
        if t_type >= 0:
            ty = p == t_type
            ts, to = s[ty], o[ty]
            for c, d in pairs:
                inst = ts[to == c]
                xs.append(inst)
                xo.append(np.full(inst.shape[0], d, np.int64))
                xp.append(np.full(inst.shape[0], t_type, np.int64))
        return (np.concatenate([s, *xs]), np.concatenate([p, *xp]),
                np.concatenate([o, *xo]))

    # -------------------------------------------------------- patterns
    def _const(self, term: str) -> int:
        return self.term_id.get(term, -2)

    def pattern(self, s, p, o) -> Table:
        """Solutions of one triple pattern."""
        if isinstance(p, Const):
            pid = self.pred_id.get(p.term)
            if pid is None:
                lo = hi = 0
            else:
                lo, hi = self.slice[pid]
            ss, oo = self.s[lo:hi], self.o[lo:hi]
            pp = None
        else:
            ss, oo, pp = self.s, self.o, self.p
        keep = np.ones(ss.shape[0], bool)
        if isinstance(s, Const):
            keep &= ss == self._const(s.term)
        if isinstance(o, Const):
            keep &= oo == self._const(o.term)
        if isinstance(s, Var) and isinstance(o, Var) and s.name == o.name:
            keep &= ss == oo
        cols: dict[str, np.ndarray] = {}
        for term, col in ((s, ss), (o, oo), (p, pp)):
            if isinstance(term, Var) and term.name not in cols:
                cols[term.name] = col[keep]
        return Table(cols, int(keep.sum()))

    def bgp(self, triples: list) -> Table:
        """Join the patterns, smallest first, then always one that shares a
        variable with what is bound so far."""
        rels = [self.pattern(*t) for t in triples]
        rels.sort(key=lambda r: r.n)
        out = rels.pop(0)
        while rels:
            pick = next((i for i, r in enumerate(rels)
                         if set(r.cols) & set(out.cols)), 0)
            out = join(out, rels.pop(pick))
        return out

    def group(self, g: Group) -> Table:
        out = Table.unit()
        for kind, body in g.elements:
            if kind == "bgp":
                out = join(out, self.bgp(body))
            elif kind == "union":
                out = join(out, union([self.group(b) for b in body]))
            elif kind == "group":
                out = join(out, self.group(body))
            elif kind == "optional":
                out = left_join(out, self.group(body))
        for f in g.filters:
            out = out.take(np.flatnonzero(self._condition(f, out)))
        return out

    # --------------------------------------------------------- filters
    def _numeric(self, ids: np.ndarray) -> np.ndarray:
        out = np.full(ids.shape[0], np.nan)
        for i, tid in enumerate(ids.tolist()):
            if tid < 0:
                continue
            val = self._num.get(tid)
            if val is None:
                term = self.terms[tid]
                val = np.nan
                if term.startswith('"'):
                    try:
                        val = float(term[1:-1])
                    except ValueError:
                        pass
                self._num[tid] = val
            out[i] = val
        return out

    def _operand(self, term, t: Table):
        if isinstance(term, Var):
            col = t.cols.get(term.name)
            return (self._numeric(col) if col is not None
                    else np.full(t.n, np.nan))
        try:
            return float(term.term.strip('"'))
        except ValueError:
            return np.nan

    def _condition(self, f, t: Table) -> np.ndarray:
        if isinstance(f, Regex):
            col = t.cols.get(f.var.name)
            if col is None:
                return np.zeros(t.n, bool)
            pat = re.compile(f.pattern)
            hit = {}
            for tid in np.unique(col).tolist():
                term = self.terms[tid] if tid >= 0 else ""
                hit[tid] = (term.startswith('"')
                            and pat.search(term[1:-1]) is not None)
            return np.fromiter((hit[x] for x in col.tolist()), bool, t.n)
        lhs, rhs = self._operand(f.lhs, t), self._operand(f.rhs, t)
        with np.errstate(invalid="ignore"):
            res = {"<": np.less, "<=": np.less_equal, ">": np.greater,
                   ">=": np.greater_equal, "=": np.equal,
                   "!=": np.not_equal}[f.op](lhs, rhs)
            # a comparison with an unbound or non-numeric operand errs
            res = res & ~np.isnan(lhs) & ~np.isnan(rhs)
        return np.broadcast_to(res, (t.n,))

    # ----------------------------------------------------------- answers
    def solve(self, text: str) -> tuple[list[str], np.ndarray]:
        """``(head, rows)``: projected variables and an int64 table of
        term ids, ``-1`` where a variable is unbound."""
        q = parse(text)
        t = self.group(q.where)
        head = q.select or list(t.cols)
        rows = np.stack([t.cols.get(v, np.full(t.n, -1, np.int64))
                         for v in head], axis=1) if head else \
            np.zeros((t.n, 0), np.int64)
        if q.distinct:
            rows = np.unique(rows, axis=0)
        return head, rows

    def answer(self, text: str) -> tuple[int, str]:
        """``(rows, digest)`` of the query, as :mod:`answers` forms them."""
        head, rows = self.solve(text)
        terms = self.terms
        return digest_rows(head, (
            {v: term_value(terms[x]) for v, x in zip(head, row) if x >= 0}
            for row in rows.tolist()))
