"""Canonical form of one SPARQL answer, shared by both sides of the check.

The load generator reduces each HTTP response to ``(rows, digest)``; the
reference reduces its own answer to the same pair.  A row is the sorted
list of its bound ``(variable, type, value)`` triples, so an unbound
OPTIONAL variable is simply absent, as it is in SPARQL JSON results.
The digest covers the head variables and the multiset of rows, so a
missing, extra, duplicated or altered row changes it.

Standard library only: the load generator imports this and nothing of JAX.
"""

from __future__ import annotations

import hashlib
import json


def term_value(term: str) -> tuple[str, str]:
    """``(type, value)`` of a data term as SPARQL JSON results give it."""
    if term.startswith('"'):
        return "literal", term[1:-1]
    return "uri", term


def digest_rows(head: list[str], rows) -> tuple[int, str]:
    """``rows`` yields ``{var: (type, value)}`` dicts of bound variables."""
    lines = sorted(json.dumps(sorted((v, t, x) for v, (t, x) in row.items()),
                              separators=(",", ":"))
                   for row in rows)
    h = hashlib.sha256(json.dumps(sorted(head)).encode())
    for line in lines:
        h.update(b"\n")
        h.update(line.encode())
    return len(lines), h.hexdigest()


def digest_response(body: bytes) -> tuple[int, str]:
    """Digest of one ``/sparql`` JSON response body."""
    doc = json.loads(body)
    rows = ({v: (b["type"], b["value"]) for v, b in binding.items()}
            for binding in doc["results"]["bindings"])
    return digest_rows(doc["head"]["vars"], rows)
