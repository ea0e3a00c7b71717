"""repro.store — versioned live RDF store with delta-aware snapshots.

The paper's engine (and everything downstream of :class:`LabeledGraph`)
assumes an immutable graph built once from a finalized triple store.  This
package makes the data *live*: a :class:`VersionedStore` keeps the frozen
base graph plus an in-memory delta overlay (COO insert buffers and
tombstones over base edges), and hands out cheap immutable
:class:`Snapshot` views that queries execute against while writers keep
appending.  The executor merges base-CSR adjacency with the snapshot's
small sorted delta adjacency per expansion step (``kernels.ops.delta_merge``),
so no CSR rebuild happens on the write path; a threshold-triggered
compaction folds the delta into a fresh ``LabeledGraph`` and *patches* the
cached ``GraphStats`` incrementally instead of recomputing them.

SPARQL UPDATE (``INSERT DATA`` / ``DELETE DATA``) is parsed by
:mod:`repro.store.update_parser` and served by ``POST /update`` in
:mod:`repro.serve.server`.
"""

from repro.store.delta import EdgeDelta
from repro.store.update_parser import UpdateError, UpdateOp, parse_update
from repro.store.versioned import Snapshot, VersionedStore

__all__ = [
    "EdgeDelta",
    "Snapshot",
    "VersionedStore",
    "UpdateError",
    "UpdateOp",
    "parse_update",
]
