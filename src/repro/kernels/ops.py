"""Kernel dispatch: one table decides which implementation each kernel runs.

Every kernel has a pure-jnp implementation in :mod:`repro.kernels.ref`,
which XLA compiles on any backend; two also have a Pallas TPU kernel.
:data:`TPU_IMPL` records, per kernel, which implementation runs on a TPU.
The choice is made here once: no kernel switches implementation by input
size.  Every dispatch can be recorded (:func:`record_kernels`), so a step
span names the kernels and implementations its program was traced with.

``REPRO_KERNELS`` overrides the backend default:

- unset: on a TPU every kernel runs what :data:`TPU_IMPL` says; on any
  other backend every kernel runs its XLA implementation;
- ``ref``: XLA everywhere;
- ``pallas``: the :data:`TPU_IMPL` table, and an error off a TPU;
- ``pallas_interpret``: the Pallas kernels run in interpret mode
  (validation on CPU; slow).
"""

from __future__ import annotations

import contextlib
import os
import threading

import jax

from repro.kernels import ref as _ref

PALLAS = "pallas"
PALLAS_INTERPRET = "pallas_interpret"
XLA = "xla"

# Implementation per kernel on a TPU v5e.  Each Pallas entry compiles at the
# executor's widths for a described v5e (tests/test_tpu_compile.py).
TPU_IMPL: dict[str, str] = {
    # row-tiled VPU superset test
    "bitmap_superset": PALLAS,
    # +INT compare-all, at the executor's one-candidate-per-row shape
    "tile_membership": PALLAS,
    # These gather single words or rows from a whole CSR or signature array.
    # Mosaic lowers only 2-D gathers from VMEM, and a real adjacency array
    # is far larger than VMEM, so they have no Pallas version.
    "expand_filter": XLA,
    "edge_exists": XLA,
    "delta_merge": XLA,
    "delta_merge_labeled": XLA,
    "signature_filter": XLA,
    "ragged_expand": XLA,
    "segment_gather_sum": XLA,
}

_MODES = ("", "ref", "pallas", "pallas_interpret")


def impl(kernel: str) -> str:
    """The implementation ``kernel`` runs in this process: ``"pallas"``,
    ``"pallas_interpret"`` or ``"xla"``."""
    table = TPU_IMPL[kernel]
    mode = os.environ.get("REPRO_KERNELS", "")
    if mode not in _MODES:
        raise ValueError(f"REPRO_KERNELS={mode!r}; expected one of "
                         f"{_MODES[1:]} or unset")
    if mode == "ref" or table == XLA:
        return XLA
    if mode == "pallas_interpret":
        return PALLAS_INTERPRET
    on_tpu = jax.default_backend() == "tpu"
    if mode == "pallas" and not on_tpu:
        raise RuntimeError("REPRO_KERNELS=pallas needs a TPU backend; this "
                           f"process runs on {jax.default_backend()!r}")
    return table if on_tpu else XLA


class _Recording(threading.local):
    steps: dict[int, list[str]] | None = None
    step: int = -1


_rec = _Recording()


@contextlib.contextmanager
def record_kernels():
    """Record ``name:impl`` of every kernel dispatched in this thread while
    the block runs, per step: yields ``{step: [name:impl, ...]}`` in first
    dispatch order, where :func:`record_step` names the step that later
    dispatches belong to.  Around the jit trace of a chunk program this is
    what the compiled program runs."""
    prev = _rec.steps, _rec.step
    _rec.steps, _rec.step = {}, -1
    try:
        yield _rec.steps
    finally:
        _rec.steps, _rec.step = prev


def record_step(step: int) -> None:
    """Attribute the following dispatches to ``step`` (no-op unless
    :func:`record_kernels` is active)."""
    _rec.step = step


def _dispatch(kernel: str) -> str:
    how = impl(kernel)
    if _rec.steps is not None:
        names = _rec.steps.setdefault(_rec.step, [])
        name = f"{kernel}:{how}"
        if name not in names:
            names.append(name)
    return how


# --------------------------------------------------------------------------


def edge_exists(nbr, lo, hi, target, n_iters: int = 32):
    _dispatch("edge_exists")
    return _ref.edge_exists_ref(nbr, lo, hi, target, n_iters=n_iters)


def tile_membership(a, b):
    how = _dispatch("tile_membership")
    if how != XLA:
        from repro.kernels.sorted_intersect import tile_membership_pallas

        return tile_membership_pallas(a, b, interpret=how == PALLAS_INTERPRET)
    return _ref.tile_membership_ref(a, b)


def bitmap_superset(bitmap, required):
    how = _dispatch("bitmap_superset")
    if how != XLA:
        from repro.kernels.bitmap_filter import bitmap_superset_pallas

        return bitmap_superset_pallas(bitmap, required,
                                      interpret=how == PALLAS_INTERPRET)
    return _ref.bitmap_superset_ref(bitmap, required)


def signature_filter(sig, v, required):
    """Neighborhood-signature prune probe: gather candidate rows from the
    resident signature table and superset-test them against the query
    vertex's required signature.  See
    :func:`repro.kernels.ref.signature_filter_ref` for semantics."""
    _dispatch("signature_filter")
    return _ref.signature_filter_ref(sig, v, required)


def segment_gather_sum(table, indices, segments, num_segments, weights=None):
    _dispatch("segment_gather_sum")
    return _ref.segment_gather_sum_ref(table, indices, segments, num_segments,
                                       weights=weights)


def ragged_expand(offsets, degrees, capacity: int):
    _dispatch("ragged_expand")
    return _ref.ragged_expand_ref(offsets, degrees, capacity)


def delta_merge(base_nbr, delta_nbr, tomb_nbr, b_start, b_deg, d_start,
                t_lo, t_hi, j, valid, n_iters: int = 32):
    """Live-store expansion: resolve merged base+delta adjacency slots and
    mask tombstoned base edges.  See
    :func:`repro.kernels.ref.delta_merge_ref` for semantics."""
    _dispatch("delta_merge")
    return _ref.delta_merge_ref(base_nbr, delta_nbr, tomb_nbr, b_start,
                                b_deg, d_start, t_lo, t_hi, j, valid,
                                n_iters=n_iters)


def delta_merge_labeled(base_nbr, base_lab, delta_nbr, delta_lab, tomb_key,
                        b_start, b_deg, d_start, t_lo, t_hi, j, valid,
                        n_elabels: int, n_iters: int = 32):
    """Predicate-variable variant of :func:`delta_merge`."""
    _dispatch("delta_merge_labeled")
    return _ref.delta_merge_labeled_ref(base_nbr, base_lab, delta_nbr,
                                        delta_lab, tomb_key, b_start, b_deg,
                                        d_start, t_lo, t_hi, j, valid,
                                        n_elabels, n_iters=n_iters)


def expand_filter_compact(nbr, bitmap, start, deg, offs, label_mask, bound_id,
                          capacity: int):
    """Fused ragged expansion + label filter + compaction (the executor's
    per-step hot path).  Returns ``(v_out, row_out, count)``; see
    :func:`repro.kernels.ref.expand_filter_compact_ref` for semantics."""
    _dispatch("expand_filter")
    return _ref.expand_filter_compact_ref(nbr, bitmap, start, deg, offs,
                                          label_mask, bound_id, capacity)
