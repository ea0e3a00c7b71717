"""Pure-jnp implementations of every kernel in this package.

These are the semantics of record: each Pallas kernel's test sweeps shapes
asserting equality with the function here, and these are tested against
brute force.  The executor can run entirely on these
(``REPRO_KERNELS=ref``), which is also the path used off a TPU.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# prefix sums run as a two-level scan over rows of this many elements
SCAN_BLOCK = 1024


def prefix_sum(x: jax.Array) -> jax.Array:
    """Inclusive prefix sum of a 1-D array (``jnp.cumsum``).

    ``jnp.cumsum`` lowers to one reduce-window over the whole array on a
    TPU, and its compile time grows with the length: about 29 s at 2^20
    int32 elements for a v5e, where the executor's capacities reach 2^22.
    Scanning rows of :data:`SCAN_BLOCK` elements, then the row totals,
    compiles in under a second at every capacity.
    """
    n = x.shape[0]
    rows = jnp.pad(x, (0, -n % SCAN_BLOCK)).reshape(-1, SCAN_BLOCK)
    inner = jnp.cumsum(rows, axis=1)
    totals = inner[:, -1]
    return (inner + (jnp.cumsum(totals) - totals)[:, None]).reshape(-1)[:n]


def compact_index(ok: jax.Array) -> jax.Array:
    """The row each slot of a stable compaction of ``ok`` takes: slot
    ``k < sum(ok)`` holds row ``compact_index(ok)[k]``; later slots hold
    some row, for the caller to mask off.  Gathering by these indices is
    the compaction.  A scatter to prefix-sum positions does the same, but
    for a v5e it compiles for about 15 s at some sizes (2^16 rows); this
    search compiles in under a second at every capacity.
    """
    n = ok.shape[0]
    incl = prefix_sum(ok.astype(jnp.int32))
    slot = jnp.arange(1, n + 1, dtype=jnp.int32)
    return jnp.minimum(jnp.searchsorted(incl, slot, side="left"),
                       n - 1).astype(jnp.int32)


def edge_exists_ref(
    nbr: jax.Array,  # int32 [m]    sorted adjacency (per (el) block, per-src runs)
    lo: jax.Array,  # int32 [B]    per-query slice start
    hi: jax.Array,  # int32 [B]    per-query slice end (exclusive)
    target: jax.Array,  # int32 [B]
    n_iters: int = 32,
) -> jax.Array:
    """Batched lower-bound binary search: target ∈ nbr[lo:hi)?  bool [B].

    This is the paper's original IsJoinable membership probe,
    O(log deg) per (candidate, non-tree edge) pair.
    """
    m = max(1, nbr.shape[0])

    def body(_, state):
        lo_, hi_ = state
        mid = (lo_ + hi_) >> 1
        v = nbr[jnp.clip(mid, 0, m - 1)]
        go_right = v < target
        return jnp.where(go_right, mid + 1, lo_), jnp.where(go_right, hi_, mid)

    lo0 = lo.astype(jnp.int32)
    hi0 = hi.astype(jnp.int32)
    lo_f, _ = jax.lax.fori_loop(0, n_iters, body, (lo0, hi0))
    found = (lo_f < hi0) & (nbr[jnp.clip(lo_f, 0, m - 1)] == target)
    return found & (lo0 < hi0)


def tile_membership_ref(a: jax.Array, b: jax.Array) -> jax.Array:
    """Per-row compare-all membership: out[i, j] = a[i, j] ∈ b[i, :].

    a: int32 [R, TA] candidate tiles (padded with -1)
    b: int32 [R, TB] adjacency tiles (padded with -1)
    This is the +INT bulk-join primitive reshaped for the VPU: rather than a
    sequential sorted-merge (CPU-optimal), a TPU does the O(TA·TB) compare-all
    inside VMEM, which vectorizes perfectly for the tile sizes the executor
    uses.
    """
    eq = a[:, :, None] == b[:, None, :]
    return jnp.any(eq & (a[:, :, None] >= 0), axis=-1)


def bitmap_superset_ref(bitmap: jax.Array, required: jax.Array) -> jax.Array:
    """Row-wise superset test on packed uint32 bitmaps.

    bitmap: uint32 [B, W] per-candidate label (or NLF neighbor-type) words
    required: uint32 [W] the query-side mask
    returns bool [B]: (bitmap & required) == required for every word.
    """
    req = required[None, :]
    return jnp.all((bitmap & req) == req, axis=-1)


def signature_filter_ref(sig: jax.Array, v: jax.Array,
                         required: jax.Array) -> jax.Array:
    """Gather-then-superset probe on the neighborhood-signature index.

    sig: uint32 [V, 2W] per-vertex folded predicate signatures
    v: int32 [B] candidate vertex ids (out-of-range ids clip; callers mask
       invalid rows separately)
    required: uint32 [2W] the query vertex's required signature
    returns bool [B]: candidate's signature is a superset of required.
    """
    rows = jnp.take(sig, jnp.clip(v, 0, sig.shape[0] - 1), axis=0)
    req = required[None, :]
    return jnp.all((rows & req) == req, axis=-1)


def segment_gather_sum_ref(
    table: jax.Array,  # [V, D] embedding rows / node features
    indices: jax.Array,  # int32 [E] gather ids
    segments: jax.Array,  # int32 [E] destination segment per gathered row
    num_segments: int,
    weights: jax.Array | None = None,  # optional [E]
) -> jax.Array:
    """Fused gather + segment-sum (EmbeddingBag-sum / GNN aggregate oracle)."""
    rows = table[indices]
    if weights is not None:
        rows = rows * weights[:, None]
    return jax.ops.segment_sum(rows, segments, num_segments=num_segments)


def expand_filter_compact_ref(
    nbr: jax.Array,  # int32 [m]     CSR adjacency values
    bitmap: jax.Array,  # uint32 [V, W] packed vertex-label words
    start: jax.Array,  # int32 [R]    per-row adjacency slice start
    deg: jax.Array,  # int32 [R]      per-row slice length
    offs: jax.Array,  # int32 [R]     exclusive cumsum of deg
    label_mask: jax.Array,  # uint32 [W] required label words (0 = no filter)
    bound_id: jax.Array,  # int32 []   required vertex id (< 0 = no check)
    capacity: int,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Fused ragged CSR expansion + label-bitmap filter + compaction.

    Logical candidate stream = concat over rows i of
    ``nbr[start[i] : start[i] + deg[i]]``; each candidate v survives iff
    ``(bitmap[v] & label_mask) == label_mask`` and (when ``bound_id >= 0``)
    ``v == bound_id``.  Survivors are compacted to a prefix, preserving
    stream order.  Returns ``(v_out, row_out, count)`` each sized
    ``capacity`` / scalar: slot k < count holds surviving candidate
    ``v_out[k]`` produced by input row ``row_out[k]``; slots >= count are
    ``-1``.  Slots beyond ``capacity`` are dropped (the caller detects that
    via its own total-vs-capacity overflow check).
    """
    row, j, valid = ragged_expand_ref(offs, deg, capacity)
    idx = jnp.clip(start[row] + j, 0, max(1, nbr.shape[0]) - 1)
    v = jnp.where(valid, nbr[idx], -1)
    vsafe = jnp.clip(v, 0, bitmap.shape[0] - 1)
    ok = valid & bitmap_superset_ref(bitmap[vsafe], label_mask)
    ok &= (bound_id < 0) | (v == bound_id)
    src = compact_index(ok)
    count = jnp.sum(ok.astype(jnp.int32))
    live = jnp.arange(capacity, dtype=jnp.int32) < count
    return (jnp.where(live, v[src], -1), jnp.where(live, row[src], -1),
            count)


def delta_merge_ref(
    base_nbr: jax.Array,  # int32 [mb]  base CSR adjacency values
    delta_nbr: jax.Array,  # int32 [md] delta-insert adjacency values
    tomb_nbr: jax.Array,  # int32 [mt]  tombstoned base neighbors (sorted runs)
    b_start: jax.Array,  # int32 [K]   per-slot base slice start
    b_deg: jax.Array,  # int32 [K]     per-slot base slice length
    d_start: jax.Array,  # int32 [K]   per-slot delta slice start
    t_lo: jax.Array,  # int32 [K]      per-slot tombstone slice start
    t_hi: jax.Array,  # int32 [K]      per-slot tombstone slice end
    j: jax.Array,  # int32 [K]         within-row candidate position
    valid: jax.Array,  # bool [K]
    n_iters: int = 32,
) -> tuple[jax.Array, jax.Array]:
    """Merged base+delta expansion slot resolution with tombstone masking.

    Slot ``k`` resolves within-row position ``j[k]`` of a logical adjacency
    list that is base slice ``base_nbr[b_start:b_start+b_deg)`` followed by
    the delta slice starting at ``d_start`` — positions ``j < b_deg`` read
    the base CSR, later positions read the delta CSR.  Base-sourced
    candidates found in the (sorted) tombstone slice ``tomb_nbr[t_lo:t_hi)``
    are masked out; delta candidates are never tombstoned (the store keeps
    inserts and tombstones disjoint).  Returns ``(v, ok)``: the candidate
    per slot (-1 when invalid) and its post-tombstone validity.
    """
    is_base = j < b_deg
    mb = max(1, base_nbr.shape[0])
    md = max(1, delta_nbr.shape[0])
    v_b = base_nbr[jnp.clip(b_start + j, 0, mb - 1)]
    v_d = delta_nbr[jnp.clip(d_start + (j - b_deg), 0, md - 1)]
    v = jnp.where(is_base, v_b, v_d)
    dead = is_base & edge_exists_ref(tomb_nbr, t_lo, t_hi, v,
                                     n_iters=n_iters)
    return jnp.where(valid, v, -1), valid & ~dead


def delta_merge_labeled_ref(
    base_nbr: jax.Array,  # int32 [mb] plain-CSR neighbors (all labels)
    base_lab: jax.Array,  # int32 [mb] edge label aligned with base_nbr
    delta_nbr: jax.Array,  # int32 [md]
    delta_lab: jax.Array,  # int32 [md]
    tomb_key: jax.Array,  # int32 [mt] sorted composite nbr*n_elabels+el runs
    b_start: jax.Array,
    b_deg: jax.Array,
    d_start: jax.Array,
    t_lo: jax.Array,
    t_hi: jax.Array,
    j: jax.Array,
    valid: jax.Array,
    n_elabels: int,
    n_iters: int = 32,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Predicate-variable variant of :func:`delta_merge_ref`: candidates
    carry their edge label, and tombstone probing matches the exact
    (neighbor, label) pair via the composite key ``nbr * n_elabels + el``.
    Returns ``(v, el, ok)``."""
    is_base = j < b_deg
    mb = max(1, base_nbr.shape[0])
    md = max(1, delta_nbr.shape[0])
    ib = jnp.clip(b_start + j, 0, mb - 1)
    idlt = jnp.clip(d_start + (j - b_deg), 0, md - 1)
    v = jnp.where(is_base, base_nbr[ib], delta_nbr[idlt])
    el = jnp.where(is_base, base_lab[ib], delta_lab[idlt])
    key = v * jnp.int32(n_elabels) + el
    dead = is_base & edge_exists_ref(tomb_key, t_lo, t_hi, key,
                                     n_iters=n_iters)
    ok = valid & ~dead
    return jnp.where(valid, v, -1), jnp.where(valid, el, -1), ok


def ragged_expand_ref(
    offsets: jax.Array,  # int32 [R] exclusive cumsum of per-row degrees
    degrees: jax.Array,  # int32 [R]
    capacity: int,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Flatten ragged per-row ranges into output slots.

    Returns (row, j, valid) each [capacity]: slot k belongs to input row
    ``row[k]`` at within-row position ``j[k]``; slots beyond the total are
    invalid.  This is the executor's expansion primitive.
    """
    total = jnp.sum(degrees)
    k = jnp.arange(capacity, dtype=jnp.int32)
    row = jnp.searchsorted(offsets, k, side="right").astype(jnp.int32) - 1
    row = jnp.clip(row, 0, max(1, offsets.shape[0]) - 1)
    j = k - offsets[row]
    valid = (k < total) & (j < degrees[row]) & (j >= 0)
    return row, j, valid
