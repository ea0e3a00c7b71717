"""Vectorized e-graph-homomorphism executor (the tamed TurboHOM++ core).

The paper's recursive ExploreCandidateRegion + SubgraphSearch become a
breadth-first *binding table* pipeline: a table of partial embeddings
``B int32[capacity, |V(q)|]`` is expanded one query vertex at a time along
the matching order.  Each step is a capacity-bounded ragged expansion over
CSR adjacency slices followed by vectorized filters:

  - vertex-label containment (packed-bitmap superset test),
  - ID-attribute equality (Definition 3's ID check),
  - optional NLF / degree filters (the paper's -NLF / -DEG toggles),
  - non-tree edge joins — either per-candidate binary search (the paper's
    original IsJoinable) or the bulk tile-compare path (+INT),
  - injectivity masks when running in subgraph-*isomorphism* mode
    (``semantics="iso"``) — the executor implements both semantics; e-hom
    is the RDF semantics and simply skips those masks (§2.2),
  - predicate-variable (M_e) binding and consistency for e-graph
    homomorphism (Definition 2).

Capacity management (the adaptive pipeline): each step runs at its own
power-of-two capacity from the planner's ``capacity_schedule`` (derived
from per-step cardinality estimates), so early low-cardinality steps stop
paying full-table compactions.  A step whose ragged expansion
exceeds its capacity *freezes* the chunk — the surviving table is carried
through the remaining (inert) steps unchanged and the program reports the
overflowing step index — and the host re-enters the plan from exactly that
step with only that step's capacity grown (*suffix-resume*), instead of
redoing the whole chunk.  Learned capacities persist per plan, so later
chunks start right-sized.  Results are exact — overflow never truncates.

The host loop keeps ``ExecOpts.async_chunks`` chunk programs in flight and
only reads back a chunk's ``(count, overflow_step)`` scalars after the
next chunk has been dispatched, hiding dispatch latency; with
``collect="count"`` the final step skips binding-table materialization and
nothing but scalars crosses the device→host boundary.  Steps with no
non-tree checks run through the fused expand/filter/compact kernel;
:mod:`repro.kernels.ops` decides whether that is the Pallas kernel or its
XLA implementation, and the step spans name which one ran.

Non-tree join directions (uniform rule): for a check attached to query
vertex u with candidate v_new and earlier vertex `other` bound to other_v,
  forward  (other --el--> u):  v_new ∈ out_adj(other_v, el)
  reverse  (u --el--> other):  v_new ∈ in_adj(other_v, el)
  self-loop (u --el--> u):     v_new ∈ out_adj(v_new, el)
i.e. the probe vertex is other_v (v_new for self-loops), the search target
is always v_new, and the direction picks the out/in CSR.
"""

from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass, field, replace
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.planner import ExecPlan, Step
from repro.core.planner.ir import _next_pow2
from repro.kernels import ops as kops
from repro.kernels import ref as kref
from repro.rdf.graph import LabeledGraph
from repro.resilience import faults as _faults
from repro.resilience.cancel import CancelToken, QueryCancelled
from repro.resilience.policy import (
    MAX_LEVEL,
    DegradationBreaker,
    RetryPolicy,
    degrade_opts,
    is_transient_fault,
)
from repro.utils import get_logger

log = get_logger("core.exec")

_NULL = jnp.int32(-1)


# --------------------------------------------------------------------------
# Device-resident graph
# --------------------------------------------------------------------------


@dataclass(frozen=True)
class DeviceGraph:
    n_vertices: int
    n_elabels: int
    n_vlabels: int
    max_log_deg: int
    arrays: dict[str, jax.Array]
    host: LabeledGraph
    # per-edge-label max degree (host, for the +INT tile decision)
    max_deg_out_el: np.ndarray = field(default=None)  # type: ignore[assignment]
    max_deg_in_el: np.ndarray = field(default=None)  # type: ignore[assignment]
    # --- live-store (snapshot) mode ---------------------------------------
    # delta_mode=True: ``arrays`` holds only the *base* graph; the merged
    # label bitmap / numeric column and all delta CSRs flow in per call via
    # the step-arrays pytree, so compiled chunk programs are reused across
    # snapshots of the same base.  ``pad_vertices`` is the pow2-padded
    # vertex bound every per-vertex gather is sized/clipped to (stable
    # across snapshots until the vertex count crosses the bucket);
    # ``base_vertices``/``base_elabels`` bound the base-CSR id spaces.
    delta_mode: bool = False
    base_vertices: int = 0
    base_elabels: int = 0
    pad_vertices: int = 0

    def key(self) -> tuple:
        """Trace-relevant identity for the compiled-chunk cache.  The
        *logical* vertex count is deliberately absent in snapshot mode —
        traces only depend on the pow2-padded bound, so growing the vertex
        set inside one pad bucket keeps every compiled program."""
        return (self.delta_mode, self.pad_vertices,
                self.base_vertices, self.n_elabels, self.max_log_deg)

    @staticmethod
    def from_snapshot(snap, with_nlf: bool = False,
                      with_prune: bool = False) -> "DeviceGraph":
        """Device view of a live-store snapshot: the base graph's arrays
        (cached on the base, shared by successive snapshots) plus
        snapshot-mode metadata.  Delta arrays are NOT uploaded here — they
        are per-plan step inputs (see ``Executor._snapshot_arrays``)."""
        import dataclasses

        want = (bool(with_nlf), bool(with_prune))
        cache = getattr(snap.base, "_device_graph", None)
        if cache is None or cache[0] != want:
            base_dg = DeviceGraph.from_graph(snap.base, with_nlf=with_nlf,
                                             with_prune=with_prune)
            snap.base._device_graph = (want, base_dg)
        else:
            base_dg = cache[1]
        n_pad = _next_pow2(max(snap.n_vertices, 8))
        return dataclasses.replace(
            base_dg,
            n_vertices=snap.n_vertices,
            n_elabels=snap.n_elabels,
            max_log_deg=32,  # safe bound: merged degrees are unbounded
            delta_mode=True,
            base_vertices=snap.base.n_vertices,
            base_elabels=snap.base.n_elabels,
            pad_vertices=n_pad,
        )

    @staticmethod
    def from_graph(g: LabeledGraph, with_nlf: bool = False,
                   with_prune: bool = False) -> "DeviceGraph":
        def dev(x, dtype):
            x = np.asarray(x, dtype=dtype)
            if x.size == 0:
                x = np.zeros((1,) + x.shape[1:], dtype=dtype)
            return jnp.asarray(x)

        arrays = {
            "out_nbr_el": dev(g.out.nbr_el, np.int32),
            "in_nbr_el": dev(g.inc.nbr_el, np.int32),
            "out_indptr_all": dev(g.out.indptr_all, np.int32),
            "in_indptr_all": dev(g.inc.indptr_all, np.int32),
            "out_nbr_all": dev(g.out.nbr_all, np.int32),
            "in_nbr_all": dev(g.inc.nbr_all, np.int32),
            "out_lab_all": dev(g.out.lab_all, np.int32),
            "in_lab_all": dev(g.inc.lab_all, np.int32),
            "label_bitmap": dev(g.label_bitmap, np.uint32),
            "out_degree": dev(g.out.degree, np.int32),
            "in_degree": dev(g.inc.degree, np.int32),
        }
        if g.numeric_value is not None:
            arrays["numeric_value"] = dev(g.numeric_value, np.float32)
        if with_nlf:
            nlf_o, nlf_i = g.nlf_bitmaps()
            arrays["nlf_out"] = dev(nlf_o, np.uint32)
            arrays["nlf_in"] = dev(nlf_i, np.uint32)
        if with_prune:
            from repro.index import get_index

            sig = get_index(g).sig
            arrays["sig"] = dev(sig, np.uint32)
            # the fused expand/filter/compact kernel is width-generic in the
            # bitmap, so composing the signature probe with the label filter
            # is just a wider bitmap (labels ++ signature) and a combined mask
            arrays["filter_bitmap"] = dev(
                np.hstack([g.label_bitmap, sig]), np.uint32)
        max_deg = int(max(g.out.degree.max(initial=1), g.inc.degree.max(initial=1)))
        # one vectorized diff+reduce over the stacked [n_elabels, V+1] indptr
        mdo = (np.max(np.diff(g.out.indptr_el, axis=1), axis=1, initial=0)
               if g.n_elabels else np.zeros(0, np.int64))
        mdi = (np.max(np.diff(g.inc.indptr_el, axis=1), axis=1, initial=0)
               if g.n_elabels else np.zeros(0, np.int64))
        return DeviceGraph(
            n_vertices=g.n_vertices,
            n_elabels=g.n_elabels,
            n_vlabels=g.n_vlabels,
            max_log_deg=max(2, int(np.ceil(np.log2(max(2, max_deg)))) + 1),
            arrays=arrays,
            host=g,
            max_deg_out_el=mdo,
            max_deg_in_el=mdi,
            base_vertices=g.n_vertices,
            base_elabels=g.n_elabels,
            pad_vertices=g.n_vertices,
        )


# --------------------------------------------------------------------------
# Options / results
# --------------------------------------------------------------------------


@dataclass(frozen=True)
class ExecOpts:
    semantics: str = "hom"  # "hom" (RDF) or "iso" (classical subgraph iso)
    use_int: bool = True  # +INT: bulk tile-compare joins where tiles fit
    use_nlf: bool = False  # paper default: disabled (-NLF)
    use_deg: bool = False  # paper default: disabled (-DEG)
    reuse_order: bool = True  # +REUSE
    int_tile: int = 128  # adjacency tile bound for the +INT path
    chunk: int = 8192  # starting vertices per chunk (§Perf: 2-3.7× over 1k on heavy queries)
    init_cap: int = 4096
    max_cap: int = 1 << 22
    # --- adaptive pipeline toggles (all False/1 ≈ the legacy executor) ---
    cap_schedule: bool = True  # per-step capacity schedule from the planner
    suffix_resume: bool = True  # overflow resumes from the overflowing step
    async_chunks: int = 2  # chunk programs kept in flight before readback
    use_fused: bool = True  # fused expand/filter/compact kernel fast path
    cap_slack: float = 1.0  # schedule headroom (pow2 rounding adds ~1.5x already)
    use_prune: bool = True  # neighborhood-signature pruning (repro.index)
    profile: bool = False  # per-step wall-time stats (adds host syncs)
    # absolute time.monotonic() deadline; checked between chunk dispatches
    # and suffix-resume re-entries (None = no deadline).  Deliberately
    # excluded from key(): deadlines never affect compiled programs.
    deadline: float | None = None

    def key(self) -> tuple:
        return (self.semantics, self.use_int, self.use_nlf, self.use_deg,
                self.int_tile, self.use_fused, self.use_prune)


@dataclass
class Result:
    count: int
    bindings: np.ndarray | None  # int32 [count, |V(q)|] (None if count-only)
    pvar_bindings: np.ndarray | None  # int32 [count, n_pvars]
    origins: np.ndarray | None = None  # source-row ids (for extension runs)
    chunks_retried: int = 0
    stats: dict[str, Any] = field(default_factory=dict)


# --------------------------------------------------------------------------
# Step arrays: per-plan device constants
# --------------------------------------------------------------------------


def _label_mask(g: LabeledGraph, labels: tuple[int, ...]) -> np.ndarray:
    n_words = g.label_bitmap.shape[1]
    mask = np.zeros(n_words, dtype=np.uint32)
    for lbl in labels:
        mask[lbl >> 5] |= np.uint32(1 << (lbl & 31))
    return mask


def _plan_arrays(g: LabeledGraph, plan: ExecPlan,
                 use_prune: bool = False) -> list[dict[str, jax.Array]]:
    """Per-step device constants: CSR indptr rows, label masks, etc."""
    out: list[dict[str, jax.Array]] = []
    flat_out = flat_in = None
    if any(c.pvar_idx >= 0 for s in plan.steps for c in s.nontree):
        flat_out = jnp.asarray(g.out.indptr_el.reshape(-1), dtype=jnp.int32)
        flat_in = jnp.asarray(g.inc.indptr_el.reshape(-1), dtype=jnp.int32)
    for s in plan.steps:
        d: dict[str, jax.Array] = {}
        if s.restart_candidates is not None:
            cands = s.restart_candidates.astype(np.int32)
            d["restart"] = jnp.asarray(cands if cands.size else np.zeros(1, np.int32))
            d["restart_n"] = jnp.int32(cands.size)
        elif s.elabel >= 0:
            dirn = g.out if s.forward else g.inc
            d["iptr"] = jnp.asarray(dirn.indptr_el[s.elabel], dtype=jnp.int32)
        if s.labels:
            d["label_mask"] = jnp.asarray(_label_mask(g, s.labels))
        if use_prune and s.sig_mask is not None \
                and s.restart_candidates is None:
            # restart steps carry pre-pruned candidate arrays; tree steps
            # probe on device.  ``fmask`` = labels ++ signature drives the
            # fused kernel's single combined superset test.
            d["sig_mask"] = jnp.asarray(s.sig_mask)
            lm = _label_mask(g, s.labels) if s.labels else \
                np.zeros(g.label_bitmap.shape[1], np.uint32)
            d["fmask"] = jnp.asarray(np.concatenate([lm, s.sig_mask]))
        if s.nlf_out_mask is not None:
            d["nlf_out_mask"] = jnp.asarray(s.nlf_out_mask)
            d["nlf_in_mask"] = jnp.asarray(s.nlf_in_mask)
        for ci, c in enumerate(s.nontree):
            use_out = c.forward or c.self_loop
            if c.pvar_idx >= 0:
                d[f"nt{ci}_flat"] = flat_out if use_out else flat_in
            else:
                dirn = g.out if use_out else g.inc
                d[f"nt{ci}_iptr"] = jnp.asarray(dirn.indptr_el[c.elabel],
                                                dtype=jnp.int32)
        out.append(d)
    return out


# --------------------------------------------------------------------------
# The compiled chunk program
# --------------------------------------------------------------------------


def _pad_rows(x: jax.Array, rows: int) -> jax.Array:
    """Pad a table/vector along axis 0 with nulls up to ``rows``."""
    pad = rows - x.shape[0]
    if pad <= 0:
        return x
    width = ((0, pad),) + ((0, 0),) * (x.ndim - 1)
    return jnp.pad(x, width, constant_values=-1)


def _nontree_mask(dg: DeviceGraph, arrays, step: Step, sarr, b_rows, p_rows,
                  v_new, opts: ExecOpts) -> jax.Array:
    n = dg.pad_vertices if dg.delta_mode else dg.n_vertices
    ok = jnp.ones(v_new.shape[0], dtype=bool)
    for ci, c in enumerate(step.nontree):
        use_out = c.forward or c.self_loop
        nbr = arrays["out_nbr_el" if use_out else "in_nbr_el"]
        probe = v_new if c.self_loop else b_rows[:, c.other]
        psafe = jnp.clip(probe, 0, n - 1)
        if c.pvar_idx >= 0:
            el_raw = p_rows[:, c.pvar_idx]
            bound_ok = el_raw >= 0
            if dg.delta_mode:
                # base flat tables cover the base id spaces only; probes or
                # labels born in the delta have no base edges by definition
                in_base = (probe < jnp.int32(dg.base_vertices)) & \
                    (el_raw < jnp.int32(dg.base_elabels))
                pb = jnp.clip(probe, 0, dg.base_vertices - 1)
                el_b = jnp.clip(el_raw, 0, dg.base_elabels - 1)
                flat = sarr[f"nt{ci}_flat"]
                bi = el_b * jnp.int32(dg.base_vertices + 1) + pb
                found = kops.edge_exists(nbr, flat[bi], flat[bi + 1], v_new,
                                         n_iters=dg.max_log_deg) & in_base
                el_m = jnp.clip(el_raw, 0, dg.n_elabels - 1)
                fi = el_m * jnp.int32(n + 1) + psafe
                tf = sarr.get(f"nt{ci}_t_flat_iptr")
                if tf is not None:
                    dead = kops.edge_exists(
                        sarr[f"nt{ci}_t_flat_nbr"], tf[fi], tf[fi + 1],
                        v_new, n_iters=dg.max_log_deg)
                    found &= ~dead
                df = sarr.get(f"nt{ci}_d_flat_iptr")
                if df is not None:
                    found |= kops.edge_exists(
                        sarr[f"nt{ci}_d_flat_nbr"], df[fi], df[fi + 1],
                        v_new, n_iters=dg.max_log_deg)
            else:
                flat = sarr[f"nt{ci}_flat"]
                el_dyn = jnp.clip(el_raw, 0, dg.n_elabels - 1)
                base = el_dyn * jnp.int32(n + 1)
                lo = flat[base + psafe]
                hi = flat[base + psafe + 1]
                found = kops.edge_exists(nbr, lo, hi, v_new,
                                         n_iters=dg.max_log_deg)
            ok &= found & bound_ok
            continue
        iptr = sarr[f"nt{ci}_iptr"]
        lo = iptr[psafe]
        hi = iptr[psafe + 1]
        if dg.delta_mode:
            # base membership (padded rows: zero-degree past the base id
            # spaces), minus tombstones, plus delta inserts — +INT tiles
            # only cover the base CSR, so dirty labels use the search path
            found = kops.edge_exists(nbr, lo, hi, v_new,
                                     n_iters=dg.max_log_deg)
            ti = sarr.get(f"nt{ci}_t_iptr")
            if ti is not None:
                dead = kops.edge_exists(sarr[f"nt{ci}_t_nbr"], ti[psafe],
                                        ti[psafe + 1], v_new,
                                        n_iters=dg.max_log_deg)
                found &= ~dead
            di = sarr.get(f"nt{ci}_d_iptr")
            if di is not None:
                found |= kops.edge_exists(sarr[f"nt{ci}_d_nbr"], di[psafe],
                                          di[psafe + 1], v_new,
                                          n_iters=dg.max_log_deg)
            ok &= found
            continue
        max_deg = int(
            (dg.max_deg_out_el if use_out else dg.max_deg_in_el)[c.elabel]
        )
        if opts.use_int and 0 < max_deg <= opts.int_tile:
            # +INT: bulk membership via tiled compare-all in VMEM.  Gather the
            # probe side's full adjacency tile (bounded by int_tile) and test
            # all candidates of this step against it at once.
            tb = _next_pow2(max(8, max_deg))
            pos = lo[:, None] + jnp.arange(tb, dtype=jnp.int32)[None, :]
            in_range = pos < hi[:, None]
            adj_tile = jnp.where(
                in_range, nbr[jnp.clip(pos, 0, nbr.shape[0] - 1)], -2
            )
            found = kops.tile_membership(v_new[:, None], adj_tile)[:, 0]
        else:
            found = kops.edge_exists(nbr, lo, hi, v_new, n_iters=dg.max_log_deg)
        ok &= found
    return ok


def _fused_eligible(step: Step, opts: ExecOpts) -> bool:
    """Steps the fused expand/filter/compact kernel covers: a tree edge (or
    restart) whose only filters are the label bitmap and a bound ID."""
    return (opts.use_fused and not step.nontree and opts.semantics == "hom"
            and step.pvar_idx < 0 and not step.num_filters
            and not step.min_out_ntypes and not step.min_in_ntypes
            and step.nlf_out_mask is None)


def build_chunk_fn(dg: DeviceGraph, plan: ExecPlan, caps: tuple[int, ...],
                   n_in: int, opts: ExecOpts, table_input: bool,
                   collect: str = "bindings", start_step: int = 0,
                   stop_step: int | None = None):
    """Build the jittable chunk program for plan steps ``[start_step,
    stop_step)`` with the per-step capacity schedule ``caps``.

    ``table_input=False``: the input is a vector of start-vertex candidates
    (``n_in`` wide) and the program seeds the binding table from it.
    ``table_input=True``: the input is ``(B0, count, P0, origins)`` rows of
    capacity ``n_in`` — OPTIONAL left-join extensions and suffix-resume
    re-entries both use this form.

    Overflow semantics: the first step whose ragged expansion total exceeds
    its capacity *freezes* the table — every later step passes it through
    unchanged — and the returned ``ovf_step`` names that step (``len(steps)``
    = completed).  The frozen table is exactly the input the overflowing
    step needs on re-entry, so the host resumes from there with only that
    step's capacity grown.  ``caps`` must be monotone non-decreasing from
    ``n_in`` so the freeze carry is lossless.

    With ``collect="count"`` the final step only tallies survivors: no
    compacted binding table is materialized for it and only scalars need to
    cross back to the host.

    Returns ``(b, p, org, count, ovf_step, totals, kepts, pins, pouts)``
    where ``totals``/``kepts`` hold each executed step's expansion total and
    surviving-row count (``-1`` once frozen / not executed) and
    ``pins``/``pouts`` the signature-prune probe's candidates in/out
    (``-1`` when the step has no probe).

    ``arrays`` is ``dg.arrays``, the device-resident graph.  It is an input
    of the program, never a closed-over value: jit embeds closed-over arrays
    as constants, which would copy the whole graph into every compiled
    program.

    ``params`` (int32 ``[plan.n_params]``, empty for fully baked plans) is a
    traced input: steps with ``param_slot >= 0`` check the new binding
    against ``params[slot]`` instead of the baked ``bound_id``, so one
    compiled program serves every constant instantiation of the shape — and
    ``jax.vmap`` over the params axis answers a whole batch per launch.

    The returned program's ``kernel_log`` maps each of its traces
    (:func:`_kernel_key` of its ``sarrs`` and ``arrays``) to the kernels
    each step dispatched while it was traced, as ``{step: ["name:impl",
    ...]}``: the kernels the compiled program runs.
    """
    nq = plan.query.n_vertices
    npv = max(1, plan.n_pvars)
    steps = plan.steps
    n_steps = len(steps)
    stop = n_steps if stop_step is None else stop_step
    dmode = dg.delta_mode
    has_numeric = "numeric_value" in dg.arrays
    n = dg.pad_vertices if dmode else dg.n_vertices
    for si in range(start_step, stop):
        prev = n_in if si == start_step else caps[si - 1]
        if caps[si] < prev:
            raise ValueError("capacity schedule must be monotone "
                             f"non-decreasing (step {si}: {caps[si]} < {prev})")

    def fn(chunk, chunk_count, p_init, org_init, params, sarrs, arrays):
        if not table_input:
            b = jnp.full((n_in, nq), _NULL, dtype=jnp.int32)
            b = b.at[:, plan.start_vertex].set(chunk)
            p = jnp.full((n_in, npv), _NULL, dtype=jnp.int32)
            org = jnp.arange(n_in, dtype=jnp.int32)
            count = jnp.minimum(chunk_count, n_in).astype(jnp.int32)
        else:
            b, p, org = chunk, p_init, org_init
            count = chunk_count.astype(jnp.int32)

        ovf_step = jnp.int32(n_steps)  # sentinel: completed
        totals: list[jax.Array] = []
        kepts: list[jax.Array] = []
        pins: list[jax.Array] = []
        pouts: list[jax.Array] = []
        cap_prev = n_in
        for si in range(start_step, stop):
            kops.record_step(si)
            step = steps[si]
            sarr = sarrs[si]
            cap = caps[si]
            active = ovf_step == jnp.int32(n_steps)
            alive = jnp.arange(cap_prev, dtype=jnp.int32) < count

            # delta overlay per-step inputs (snapshot mode only; the step
            # arrays pytree carries them so jit retraces exactly when a
            # label's delta appears or vanishes)
            d_iptr = sarr.get("d_iptr") if dmode else None
            t_iptr = sarr.get("t_iptr") if dmode else None
            start_d = deg_b = t_lo = t_hi = None
            if step.restart_candidates is not None:
                k_cands = int(sarr["restart"].shape[0])
                deg = jnp.where(alive, sarr["restart_n"], 0)
                nbr_src = sarr["restart"]
                start = jnp.zeros(cap_prev, dtype=jnp.int32)
                deg_bound = k_cands
                d_iptr = t_iptr = None
            elif step.elabel >= 0:
                iptr = sarr["iptr"]
                vp = jnp.clip(b[:, step.parent], 0, n - 1)
                start = iptr[vp]
                deg_b = iptr[vp + 1] - start
                deg = deg_b
                if d_iptr is not None:
                    start_d = d_iptr[vp]
                    deg = deg + (d_iptr[vp + 1] - start_d)
                if t_iptr is not None:
                    t_lo, t_hi = t_iptr[vp], t_iptr[vp + 1]
                deg = jnp.where(alive, deg, 0)
                nbr_src = arrays["out_nbr_el" if step.forward else "in_nbr_el"]
                deg_bound = int(
                    (dg.max_deg_out_el if step.forward
                     else dg.max_deg_in_el)[step.elabel]) \
                    if step.elabel < dg.base_elabels else 0
            else:  # predicate variable: plain CSR
                iptr = sarr["all_iptr"] if dmode else \
                    arrays["out_indptr_all" if step.forward
                              else "in_indptr_all"]
                vp = jnp.clip(b[:, step.parent], 0, n - 1)
                start = iptr[vp]
                deg_b = iptr[vp + 1] - start
                deg = deg_b
                if d_iptr is not None:
                    start_d = d_iptr[vp]
                    deg = deg + (d_iptr[vp + 1] - start_d)
                if t_iptr is not None:
                    t_lo, t_hi = t_iptr[vp], t_iptr[vp + 1]
                deg = jnp.where(alive, deg, 0)
                nbr_src = arrays["out_nbr_all" if step.forward
                                    else "in_nbr_all"]
                deg_bound = 1 << dg.max_log_deg

            merged = d_iptr is not None or t_iptr is not None
            coffs = kref.prefix_sum(deg.astype(jnp.int32))
            total = coffs[-1]
            offs = (coffs - deg).astype(jnp.int32)
            ovf_here = total > cap
            if dmode or cap_prev * max(1, deg_bound) >= 2**31:
                # the int32 prefix sums can wrap; redo the *total* in a wide
                # dtype (int64 with x64 enabled, else float32 — exact enough
                # for a compare against cap <= 2**22) so a wrapped cumsum is
                # still reported as overflow instead of silent truncation.
                wide = jnp.int64 if jax.config.jax_enable_x64 else jnp.float32
                total_w = jnp.sum(deg.astype(wide))
                ovf_here = ovf_here | (total < 0) | (total_w > cap)
            ovf_here = active & ovf_here
            keep_new = active & ~ovf_here
            ovf_step = jnp.where(ovf_here, jnp.int32(si), ovf_step)
            count_only = collect == "count" and si == n_steps - 1

            bitmap_src = (sarr.get("bitmap") if dmode
                          else arrays["label_bitmap"])
            p_in = p_out = None
            if _fused_eligible(step, opts) and not count_only and not merged:
                fmask = sarr.get("fmask")
                fb_src = (sarr.get("filter_bitmap") if dmode
                          else arrays.get("filter_bitmap")) \
                    if fmask is not None else None
                if fmask is not None and fb_src is not None:
                    # composed label + signature probe: one superset test
                    # over the widened (labels ++ signature) bitmap
                    filt_bitmap, filt_mask = fb_src, fmask
                    p_in, p_out = total, None  # p_out = kept, set below
                else:
                    filt_bitmap = bitmap_src
                    filt_mask = sarr.get("label_mask")
                    if filt_mask is None:
                        filt_mask = jnp.zeros(
                            (bitmap_src.shape[1],), jnp.uint32)
                bid = (params[step.param_slot] if step.param_slot >= 0
                       else jnp.int32(step.bound_id))
                v_out, row_sel, kept = kops.expand_filter_compact(
                    nbr_src, filt_bitmap, start, deg, offs,
                    filt_mask, bid, cap)
                if p_in is not None:
                    p_out = kept
                # gather-based table build: when frozen, the identity index
                # carries the old table through at zero extra cost
                idg = jnp.where(
                    keep_new,
                    jnp.clip(row_sel, 0, cap_prev - 1),
                    jnp.minimum(jnp.arange(cap, dtype=jnp.int32), cap_prev - 1))
                nb = b[idg]
                nb = nb.at[:, step.u].set(
                    jnp.where(keep_new, v_out, nb[:, step.u]))
                b, p, org = nb, p[idg], org[idg]
                count = jnp.where(keep_new, kept, count)
            else:
                row, j, valid = kops.ragged_expand(offs, deg, cap)
                el_new = None
                if merged:
                    # live store: position j < deg_b reads the base CSR
                    # (minus tombstones), later positions read the delta
                    sb = start[row]
                    db = deg_b[row]
                    sd = start_d[row] if start_d is not None else \
                        jnp.zeros_like(row)
                    tl = t_lo[row] if t_lo is not None else \
                        jnp.zeros_like(row)
                    th = t_hi[row] if t_hi is not None else \
                        jnp.zeros_like(row)
                    dummy = jnp.full(1, -1, jnp.int32)
                    d_nbr = sarr.get("d_nbr", dummy)
                    if step.elabel >= 0:
                        v_new, ok = kops.delta_merge(
                            nbr_src, d_nbr, sarr.get("t_nbr", dummy),
                            sb, db, sd, tl, th, j, valid,
                            n_iters=dg.max_log_deg)
                    else:
                        lab_src = arrays["out_lab_all" if step.forward
                                            else "in_lab_all"]
                        v_new, el_new, ok = kops.delta_merge_labeled(
                            nbr_src, lab_src, d_nbr,
                            sarr.get("d_lab", dummy),
                            sarr.get("t_key", dummy),
                            sb, db, sd, tl, th, j, valid,
                            n_elabels=dg.n_elabels,
                            n_iters=dg.max_log_deg)
                else:
                    idx = jnp.clip(start[row] + j, 0, nbr_src.shape[0] - 1)
                    v_new = jnp.where(valid, nbr_src[idx], _NULL)
                    ok = valid

                b_rows = b[row]
                p_rows = p[row]
                org_rows = org[row]
                b_rows = b_rows.at[:, step.u].set(v_new)

                if step.pvar_idx >= 0:  # tree-edge M_e binding
                    if el_new is None:
                        lab_src = arrays["out_lab_all" if step.forward
                                            else "in_lab_all"]
                        el_new = jnp.where(valid, lab_src[idx], _NULL)
                    prev = p_rows[:, step.pvar_idx]
                    ok &= (prev < 0) | (prev == el_new)
                    p_rows = p_rows.at[:, step.pvar_idx].set(
                        jnp.where(prev < 0, el_new, prev))
                if step.param_slot >= 0:
                    ok &= v_new == params[step.param_slot]
                elif step.bound_id >= 0:
                    ok &= v_new == jnp.int32(step.bound_id)
                if "label_mask" in sarr:
                    bm = bitmap_src[jnp.clip(v_new, 0, n - 1)]
                    ok &= kops.bitmap_superset(bm, sarr["label_mask"])
                sig_mask = sarr.get("sig_mask")
                sig_src = (sarr.get("sig") if dmode
                           else arrays.get("sig")) \
                    if sig_mask is not None else None
                if sig_src is not None:
                    p_in = jnp.sum(ok.astype(jnp.int32))
                    ok &= kops.signature_filter(
                        sig_src, jnp.clip(v_new, 0, n - 1), sig_mask)
                    p_out = jnp.sum(ok.astype(jnp.int32))
                if (step.min_out_ntypes or step.min_in_ntypes) and not dmode:
                    # degree/NLF prunes use base-build summaries; they are
                    # not maintained across deltas, so snapshot execution
                    # skips them (they are pure optimizations)
                    safe = jnp.clip(v_new, 0, n - 1)
                    ok &= arrays["out_degree"][safe] >= jnp.int32(
                        step.min_out_ntypes)
                    ok &= arrays["in_degree"][safe] >= jnp.int32(
                        step.min_in_ntypes)
                if "nlf_out_mask" in sarr and "nlf_out" in arrays \
                        and not dmode:
                    safe = jnp.clip(v_new, 0, n - 1)
                    ok &= kops.bitmap_superset(arrays["nlf_out"][safe],
                                               sarr["nlf_out_mask"])
                    ok &= kops.bitmap_superset(arrays["nlf_in"][safe],
                                               sarr["nlf_in_mask"])
                if step.num_filters:
                    num_src = sarr.get("numeric") if dmode else (
                        arrays["numeric_value"] if has_numeric else None)
                    if num_src is not None:
                        vals = num_src[jnp.clip(v_new, 0, n - 1)]
                        for op, cval in step.num_filters:
                            ok &= _jnp_cmp(vals, op, cval)
                if opts.semantics == "iso":
                    for w in plan.order:
                        if w == step.u:
                            break
                        ok &= b_rows[:, w] != v_new
                if step.nontree:
                    ok &= _nontree_mask(dg, arrays, step, sarr, b_rows,
                                        p_rows, v_new, opts)

                kept = jnp.sum(ok.astype(jnp.int32))
                if count_only:
                    # final tally only: carry the (possibly frozen) table —
                    # no compacted binding table is materialized
                    b = _pad_rows(b, cap)
                    p = _pad_rows(p, cap)
                    org = _pad_rows(org, cap)
                    count = jnp.where(keep_new, kept, count)
                else:
                    # gather the kept rows to the front; rows past ``kept``
                    # hold leftovers, which every consumer masks off by
                    # ``count``, and a frozen step carries its input
                    src = kref.compact_index(ok)
                    b = jnp.where(keep_new, b_rows[src], _pad_rows(b, cap))
                    p = jnp.where(keep_new, p_rows[src], _pad_rows(p, cap))
                    org = jnp.where(keep_new, org_rows[src],
                                    _pad_rows(org, cap))
                    count = jnp.where(keep_new, kept, count)

            totals.append(jnp.where(active, total, jnp.int32(-1)))
            kepts.append(jnp.where(keep_new, count, jnp.int32(-1)))
            if p_in is None:
                pins.append(jnp.int32(-1))
                pouts.append(jnp.int32(-1))
            else:
                pins.append(jnp.where(active, p_in, jnp.int32(-1)))
                pouts.append(jnp.where(keep_new, p_out, jnp.int32(-1)))
            cap_prev = cap

        z = jnp.zeros(0, jnp.int32)
        return (b, p, org, count, ovf_step,
                jnp.stack(totals) if totals else z,
                jnp.stack(kepts) if kepts else z,
                jnp.stack(pins) if pins else z,
                jnp.stack(pouts) if pouts else z)

    kernel_log: dict = {}

    def traced(*args):
        with kops.record_kernels() as kernels:
            out = fn(*args)
        kernel_log[_kernel_key(args[5], args[6])] = kernels
        return out

    traced.kernel_log = kernel_log
    return traced


def _kernel_key(sarrs, arrays):
    """Which of a chunk program's traces ran: the kernels a step picks
    depend on which step and graph arrays exist, not on their shapes."""
    return jax.tree.structure((sarrs, arrays))


def _note_kernels(kernels: dict[int, list[str]], fn, sarrs, arrays) -> None:
    """Fold the kernels a called chunk program was traced with for these
    inputs into a run's per-step record."""
    kernels.update(fn.kernel_log[_kernel_key(sarrs, arrays)])


def _jnp_cmp(vals, op: str, c: float):
    c = jnp.float32(c)
    if op == "<":
        return vals < c
    if op == "<=":
        return vals <= c
    if op == ">":
        return vals > c
    if op == ">=":
        return vals >= c
    if op == "=":
        return vals == c
    if op == "!=":
        return vals != c
    raise ValueError(op)


# --------------------------------------------------------------------------
# Host-level executor
# --------------------------------------------------------------------------


def _grow_caps(caps: list[int], si: int, max_cap: int,
               need: int = 0) -> list[int]:
    """Grow step ``si``'s capacity after an overflow to the power of two
    that holds ``need`` rows — the expansion the overflowing dispatch
    counted — and at least double it (raising once it is already at
    ``max_cap``); restore monotonicity for later steps.  Growing to the
    observed need re-enters once per overflow instead of once per
    doubling, and each new capacity is a new compiled program.  Mutates
    and returns ``caps`` — the single overflow-retry policy shared by the
    async drain and the profiled per-step path."""
    if caps[si] >= max_cap:
        raise RuntimeError(
            f"binding-table overflow at max capacity {max_cap};"
            " raise ExecOpts.max_cap")
    caps[si] = min(max_cap, max(caps[si] * 2, _next_pow2(need)))
    for j in range(si + 1, len(caps)):
        caps[j] = max(caps[j], caps[si])
    return caps


_SMALL_PLAN_ROWS = 512.0
_SMALL_PLAN_STEPS = 6


def _small_plan(plan: ExecPlan, opts: ExecOpts) -> bool:
    """Is this plan a *candidate* for skipping the pipelined machinery?
    For B1-class point lookups the per-step capacity schedule,
    fused-kernel setup and async bookkeeping cost more than they save —
    the legacy single-shot configuration is faster.  Planner estimates
    alone cannot make the call (B1 and B8 are estimate-twins but land on
    opposite sides), so this gate only shortlists: a tiny expected result,
    few steps, no estimated intermediate blow-up, and a start set that
    fits one chunk.  The executor settles shortlisted plans with a
    one-time timed probe of both configurations (``_small_mode``)."""
    if not (opts.cap_schedule or opts.use_fused or opts.suffix_resume):
        return False  # already running the legacy configuration
    if not plan.steps or len(plan.steps) > _SMALL_PLAN_STEPS:
        return False
    if plan.start_candidates.shape[0] > opts.chunk:
        return False
    peak = max(plan.est_rows, default=plan.estimated_rows())
    return (plan.estimated_rows() <= _SMALL_PLAN_ROWS
            and peak <= 4 * _SMALL_PLAN_ROWS)


def _donates(table_input: bool, start: int, out_cap: int, n_in: int) -> bool:
    """Does the chunk program for this window donate its binding-table
    inputs?  Only re-entries (a table input past step 0) whose output
    table has the input's shape can reuse the buffers in place — in
    practice the profiled per-step path across equal-capacity steps.  A
    donated input is gone after the call, so an overflow retry re-enters
    from the frozen table the program returned.  Initial whole-chunk
    dispatches are excluded: a legacy retry re-feeds the same host args."""
    return table_input and start > 0 and out_cap == n_in


def _empty_stats(n_steps: int) -> dict[str, Any]:
    return {
        "step_rows": [0] * n_steps,
        "step_kept": [0] * n_steps,
        "step_retries": [0] * n_steps,
        "step_prune_in": [0] * n_steps,
        "step_prune_out": [0] * n_steps,
        "step_wall_ms": None,
        "caps": [],
        "chunks": 0,
        "resumes": 0,
        "donated": 0,  # dispatches that reused their input table buffers
        "compiles": 0,
        "wall_ms": 0.0,
    }


# the expansion kernel among a step's kernels, by precedence: a live-store
# merge resolves the slots of the ragged expansion that precedes it
_EXPANSION_KERNELS = ("delta_merge_labeled", "delta_merge", "expand_filter",
                      "ragged_expand")


def _expansion_kernel(kernels: list[str]) -> str:
    """The expansion kernel's name among a step's ``name:impl`` kernels."""
    names = {k.split(":")[0] for k in kernels}
    return next((k for k in _EXPANSION_KERNELS if k in names), "")


def _annotate_step_spans(trace, plan: ExecPlan, dg: DeviceGraph,
                         kernels: dict[int, list[str]], stats: dict,
                         n_src: int) -> None:
    """Attach one summary span per plan step: executed-counter meta
    (rows/kept/retries/capacity), the kernels its programs were traced with
    (``kernels``, as ``name:impl``), and a roofline estimate next to the
    measured wall time where the device has published peaks (profiled runs
    only have real per-step durations; sampled traces report zero-duration
    spans)."""
    from repro.analysis.roofline import estimate_step_ms

    device_kind = jax.devices()[0].device_kind
    nq = plan.query.n_vertices
    bitmap_words = int(dg.arrays["label_bitmap"].shape[1])
    wall = stats.get("step_wall_ms")
    caps = stats.get("caps") or []
    rows_in = float(n_src)
    for si, step in enumerate(plan.steps):
        kernel = stats["step_kernels"][si]
        expanded = stats["step_rows"][si]
        kept = stats["step_kept"][si]
        cap = int(caps[si]) if si < len(caps) else 0
        meta: dict[str, Any] = {
            "step": si, "kernel": kernel, "kernels": kernels.get(si, []),
            "rows": expanded, "kept": kept,
            "retries": stats["step_retries"][si], "capacity": cap,
        }
        if step.sig_mask is not None:
            p_in = stats["step_prune_in"][si]
            meta["prune_in"] = p_in
            meta["prune_out"] = stats["step_prune_out"][si]
            if p_in:
                meta["prune_ratio"] = round(
                    stats["step_prune_out"][si] / p_in, 4)
        if step.nontree:
            meta["nontree_checks"] = len(step.nontree)
        est = estimate_step_ms(
            kernel, device_kind, expanded=expanded, rows=rows_in,
            capacity=cap, nq=nq, bitmap_words=bitmap_words,
            n_iters=dg.max_log_deg)
        if est is not None:
            model_ms = est["model_ms"]
            for _ in step.nontree:
                model_ms += estimate_step_ms(
                    "edge_exists", device_kind, expanded=expanded,
                    n_iters=dg.max_log_deg)["model_ms"]
            meta["model_ms"] = round(model_ms, 6)
            meta["model_dominant"] = est["dominant"]
        dur_s = (wall[si] / 1e3) if wall is not None else 0.0
        trace.add("step", dur_s, **meta)
        rows_in = float(kept)


class Executor:
    """Chunked plan executor: per-step capacity schedule, suffix-resume on
    overflow, double-buffered async chunk dispatch, compile cache.

    ``g`` may be a plain :class:`LabeledGraph` or a live-store
    :class:`~repro.store.versioned.Snapshot`.  In snapshot mode the base
    graph's device arrays are shared across snapshots, delta CSRs flow in
    per call through the step-arrays pytree (so compiled chunk programs
    survive updates), and start / restart candidate sets are re-resolved
    against the current snapshot — which also makes *cached plans* built
    against an older version execute correctly."""

    def __init__(self, g, opts: ExecOpts | None = None, *,
                 policy: RetryPolicy | None = None,
                 breaker: DegradationBreaker | None = None):
        self.opts = opts or ExecOpts()
        # transient-fault policy + per-plan-signature degradation breaker;
        # callers rebuilding an executor (e.g. engine compaction) pass the
        # old instances through so learned degradations survive
        self._policy = policy or RetryPolicy.from_env()
        self._breaker = breaker or DegradationBreaker(
            cooldown_s=self._policy.cooldown_s)
        self._res_counters = {"degraded_runs": 0, "fault_retries": 0,
                              "escalations": 0}
        if getattr(g, "is_snapshot", False):
            view = g
            self.graph = g.base
            dg = DeviceGraph.from_snapshot(g, with_nlf=self.opts.use_nlf,
                                           with_prune=self.opts.use_prune)
        else:
            view = None
            self.graph = g
            dg = DeviceGraph.from_graph(g, with_nlf=self.opts.use_nlf,
                                        with_prune=self.opts.use_prune)
        # (view, dg) swap together atomically (single tuple assignment), so
        # a query that pinned the pair mid-update stays internally
        # consistent; ``view``/``dg`` attributes mirror the latest state
        self._state: tuple[Any, DeviceGraph] = (view, dg)
        self._compiled: dict[tuple, Any] = {}
        self._plan_arrays_cache: dict[int, list[dict[str, jax.Array]]] = {}
        # learned per-plan capacity schedules (overflow growth persists,
        # so later chunks / queries start right-sized)
        self._caps_cache: dict[tuple, list[int]] = {}
        # learned pipelined-vs-legacy choice for small plans (see
        # _small_plan): True = legacy single-shot config wins for this
        # plan signature
        self._small_mode: dict[tuple, bool] = {}

    @property
    def view(self):
        return self._state[0]

    @property
    def dg(self) -> DeviceGraph:
        return self._state[1]

    def pin(self) -> tuple[Any, DeviceGraph]:
        """Capture the current (view, dg) pair.  Callers composing several
        ``run`` calls into one logical query pass it to each so concurrent
        ``set_snapshot`` swaps cannot tear the query across versions."""
        return self._state

    def set_snapshot(self, snap) -> None:
        """Swap to a newer snapshot of the *same* base graph (post-update).
        Compiled chunk programs are reused: only the pytree of delta/step
        arrays changes, and jit retraces exactly when shapes/structure do.
        In-flight queries keep executing against the state they pinned."""
        if self.view is None or snap.base is not self.graph:
            raise ValueError("snapshot has a different base graph; "
                             "build a new Executor")
        self._state = (snap,
                       DeviceGraph.from_snapshot(
                           snap, with_nlf=self.opts.use_nlf,
                           with_prune=self.opts.use_prune))

    @property
    def policy(self) -> RetryPolicy:
        return self._policy

    @property
    def breaker(self) -> DegradationBreaker:
        return self._breaker

    def resilience_snapshot(self) -> dict:
        """Breaker state + fault counters, for /healthz and gauges."""
        d = self._breaker.snapshot()
        d.update(self._res_counters)
        return d

    def _get_fn(self, plan: ExecPlan, caps: tuple[int, ...], n_in: int,
                table_input: bool, collect: str, start: int, stop: int,
                dg: DeviceGraph | None = None, opts: ExecOpts | None = None):
        dg = self.dg if dg is None else dg
        opts = self.opts if opts is None else opts
        # key on the [start, stop) capacity window only: suffix programs
        # that differ in capacities of steps they never execute are
        # byte-identical and must share one compile
        key = (plan.signature(), caps[start:stop], n_in, table_input,
               collect, start, stop, opts.key(), dg.key())
        fn = self._compiled.get(key)
        fresh = fn is None
        if fresh:
            _faults.fire("compile")
            raw = build_chunk_fn(dg, plan, caps, n_in, opts,
                                 table_input, collect, start, stop)
            out_cap = caps[stop - 1] if stop > start else n_in
            donate = (0, 2, 3) if _donates(table_input, start, out_cap,
                                           n_in) else ()
            fn = jax.jit(raw, donate_argnums=donate)
            fn.kernel_log = raw.kernel_log
            self._compiled[key] = fn
        # freshness is returned (not kept on self) so concurrent runs on a
        # shared executor each see their own compile events
        return fn, fresh

    def _arrays(self, plan: ExecPlan,
                state: tuple | None = None) -> list[dict[str, jax.Array]]:
        view, dg = state if state is not None else self._state
        if view is not None:
            return self._snapshot_arrays(plan, view, dg)
        # cache on the plan object itself (an id()-keyed dict can collide
        # when a dead plan's id is recycled by the allocator)
        use_prune = self.opts.use_prune
        cached = getattr(plan, "_dev_arrays", None)
        if cached is not None and cached[0] is self.graph \
                and cached[1] == use_prune:
            return cached[2]
        arrs = _plan_arrays(self.graph, plan, use_prune)
        plan._dev_arrays = (self.graph, use_prune, arrs)  # type: ignore[attr-defined]
        return arrs

    def _snapshot_arrays(self, plan: ExecPlan, snap,
                         dg: DeviceGraph) -> list[dict[str, jax.Array]]:
        """Per-step device constants for snapshot execution: padded base
        CSR rows, the snapshot's delta/tombstone CSRs, merged label bitmap
        and numeric column, and freshly resolved restart candidates."""
        from repro.core.planner.cost import CostModel

        use_prune = self.opts.use_prune
        token = (snap.token(), use_prune)
        cached = getattr(plan, "_dev_arrays_snap", None)
        if cached is not None and cached[0] == token:
            return cached[1]
        _faults.fire("delta_merge")
        n_pad = dg.pad_vertices
        cm = CostModel(snap)
        flat_cache: dict[bool, jax.Array] = {}

        def base_flat(fwd: bool) -> jax.Array:
            if fwd not in flat_cache:
                dirn = self.graph.out if fwd else self.graph.inc
                flat_cache[fwd] = jnp.asarray(dirn.indptr_el.reshape(-1),
                                              dtype=jnp.int32)
            return flat_cache[fwd]

        out: list[dict[str, jax.Array]] = []
        for s in plan.steps:
            d: dict[str, jax.Array] = {}
            if s.restart_candidates is not None:
                cands = np.sort(cm.candidates(plan.query, s.u)) \
                    .astype(np.int32)
                if use_prune and s.sig_mask is not None and cands.size:
                    # re-apply the plan's baked candidate prune to the
                    # freshly resolved set (conservative snapshot rows)
                    from repro.index import signature_rows

                    rows = signature_rows(snap)
                    keep = np.all((rows[cands] & s.sig_mask) == s.sig_mask,
                                  axis=-1)
                    cands = cands[keep]
                n_real = cands.size
                # pow2 padding keeps the trace stable across snapshots
                target = _next_pow2(max(1, n_real))
                if n_real < target:
                    cands = np.concatenate(
                        [cands, np.full(target - n_real, -1, np.int32)])
                d["restart"] = jnp.asarray(cands)
                d["restart_n"] = jnp.int32(n_real)
            elif s.elabel >= 0:
                d["iptr"] = snap.base_el_row_padded(s.elabel, s.forward,
                                                    n_pad)
                d.update(snap.dev_el_step(s.elabel, s.forward, n_pad))
            else:
                d["all_iptr"] = snap.base_plain_padded(s.forward, n_pad)
                d.update(snap.dev_plain(s.forward, n_pad))
            if s.labels:
                d["label_mask"] = jnp.asarray(_label_mask(self.graph,
                                                          s.labels))
            if s.labels or _fused_eligible(s, self.opts):
                d["bitmap"] = snap.dev_bitmap(n_pad)
            if use_prune and s.sig_mask is not None \
                    and s.restart_candidates is None:
                d["sig_mask"] = jnp.asarray(s.sig_mask)
                d["sig"] = snap.dev_sig(n_pad)
                if _fused_eligible(s, self.opts):
                    lm = _label_mask(self.graph, s.labels) if s.labels else \
                        np.zeros(self.graph.label_bitmap.shape[1], np.uint32)
                    d["fmask"] = jnp.asarray(
                        np.concatenate([lm, s.sig_mask]))
                    d["filter_bitmap"] = snap.dev_filter_bitmap(n_pad)
            if s.num_filters:
                nv = snap.dev_numeric(n_pad)
                if nv is not None:
                    d["numeric"] = nv
            for ci, c in enumerate(s.nontree):
                use_out = c.forward or c.self_loop
                if c.pvar_idx >= 0:
                    d[f"nt{ci}_flat"] = base_flat(use_out)
                    for k, v in snap.dev_flat(use_out, n_pad).items():
                        d[f"nt{ci}_{k}"] = v
                else:
                    d[f"nt{ci}_iptr"] = snap.base_el_row_padded(
                        c.elabel, use_out, n_pad)
                    for k, v in snap.dev_el_step(c.elabel, use_out,
                                                 n_pad).items():
                        d[f"nt{ci}_{k}"] = v
            out.append(d)
        plan._dev_arrays_snap = (token, out)  # type: ignore[attr-defined]
        return out

    def _start_candidates(self, plan: ExecPlan,
                          view=None) -> np.ndarray:
        """The plan's start-candidate set, re-resolved against the current
        snapshot when executing a live store (plans are cached across
        versions; their baked candidate arrays go stale, the spec —
        labels / bound id / cheap numeric filters — does not)."""
        if view is None:
            view = self.view
        if view is None:
            return plan.start_candidates
        from repro.core.planner.cost import CostModel
        from repro.core.planner.ir import np_cmp

        token = (view.token(), self.opts.use_prune)
        cached = getattr(plan, "_snap_start", None)
        if cached is not None and cached[0] == token:
            return cached[1]
        cands = CostModel(view).candidates(plan.query, plan.start_vertex)
        nf = getattr(plan, "start_num_filters", ())
        if nf and view.numeric_value is not None:
            vals = view.numeric_value[cands]
            keep = np.ones(cands.shape[0], bool)
            for op, c in nf:
                keep &= np_cmp(vals, op, c)
            cands = cands[keep]
        sig = getattr(plan, "start_sig", None)
        if self.opts.use_prune and sig is not None and cands.size:
            from repro.index import signature_rows

            rows = signature_rows(view)
            cands = cands[np.all((rows[cands] & sig) == sig, axis=-1)]
        cands = np.sort(cands).astype(np.int32)
        plan._snap_start = (token, cands)  # type: ignore[attr-defined]
        return cands

    def _param_start_candidates(self, plan: ExecPlan, params: np.ndarray,
                                view=None) -> np.ndarray:
        """Start-candidate resolution for a parameterized start vertex: the
        set is exactly the parameter's vertex id, subject to the same
        label-containment check the cost model applies to baked bound
        vertices.  Signature pruning is skipped (it is a pure optimization
        on a one-element set).  Never cached on the plan — it varies with
        ``params`` — and valid against both the base graph and snapshots
        (ids are stable across versions)."""
        g = view if view is not None else self.graph
        cid = int(params[plan.start_param_slot])
        if cid < 0 or cid >= int(g.n_vertices):
            return np.zeros(0, np.int32)
        qv = plan.query.vertices[plan.start_vertex]
        if qv.labels:
            bm = np.asarray(g.label_bitmap[cid])
            for lbl in qv.labels:
                if not (int(bm[lbl >> 5]) >> (lbl & 31)) & 1:
                    return np.zeros(0, np.int32)
        return np.array([cid], np.int32)

    def _schedule(self, plan: ExecPlan, chunk_size: int,
                  opts: ExecOpts | None = None) -> tuple[tuple, list[int]]:
        """The (learned) per-step capacity schedule for this plan+chunk."""
        opts = self.opts if opts is None else opts
        # cap_slack/init_cap are in the key so degraded-ladder runs learn
        # their own schedules instead of polluting the normal path's
        key = (plan.signature(), chunk_size, bool(opts.cap_schedule),
               opts.cap_slack, opts.init_cap)
        caps = self._caps_cache.get(key)
        if caps is None:
            if opts.cap_schedule:
                caps = list(plan.capacity_schedule(
                    chunk_size, opts.init_cap, opts.max_cap, opts.cap_slack))
            else:
                # legacy presizing: one global capacity from the whole-plan
                # fanout product, identical for every step
                est = 1.0
                for f in plan.est_fanout:
                    est *= max(1.0, min(f, 64.0))
                cap0 = int(min(opts.max_cap,
                               max(opts.init_cap,
                                   _next_pow2(int(chunk_size * min(est, 512.0))))))
                cap0 = max(cap0, _next_pow2(chunk_size))
                caps = [cap0] * len(plan.steps)
            self._caps_cache[key] = caps
        return key, caps

    def run(
        self,
        plan: ExecPlan,
        collect: str = "bindings",
        initial: tuple[np.ndarray, np.ndarray, np.ndarray] | None = None,
        profile: bool | None = None,
        state: tuple | None = None,
        trace=None,
        params: np.ndarray | None = None,
        cancel: CancelToken | None = None,
        _opts_override: ExecOpts | None = None,
    ) -> Result:
        """Execute a plan.  ``initial=(B0, P0, origins)`` runs the plan's
        steps as an *extension* of existing rows (OPTIONAL left joins).
        ``profile=True`` (or ``ExecOpts.profile``) executes step-by-step
        with host syncs to fill per-step wall times in ``Result.stats``.
        ``state`` pins a ``pin()``-captured (view, device-graph) pair so a
        multi-run query stays on one snapshot under concurrent updates.
        ``trace`` (a :class:`repro.obs.Trace`) records compile / dispatch /
        device-wait / per-step spans under the caller's current span; a
        trace with ``profile_steps=True`` forces profiled execution so the
        step spans carry real device wall times.  ``params`` supplies a
        parameterized plan's constant vector (int32 ``[plan.n_params]``);
        a negative entry means the constant is absent from the dictionary
        and short-circuits to an empty result.  ``cancel`` (a
        :class:`repro.resilience.CancelToken`) is polled between chunk
        dispatches and suffix-resume re-entries; an expired or cancelled
        token raises :class:`QueryCancelled` with partial stats.

        Transient faults (RESOURCE_EXHAUSTED-shaped) are absorbed by a
        retry/degradation ladder: bounded backoff retries at the current
        config, then progressively degraded configs down to the legacy
        executor, with the working level remembered per plan signature
        (see :mod:`repro.resilience.policy`).  Runs are pure with respect
        to their host inputs, so a ladder re-run is exact."""
        if cancel is None and self.opts.deadline is not None:
            cancel = CancelToken(self.opts.deadline)
        if _opts_override is not None:
            # explicit config (small-plan probes, degraded re-runs):
            # bypass the ladder so probe timings stay undistorted
            return self._run_impl(plan, collect, initial, profile, state,
                                  trace, params, cancel, _opts_override)
        sig = plan.signature()
        policy = self._policy
        level = self._breaker.level(sig)
        attempt = 0
        while True:
            try:
                res = self._run_impl(
                    plan, collect, initial, profile, state, trace, params,
                    cancel, degrade_opts(self.opts, level) if level else None)
            except QueryCancelled:
                raise
            except Exception as e:  # noqa: BLE001 - filtered just below
                if not is_transient_fault(e):
                    raise
                self._res_counters["fault_retries"] += 1
                if attempt < policy.max_retries:
                    delay = policy.backoff(attempt)
                    attempt += 1
                    if cancel is not None:
                        if cancel.expired:
                            raise QueryCancelled(
                                f"query cancelled: "
                                f"{cancel.reason or 'cancelled'}") from e
                        rem = cancel.remaining()
                        if rem is not None:
                            delay = min(delay, max(0.0, rem))
                    time.sleep(delay)
                    continue
                if level >= MAX_LEVEL:
                    raise
                prev = level
                level = self._breaker.record_failure(sig, level)
                self._res_counters["escalations"] += 1
                attempt = 0
                log.warning(
                    "transient fault at degradation level %d; "
                    "escalating to level %d: %s", prev, level, e)
                continue
            self._breaker.record_success(sig, level)
            if level:
                self._res_counters["degraded_runs"] += 1
                res.stats["degraded_level"] = level
            return res

    def _warm_run(self, plan: ExecPlan, opts: ExecOpts,
                  kw: dict) -> tuple[float, Result]:
        """``(seconds, result)`` of a run of ``plan`` under ``opts`` that
        compiled nothing.  A run that grew a capacity leaves the next one a
        new program to compile, so runs repeat (at most three) until one
        is warm."""
        for _ in range(3):
            t0 = time.perf_counter()
            res = self.run(plan, _opts_override=opts, **kw)
            t = time.perf_counter() - t0
            if not res.stats.get("compiles"):
                break
        return t, res

    def _run_impl(
        self,
        plan: ExecPlan,
        collect: str = "bindings",
        initial: tuple[np.ndarray, np.ndarray, np.ndarray] | None = None,
        profile: bool | None = None,
        state: tuple | None = None,
        trace=None,
        params: np.ndarray | None = None,
        cancel: CancelToken | None = None,
        _opts_override: ExecOpts | None = None,
    ) -> Result:
        state = self.pin() if state is None else state
        view, dg = state
        if plan.unsat:
            return Result(0, _empty(plan), _empty_p(plan), np.zeros(0, np.int32))
        if plan.n_params:
            if params is None:
                raise ValueError(
                    f"plan expects {plan.n_params} parameters; none given")
            params = np.asarray(params, np.int32).reshape(-1)
            if params.shape[0] != plan.n_params:
                raise ValueError(f"expected {plan.n_params} parameters, "
                                 f"got {params.shape[0]}")
            if (params < 0).any():
                # a hoisted constant missing from the dictionary: provably
                # zero solutions (same contract as an unsat baked plan)
                return Result(0,
                              _empty(plan) if collect == "bindings" else None,
                              _empty_p(plan), np.zeros(0, np.int32))
        opts = self.opts if _opts_override is None else _opts_override
        small_legacy = False  # remembered small-probe verdict applied?
        if (_opts_override is None and initial is None and trace is None
                and not profile and _small_plan(plan, opts)):
            # B1-class small queries: the pipelined machinery's fixed
            # overhead (per-step capacity schedule, fused-kernel setup,
            # async bookkeeping) can exceed the work saved.  Estimates
            # can't settle which side a plan lands on, so probe once per
            # plan signature: time one warm run of each configuration and
            # remember the winner.  Both configurations return identical
            # results, so the probe is invisible to callers beyond
            # one-time latency.
            sig = plan.signature()
            mode = self._small_mode.get(sig)
            if mode is None:
                legacy = replace(opts, cap_schedule=False,
                                 suffix_resume=False, async_chunks=1,
                                 use_fused=False)
                kw = dict(collect=collect, state=state, params=params,
                          cancel=cancel)
                res = self.run(plan, _opts_override=opts, **kw)
                peak = max(res.stats.get("step_kept") or (), default=0)
                if max(peak, res.count) > 4 * _SMALL_PLAN_ROWS:
                    # the estimates that shortlisted the plan were wrong:
                    # it is not small, so the pipeline stays, untimed
                    self._small_mode[sig] = False
                    return res
                t_pipe, res = self._warm_run(plan, opts, kw)
                self.run(plan, _opts_override=legacy, **kw)
                t_leg, res_l = self._warm_run(plan, legacy, kw)
                # require a clear win before abandoning the pipeline: the
                # probe is a single sample and ties should keep defaults
                mode = t_leg < 0.9 * t_pipe
                self._small_mode[sig] = mode
                win = res_l if mode else res
                win.stats["small_probe"] = {
                    "t_pipelined_ms": round(t_pipe * 1e3, 3),
                    "t_legacy_ms": round(t_leg * 1e3, 3),
                    "legacy_wins": bool(mode)}
                return win
            if mode:
                opts = replace(opts, cap_schedule=False, suffix_resume=False,
                               async_chunks=1, use_fused=False)
                small_legacy = True
        profile = opts.profile if profile is None else profile
        if trace is not None and trace.profile_steps:
            profile = True
        nq = plan.query.n_vertices
        params_dev = jnp.asarray(params) if plan.n_params \
            else jnp.zeros(0, jnp.int32)

        if initial is None and not plan.steps:
            # point-shaped query (paper Algorithm 1 lines 2–4)
            if plan.start_param_slot >= 0 and params is not None:
                cands = self._param_start_candidates(plan, params, view)
            else:
                cands = self._start_candidates(plan, view)
            b = np.full((cands.shape[0], nq), -1, dtype=np.int32)
            b[:, plan.start_vertex] = cands
            return Result(
                int(cands.shape[0]),
                b if collect == "bindings" else None,
                np.full((cands.shape[0], max(1, plan.n_pvars)), -1, np.int32),
                np.arange(cands.shape[0], dtype=np.int32),
            )

        sarrs = self._arrays(plan, state)
        extension = initial is not None
        if extension:
            b0, p0, org0 = initial
            n_src = b0.shape[0]
        else:
            if plan.start_param_slot >= 0 and params is not None:
                start_cands = self._param_start_candidates(plan, params, view)
            else:
                start_cands = self._start_candidates(plan, view)
            n_src = start_cands.shape[0]
        if n_src == 0 or (not extension and not plan.steps):
            # honor the collect contract even on the empty fast path —
            # count-collect promises bindings=None (start pruning can make
            # this reachable for plans that would otherwise produce rows)
            return Result(0, _empty(plan) if collect == "bindings" else None,
                          _empty_p(plan), np.zeros(0, np.int32))

        t_run0 = time.perf_counter()
        n_steps = len(plan.steps)
        npv = max(1, plan.n_pvars)
        stats = _empty_stats(n_steps)
        if small_legacy:
            stats["small_mode"] = True

        def check_cancel() -> None:
            if cancel is not None and cancel.expired:
                stats["wall_ms"] = (time.perf_counter() - t_run0) * 1e3
                raise QueryCancelled(
                    f"query cancelled: {cancel.reason or 'cancelled'}",
                    partial_stats=dict(stats))

        if profile:
            stats["step_wall_ms"] = [0.0] * n_steps
        kernels: dict[int, list[str]] = {}  # step -> "name:impl" it ran
        total = 0
        out_b: list[np.ndarray] = []
        out_p: list[np.ndarray] = []
        out_o: list[np.ndarray] = []
        chunk_size = min(opts.chunk, max(1, n_src))
        caps_key, caps = self._schedule(plan, chunk_size, opts)

        def host_args(offset: int, hi: int):
            n_real = hi - offset
            if not extension:
                chunk = np.full(chunk_size, -1, dtype=np.int32)
                chunk[:n_real] = start_cands[offset:hi]
                # host arrays: an eager jnp.zeros compiles per chunk size
                return (jnp.asarray(chunk), jnp.int32(n_real),
                        jnp.asarray(np.zeros((chunk_size, npv), np.int32)),
                        jnp.asarray(np.zeros(chunk_size, np.int32)))
            bpad = np.full((chunk_size, nq), -1, dtype=np.int32)
            bpad[:n_real] = b0[offset:hi]
            ppad = np.full((chunk_size, npv), -1, np.int32)
            ppad[:n_real, : p0.shape[1]] = p0[offset:hi]
            opad = np.full(chunk_size, -1, dtype=np.int32)
            opad[:n_real] = org0[offset:hi]
            return (jnp.asarray(bpad), jnp.int32(n_real),
                    jnp.asarray(ppad), jnp.asarray(opad))

        def call_fn(fn, fresh, args, **meta):
            """One chunk-program invocation; with tracing on, the span is
            named ``compile`` when this call triggers the first-dispatch
            XLA compile (jit compiles synchronously inside the call) and
            ``dispatch`` when it only enqueues the async chunk."""
            poison = _faults.fire("dispatch")
            if fresh:
                stats["compiles"] += 1
            if trace is None:
                out = fn(*args)
            else:
                with trace.span("compile" if fresh else "dispatch", **meta):
                    out = fn(*args)
            _note_kernels(kernels, fn, sarrs, dg.arrays)
            if poison:
                # injected silent corruption: zero this chunk's count so
                # end-to-end checks can detect a poisoned dispatch
                stats["poisoned"] = stats.get("poisoned", 0) + 1
                out = (*out[:3], out[3] * 0, *out[4:])
            return out

        def dispatch(offset: int, hi: int) -> dict:
            args = host_args(offset, hi)
            used = tuple(caps)
            fn, fresh = self._get_fn(plan, used, chunk_size, extension,
                                     collect, 0, n_steps, dg, opts)
            ci = stats["chunks"]
            stats["chunks"] += 1
            return {"out": call_fn(fn, fresh,
                                   (*args, params_dev, sarrs, dg.arrays),
                                   chunk=ci),
                    "args": args, "caps": used, "offset": offset}

        def accumulate(start: int, upto: int, acc_from: int, totals, kepts,
                       pins, pouts):
            """Fold one window's step counters into the run stats."""
            if upto <= acc_from:
                return
            t_np = np.asarray(totals)
            k_np = np.asarray(kepts)
            pi_np = np.asarray(pins)
            po_np = np.asarray(pouts)
            for si in range(max(start, acc_from), min(upto, n_steps)):
                ii = si - start
                if t_np[ii] >= 0:
                    stats["step_rows"][si] += int(t_np[ii])
                if k_np[ii] >= 0:
                    stats["step_kept"][si] += int(k_np[ii])
                if pi_np[ii] >= 0:
                    stats["step_prune_in"][si] += int(pi_np[ii])
                if po_np[ii] >= 0:
                    stats["step_prune_out"][si] += int(po_np[ii])

        def drain(rec: dict) -> None:
            nonlocal total
            b, p, org, count, ovf_step, totals, kepts, pins, pouts = rec["out"]
            used = list(rec["caps"])
            start = 0
            acc_from = 0
            while True:
                # device sync for this chunk's scalars — with tracing on,
                # the host's wait for buffer-ready shows up as device_wait
                if trace is None:
                    ovf = int(ovf_step)
                else:
                    with trace.span("device_wait"):
                        ovf = int(ovf_step)
                accumulate(start, ovf, acc_from, totals, kepts, pins, pouts)
                acc_from = max(acc_from, min(ovf, n_steps))
                if ovf >= n_steps:
                    break
                # overflow retry is a fresh dispatch: honor an expired
                # deadline before re-entering the plan
                check_cancel()
                stats["step_retries"][ovf] += 1
                need = int(np.asarray(totals)[ovf - start])
                if opts.suffix_resume:
                    # re-enter from the overflowing step only: the frozen
                    # table returned by the chunk program is exactly that
                    # step's input
                    new_caps = _grow_caps(list(used), ovf, opts.max_cap,
                                          need)
                    n_in = used[ovf - 1] if ovf > 0 else chunk_size
                    fn, fresh = self._get_fn(plan, tuple(new_caps), n_in,
                                             True, collect, ovf, n_steps, dg,
                                             opts)
                    (b, p, org, count, ovf_step, totals, kepts, pins,
                     pouts) = call_fn(
                        fn, fresh,
                        (b[:n_in], count, p[:n_in], org[:n_in], params_dev,
                         sarrs, dg.arrays),
                        resume_step=ovf)
                    start = ovf
                    acc_from = ovf
                    stats["resumes"] += 1
                else:
                    # legacy: grow every capacity alike, redo the whole chunk
                    if used[ovf] >= opts.max_cap:
                        raise RuntimeError(
                            f"binding-table overflow at max capacity "
                            f"{opts.max_cap}; raise ExecOpts.max_cap")
                    new_caps = [min(opts.max_cap, max(c * 2, _next_pow2(need)))
                                for c in used]
                    fn, fresh = self._get_fn(plan, tuple(new_caps),
                                             chunk_size, extension, collect,
                                             0, n_steps, dg, opts)
                    (b, p, org, count, ovf_step, totals, kepts, pins,
                     pouts) = call_fn(
                        fn, fresh,
                        (*rec["args"], params_dev, sarrs, dg.arrays),
                        retry=True)
                    start = 0
                used = new_caps
                # persist the learned schedule for subsequent chunks
                shared = self._caps_cache[caps_key]
                for si in range(n_steps):
                    shared[si] = max(shared[si], used[si])
            c = int(count)
            total += c
            if collect == "bindings" and c:
                out_b.append(_host_rows(b, c))
                out_p.append(_host_rows(p, c))
                o = _host_rows(org, c)
                if not extension:
                    o = o + rec["offset"]  # chunk-local start index -> global
                out_o.append(o)

        pending: deque[dict] = deque()
        max_inflight = max(1, int(opts.async_chunks))
        offset = 0
        while offset < n_src:
            check_cancel()
            hi = min(offset + chunk_size, n_src)
            if profile and n_steps:
                self._run_profiled_chunk(plan, sarrs, offset, hi, chunk_size,
                                         extension, collect, caps_key, stats,
                                         host_args, drain, dg, trace,
                                         params_dev, opts, check_cancel,
                                         kernels)
            else:
                pending.append(dispatch(offset, hi))
                if len(pending) >= max_inflight:
                    drain(pending.popleft())
            offset = hi
        while pending:
            drain(pending.popleft())

        stats["caps"] = list(self._caps_cache[caps_key])
        stats["wall_ms"] = (time.perf_counter() - t_run0) * 1e3
        # which expansion kernel each step ran, consumed by the workload
        # profiler's kernel-mix accounting
        stats["step_kernels"] = [_expansion_kernel(kernels.get(si, []))
                                 for si in range(n_steps)]
        if trace is not None and n_steps:
            _annotate_step_spans(trace, plan, dg, kernels, stats, n_src)
        bindings = (np.concatenate(out_b) if out_b else _empty(plan)) \
            if collect == "bindings" else None
        pb = (np.concatenate(out_p) if out_p else _empty_p(plan)) \
            if collect == "bindings" else None
        origins = np.concatenate(out_o) if out_o else np.zeros(0, np.int32)
        # one overflow event == one step retry, in every execution mode
        return Result(total, bindings, pb, origins,
                      chunks_retried=sum(stats["step_retries"]), stats=stats)

    def run_batch(self, plan: ExecPlan, params_mat: np.ndarray,
                  collect: str = "bindings",
                  state: tuple | None = None,
                  cancel: CancelToken | None = None) -> list[Result]:
        """Answer ``B`` same-shape queries in one device launch.

        ``params_mat`` (int32 ``[B, plan.n_params]``) stacks one constant
        vector per query; the chunk program is ``jax.vmap``-ed over the
        params axis (and, when the start vertex itself is parameterized,
        over per-lane start chunks), so a whole batch costs one dispatch.
        Per-lane capacity overflow is handled by masking: an overflowing
        lane freezes exactly like a single-query chunk, and only those
        lanes are re-run individually through :meth:`run` (suffix-resume) —
        results are bit-identical to per-query execution either way.

        Lanes whose constants are missing from the dictionary (negative
        ids) or whose parameterized start fails its label check return
        empty results without touching the device.  Falls back to
        sequential :meth:`run` calls when the plan's start set does not fit
        one chunk.  The fused Pallas kernel is disabled under vmap — the
        ref/jnp path is batchable on every backend."""
        state = self.pin() if state is None else state
        view, dg = state
        params_mat = np.asarray(params_mat, np.int32)
        if params_mat.ndim != 2 or params_mat.shape[1] != plan.n_params:
            raise ValueError(
                f"expected params [B, {plan.n_params}], got "
                f"{params_mat.shape}")
        B = params_mat.shape[0]
        n_steps = len(plan.steps)

        def empty() -> Result:
            return Result(0,
                          _empty(plan) if collect == "bindings" else None,
                          _empty_p(plan), np.zeros(0, np.int32))

        results: list[Result | None] = [None] * B
        if plan.unsat:
            return [empty() for _ in range(B)]
        if not plan.steps or plan.n_params == 0 or B == 1:
            # degenerate shapes: nothing to amortize, reuse the single path
            return [self.run(plan, collect=collect, state=state,
                             params=params_mat[i], cancel=cancel)
                    for i in range(B)]

        opts = replace(self.opts, use_fused=False, async_chunks=1)
        per_lane_start = plan.start_param_slot >= 0
        if per_lane_start:
            chunk_size = 1
            lane_chunks = np.full((B, 1), -1, np.int32)
            lane_counts = np.zeros(B, np.int32)
            for i in range(B):
                if (params_mat[i] < 0).any():
                    results[i] = empty()
                    continue
                cands = self._param_start_candidates(plan, params_mat[i],
                                                     view)
                if cands.size == 0:
                    results[i] = empty()
                else:
                    lane_chunks[i, 0] = cands[0]
                    lane_counts[i] = 1
        else:
            start_cands = self._start_candidates(plan, view)
            n_src = start_cands.shape[0]
            if n_src == 0:
                return [empty() for _ in range(B)]
            if n_src > opts.chunk:
                # multi-chunk start sets: per-lane accumulation across
                # chunks loses the one-launch win anyway — run sequentially
                return [self.run(plan, collect=collect, state=state,
                                 params=params_mat[i], cancel=cancel)
                        for i in range(B)]
            chunk_size = n_src
            for i in range(B):
                if (params_mat[i] < 0).any():
                    results[i] = empty()

        live = [i for i in range(B) if results[i] is None]
        if not live:
            return results  # type: ignore[return-value]

        # pow2-pad the lane axis (bounds recompiles to log-many shapes);
        # pad lanes duplicate the first live lane and are discarded
        L = len(live)
        L_pad = 1 << max(0, (L - 1).bit_length())
        rows = live + [live[0]] * (L_pad - L)
        pmat = jnp.asarray(params_mat[rows])
        sarrs = self._arrays(plan, state)
        if per_lane_start:
            # one start row per lane: the single-query capacity floor
            # (init_cap) would make every lane pay for the whole batch's
            # worth of slots — vmapped compute is per-lane, so size caps to
            # the estimate with a small floor.  Undersized lanes freeze and
            # rerun solo, which keeps results bit-identical.
            caps = list(plan.capacity_schedule(
                chunk_size, min(opts.init_cap, 64), opts.max_cap,
                opts.cap_slack))
        else:
            _, caps = self._schedule(plan, chunk_size, opts)
        npv = max(1, plan.n_pvars)
        used = tuple(caps)

        key = ("batch", plan.signature(), used, chunk_size, L_pad,
               per_lane_start, collect, opts.key(), dg.key())
        fn = self._compiled.get(key)
        if fn is None:
            raw = build_chunk_fn(dg, plan, used, chunk_size, opts,
                                 table_input=False, collect=collect,
                                 start_step=0, stop_step=n_steps)
            lane_ax = 0 if per_lane_start else None
            fn = jax.jit(jax.vmap(raw,
                                  in_axes=(lane_ax, lane_ax, None, None, 0,
                                           None, None)))
            fn.kernel_log = raw.kernel_log
            self._compiled[key] = fn
        p0 = jnp.zeros((chunk_size, npv), jnp.int32)
        o0 = jnp.zeros((chunk_size,), jnp.int32)
        if per_lane_start:
            chunk_in = jnp.asarray(lane_chunks[rows])
            count_in = jnp.asarray(lane_counts[rows])
        else:
            chunk_in = jnp.asarray(start_cands)
            count_in = jnp.int32(n_src)
        if cancel is not None and cancel.expired:
            raise QueryCancelled(
                f"query cancelled: {cancel.reason or 'cancelled'}")
        try:
            poison = _faults.fire("dispatch")
            (b, p, org, count, ovf_step, totals, kepts, pins,
             pouts) = fn(chunk_in, count_in, p0, o0, pmat, sarrs, dg.arrays)
        except Exception as e:  # noqa: BLE001 - filtered just below
            if not is_transient_fault(e):
                raise
            # batched dispatch hit memory pressure: fall back to the
            # sequential path, whose per-run ladder absorbs the fault
            return [results[i] if results[i] is not None
                    else self.run(plan, collect=collect, state=state,
                                  params=params_mat[i], cancel=cancel)
                    for i in range(B)]
        count_h = np.asarray(count)
        if poison:
            count_h = np.zeros_like(count_h)
        ovf_h = np.asarray(ovf_step)
        b_h = np.asarray(b) if collect == "bindings" else None
        p_h = np.asarray(p) if collect == "bindings" else None
        org_h = np.asarray(org) if collect == "bindings" else None
        # per-lane step counters ([L_pad, n_steps]; -1 = frozen/no-probe,
        # same sentinel contract as the single-query chunk program)
        tot_h, kep_h = np.asarray(totals), np.asarray(kepts)
        pin_h, pout_h = np.asarray(pins), np.asarray(pouts)
        traced = fn.kernel_log[_kernel_key(sarrs, dg.arrays)]
        kernels = [_expansion_kernel(traced.get(si, []))
                   for si in range(n_steps)]
        for li, qi in enumerate(live):
            if int(ovf_h[li]) < n_steps:
                # overflowing lane: redo it alone — run()'s suffix-resume
                # growth is deterministic, so the answer is identical to
                # a lane that had fit
                results[qi] = self.run(plan, collect=collect, state=state,
                                       params=params_mat[qi], cancel=cancel)
                continue
            c = int(count_h[li])
            stats = _empty_stats(n_steps)
            stats["chunks"] = 1
            stats["batched"] = True
            stats["batch_lanes"] = L_pad
            stats["batch_fill"] = L / L_pad
            stats["step_kernels"] = kernels
            for si in range(n_steps):
                if tot_h[li, si] >= 0:
                    stats["step_rows"][si] = int(tot_h[li, si])
                if kep_h[li, si] >= 0:
                    stats["step_kept"][si] = int(kep_h[li, si])
                if pin_h[li, si] >= 0:
                    stats["step_prune_in"][si] = int(pin_h[li, si])
                if pout_h[li, si] >= 0:
                    stats["step_prune_out"][si] = int(pout_h[li, si])
            if collect == "bindings":
                results[qi] = Result(c, b_h[li, :c].copy(),
                                     p_h[li, :c].copy(),
                                     org_h[li, :c].copy(), stats=stats)
            else:
                results[qi] = Result(c, None, _empty_p(plan),
                                     np.zeros(0, np.int32), stats=stats)
        return results  # type: ignore[return-value]

    def _run_profiled_chunk(self, plan, sarrs, offset, hi, chunk_size,
                            extension, collect, caps_key, stats, host_args,
                            drain, dg: DeviceGraph | None = None,
                            trace=None, params_dev=None,
                            opts: ExecOpts | None = None,
                            check_cancel=None,
                            kernels: dict | None = None) -> None:
        """Step-at-a-time execution of one chunk with host syncs, filling
        per-step wall times and, in ``kernels``, the kernels each step's
        program ran; overflow handling is inherently suffix-resume (each
        window re-runs alone with a grown capacity)."""
        opts = self.opts if opts is None else opts
        dg = self.dg if dg is None else dg
        if params_dev is None:
            params_dev = jnp.zeros(0, jnp.int32)
        n_steps = len(plan.steps)
        caps = self._caps_cache[caps_key]
        args = host_args(offset, hi)
        state = None
        ci = stats["chunks"]
        stats["chunks"] += 1
        for si in range(n_steps):
            while True:
                if check_cancel is not None:
                    check_cancel()
                used = tuple(caps)
                n_in = chunk_size if si == 0 else used[si - 1]
                fn, fresh = self._get_fn(plan, used, n_in,
                                         extension or si > 0,
                                         collect, si, si + 1, dg, opts)
                if fresh:
                    stats["compiles"] += 1
                if _donates(extension or si > 0, si, used[si], n_in):
                    stats["donated"] += 1
                span_cm = (trace.span("compile" if fresh else "dispatch",
                                      chunk=ci, step=si)
                           if trace is not None else None)
                if span_cm is not None:
                    span_cm.__enter__()
                poison = _faults.fire("dispatch")
                t0 = time.perf_counter()
                if si == 0:
                    out = fn(*args, params_dev, sarrs, dg.arrays)
                else:
                    b, p, org, count = state
                    out = fn(b[:n_in], count, p[:n_in], org[:n_in],
                             params_dev, sarrs, dg.arrays)
                if kernels is not None:
                    _note_kernels(kernels, fn, sarrs, dg.arrays)
                if poison:
                    stats["poisoned"] = stats.get("poisoned", 0) + 1
                    out = (*out[:3], out[3] * 0, *out[4:])
                jax.block_until_ready(out)
                if span_cm is not None:
                    span_cm.__exit__(None, None, None)
                stats["step_wall_ms"][si] += (time.perf_counter() - t0) * 1e3
                b, p, org, count, ovf_step, totals, kepts, pins, pouts = out
                if int(ovf_step) >= n_steps:
                    if int(totals[0]) >= 0:
                        stats["step_rows"][si] += int(totals[0])
                    if int(kepts[0]) >= 0:
                        stats["step_kept"][si] += int(kepts[0])
                    if int(pins[0]) >= 0:
                        stats["step_prune_in"][si] += int(pins[0])
                    if int(pouts[0]) >= 0:
                        stats["step_prune_out"][si] += int(pouts[0])
                    state = (b, p, org, count)
                    break
                stats["step_retries"][si] += 1
                stats["resumes"] += 1
                _grow_caps(caps, si, opts.max_cap, int(totals[0]))
                if si > 0:
                    # retry from the frozen table the program returned: it
                    # is this step's input, which the dispatch may have
                    # donated (step 0 re-feeds its never-donated host args)
                    state = (b, p, org, count)
        # hand the finished table to the shared collection path (the -1
        # counter vectors mean "already accumulated above")
        b, p, org, count = state
        rec = {"out": (b, p, org, count, jnp.int32(n_steps),
                       jnp.full(n_steps, -1, jnp.int32),
                       jnp.full(n_steps, -1, jnp.int32),
                       jnp.full(n_steps, -1, jnp.int32),
                       jnp.full(n_steps, -1, jnp.int32)),
               "args": args, "caps": tuple(caps), "offset": offset}
        drain(rec)


def _host_rows(x: jax.Array, n: int) -> np.ndarray:
    """The first ``n`` rows of a device table, on the host.  The device
    slice is rounded up to a power of two rows: an eager slice compiles
    once per length, and a run has a different count per chunk."""
    return np.asarray(x[:min(x.shape[0], _next_pow2(n))])[:n]


def _empty(plan: ExecPlan) -> np.ndarray:
    return np.zeros((0, plan.query.n_vertices), dtype=np.int32)


def _empty_p(plan: ExecPlan) -> np.ndarray:
    return np.zeros((0, max(1, plan.n_pvars)), dtype=np.int32)

