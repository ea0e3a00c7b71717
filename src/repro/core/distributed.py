"""Distributed engine execution (paper §5.2, NUMA → mesh).

The paper parallelizes over *starting data vertices* with dynamic chunking
across NUMA sockets.  Here:

- ``run_sharded``: host-level scatter of starting-vertex chunks across the
  data-parallel axes via a shard_map'd chunk program (graph replicated —
  the analogue of the paper's per-socket round-robin page interleave),
  counts combined with ``psum``.  Used on real multi-device runs and tested
  with forced host devices.
- ``engine_chunk_step``: the SPMD query step the multi-pod dry-run lowers —
  the same expansion/filter/join pipeline as core.exec.build_chunk_fn, but
  expressed over explicit graph-array *arguments* so it can be lowered with
  ShapeDtypeStructs at production scale (billion-edge arrays, 512 devices).
  A unit test checks it against the host Executor on a real graph.
- dynamic chunk scheduling: ``GreedyChunker`` orders candidate chunks by
  estimated region size (degree sum) and deals them round-robin so every
  device gets a balanced workload — the paper's dynamic distribution,
  precomputed (SPMD programs cannot work-steal at runtime; imbalance shows
  up as stragglers, which the tracker in train/straggler.py surfaces).
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core.exec import ExecOpts, Executor, build_chunk_fn
from repro.core.planner import ExecPlan
from repro.kernels import ops as kops
from repro.utils import get_logger

log = get_logger("core.distributed")


# ---------------------------------------------------------------------------
# work partitioning (the paper's dynamic chunking, precomputed)
# ---------------------------------------------------------------------------


@dataclass
class GreedyChunker:
    """Deal starting vertices to D shards, balancing estimated region size."""

    n_shards: int

    def partition(self, candidates: np.ndarray, degree: np.ndarray):
        est = degree[candidates].astype(np.float64) + 1.0
        order = np.argsort(-est)  # heaviest first
        loads = np.zeros(self.n_shards)
        shard_of = np.zeros(candidates.shape[0], dtype=np.int32)
        for idx in order:
            s = int(np.argmin(loads))
            shard_of[idx] = s
            loads[s] += est[idx]
        shards = [candidates[shard_of == s] for s in range(self.n_shards)]
        width = max(1, max(s.shape[0] for s in shards))
        out = np.full((self.n_shards, width), -1, dtype=np.int32)
        counts = np.zeros(self.n_shards, dtype=np.int32)
        for s, arr in enumerate(shards):
            out[s, : arr.shape[0]] = arr
            counts[s] = arr.shape[0]
        return out, counts, loads


# ---------------------------------------------------------------------------
# host-level sharded execution over real devices
# ---------------------------------------------------------------------------


def run_sharded(executor: Executor, plan: ExecPlan, mesh,
                collect: str = "count"):
    """Execute a plan with starting chunks scattered over the mesh's data
    axes.  Single-program path: shard_map over ("data",) [+ "pod"]."""
    if getattr(executor, "view", None) is not None:
        # live-store snapshots re-resolve candidates per version and ship
        # delta arrays per call; the shard_map path below bakes both, so
        # route snapshot execution through the (correct) host loop
        return executor.run(plan, collect="count").count
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    n_shards = 1
    for a in dp:
        n_shards *= mesh.shape[a]
    cands = plan.start_candidates
    if cands.shape[0] == 0 or plan.unsat:
        return 0
    chunker = GreedyChunker(n_shards)
    chunks, counts, loads = chunker.partition(cands, executor.graph.out.degree)
    width = chunks.shape[1]
    cap = max(executor.opts.init_cap, 1 << max(6, (width - 1).bit_length()))
    # widen capacity by the plan's fanout estimate, like the host loop
    est = 1.0
    for f in plan.est_fanout:
        est *= max(1.0, min(f, 64.0))
    cap = min(executor.opts.max_cap,
              max(cap, 1 << int(np.ceil(np.log2(max(2.0, width * min(est, 512.0)))))))

    n_steps = len(plan.steps)
    fn = build_chunk_fn(executor.dg, plan, (cap,) * n_steps, width,
                        executor.opts, table_input=False, collect="count")
    sarrs = executor._arrays(plan)

    def local(chunk_row, count_row):
        _, _, _, count, ovf_step, _, _, _, _ = fn(
            chunk_row[0], count_row[0],
            jnp.zeros((width, max(1, plan.n_pvars)), jnp.int32),
            jnp.zeros((width,), jnp.int32), jnp.zeros(0, jnp.int32), sarrs,
            executor.dg.arrays)
        total = jax.lax.psum(count, dp)
        ovf = (ovf_step < jnp.int32(n_steps)).astype(jnp.int32)
        any_ovf = jax.lax.pmax(ovf, dp)
        return total, any_ovf

    spec_in = P(dp, None)
    mapped = jax.shard_map(
        local, mesh=mesh,
        in_specs=(spec_in, P(dp)),
        out_specs=(P(), P()),
        check_vma=False)
    total, ovf = jax.jit(mapped)(jnp.asarray(chunks), jnp.asarray(counts))
    if int(ovf) > 0:
        log.warning("sharded run overflowed capacity %d; falling back to host "
                    "loop with retry", cap)
        return executor.run(plan, collect="count").count
    return int(total)


# ---------------------------------------------------------------------------
# SPMD dry-run step (production-scale lowering)
# ---------------------------------------------------------------------------


def engine_chunk_step(nbr_el, iptr_rows, label_bitmap, chunk, chunk_count,
                      *, cap: int, n_steps: int, max_log_deg: int = 32):
    """One fused query-chunk step at production scale.

    Semantically the executor's plan program for an n_steps-deep tree query
    with a label filter per step and one non-tree join check at the last
    step (the Q2/Q9 triangle shape):

      nbr_el       int32 [n_edges]           (el,src,dst)-sorted adjacency
      iptr_rows    int32 [n_steps, n_v + 1]  per-step CSR indptr rows
      label_bitmap uint32 [n_v, W]           vertex label words
      chunk        int32 [chunk_width]       starting vertices (-1 padded)
      chunk_count  int32 []

    Returns (count, overflow).  shard over: chunk → (pod, data); graph
    arrays replicated; candidate axis work is local (psum at the end).
    """
    n_v = label_bitmap.shape[0]
    w = label_bitmap.shape[1]
    required = jnp.full((w,), jnp.uint32(1))  # representative label mask

    b = jnp.full((cap,), -1, jnp.int32).at[: chunk.shape[0]].set(chunk)
    count = jnp.minimum(chunk_count, cap)
    prev = b  # previous-step bindings (for the final join check)
    overflow = jnp.zeros((), bool)

    for step in range(n_steps):
        iptr = iptr_rows[step]
        alive = jnp.arange(cap, dtype=jnp.int32) < count
        vp = jnp.clip(b, 0, n_v - 1)
        start = iptr[vp]
        deg = jnp.where(alive, iptr[vp + 1] - start, 0)
        coffs = jnp.cumsum(deg)
        total = coffs[-1]
        offs = (coffs - deg).astype(jnp.int32)
        overflow |= total > cap
        row, j, valid = kops.ragged_expand(offs, deg, cap)
        idx = jnp.clip(start[row] + j, 0, nbr_el.shape[0] - 1)
        v_new = jnp.where(valid, nbr_el[idx], -1)
        ok = valid
        bm = label_bitmap[jnp.clip(v_new, 0, n_v - 1)]
        ok &= kops.bitmap_superset(bm, required)
        if step == n_steps - 1:
            # non-tree join: edge (prev_binding -> v_new) must exist
            pv = jnp.clip(b[row], 0, n_v - 1)
            lo = iptr_rows[0][pv]
            hi = iptr_rows[0][pv + 1]
            ok &= kops.edge_exists(nbr_el, lo, hi, v_new, n_iters=max_log_deg)
        prev = b
        # compact
        cnt = jnp.sum(ok.astype(jnp.int32))
        pos = jnp.where(ok, jnp.cumsum(ok.astype(jnp.int32)) - 1, cap)
        b = jnp.full((cap + 1,), -1, jnp.int32).at[pos].set(v_new)[:cap]
        count = cnt
    return count, overflow


def lower_engine_cell(mesh, cfg, cell_meta, multi_pod: bool):
    """Lower the SPMD engine step over the production mesh (dry-run)."""
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    cap = cell_meta["cap"]
    chunk = cell_meta["chunk"]
    n_steps = cell_meta.get("n_steps", cfg.n_steps)
    w = (cfg.n_vlabels + 31) // 32

    def step(nbr_el, iptr_rows, label_bitmap, chunks, counts):
        local = partial(engine_chunk_step, cap=cap, n_steps=n_steps)

        def shard_fn(nbr, iptr, bm, ch, cnt):
            c, ovf = local(nbr, iptr, bm, ch[0], cnt[0])
            return jax.lax.psum(c, dp), jax.lax.pmax(ovf.astype(jnp.int32), dp)

        return jax.shard_map(
            shard_fn, mesh=mesh,
            in_specs=(P(), P(), P(), P(dp, None), P(dp)),
            out_specs=(P(), P()), check_vma=False,
        )(nbr_el, iptr_rows, label_bitmap, chunks, counts)

    n_shards = 1
    for a in dp:
        n_shards *= mesh.shape[a]
    sds = jax.ShapeDtypeStruct
    args = (
        sds((cfg.n_edges,), jnp.int32),
        sds((n_steps, cfg.n_vertices + 1), jnp.int32),
        sds((cfg.n_vertices, w), jnp.uint32),
        sds((n_shards, chunk), jnp.int32),
        sds((n_shards,), jnp.int32),
    )
    return jax.jit(step).lower(*args)
