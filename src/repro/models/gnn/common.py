"""Message-passing primitives shared by the GNN zoo.

JAX has no native sparse message passing (BCOO only) — per the assignment,
aggregation is built on ``jax.ops.segment_sum`` / ``segment_max`` over an
edge-index scatter.  These wrappers add degree normalization, mean/std
aggregators, and a numerically safe segment softmax.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def segment_sum(x, seg, n):
    return jax.ops.segment_sum(x, seg, num_segments=n)


# --------------------------------------------------------------------------
# Explicit-SPMD variants (the "shard_map" GNN profile).
#
# GSPMD's auto-partitioning of scatter-adds over sharded edge arrays falls
# back to full rematerialization (replicating the whole aggregation on every
# device — the warnings the baseline dry-run logs, and the ~0.005 useful
# ratios in the baseline roofline).  These variants run the aggregation
# LOCALLY on each shard's edges and combine with psum/pmax across the mesh.
#
# Gradient correctness: inside shard_map, the transpose of ``psum`` is
# ``psum`` (the pmap convention), so backward cotangents crossing these
# aggregations are automatically all-reduced; taking ``pmean`` of the
# per-shard parameter gradients then reconstructs the exact global gradient
# (verified to ~1e-7 against the single-device gradient in
# tests/test_distributed.py::test_gnn_spmd_matches_single_device).
# --------------------------------------------------------------------------


def segment_sum_spmd(x, seg, n, axes, n_shards):
    """Local scatter-add over this shard's edges + cross-shard psum."""
    if not axes:
        return segment_sum(x, seg, n)
    local = jax.ops.segment_sum(x, seg, num_segments=n)
    return jax.lax.psum(local, axes)


def segment_max_spmd(x, seg, n, axes, n_shards, grad_scale: float = 1.0):
    """Cross-shard segment max, expressed through a masked psum so the
    backward pass uses the same collective transpose as the sum aggregators
    (a straight-through pmax composes incorrectly with deeper layers whose
    cotangents are shard-varying).  Empty segments: local counts guard the
    -inf identity (-inf − -inf = NaN otherwise); globally-empty segments
    restore -inf so downstream nan_to_num treats both paths identically.
    Cross-shard value ties share the gradient equally."""
    if not axes:
        return jax.ops.segment_max(x, seg, num_segments=n)
    local = jax.ops.segment_max(x, seg, num_segments=n)
    cnt_l = jax.ops.segment_sum(jnp.ones(seg.shape[0], local.dtype), seg,
                                num_segments=n)
    while cnt_l.ndim < local.ndim:
        cnt_l = cnt_l[..., None]
    sentinel = jnp.asarray(-3.0e38, local.dtype)
    local_f = jnp.where(cnt_l > 0, local, sentinel)
    m = jax.lax.pmax(jax.lax.stop_gradient(local_f), axes)
    mask = ((jax.lax.stop_gradient(local_f) == m) & (cnt_l > 0)).astype(
        local.dtype)
    ties = jax.lax.psum(mask, axes)
    out = jax.lax.psum(local_f * mask, axes) / jnp.maximum(ties, 1.0)
    cnt_g = jax.lax.psum(jnp.minimum(cnt_l, 1.0), axes)
    return jnp.where(cnt_g > 0, out, -jnp.inf)


def segment_min_spmd(x, seg, n, axes, n_shards, grad_scale: float = 1.0):
    if not axes:
        return jax.ops.segment_min(x, seg, num_segments=n)
    return -segment_max_spmd(-x, seg, n, axes, n_shards,
                             grad_scale=grad_scale)


def segment_mean_spmd(x, seg, n, axes, n_shards):
    s = segment_sum_spmd(x, seg, n, axes, n_shards)
    cnt = segment_sum_spmd(jnp.ones((x.shape[0], 1), x.dtype), seg, n, axes,
                           n_shards)
    return s / jnp.maximum(cnt, 1.0)


def segment_std_spmd(x, seg, n, axes, n_shards, eps: float = 1e-5):
    mu = segment_mean_spmd(x, seg, n, axes, n_shards)
    mu2 = segment_mean_spmd(x * x, seg, n, axes, n_shards)
    return jnp.sqrt(jnp.maximum(mu2 - mu * mu, 0.0) + eps)


def degrees_spmd(seg, n, axes, n_shards, dtype=jnp.float32):
    local = jax.ops.segment_sum(jnp.ones(seg.shape[0], dtype), seg,
                                num_segments=n)
    return jax.lax.psum(local, axes) if axes else local


def segment_mean(x, seg, n):
    s = jax.ops.segment_sum(x, seg, num_segments=n)
    cnt = jax.ops.segment_sum(jnp.ones((x.shape[0], 1), x.dtype), seg,
                              num_segments=n)
    return s / jnp.maximum(cnt, 1.0)


def segment_max(x, seg, n):
    return jax.ops.segment_max(x, seg, num_segments=n)


def segment_min(x, seg, n):
    return jax.ops.segment_min(x, seg, num_segments=n)


def segment_std(x, seg, n, eps: float = 1e-5):
    mu = segment_mean(x, seg, n)
    var = segment_mean(x * x, seg, n) - mu * mu
    return jnp.sqrt(jnp.maximum(var, 0.0) + eps)


def segment_softmax_norm(scores, seg, n):
    """Edge-softmax: normalize scores within each destination segment."""
    smax = jax.ops.segment_max(scores, seg, num_segments=n)
    ex = jnp.exp(scores - smax[seg])
    denom = jax.ops.segment_sum(ex, seg, num_segments=n)
    return ex / jnp.maximum(denom[seg], 1e-9)


def degrees(seg, n, dtype=jnp.float32):
    return jax.ops.segment_sum(jnp.ones(seg.shape[0], dtype), seg,
                               num_segments=n)
