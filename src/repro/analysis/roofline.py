"""Roofline analysis from the compiled dry-run artifacts.

Three terms per (arch × cell), single-pod mesh (256 chips), TPU v5e
constants:

    compute_s    = HLO_FLOPs_per_chip / 197e12
    memory_s     = HLO_bytes_per_chip / 819e9
    collective_s = Σ_op factor(op) · collective_bytes_per_chip / 50e9
        factors: all-reduce 2 (ring send+recv of ~2(n−1)/n·s), all-gather 1
        (output ≈ wire), reduce-scatter 1 (underestimates by ~n·out ≈ in;
        noted), all-to-all 1, collective-permute 1.

``cost_analysis`` counts a lax.scan body ONCE (XLA HloCostAnalysis does not
multiply while-loop trip counts — verified in tests/test_roofline.py), so
LM stacks are corrected by *depth differencing*: compile the same cell at
small depths, per_layer = cost(L+1) − cost(L), total = fixed + depth ·
per_layer.  GNN/recsys/engine cells have python-unrolled stacks and need no
correction.

MODEL_FLOPS comes from analysis/model_flops.py (6·N_active·D etc.);
ratio = MODEL_FLOPS / (HLO_FLOPs_per_chip × chips) — remat and redundant
compute push it below the family's natural ceiling (≈0.33 for 6ND training
accounting with full remat ≈ 0.25).

The CLI compiles for 512 forced host devices; it sets ``XLA_FLAGS`` for
that itself, under ``__main__``, so importing this module changes nothing.
"""

import argparse
import json
import os
from pathlib import Path

import jax

from repro.analysis.model_flops import model_flops
from repro.configs import all_archs, get_arch

# Published per-chip peaks keyed by ``jax.Device.device_kind``:
# (peak FLOP/s, HBM bytes/s).  TPU v5e: 197 TFLOP/s bf16 and 819 GB/s HBM
# (Google Cloud documentation, "TPU v5e").  A device missing here has no
# roofline: nothing assumes a peak for it.
DEVICE_PEAKS = {
    "TPU v5 lite": (197e12, 819e9),
}
PEAK_FLOPS, HBM_BW = DEVICE_PEAKS["TPU v5 lite"]
LINK_BW = 50e9  # B/s per ICI link
CHIPS_SINGLE = 256
COLL_FACTORS = {"all-reduce": 2.0, "all-gather": 1.0, "reduce-scatter": 1.0,
                "all-to-all": 1.0, "collective-permute": 1.0}

# --------------------------------------------------------------------------
# Binding-table kernel cost models (current repro.kernels API)
#
# First-order traffic/flop models for the executor's step kernels, keyed by
# the names the dispatch layer (kernels/ops.py) actually exposes:
# ``expand_filter`` (the fused expand/filter/compact Pallas kernel),
# ``ragged_expand`` (the legacy expand + separate filter + scatter-compact
# path), ``delta_merge`` / ``delta_merge_labeled`` (live-store snapshot
# merge), and ``edge_exists`` (per-candidate binary-search join).  The
# executor's trace annotations evaluate these per step so measured wall
# time sits next to a roofline estimate in every span.
#
# Units: int32/float32 elements (4 B).  ``expanded`` = ragged expansion
# total for the step, ``rows`` = input binding-table rows, ``capacity`` =
# the step's capacity (table writes are capacity-shaped, not row-shaped),
# ``nq`` = binding-table width, ``bitmap_words`` = label-bitmap words per
# vertex, ``n_iters`` = binary-search iterations (≈ log2(max degree)).
# --------------------------------------------------------------------------

KERNEL_MODELS = ("expand_filter", "ragged_expand", "delta_merge",
                 "delta_merge_labeled", "edge_exists")


def kernel_cost(kernel: str, *, expanded: float, rows: float = 0.0,
                capacity: float = 0.0, nq: int = 4, bitmap_words: int = 1,
                n_iters: int = 20) -> dict:
    """Cost tuple ({flops, bytes, coll}) for one executor step kernel —
    the same shape ``roofline_terms`` consumes."""
    expanded = max(0.0, float(expanded))
    rows = max(0.0, float(rows))
    capacity = max(0.0, float(capacity))
    w = max(1, int(bitmap_words))
    it = max(1, int(n_iters))
    table = capacity * (nq + 1) * 4.0  # one table image (B + pvar/org cols)
    if kernel == "expand_filter":
        # CSR degree/start reads, one neighbor gather + bitmap gather per
        # expansion, in-kernel prefix sum, one gather-built output table
        bytes_ = rows * 12.0 + expanded * (8.0 + 4.0 * w) + 2.0 * table
        flops = expanded * (2.0 + w) + 2.0 * capacity
    elif kernel == "ragged_expand":
        # unfused: expansion triple (row, j, valid) materialized, filters
        # re-read candidates, scatter-compact touches the padded table twice
        bytes_ = rows * 12.0 + expanded * (16.0 + 4.0 * w) + 3.0 * table
        flops = expanded * (4.0 + w) + 3.0 * capacity
    elif kernel in ("delta_merge", "delta_merge_labeled"):
        # base + delta CSR reads and a tombstone binary search per
        # expansion on top of the unfused path; the labeled variant also
        # reads/writes the edge-label column
        lab = 8.0 if kernel == "delta_merge_labeled" else 0.0
        bytes_ = (rows * 24.0 + expanded * (16.0 + lab + 4.0 * (w + it))
                  + 3.0 * table)
        flops = expanded * (6.0 + w + it) + 3.0 * capacity
    elif kernel == "edge_exists":
        # per-candidate binary search over the probe vertex's adjacency
        bytes_ = expanded * 4.0 * it
        flops = expanded * float(it)
    else:
        raise ValueError(f"unknown kernel {kernel!r}; "
                         f"known: {KERNEL_MODELS}")
    return {"flops": flops, "bytes": bytes_, "coll": {}}


def estimate_step_ms(kernel: str, device_kind: str, **kw) -> dict | None:
    """Roofline time estimate for one executor step on one device of
    kind ``device_kind``.  Returns ``{model_ms, dominant, flops, bytes}`` —
    what the executor attaches to kernel-level trace spans — or ``None``
    when :data:`DEVICE_PEAKS` has no published peaks for the device."""
    if device_kind not in DEVICE_PEAKS:
        return None
    cost = kernel_cost(kernel, **kw)
    peak_f, bw = DEVICE_PEAKS[device_kind]
    compute_s = cost["flops"] / peak_f
    memory_s = cost["bytes"] / bw
    return {"model_ms": max(compute_s, memory_s) * 1e3,
            "dominant": "compute" if compute_s >= memory_s else "memory",
            "flops": cost["flops"], "bytes": cost["bytes"]}


def _cost_tuple(rec: dict) -> dict:
    coll = rec.get("collective_bytes", {})
    return {
        "flops": rec.get("flops", 0.0),
        "bytes": rec.get("bytes_accessed", 0.0),
        "coll": {k: v for k, v in coll.items() if k != "total"},
    }


def _combine(fixed, per, n):
    out = {"flops": fixed["flops"] + n * per["flops"],
           "bytes": fixed["bytes"] + n * per["bytes"],
           "coll": {}}
    keys = set(fixed["coll"]) | set(per["coll"])
    for k in keys:
        out["coll"][k] = fixed["coll"].get(k, 0) + n * per["coll"].get(k, 0)
    return out


def _sub(a, b):
    return {"flops": a["flops"] - b["flops"], "bytes": a["bytes"] - b["bytes"],
            "coll": {k: a["coll"].get(k, 0) - b["coll"].get(k, 0)
                     for k in set(a["coll"]) | set(b["coll"])}}


def xla_cost(compiled) -> dict:
    """``Compiled.cost_analysis()`` as one dict (empty when XLA reports
    nothing)."""
    return compiled.cost_analysis() or {}


def _variant_cost(arch_name: str, cell: str, depth: tuple[int, int],
                  cache_dir: Path) -> dict:
    """Compile the cell at a small depth and return its cost tuple."""
    key = f"{arch_name}--{cell}--d{depth[0]}-{depth[1]}.json"
    path = cache_dir / key
    if path.exists():
        return _cost_tuple(json.loads(path.read_text()))
    from repro.launch.cells import build_cell
    from repro.launch.dryrun import collective_bytes
    from repro.launch.mesh import make_production_mesh

    mesh = make_production_mesh(multi_pod=False)
    built = build_cell(arch_name, cell, mesh, lm_depth=depth)
    with jax.set_mesh(mesh):
        compiled = built["step"].lower(*built["args"]).compile()
    cost = xla_cost(compiled)
    rec = {
        "flops": float(cost.get("flops", 0.0)),
        "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
        "collective_bytes": collective_bytes(compiled.as_text()),
    }
    cache_dir.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(rec))
    print(f"[roofline] variant {arch_name}/{cell} depth={depth}: "
          f"flops={rec['flops']:.3e}", flush=True)
    return _cost_tuple(rec)


def corrected_cost(arch_name: str, cell: str, dryrun_rec: dict,
                   cache_dir: Path) -> dict:
    """Per-chip cost with scan-body depth correction (LM cells only)."""
    arch = get_arch(arch_name)
    if arch.family != "lm":
        return _cost_tuple(dryrun_rec)
    cfg = arch.config
    if cfg.moe is None:
        nd_full, nm_full = cfg.n_layers, 0
        c1 = _variant_cost(arch_name, cell, (1, 0), cache_dir)
        c2 = _variant_cost(arch_name, cell, (2, 0), cache_dir)
        per_dense = _sub(c2, c1)
        fixed = _sub(c1, per_dense)
        return _combine(fixed, per_dense, nd_full)
    nd_full = cfg.moe.first_dense_layers
    nm_full = cfg.n_layers - nd_full
    c11 = _variant_cost(arch_name, cell, (1, 1), cache_dir)
    c12 = _variant_cost(arch_name, cell, (1, 2), cache_dir)
    per_moe = _sub(c12, c11)
    if nd_full:
        c01 = _variant_cost(arch_name, cell, (0, 1), cache_dir)
        per_dense = _sub(c11, c01)
        fixed = _sub(c01, per_moe)
        out = _combine(fixed, per_dense, nd_full)
        return _combine(out, per_moe, nm_full - 0)
    # nd_full == 0 (dbrx): all layers MoE; fixed from the (0,1) variant
    c01 = _variant_cost(arch_name, cell, (0, 1), cache_dir)
    fixed = _sub(c01, per_moe)
    return _combine(fixed, per_moe, nm_full)


def roofline_terms(cost: dict, chips: int = CHIPS_SINGLE) -> dict:
    compute_s = cost["flops"] / PEAK_FLOPS
    memory_s = cost["bytes"] / HBM_BW
    coll_s = sum(COLL_FACTORS.get(k, 1.0) * v
                 for k, v in cost["coll"].items()) / LINK_BW
    dominant = max(
        (("compute", compute_s), ("memory", memory_s),
         ("collective", coll_s)), key=lambda kv: kv[1])[0]
    return {"compute_s": compute_s, "memory_s": memory_s,
            "collective_s": coll_s, "dominant": dominant}


def analyze(dryrun_dir: Path, out_dir: Path, archs=None) -> list[dict]:
    cache_dir = out_dir / "variants"
    rows = []
    for arch_name in (archs or all_archs()):
        arch = get_arch(arch_name)
        for cell in sorted(arch.cells):
            rec_path = dryrun_dir / "single" / f"{arch_name}--{cell}.json"
            if not rec_path.exists():
                continue
            rec = json.loads(rec_path.read_text())
            if rec.get("status") != "ok":
                continue
            cost = corrected_cost(arch_name, cell, rec, cache_dir)
            terms = roofline_terms(cost)
            row = {"arch": arch_name, "cell": cell, **terms,
                   "hlo_flops_per_chip": cost["flops"],
                   "hlo_bytes_per_chip": cost["bytes"],
                   "coll_bytes_per_chip": sum(cost["coll"].values()),
                   "raw_flops_per_chip": rec.get("flops", 0.0)}
            if arch.family != "engine":
                mf = model_flops(arch_name, cell)
                row["model_flops"] = mf
                denom = cost["flops"] * CHIPS_SINGLE
                row["useful_ratio"] = mf / denom if denom else 0.0
                step_s = max(terms["compute_s"], terms["memory_s"],
                             terms["collective_s"])
                row["roofline_frac"] = (
                    mf / CHIPS_SINGLE / PEAK_FLOPS) / step_s if step_s else 0.0
            rows.append(row)
            print(f"[roofline] {arch_name:18s} {cell:14s} "
                  f"c={terms['compute_s']:.2e}s m={terms['memory_s']:.2e}s "
                  f"n={terms['collective_s']:.2e}s dom={terms['dominant']:10s}"
                  f" ratio={row.get('useful_ratio', float('nan')):.3f}",
                  flush=True)
    out_dir.mkdir(parents=True, exist_ok=True)
    (out_dir / "roofline.json").write_text(json.dumps(rows, indent=1))
    (out_dir / "roofline.md").write_text(to_markdown(rows))
    return rows


def to_markdown(rows: list[dict]) -> str:
    hdr = ("| arch | cell | compute_s | memory_s | collective_s | dominant | "
           "MODEL_FLOPS | useful ratio | roofline frac |\n"
           "|---|---|---|---|---|---|---|---|---|\n")
    body = "".join(
        f"| {r['arch']} | {r['cell']} | {r['compute_s']:.3e} | "
        f"{r['memory_s']:.3e} | {r['collective_s']:.3e} | {r['dominant']} | "
        f"{r.get('model_flops', 0):.3e} | {r.get('useful_ratio', 0):.3f} | "
        f"{r.get('roofline_frac', 0):.3f} |\n"
        for r in rows)
    return hdr + body


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dryrun", default="runs/dryrun")
    ap.add_argument("--out", default="runs/roofline")
    ap.add_argument("--arch", default=None)
    args = ap.parse_args()
    analyze(Path(args.dryrun), Path(args.out),
            archs=[args.arch] if args.arch else None)


if __name__ == "__main__":
    if "--xla_force_host_platform_device_count" not in os.environ.get(
            "XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                                   + " --xla_force_host_platform_device_count=512")
    main()
