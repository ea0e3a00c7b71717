"""Multi-pod dry-run: lower + compile EVERY (architecture × input shape)
over the production meshes, record memory/cost analysis + collective bytes.

The CLI sets ``XLA_FLAGS`` to 512 forced host devices under ``__main__``,
before anything initializes a jax backend (jax locks the device count at
first initialization).  Importing this module sets nothing: tests and
benches must not inherit 512 devices.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun                      # all cells
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-8b      # one arch
  PYTHONPATH=src python -m repro.launch.dryrun --mesh single        # one mesh
  PYTHONPATH=src python -m repro.launch.dryrun --cell train_4k
Results: runs/dryrun/<mesh>/<arch>--<cell>.json (existing cells skipped,
so interrupted sweeps resume).
"""

import argparse
import json
import os
import re
import time
import traceback
from pathlib import Path

import jax

from repro.configs import all_archs, get_arch
from repro.launch.cells import build_cell
from repro.launch.mesh import make_production_mesh
from repro.utils import get_logger

log = get_logger("launch.dryrun")

COLLECTIVE_RE = re.compile(
    r"^\s*%?\S*\s*=\s*(\S+)\s+(all-gather|all-reduce|reduce-scatter|"
    r"all-to-all|collective-permute)", re.MULTILINE)
SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}


def _shape_bytes(type_str: str) -> int:
    """Bytes of one HLO type string like 'bf16[16,4096]' or a tuple."""
    total = 0
    for m in SHAPE_RE.finditer(type_str):
        dt, dims = m.group(1), m.group(2)
        nb = _DTYPE_BYTES.get(dt)
        if nb is None:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * nb
    return total


def collective_bytes(hlo_text: str) -> dict:
    """Sum output-shape bytes of every collective op in (post-SPMD) HLO."""
    out: dict[str, int] = {}
    for line in hlo_text.splitlines():
        s = line.strip()
        m = re.match(
            r"^%?\S+\s*=\s*((?:\([^)]*\)|\S+))\s+(all-gather|all-reduce|"
            r"reduce-scatter|all-to-all|collective-permute)", s)
        if not m:
            continue
        ty, op = m.group(1), m.group(2)
        out[op] = out.get(op, 0) + _shape_bytes(ty)
    out["total"] = sum(v for k, v in out.items() if k != "total")
    return out


def run_cell(arch_name: str, cell_name: str, mesh_name: str, out_dir: Path,
             force: bool = False) -> dict:
    out_path = out_dir / f"{arch_name}--{cell_name}.json"
    if out_path.exists() and not force:
        return json.loads(out_path.read_text())
    multi = mesh_name == "multi"
    mesh = make_production_mesh(multi_pod=multi)
    rec = {"arch": arch_name, "cell": cell_name, "mesh": mesh_name,
           "mesh_shape": dict(zip(mesh.axis_names,
                                  [int(mesh.shape[a]) for a in mesh.axis_names])),
           "status": "error"}
    t0 = time.time()
    try:
        built = build_cell(arch_name, cell_name, mesh)
        with jax.set_mesh(mesh):
            if built.get("family") == "engine":
                lowered = built["lower"]()
            else:
                lowered = built["step"].lower(*built["args"])
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        hlo = compiled.as_text()
        coll = collective_bytes(hlo)
        rec.update({
            "status": "ok",
            "lower_s": round(t_lower, 2),
            "compile_s": round(t_compile, 2),
            "flops": float(cost.get("flops", -1)) if cost else -1,
            "bytes_accessed": float(cost.get("bytes accessed", -1))
            if cost else -1,
            "cost_raw": {k: float(v) for k, v in (cost or {}).items()
                         if isinstance(v, (int, float))},
            "collective_bytes": coll,
            "memory": _mem_dict(mem),
            "hlo_bytes": len(hlo),
        })
        print(f"[dryrun] {mesh_name}/{arch_name}/{cell_name}: OK  "
              f"flops={rec['flops']:.3e} coll={coll.get('total', 0):.3e}B "
              f"compile={t_compile:.1f}s", flush=True)
        print(f"  memory_analysis: {rec['memory']}", flush=True)
    except Exception as e:  # noqa: BLE001 — record and continue the sweep
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
        print(f"[dryrun] {mesh_name}/{arch_name}/{cell_name}: FAIL {e}",
              flush=True)
    out_dir.mkdir(parents=True, exist_ok=True)
    out_path.write_text(json.dumps(rec, indent=1))
    return rec


def _mem_dict(mem) -> dict:
    if mem is None:
        return {}
    out = {}
    for attr in ("generated_code_size_in_bytes", "argument_size_in_bytes",
                 "output_size_in_bytes", "temp_size_in_bytes",
                 "alias_size_in_bytes"):
        v = getattr(mem, attr, None)
        if v is not None:
            out[attr] = int(v)
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="one arch id (default: all)")
    ap.add_argument("--cell", default=None, help="one cell (default: all)")
    ap.add_argument("--mesh", default="both", choices=["single", "multi",
                                                       "both"])
    ap.add_argument("--out", default="runs/dryrun")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    assert len(jax.devices()) == 512, (
        "dryrun needs 512 forced host devices; run it as "
        "`python -m repro.launch.dryrun`, which sets XLA_FLAGS")

    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    archs = [args.arch] if args.arch else all_archs()
    n_ok = n_fail = 0
    for mesh_name in meshes:
        for arch_name in archs:
            arch = get_arch(arch_name)
            cells = [args.cell] if args.cell else sorted(arch.cells)
            for cell_name in cells:
                rec = run_cell(arch_name, cell_name, mesh_name,
                               Path(args.out) / mesh_name, force=args.force)
                if rec["status"] == "ok":
                    n_ok += 1
                else:
                    n_fail += 1
    print(f"[dryrun] done: {n_ok} ok, {n_fail} failed", flush=True)
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    main()
