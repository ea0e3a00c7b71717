"""Perf-iteration harness: compile a (arch × cell × profile) variant at small
unrolled depth, difference against depth-1, and report the corrected
three-term roofline — one hypothesis→measure cycle per invocation.

  PYTHONPATH=src python -m repro.analysis.perf --arch qwen3-8b \
      --cell train_4k --profile act_replicated

Results append to runs/perf/log.json so EXPERIMENTS.md §Perf can cite the
whole iteration history.  The CLI compiles for 512 forced host devices and
sets ``XLA_FLAGS`` for that under ``__main__``.
"""

import argparse
import json
import os
import time
from pathlib import Path

import jax

from repro.analysis.model_flops import model_flops
from repro.analysis.roofline import (CHIPS_SINGLE, PEAK_FLOPS, _combine,
                                     _sub, roofline_terms, xla_cost)
from repro.configs import get_arch
from repro.launch.dryrun import collective_bytes
from repro.launch.mesh import make_production_mesh


def _compile_cost(arch_name, cell, depth, profile):
    from repro.launch.cells import build_cell

    mesh = make_production_mesh(multi_pod=False)
    built = build_cell(arch_name, cell, mesh, lm_depth=depth, profile=profile)
    t0 = time.time()
    with jax.set_mesh(mesh):
        if built.get("family") == "engine":
            compiled = built["lower"]().compile()
        else:
            compiled = built["step"].lower(*built["args"]).compile()
    cost = xla_cost(compiled)
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "coll": {k: v for k, v in
                 collective_bytes(compiled.as_text()).items() if k != "total"},
        "compile_s": time.time() - t0,
    }


def measure(arch_name: str, cell: str, profile: str) -> dict:
    arch = get_arch(arch_name)
    if arch.family == "lm":
        cfg = arch.config
        if cfg.moe is None:
            c1 = _compile_cost(arch_name, cell, (1, 0), profile)
            c2 = _compile_cost(arch_name, cell, (2, 0), profile)
            per = _sub(c2, c1)
            total = _combine(_sub(c1, per), per, cfg.n_layers)
        else:
            nd = cfg.moe.first_dense_layers
            c11 = _compile_cost(arch_name, cell, (min(1, nd), 1), profile)
            c12 = _compile_cost(arch_name, cell, (min(1, nd), 2), profile)
            per = _sub(c12, c11)
            base = _combine(_sub(c11, per), per, cfg.n_layers - nd)
            total = base  # dense prefix folded into fixed for nd<=1
    else:
        total = _compile_cost(arch_name, cell, None, profile)
    terms = roofline_terms(total)
    rec = {"arch": arch_name, "cell": cell, "profile": profile, **terms,
           "flops_per_chip": total["flops"], "bytes_per_chip": total["bytes"],
           "coll_per_chip": total["coll"], "ts": time.time()}
    if arch.family != "engine":
        mf = model_flops(arch_name, cell)
        step_s = max(terms["compute_s"], terms["memory_s"],
                     terms["collective_s"])
        rec["model_flops"] = mf
        rec["useful_ratio"] = mf / max(total["flops"] * CHIPS_SINGLE, 1.0)
        rec["roofline_frac"] = (mf / CHIPS_SINGLE / PEAK_FLOPS) / step_s \
            if step_s else 0.0
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--cell", required=True)
    ap.add_argument("--profile", default="baseline")
    ap.add_argument("--out", default="runs/perf/log.json")
    args = ap.parse_args()
    rec = measure(args.arch, args.cell, args.profile)
    print(json.dumps(rec, indent=1))
    out = Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    log = json.loads(out.read_text()) if out.exists() else []
    log.append(rec)
    out.write_text(json.dumps(log, indent=1))


if __name__ == "__main__":
    if "--xla_force_host_platform_device_count" not in os.environ.get(
            "XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                                   + " --xla_force_host_platform_device_count=512")
    main()
