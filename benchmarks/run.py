"""Benchmark orchestrator — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.  ``--quick`` shrinks datasets
(CI-sized); default sizes match EXPERIMENTS.md.  Select suites with
``--only lubm,opts``.

``--check`` turns the run into a regression gate: each snapshot suite's
fresh results are diffed against its committed ``BENCH_*.json`` baseline
(see :mod:`benchmarks.check` — counts exact, internal speedup ratios within
tolerance) and a regression exits non-zero.  ``--trace-out FILE`` wraps
every suite in a :class:`repro.obs.Trace` span and writes Chrome
``trace_event`` JSON for chrome://tracing / Perfetto.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time


SUITES = ["lubm", "typeaware", "opts", "parallel", "hetero", "bsbm",
          "kernels", "exec", "archs", "serve", "planner", "store", "index"]

# suites whose module name differs from the suite name
SUITE_MODULES = {"store": "bench_update"}

# suites whose run() return value is persisted as BENCH_<name>.json next to
# this file (named after the module), giving future PRs a perf trajectory
# to compare against
SNAPSHOT_SUITES = {"planner", "exec", "store", "index", "typeaware", "serve"}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None,
                    help=f"comma list from {SUITES}")
    ap.add_argument("--check", action="store_true",
                    help="diff snapshot suites against committed BENCH_*"
                         " baselines; exit 1 on regression")
    ap.add_argument("--trace-out", default=None, metavar="FILE",
                    help="write a Chrome trace_event JSON of the run")
    args = ap.parse_args()
    chosen = args.only.split(",") if args.only else SUITES
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()

    trace = None
    if args.trace_out:
        from repro.obs import Trace, chrome_trace
        trace = Trace("bench")

    print("name,us_per_call,derived", flush=True)
    t0 = time.time()
    regressions: list[str] = []
    for suite in chosen:
        modname = SUITE_MODULES.get(suite, f"bench_{suite}")
        mod = __import__(f"benchmarks.{modname}", fromlist=["run"])
        t1 = time.time()
        try:
            if trace is not None:
                with trace.span(suite):
                    out = mod.run(quick=args.quick)
            else:
                out = mod.run(quick=args.quick)
            if suite in SNAPSHOT_SUITES and isinstance(out, dict):
                # quick runs land in a sibling file so smoke tests never
                # clobber the committed full-scale trajectory baseline
                base = modname.removeprefix("bench_")
                name = (f"BENCH_{base}.quick.json" if args.quick
                        else f"BENCH_{base}.json")
                path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                    name)
                if args.check:
                    # gate BEFORE overwriting the committed snapshot
                    from benchmarks import check

                    found = check.check_suite(base, out, quick=args.quick)
                    regressions.extend(found)
                    status = "regressed" if found else "ok"
                    print(f"_meta.{suite}.check,0,{status}", flush=True)
                with open(path, "w") as f:
                    json.dump({"quick": args.quick, "results": out}, f,
                              indent=1, sort_keys=True)
                print(f"_meta.{suite}.snapshot,0,{path}", flush=True)
        except Exception as e:  # keep the harness going; report the failure
            print(f"{suite}.SUITE_FAILED,0,{type(e).__name__}:{e}",
                  flush=True)
            import traceback

            traceback.print_exc(file=sys.stderr)
        print(f"_meta.{suite}.suite_seconds,{(time.time() - t1) * 1e6:.0f},",
              flush=True)
    print(f"_meta.total_seconds,{(time.time() - t0) * 1e6:.0f},", flush=True)

    if trace is not None:
        trace.finish()
        with open(args.trace_out, "w") as f:
            f.write(chrome_trace(trace, as_text=True))
        print(f"_meta.trace,0,{args.trace_out}", flush=True)

    if args.check and regressions:
        for r in regressions:
            print(f"REGRESSION: {r}", file=sys.stderr, flush=True)
        sys.exit(1)


if __name__ == "__main__":
    main()
