"""The control of the answer check: a reference that breaks a guarantee.

    python3 benchmarks/chip/control.py --workload lubm-mix --seeds 1,2,3

The configurations state exact answers under RDFS ``subClassOf``
entailment on ``rdf:type``.  The control puts the plain reference without
that entailment in the program's place: it answers every request of the
cell's window (the same requests a run sends, at the cell's size and
``run_seconds``) and is compared with the reference exactly as a run's
answers are.  It has to come out not correct; its ``wrong_answers`` is the
upper reading the check's limit is set below.  The benchmark's own runs
do not run it.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmarks.chip import run as R  # noqa: E402
from benchmarks.chip import traffic  # noqa: E402
from benchmarks.chip.reference import Reference  # noqa: E402


def reading(workload: str, seed: int, seconds: float,
            overrides: dict | None = None, root: Path = R.ROOT) -> dict:
    """The control's compared numbers for one seed."""
    _, _, cfg, mix = R.load_cell(workload, root)
    overrides = overrides or {}
    cfg = R.merge(cfg, overrides.get("config", {}))
    mix = R.merge(mix, overrides.get("traffic", {}))
    t = time.monotonic()
    ds = R.generate(cfg, seed)
    plan = traffic.plan(mix, ds, seconds)
    control = Reference(ds, entail=False)
    window = []
    for due, name, query in plan["window"]:
        rows, digest = control.answer(query)
        window.append(R.Request(name, due, due, due, 200, rows, digest, "",
                                query=query))
    compared, _ = R.check_answers(ds, window)
    return {"workload": workload, "seed": seed, **compared,
            "correct": all(compared[k] <= v for k, v in R.LIMITS.items()),
            "seconds_spent": round(time.monotonic() - t, 3)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    bench = json.loads((R.ROOT / "BENCHMARK.json").read_text())
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps(reading(args.workload, seed,
                                 float(bench["run_seconds"]))), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
