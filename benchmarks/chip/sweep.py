"""Find a traffic mix's knee once, by a sweep of fixed rates on the chip.

    python3 benchmarks/chip/sweep.py --config lubm-50 --seed 5 \\
        --seconds 51 --sweep lubm-mix=1.5,2,2.5,3 --sweep lubm-anchored=10,20,40

One set-up (the configuration's data, loaded and served as ``run.py``
does), then for each mix a warm-up and a window at each rate in turn.  Per
rate it prints one JSON line: requests, failures, latency percentiles from
due time, how late the generator ran, programs compiled in the window,
the backlog at the window's close
(requests still open then) and its trend (median latency of the last third
of the window's requests over that of the first third).
The knee is the highest rate whose backlog does not grow and whose
requests all come back before the server's timeout; cells run at four
fifths of it.  Answers are not checked here; ``run.py`` checks them.
"""

from __future__ import annotations

import argparse
import json
import statistics
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmarks.chip import run as R  # noqa: E402
from benchmarks.chip import traffic  # noqa: E402
from benchmarks.chip.metrics import percentile  # noqa: E402
from benchmarks.chip.probes import Probes  # noqa: E402


def summarize(reqs: list, seconds: float, timeout_s: float) -> dict:
    ok = [r for r in reqs if r[4] == 200]
    lat = sorted((r[3] - r[1]) * 1e3 for r in ok)
    thirds = len(reqs) // 3
    first = [(r[3] - r[1]) * 1e3 for r in reqs[:thirds] if r[4] == 200]
    last = [(r[3] - r[1]) * 1e3 for r in reqs[-thirds:] if r[4] == 200]
    trend = (statistics.median(last) / statistics.median(first)
             if first and last else float("nan"))
    return {"requests": len(reqs), "failed": len(reqs) - len(ok),
            "statuses": sorted({r[4] for r in reqs}),
            "p50_ms": percentile(lat, 50), "p90_ms": percentile(lat, 90),
            "p95_ms": percentile(lat, 95), "max_ms": lat[-1] if lat else None,
            "gen_late_p95_ms": percentile(
                [(r[2] - r[1]) * 1e3 for r in reqs], 95),
            "completed_qps": sum(1 for r in ok if r[3] <= seconds) / seconds,
            "open_at_close": sum(1 for r in reqs if r[3] > seconds),
            "backlog_trend": trend,
            "over_timeout": sum(1 for x in lat if x >= timeout_s * 1e3)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--sweep", action="append", required=True,
                    help="MIX=RATE,RATE,...")
    args = ap.parse_args(argv)
    bench = json.loads((R.ROOT / "BENCHMARK.json").read_text())
    entry = {c["name"]: c for c in bench["configs"]}[args.config]
    cfg = json.loads((R.ROOT / entry["file"]).read_text())
    devs = R.check_chips(1, require_tpu=True)
    import jax

    jax.config.update("jax_compilation_cache_dir", str(R.CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    probes = Probes()
    t = time.monotonic()
    ds = R.generate(cfg, args.seed)
    phases: dict = {"generate_s": time.monotonic() - t}
    server = R.load_program(ds, cfg, False, phases)
    print(json.dumps({"device": devs[0].device_kind, "setup": phases,
                      "triples": ds.n_triples}), flush=True)
    srv = cfg["server"]
    host, port = server.server_address[:2]
    url = f"http://{host}:{port}/sparql"
    try:
        for spec in args.sweep:
            name, rates = spec.split("=")
            mix = traffic.load(name)
            t = time.monotonic()
            for i, rate in enumerate(float(x) for x in rates.split(",")):
                plan = traffic.plan({**mix, "rate_qps": rate}, ds,
                                    args.seconds)
                if i:
                    plan["warmup"] = []
                gen = R.LoadGen(url, cfg["dataset"], plan,
                                srv["timeout_s"] + R.CLIENT_GRACE_S)
                gen.started.wait()
                warm_s = time.monotonic() - t
                gen.wait(timeout=args.seconds + srv["timeout_s"]
                         + 2 * R.CLIENT_GRACE_S)
                out = summarize(gen.results or [], args.seconds,
                                srv["timeout_s"])
                t0 = gen.window_t0 if gen.window_t0 is not None else t
                print(json.dumps({"mix": name, "rate_qps": rate,
                                  "warmup_s": round(warm_s, 3), **out,
                                  "window": probes.compile_counts(
                                      t0, t0 + args.seconds)}),
                      flush=True)
                t = time.monotonic()
    finally:
        R.stop_program(server)
        probes.close()
    stats = devs[0].memory_stats() or {}
    print(json.dumps({"peak_bytes_in_use": stats.get("peak_bytes_in_use")}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
