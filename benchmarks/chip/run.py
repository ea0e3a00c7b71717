"""Run one benchmark cell once on the chip and print its result line.

    python3 benchmarks/chip/run.py --workload lubm-mix --seed 7 \\
        --seconds 51 --trace 0

The cell (``BENCHMARK.json`` ``workloads``) names a configuration
(``configs/<name>.json``, whose ``generator`` is ``datagen/<name>.py``) and
a traffic mix (``traffic/<name>.json``).  One run:

1. builds the dataset from ``--seed`` with the benchmark's generator and
   loads it through the program's own path (``TripleStore``,
   ``type_aware_transform``, ``DatasetRegistry.register``);
2. serves it with ``make_server`` and a ``Scheduler`` at the settings the
   configuration records (the serving CLI's defaults);
3. warms up: every template over HTTP ``warmup_per_template`` times (or
   its own ``warmup`` times), with constants that are not the window's;
4. drives ``--seconds`` of open-loop HTTP traffic from a child process
   (``loadgen.py``), timing each request from its due time;
5. with ``--trace 1``, records a device trace of the middle of the window
   plus the benchmark's layer probes and sampled host spans;
6. checks every answered request against the plain reference
   (``reference.py``) over the raw triples, and prints each compared
   number beside its limit on standard error and in the result line.

The last line of standard output is the result JSON.  The run exits
non-zero, with no result, when JAX finds no TPU or fewer chips than the
cell asks for.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import numpy as np  # noqa: E402

from benchmarks.chip import traffic  # noqa: E402
from benchmarks.chip.metrics import reader  # noqa: E402
from benchmarks.chip.triples import Dataset, RDF_TYPE, RDFS_SUBCLASSOF  # noqa: E402,E501

CACHE_DIR = ROOT / ".jax_cache"
WORK_DIR = ROOT / ".bench_work"
# a window request that fails (503, 504, no response) counts as this late,
# at the least: beyond any answered request at the server's timeout
CLIENT_GRACE_S = 60.0
LOADGEN_THREADS = 256


class NoChip(RuntimeError):
    pass


@dataclass
class Request:
    name: str
    due: float
    sent: float
    done: float
    status: int
    rows: int
    digest: str
    error: str
    query: str = ""
    correct: bool = False


@dataclass
class RunData:
    """What the metric readers read (``metrics/<name>.py``)."""

    seconds: float
    setup_s: float
    t0: float                      # window start, time.monotonic()
    timeout_s: float
    window: list[Request] = field(default_factory=list)
    probes: object = None
    device_trace: dict | None = None
    trace_span: tuple[float, float] = (0.0, 0.0)
    trace_window_s: float = 0.0
    memory_peak_bytes: int | None = None
    peak: dict | None = None
    max_degree: int = 1

    def in_window(self, t: float) -> bool:
        return self.t0 <= t < self.t0 + self.seconds

    def latencies_ms(self) -> list[float]:
        out = []
        for r in self.window:
            lat = (r.done - r.due) * 1e3
            if not r.correct:
                lat = max(lat, self.timeout_s * 1e3)
            out.append(lat)
        return out


# --------------------------------------------------------------------- cell
def load_cell(name: str, root: Path = ROOT) -> tuple[dict, dict, dict, dict]:
    """``(benchmark, cell, configuration, traffic mix)`` of a cell."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cfg = json.loads((root / cfg_entry["file"]).read_text())
    return bench, cell, cfg, traffic.load(cell["traffic"])


def merge(base: dict, over: dict) -> dict:
    """``base`` with ``over`` laid on it, one level deep: where both values
    are objects (``ranges``, ``server``) their keys merge, otherwise the
    override wins.  With no overrides, as on the chip, ``base`` comes back
    unchanged."""
    out = dict(base)
    for key, value in over.items():
        if isinstance(value, dict) and isinstance(base.get(key), dict):
            value = {**base[key], **value}
        out[key] = value
    return out


def generate(cfg: dict, seed: int) -> Dataset:
    gen = importlib.import_module(f"benchmarks.chip.datagen.{cfg['generator']}")
    return gen.generate(cfg, seed)


def max_degree(ds: Dataset) -> int:
    """Largest in- or out-degree over the data edges (type and subclass
    triples are labels, not edges, in the served graph)."""
    plain = np.ones(ds.n_triples, bool)
    for name in (RDF_TYPE, RDFS_SUBCLASSOF):
        if name in ds.preds:
            plain &= ds.p != ds.preds.index(name)
    n = len(ds.terms)
    return int(max(np.bincount(ds.s[plain], minlength=n).max(initial=0),
                   np.bincount(ds.o[plain], minlength=n).max(initial=0)))


# ------------------------------------------------------------------- device
def check_chips(chips: int, require_tpu: bool):
    import jax

    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < chips):
        raise NoChip(f"this cell needs {chips} TPU chip(s); JAX found "
                     f"{len(devs)} {devs[0].platform} device(s)")
    return devs


def memory_peak(dev) -> int | None:
    stats = dev.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


# ------------------------------------------------------------------ program
def load_program(ds: Dataset, cfg: dict, trace: bool, phases: dict):
    """Load ``ds`` through the program's path and serve it over HTTP."""
    from repro.rdf.transform import type_aware_transform
    from repro.rdf.triples import TripleStore
    from repro.serve.metrics import ServeMetrics
    from repro.serve.scheduler import Scheduler
    from repro.serve.server import DatasetRegistry, make_server, serve_in_thread

    t = time.monotonic()
    st = TripleStore()
    add = st.add
    for s, p, o in ds.term_strings():
        add(s, p, o)
    phases["encode_s"] = time.monotonic() - t
    t = time.monotonic()
    st.finalize()
    phases["finalize_s"] = time.monotonic() - t
    t = time.monotonic()
    g, maps = type_aware_transform(st)
    phases["transform_s"] = time.monotonic() - t
    del st
    srv = cfg["server"]
    metrics = ServeMetrics()
    registry = DatasetRegistry(
        metrics, result_cache_size=srv["result_cache_size"],
        trace_sample=1.0 if trace else 0.0, feedback=srv["feedback"],
        qerror_threshold=srv["feedback_threshold"],
        feedback_min_runs=srv["feedback_min_runs"])
    t = time.monotonic()
    registry.register(cfg["dataset"], g, maps)
    phases["upload_s"] = time.monotonic() - t
    scheduler = Scheduler(registry, workers=srv["workers"],
                          max_queue=srv["max_queue"],
                          default_timeout_s=srv["timeout_s"],
                          metrics=metrics, batch_max=srv["batch_max"],
                          batch_window_ms=srv["batch_window_ms"])
    server = make_server(registry, host="127.0.0.1", port=0,
                         scheduler=scheduler)
    serve_in_thread(server)
    return server


def stop_program(server) -> None:
    server.shutdown()
    server.scheduler.stop()
    server.server_close()


# --------------------------------------------------------------------- load
class LoadGen:
    """The child process that sends the traffic (``loadgen.py``)."""

    def __init__(self, url: str, dataset: str, plan: dict, timeout_s: float):
        job = {"url": url, "dataset": dataset, "timeout_s": timeout_s,
               "threads": LOADGEN_THREADS, "lead_s": 0.5,
               "warmup": plan["warmup"], "window": plan["window"]}
        self.proc = subprocess.Popen(
            [sys.executable, str(HERE / "loadgen.py")],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
        self.window_t0: float | None = None
        self.results: list | None = None
        self.started = threading.Event()
        self._reader = threading.Thread(target=self._read, daemon=True)
        self._reader.start()
        self.proc.stdin.write(json.dumps(job))
        self.proc.stdin.close()

    def _read(self) -> None:
        for line in self.proc.stdout:
            msg = json.loads(line)
            if msg["event"] == "window":
                self.window_t0 = msg["t0"]
                self.started.set()
            elif msg["event"] == "results":
                self.results = msg["requests"]
        self.started.set()

    def wait(self, timeout: float) -> None:
        try:
            self.proc.wait(timeout=timeout)
        finally:
            if self.proc.poll() is None:
                self.proc.kill()
                self.proc.wait()
            self._reader.join(timeout=10)


# -------------------------------------------------------------------- check
def check_answers(ds: Dataset, window: list[Request], entail: bool = True):
    """Compare every answered window request with the reference; returns
    the compared numbers."""
    from benchmarks.chip.reference import Reference

    ref = Reference(ds, entail=entail)
    want: dict[str, tuple[int, str]] = {}
    wrong = errors = 0
    examples = []
    for r in window:
        if r.status == 200:
            if r.query not in want:
                want[r.query] = ref.answer(r.query)
            r.correct = (r.rows, r.digest) == want[r.query]
            if not r.correct:
                wrong += 1
                if len(examples) < 3:
                    examples.append(f"{r.name}: served {r.rows} rows, "
                                    f"reference {want[r.query][0]}")
        elif r.status in (400, 404, 500):
            # the server answered a valid query with an error of its own
            errors += 1
            if len(examples) < 3:
                examples.append(f"{r.name}: HTTP {r.status} {r.error[:120]}")
    lost = sum(1 for r in window if r.status == 0)
    return {"wrong_answers": wrong, "error_answers": errors,
            "lost_requests": lost,
            "checked": sum(1 for r in window if r.status == 200),
            "distinct_queries": len(want)}, examples


LIMITS = {"wrong_answers": 0, "error_answers": 0, "lost_requests": 0}


# ---------------------------------------------------------------------- run
def run(workload: str, seed: int, seconds: float, trace: bool,
        require_tpu: bool = True, root: Path = ROOT,
        overrides: dict | None = None, log=print) -> dict:
    bench, cell, cfg, mix = load_cell(workload, root)
    overrides = overrides or {}
    cfg = merge(cfg, overrides.get("config", {}))
    mix = merge(mix, overrides.get("traffic", {}))
    devs = check_chips(int(cell["chips"]), require_tpu)
    import jax

    from benchmarks.chip import devtrace
    from benchmarks.chip.peaks import peaks
    from benchmarks.chip.probes import Probes

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    dev = devs[0]
    peak = peaks(dev.device_kind) if require_tpu else None
    probes = Probes()
    phases: dict = {}
    t = time.monotonic()
    ds = generate(cfg, seed)
    phases["generate_s"] = time.monotonic() - t
    server = load_program(ds, cfg, trace, phases)
    dataset = cfg["dataset"]
    srv = cfg["server"]
    plan = traffic.plan(mix, ds, seconds)
    queries = [q for _, _, q in plan["window"]]
    t = time.monotonic()
    rd = None
    loadgen = None
    try:
        if trace:
            probes.install_layers(server, dataset)
        host, port = server.server_address[:2]
        loadgen = LoadGen(f"http://{host}:{port}/sparql", dataset, plan,
                          srv["timeout_s"] + CLIENT_GRACE_S)
        loadgen.started.wait()
        if loadgen.window_t0 is None:
            raise RuntimeError("the load generator ended before its window")
        t0 = loadgen.window_t0
        phases["warmup_s"] = t0 - t
        warm = probes.compile_counts(T_START, t0)
        rd = RunData(seconds=seconds, setup_s=t0 - T_START, t0=t0,
                     timeout_s=srv["timeout_s"], probes=probes if trace
                     else None, peak=peak)
        log(json.dumps({"setup": {k: round(v, 3) for k, v in phases.items()},
                        "warmup": warm, "triples": ds.n_triples,
                        "compile_cache": str(CACHE_DIR)}), file=sys.stderr)
        if trace:
            _traced_stretch(rd, seconds)
        loadgen.wait(timeout=seconds + srv["timeout_s"]
                     + 2 * CLIENT_GRACE_S)
        if loadgen.results is None:
            raise RuntimeError(f"the load generator exited "
                               f"{loadgen.proc.returncode} with no results")
        log(json.dumps({"window": probes.compile_counts(t0, t0 + seconds)}),
            file=sys.stderr)
    finally:
        if loadgen is not None and loadgen.proc.poll() is None:
            loadgen.wait(timeout=1)
        stop_program(server)
        probes.close()
    rd.memory_peak_bytes = memory_peak(dev)
    if trace:
        try:
            rd.device_trace = devtrace.reduce(devtrace.load(WORK_DIR / "trace"))
        finally:
            shutil.rmtree(WORK_DIR / "trace", ignore_errors=True)
    rd.window = [Request(*r, query=q)
                 for r, q in zip(loadgen.results, queries)]
    del server
    gc.collect()
    if trace and rd.device_trace is not None:
        rd.max_degree = max_degree(ds)
    t = time.monotonic()
    compared, examples = check_answers(ds, rd.window)
    ref_s = time.monotonic() - t
    correct = all(compared[k] <= LIMITS[k] for k in LIMITS)
    checks = {k: {"value": compared[k], "limit": LIMITS[k]} for k in LIMITS}
    checks["checked"] = compared["checked"]
    checks["distinct_queries"] = compared["distinct_queries"]
    checks["reference_s"] = round(ref_s, 3)

    names = [m["name"] for m in (bench["per_layer"] if trace
                                 else bench["end_to_end"])
             if workload in m.get("workloads", [workload])]
    units = {m["name"]: m["unit"] for m in bench["end_to_end"]
             + bench["per_layer"]}
    metrics = {}
    for name in names:
        value = reader(name)(rd)
        if value is not None:
            metrics[name] = {"value": value, "unit": units[name]}
    failed = sum(1 for r in rd.window if r.status != 200)
    result = {"correct": correct, "attempted": len(rd.window),
              "failed": failed, "metrics": metrics,
              "device": {"platform": dev.platform, "kind": dev.device_kind,
                         "count": len(devs),
                         "memory_peak_bytes": rd.memory_peak_bytes}}
    if trace and rd.device_trace is not None:
        result["device"]["busy_s"] = rd.device_trace["busy_s"]
        result["device"]["window_s"] = rd.trace_window_s
        result["breakdown"] = {"device_ops": rd.device_trace["device_ops"],
                               "idle_gaps": rd.device_trace["idle_gaps"]}
    for line in examples:
        log(f"mismatch {line}", file=sys.stderr)
    for k, v in checks.items():
        if isinstance(v, dict):
            log(f"check {k} {v['value']} limit {v['limit']}", file=sys.stderr)
        else:
            log(f"check {k} {v}", file=sys.stderr)
    result["checks"] = checks
    return result


def _traced_stretch(rd: RunData, seconds: float) -> None:
    """Profile the middle of the window into ``WORK_DIR/trace``."""
    import jax

    start = rd.t0 + seconds / 4
    length = min(10.0, seconds / 2)
    time.sleep(max(0.0, start - time.monotonic()))
    out = WORK_DIR / "trace"
    shutil.rmtree(out, ignore_errors=True)
    out.mkdir(parents=True)
    jax.profiler.start_trace(str(out))
    a = time.monotonic()
    time.sleep(max(0.0, a + length - time.monotonic()))
    b = time.monotonic()
    jax.profiler.stop_trace()
    rd.trace_span = (a, b)
    rd.trace_window_s = b - a


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print("run.py: the program (src/repro) is not in this checkout",
              file=sys.stderr)
        return 2
    try:
        result = run(args.workload, args.seed, args.seconds,
                     bool(args.trace))
    except NoChip as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 1
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
