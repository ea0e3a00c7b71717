"""Smoke run of the served SPARQL path on one TPU chip.

Builds LUBM from ``--seed`` at ``--scale`` universities (default 1000,
about 2.7M vertices and 6M edges), hosts it in an updatable
``DatasetRegistry`` behind the HTTP server in this process, and drives the
path a user would:

1. the LUBM mix over HTTP ``/sparql``, cold and warm, plus one traced
   request per query whose step spans name the kernels that ran, all at
   the server's default query timeout: a cold query slower than that is
   a 504 and fails the run;
2. the count-only path (``SparqlEngine.count``) and a SPARQL ``LIMIT``;
3. a forced suffix-resume on a second registry entry with a tiny capacity
   schedule, and a traced run of it whose equal-capacity re-entries
   donate their binding-table buffers;
4. ``INSERT DATA`` and ``DELETE DATA`` over ``/update``, each followed by
   re-queries, so queries run the ``delta_merge`` path on a snapshot;
5. queries checked against the plain host matcher
   (``repro.core.reference.enumerate_matches``) on the same graph: the
   selective ones, and the triangles Q2 and Q9, whose second step runs
   the Pallas ``bitmap_superset`` kernel;
6. every kernel ``kernels.ops`` runs as Pallas on this chip, against its
   XLA implementation on random inputs at the executor's shapes (the mix
   need not reach each of them).

It exits non-zero on any count mismatch, HTTP error or resilience event
(retry, escalation, degraded run), and when JAX finds no TPU.  The last
line of its output is ``{"ok": true, "device": {...}}``.

    python chip_smoke.py [--scale 1000] [--density 1.0] [--seed 0]
"""

from __future__ import annotations

import argparse
import json
import sys
import time
import traceback
import urllib.error
import urllib.parse
import urllib.request
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import ExecOpts, build_query_graph  # noqa: E402
from repro.core.reference import enumerate_matches  # noqa: E402
from repro.kernels import ops as kops  # noqa: E402
from repro.kernels import ref as kref  # noqa: E402
from repro.obs import Trace  # noqa: E402
from repro.rdf.generator import generate_lubm  # noqa: E402
from repro.rdf.sparql import parse_sparql  # noqa: E402
from repro.rdf.transform import type_aware_transform  # noqa: E402
from repro.rdf.workloads import LUBM_QUERIES  # noqa: E402
from repro.serve.server import (DatasetRegistry, make_server,  # noqa: E402
                                serve_in_thread)
from repro.utils.compile_cache import enable_compile_cache  # noqa: E402

MIX = ("Q1", "Q2", "Q4", "Q6", "Q7", "Q8", "Q9", "Q10", "Q14")
# the anchored queries, and the triangles whose steps run Pallas kernels
# (the host matcher takes tens of seconds on these at scale 1000)
REFERENCE = ("Q1", "Q4", "Q7", "Q8", "Q2", "Q9")
# the count-only path, checked against the bindings path's count
COUNTED = ("Q2", "Q9")
LIMITED, LIMIT = "Q9", 10
# a tiny capacity schedule makes these overflow and resume
RESUMED = ("Q9", "Q2")
RESUME_OPTS = ExecOpts(init_cap=16, cap_slack=1 / 64)
# queries whose answer grows by one per inserted GraduateStudent taking
# GraduateCourse0.Dept0.Univ0 (Q10 asks for Students; the type inference
# makes every GraduateStudent one)
UPDATED = ("Q1", "Q10")
NEW_STUDENT = "ub:SmokeStudent0.Dept0.Univ0"
COURSE = "ub:GraduateCourse0.Dept0.Univ0"
# one per program JAX compiles or fetches from the persistent cache
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
# the client waits longer than the server's default query timeout, so a
# slow query comes back as the server's 504, not a client timeout
CLIENT_TIMEOUT_S = 300


class CompileCounter:
    """Counts, in this process (all threads), the programs JAX compiled
    (``n``, persistent-cache hits included), the seconds that took
    (``seconds``), and the persistent-cache hits (``cache_hits``)."""

    def __init__(self) -> None:
        self.n = self.cache_hits = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_compile)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_compile(self, event: str, duration: float, **kw) -> None:
        if event == COMPILE_EVENT:
            self.n += 1
            self.seconds += duration

    def _on_event(self, event: str, **kw) -> None:
        if event == CACHE_HIT_EVENT:
            self.cache_hits += 1

    def close(self) -> None:
        jax.monitoring.unregister_event_duration_listener(self._on_compile)
        jax.monitoring.unregister_event_listener(self._on_event)


def build_dataset(scale: int, density: float, seed: int):
    """Generate and transform LUBM; return ``(graph, maps)``."""
    st = generate_lubm(scale=scale, density=density, seed=seed)
    st.finalize()
    return type_aware_transform(st)


def start_server(registry: DatasetRegistry):
    """Serve ``registry`` over HTTP on a free localhost port, with the
    server's default query timeout."""
    server = make_server(registry, host="127.0.0.1", port=0, workers=2)
    serve_in_thread(server)
    host, port = server.server_address[:2]
    return server, f"http://{host}:{port}"


def stop_server(server) -> None:
    server.shutdown()
    server.scheduler.stop()
    server.server_close()


def _post(url: str, body: bytes, ctype: str) -> dict:
    req = urllib.request.Request(url, data=body,
                                 headers={"Content-Type": ctype})
    try:
        with urllib.request.urlopen(req, timeout=CLIENT_TIMEOUT_S) as resp:
            return json.loads(resp.read())
    except urllib.error.HTTPError as e:
        raise RuntimeError(f"HTTP {e.code} from {url}: "
                           f"{e.read().decode(errors='replace')}") from e


def sparql(base: str, query: str, dataset: str = "lubm", limit: int = 5,
           trace: bool = False) -> dict:
    """One ``/sparql`` request; bindings are LIMITed, the count is not."""
    params = {"query": query, "dataset": dataset, "limit": limit}
    if trace:
        params["trace"] = 1
    return _post(base + "/sparql", urllib.parse.urlencode(params).encode(),
                 "application/x-www-form-urlencoded")


def update(base: str, text: str, dataset: str = "lubm") -> dict:
    return _post(base + "/update",
                 urllib.parse.urlencode({"update": text,
                                         "dataset": dataset}).encode(),
                 "application/x-www-form-urlencoded")


def step_kernels(trace: dict) -> list[list[str]]:
    """Per step span, the ``name:impl`` kernels it ran."""
    out: list[list[str]] = []

    def walk(span: dict) -> None:
        if span.get("name") == "step":
            out.append(list(span["meta"]["kernels"]))
        for child in span.get("children", ()):
            walk(child)

    walk(trace["root"])
    return out


def run_mix(base: str, compiles: CompileCounter,
            names=MIX) -> dict[str, dict]:
    """Cold, warm and traced request per query.  Returns per-query
    ``{count, cold_ms, warm_ms, compiles_cold, compile_s_cold,
    cache_hits_cold, compiles_warm, ..., kernels}``."""
    out: dict[str, dict] = {}
    for name in names:
        q = LUBM_QUERIES[name]
        rec: dict = {}
        for phase in ("cold", "warm"):
            c0, s0, h0 = compiles.n, compiles.seconds, compiles.cache_hits
            t0 = time.perf_counter()
            res = sparql(base, q)
            rec[f"{phase}_ms"] = (time.perf_counter() - t0) * 1e3
            rec[f"compiles_{phase}"] = compiles.n - c0
            rec[f"compile_s_{phase}"] = compiles.seconds - s0
            rec[f"cache_hits_{phase}"] = compiles.cache_hits - h0
            rec.setdefault("count", res["stats"]["count"])
            if res["stats"]["count"] != rec["count"]:
                raise RuntimeError(f"{name}: warm count "
                                   f"{res['stats']['count']} != cold "
                                   f"{rec['count']}")
            rec["returned"] = res["stats"]["returned"]
        traced = sparql(base, q, trace=True)
        rec["traced_count"] = traced["stats"]["count"]
        rec["kernels"] = step_kernels(traced["trace"])
        out[name] = rec
    return out


def check_reference(g, maps, results: dict, names=REFERENCE) -> list[str]:
    """Compare served counts with the host matcher on the same graph."""
    bad = []
    for name in names:
        qg = build_query_graph(parse_sparql(LUBM_QUERIES[name]).where.triples,
                               maps)
        t0 = time.perf_counter()
        want = len(enumerate_matches(g, qg))
        print(f"reference {name}: {want} matches "
              f"({time.perf_counter() - t0:.2f} s on the host); served by "
              f"{results[name]['kernels']}", flush=True)
        if results[name]["count"] != want:
            bad.append(f"{name}: served {results[name]['count']} != "
                       f"reference {want}")
    return bad


def check_consistency(results: dict) -> list[str]:
    """Traced (profiled, exact-plan) runs agree with the served counts."""
    return [f"{n}: traced count {r['traced_count']} != {r['count']}"
            for n, r in results.items() if r["traced_count"] != r["count"]]


def check_count_path(registry: DatasetRegistry, results: dict,
                     names=COUNTED) -> list[str]:
    engine = registry.get("lubm").engine
    bad = []
    for name in names:
        got = engine.count(LUBM_QUERIES[name])
        print(f"count path {name}: {got}", flush=True)
        if got != results[name]["count"]:
            bad.append(f"{name}: count path {got} != bindings path "
                       f"{results[name]['count']}")
    return bad


def check_limit(base: str, results: dict, name: str = LIMITED,
                limit: int = LIMIT) -> list[str]:
    """A SPARQL ``LIMIT`` returns that many bindings, decoded."""
    res = sparql(base, LUBM_QUERIES[name] + f" LIMIT {limit}", limit=limit)
    want = min(limit, results[name]["count"])
    got = (res["stats"]["count"], len(res["results"]["bindings"]))
    print(f"{name} LIMIT {limit}: count={got[0]} bindings={got[1]}",
          flush=True)
    return [] if got == (want, want) else [
        f"{name} LIMIT {limit}: (count, bindings) {got} != {want}"]


def check_resume(registry: DatasetRegistry, results: dict,
                 names=RESUMED) -> tuple[list[str], dict]:
    """Run ``names[0]`` on the fast path and ``names[1]`` traced on the
    tiny-capacity entry ``lubm_resume``: the first must suffix-resume, the
    second runs the profiled per-step path, whose equal-capacity
    re-entries must donate their input buffers.  The fast-path run
    carries a sampled trace, which keeps it out of the small-plan probe
    (that probe re-runs a plan after its capacities were learned)."""
    engine = registry.get("lubm_resume").engine
    plain, traced = names
    res = engine.query(LUBM_QUERIES[plain], trace=Trace(sampled=True))
    base = res.stats["exec"]["branches"][0]["base"]
    res_t = engine.query(LUBM_QUERIES[traced], trace=True)
    base_t = res_t.stats["exec"]["branches"][0]["base"]
    info = {"resumes": base["resumes"], "step_retries": base["step_retries"],
            "traced_resumes": base_t["resumes"],
            "donated": base_t["donated"]}
    bad = []
    if res.count != results[plain]["count"]:
        bad.append(f"resume {plain}: {res.count} != {results[plain]['count']}")
    if res_t.count != results[traced]["count"]:
        bad.append(f"resume {traced} traced: {res_t.count} != "
                   f"{results[traced]['count']}")
    if not base["resumes"]:
        bad.append(f"resume {plain}: no suffix-resume happened")
    if not base_t["donated"]:
        bad.append(f"resume {traced} traced: no dispatch donated its "
                   "input buffers")
    return bad, info


def check_updates(base: str, results: dict, names=UPDATED) -> list[str]:
    """INSERT one matching student, then DELETE an existing match; every
    re-query must move by exactly that change, and run ``delta_merge``."""
    bad = []
    before = {n: results[n]["count"] for n in names}
    victim = sparql(base, LUBM_QUERIES["Q1"], limit=1)
    victim = victim["results"]["bindings"][0]["x"]["value"]
    steps = [
        ("INSERT", f"INSERT DATA {{ {NEW_STUDENT} rdf:type "
                   f"ub:GraduateStudent . {NEW_STUDENT} ub:takesCourse "
                   f"{COURSE} . }}", +1, "inserted", 2),
        ("DELETE", f"DELETE DATA {{ {victim} ub:takesCourse {COURSE} . }}",
         -1, "deleted", 1),
    ]
    delta = 0
    for label, text, change, key, n_triples in steps:
        res = update(base, text)
        print(f"update {label}: {json.dumps(res, sort_keys=True)}",
              flush=True)
        if res.get(key) != n_triples:
            bad.append(f"{label}: {key}={res.get(key)} != {n_triples}")
        delta += change
        for name in names:
            got = sparql(base, LUBM_QUERIES[name], trace=True)
            kernels = step_kernels(got["trace"])
            count = got["stats"]["count"]
            print(f"after {label} {name}: count={count} "
                  f"(before {before[name]}) kernels={kernels}", flush=True)
            if count != before[name] + delta:
                bad.append(f"after {label} {name}: {count} != "
                           f"{before[name]} {delta:+d}")
            if not any(k.startswith("delta_merge") for ks in kernels
                       for k in ks):
                bad.append(f"after {label} {name}: no delta_merge step "
                           f"({kernels})")
    return bad


def check_kernels(seed: int, rows: int = 1 << 16) -> tuple[list[str], list]:
    """Each kernel that runs as Pallas here, through ``kernels.ops``,
    against its XLA implementation on random inputs at the executor's
    shapes.  Returns ``(failures, kernels checked)``."""
    rng = np.random.default_rng(seed)
    bm = rng.integers(0, 2**32, (rows, 2), dtype=np.uint64).astype(np.uint32)
    req = (bm[0] & bm[1] & np.uint32(0xF0F0F0F0)).astype(np.uint32)
    cand = rng.integers(-1, 400, (rows, 1)).astype(np.int32)
    tile = np.sort(rng.integers(0, 400, (rows, 128)), axis=1).astype(np.int32)
    tile[:, 100:] = -2  # adjacency tile padding
    cases = {
        "bitmap_superset": (kops.bitmap_superset, kref.bitmap_superset_ref,
                            (bm, req)),
        "tile_membership": (kops.tile_membership, kref.tile_membership_ref,
                            (cand, tile)),
    }
    bad, checked = [], []
    for name in kops.TPU_IMPL:
        if kops.impl(name) == kops.XLA:
            continue
        if name not in cases:
            bad.append(f"{name}: runs as {kops.impl(name)} but has no "
                       "on-chip check")
            continue
        op, ref, args = cases[name]
        args = [jnp.asarray(a) for a in args]
        got, want = np.asarray(op(*args)), np.asarray(ref(*args))
        checked.append(f"{name}:{kops.impl(name)}")
        if not np.array_equal(got, want):
            bad.append(f"{name}: {int((got != want).sum())} of {got.size} "
                       "results differ from the XLA implementation")
    return bad, checked


def check_resilience(registry: DatasetRegistry) -> list[str]:
    bad = []
    for name in registry.names():
        snap = registry.get(name).engine.executor.resilience_snapshot()
        for key in ("degraded_runs", "fault_retries", "escalations"):
            if snap[key]:
                bad.append(f"{name}: {key}={snap[key]}")
    return bad


def device_bytes(registry: DatasetRegistry, name: str = "lubm") -> dict:
    dg = registry.get(name).engine.executor.dg
    return {"graph_array_bytes": int(sum(a.nbytes
                                         for a in dg.arrays.values())),
            "nbr_entries": int(dg.arrays["out_nbr_el"].shape[0])}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--scale", type=int, default=1000,
                    help="LUBM universities")
    ap.add_argument("--density", type=float, default=1.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    backend = jax.default_backend()
    if backend != "tpu":
        print(f"chip_smoke: JAX found no TPU (backend {backend!r}); "
              "nothing was run", file=sys.stderr)
        return 1
    cache = enable_compile_cache()
    dev = jax.devices()[0]
    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
          f"compile cache {cache}", flush=True)

    t0 = time.perf_counter()
    g, maps = build_dataset(args.scale, args.density, args.seed)
    print(f"graph: scale={args.scale} density={args.density} "
          f"seed={args.seed} vertices={g.n_vertices} edges={g.n_edges} "
          f"built in {time.perf_counter() - t0:.1f} s", flush=True)

    compiles = CompileCounter()
    registry = DatasetRegistry()
    t0 = time.perf_counter()
    registry.register("lubm", g, maps, updatable=True)
    registry.register("lubm_resume", g, maps, opts=RESUME_OPTS)
    print(f"registered in {time.perf_counter() - t0:.1f} s; device arrays "
          f"{json.dumps(device_bytes(registry))}", flush=True)
    server, base = start_server(registry)
    failures: list[str] = []
    try:
        t0 = time.perf_counter()
        results = run_mix(base, compiles)
        for name, r in results.items():
            print(f"{name}: count={r['count']} cold_ms={r['cold_ms']:.3f} "
                  f"warm_ms={r['warm_ms']:.3f} "
                  f"compiles_cold={r['compiles_cold']} "
                  f"compile_s_cold={r['compile_s_cold']:.3f} "
                  f"cache_hits_cold={r['cache_hits_cold']} "
                  f"compiles_warm={r['compiles_warm']} "
                  f"kernels={r['kernels']}", flush=True)
        print(f"mix done in {time.perf_counter() - t0:.1f} s", flush=True)
        failures += check_consistency(results)
        failures += check_count_path(registry, results)
        failures += check_limit(base, results)
        bad, info = check_resume(registry, results)
        print(f"resume: {json.dumps(info)}", flush=True)
        failures += bad
        failures += check_reference(g, maps, results)
        if not any(k.endswith(f":{kops.PALLAS}") for n in REFERENCE
                   for ks in results[n]["kernels"] for k in ks):
            failures.append("no query checked against the reference ran a "
                            "Pallas kernel")
        failures += check_updates(base, results)
        bad, checked = check_kernels(args.seed)
        print(f"kernels checked against XLA: {checked}", flush=True)
        failures += bad
        failures += check_resilience(registry)
    except Exception as e:  # noqa: BLE001 — report, then fail the run
        traceback.print_exc()
        failures.append(f"{type(e).__name__}: {e}")
    finally:
        stop_server(server)
        compiles.close()
    stats = dev.memory_stats() or {}
    print(f"compiles: {compiles.n} ({compiles.seconds:.3f} s), of which "
          f"persistent-cache hits: {compiles.cache_hits}; "
          f"peak_bytes_in_use: {stats.get('peak_bytes_in_use')}", flush=True)
    if failures:
        for f in failures:
            print(f"FAIL {f}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
