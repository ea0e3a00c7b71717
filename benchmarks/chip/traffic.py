"""The one traffic generator: a mix file and a dataset give the requests.

A mix is ``traffic/<name>.json``::

    {"rate_qps": 4.0, "arrivals": "poisson", "schedule_seed": 0,
     "warmup_per_template": 3,
     "templates": [{"name": "Q1", "weight": 1, "query": "... {course} ...",
                    "params": {"course": {"population": "graduate_course",
                                          "zipf_s": 1.0}}}, ...]}

Warm-up sends every template ``warmup_per_template`` times, or its own
``warmup`` times where the template gives one, in round-robin order.  A
template whose plan the server's feedback replans needs more: each replan
comes after ``feedback_min_runs`` runs, at most three times, and the new
plan compiles on its first two runs, so such a template warms up
``3 * 5 + 3 = 18`` times and its replans and their compiles fall into
set-up, not into the window.

A placeholder ``{name}`` in a query is replaced by a term drawn from the
dataset's population of that name, Zipf-distributed with exponent
``zipf_s`` over the population in the order the generator made it (rank 1
is the first), so every seed has the same hot set.

A window of ``seconds`` holds exactly ``round(rate_qps * seconds)``
requests, and each template exactly its weighted share of them (largest
remainders first).  Arrival times are a Poisson process conditioned on that
count, i.e. sorted uniform draws over the window.  The order, the
constants and the arrival times come from a stream fixed by the mix's
``schedule_seed``, not from the run's seed: every seed sends the same
requests at the same times, and the seed changes only the data they run
on (``datagen/``).  Warm-up requests draw their constants from a stream of
their own, so they are not the window's draws.
"""

from __future__ import annotations

import json
from pathlib import Path

import numpy as np

from benchmarks.chip.triples import Dataset

HERE = Path(__file__).resolve().parent


def load(name: str) -> dict:
    mix = json.loads((HERE / "traffic" / f"{name}.json").read_text())
    if mix.get("arrivals") != "poisson":
        raise ValueError(f"traffic {name}: arrivals must be 'poisson'")
    return mix


class _Drawer:
    """Fills a template's placeholders from its populations."""

    def __init__(self, ds: Dataset, rng: np.random.Generator):
        self.ds = ds
        self.rng = rng
        self._p: dict[tuple[str, float], np.ndarray] = {}

    def _probs(self, population: str, s: float) -> np.ndarray:
        key = (population, s)
        if key not in self._p:
            n = len(self.ds.populations[population])
            w = 1.0 / np.arange(1, n + 1, dtype=float) ** s
            self._p[key] = w / w.sum()
        return self._p[key]

    def fill(self, template: dict) -> str:
        query = template["query"]
        for ph, spec in template.get("params", {}).items():
            pop = self.ds.populations[spec["population"]]
            k = int(self.rng.choice(len(pop), p=self._probs(
                spec["population"], float(spec["zipf_s"]))))
            query = query.replace("{" + ph + "}", self.ds.terms[pop[k]])
        return query


def counts(mix: dict, n: int) -> list[int]:
    """Exact per-template request counts summing to ``n``."""
    w = np.asarray([t["weight"] for t in mix["templates"]], dtype=float)
    share = n * w / w.sum()
    out = np.floor(share).astype(int)
    for i in np.argsort(-(share - out), kind="stable")[: n - out.sum()]:
        out[i] += 1
    return out.tolist()


def plan(mix: dict, ds: Dataset, seconds: float) -> dict:
    """``{"warmup": [(template, query)], "window": [(due_s, template,
    query)]}`` for one run."""
    seed = int(mix["schedule_seed"])
    warm = _Drawer(ds, np.random.default_rng([seed, 1]))
    reps = [int(t.get("warmup", mix["warmup_per_template"]))
            for t in mix["templates"]]
    warmup = [(t["name"], warm.fill(t))
              for r in range(max(reps))
              for t, n in zip(mix["templates"], reps) if r < n]
    rng = np.random.default_rng([seed, 2])
    draw = _Drawer(ds, rng)
    n = int(round(float(mix["rate_qps"]) * seconds))
    picks = [t for t, k in zip(mix["templates"], counts(mix, n))
             for _ in range(k)]
    order = rng.permutation(n)
    due = np.sort(rng.uniform(0.0, seconds, size=n))
    window = [(float(due[i]), picks[j]["name"], draw.fill(picks[j]))
              for i, j in enumerate(order.tolist())]
    return {"warmup": warmup, "window": window}
