"""Every seed serves data of the same size and sends the same schedule;
the seed changes only which links, and in BSBM which values, the data
holds.  (In LUBM the number of terms is the same too once every degree
university is drawn, as at the configuration's 50 universities; at this
test's size some are not.)"""

from __future__ import annotations

import numpy as np
import pytest

from benchmarks.chip import traffic
from benchmarks.chip.datagen import bsbm, lubm
from benchmarks.chip.tests.conftest import SEED, rehearsed


@pytest.fixture(scope="module")
def two_seeds():
    cfg = rehearsed("lubm-50")
    return lubm.generate(cfg, 1), lubm.generate(cfg, SEED)


def test_seed_changes_links_not_sizes(two_seeds):
    a, b = two_seeds
    assert a.n_triples == b.n_triples
    assert ({k: len(v) for k, v in a.populations.items()}
            == {k: len(v) for k, v in b.populations.items()})
    assert not (np.array_equal(a.s, b.s) and np.array_equal(a.o, b.o))


@pytest.mark.parametrize("mix", ["lubm-mix", "lubm-anchored"])
def test_schedule_does_not_depend_on_seed(two_seeds, mix):
    a, b = two_seeds
    m = traffic.load(mix)
    assert traffic.plan(m, a, 51.0) == traffic.plan(m, b, 51.0)


def test_replanned_templates_warm_up_past_their_replans(two_seeds):
    m = traffic.load("lubm-mix")
    warm = [name for name, _ in traffic.plan(m, two_seeds[0], 51.0)["warmup"]]
    for t in m["templates"]:
        assert warm.count(t["name"]) == t.get("warmup",
                                              m["warmup_per_template"])


def test_bsbm_seed_changes_values_not_counts():
    cfg = rehearsed("bsbm-20k")
    a, b = bsbm.generate(cfg, 1), bsbm.generate(cfg, SEED)
    assert a.n_triples == b.n_triples
    assert a.preds == b.preds
    assert (np.bincount(a.p, minlength=len(a.preds)).tolist()
            == np.bincount(b.p, minlength=len(b.preds)).tolist())
    assert not (np.array_equal(a.s, b.s) and np.array_equal(a.o, b.o))
