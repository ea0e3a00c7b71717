"""Every seed serves data of the same size and sends the same schedule;
the seed changes only which links the data holds.  (The number of terms
is the same too once every degree university is drawn, as at the
configuration's 50 universities; at this test's size some are not.)"""

from __future__ import annotations

import json

import numpy as np
import pytest

from benchmarks.chip import traffic
from benchmarks.chip.datagen import lubm
from benchmarks.chip.tests.conftest import ROOT, SEED, tiny


@pytest.fixture(scope="module")
def two_seeds():
    cfg = json.loads((ROOT / "benchmarks/chip/configs/lubm-50.json")
                     .read_text())
    cfg = {**cfg, **tiny()}
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
