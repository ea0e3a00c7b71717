"""The control (the reference without entailment, in the program's place)
comes out not correct, at a size a test holds."""

from __future__ import annotations

import pytest

from benchmarks.chip.control import reading
from benchmarks.chip.tests.conftest import SEED, config_file, rehearsal


@pytest.mark.parametrize("cell", ["lubm-mix", "lubm-anchored"])
def test_control_is_not_correct(cell, anchored_root):
    size = rehearsal(config_file(cell, anchored_root))
    out = reading(cell, SEED, 6.0, {"config": size,
                                    "traffic": {"rate_qps": 4.0}},
                  root=anchored_root)
    assert not out["correct"]
    assert out["wrong_answers"] > 0
    assert out["checked"] == 24
