"""Least work of one executor step, from the counts the step reports.

It counts what any implementation of the step has to touch, from the
step's own counters and the data, never from capacities or padding (those
are the implementation's choice, and a PR that changed them would move
the yardstick):

- each input row reads its vertex's adjacency offsets: 8 bytes;
- each expanded candidate reads its neighbour id and that neighbour's
  label word: 8 bytes and one operation;
- each kept row writes the columns bound so far, ``step + 2`` int32s
  (the start vertex and one per step);
- each non-tree edge check of a kept row is a binary search over an
  adjacency list: ``ceil(log2(max degree))`` int32 reads and operations.

Rows in for step 0 (the start candidates) are not reported by the step, so
they are not counted: the total is a lower bound of the work, and a
roofline share computed from it cannot exceed the true one.
"""

from __future__ import annotations

import math


def step_cost(step: int, rows_in: float, expanded: float, kept: float,
              nontree: int, max_degree: int) -> tuple[float, float]:
    """``(operations, bytes)`` of one step."""
    log_deg = max(1, math.ceil(math.log2(max(2, max_degree))))
    ops = expanded + nontree * log_deg * kept
    nbytes = (8.0 * rows_in + 8.0 * expanded + 4.0 * (step + 2) * kept
              + 4.0 * nontree * log_deg * kept)
    return ops, nbytes


def least_seconds(steps, max_degree: int, peak: dict) -> float:
    """Least device time of a list of step records (dicts with ``step``,
    ``rows``, ``kept``, ``nontree_checks``), at the chip's peaks: the larger
    of operations over peak operations and bytes over peak bandwidth,
    summed over steps.  ``rows`` is the step's expansion count, as the
    executor's step spans name it."""
    total = 0.0
    prev_kept = 0.0
    for rec in steps:
        si = int(rec["step"])
        rows_in = prev_kept if si > 0 else 0.0
        ops, nbytes = step_cost(si, rows_in, float(rec["rows"]),
                                float(rec["kept"]),
                                int(rec.get("nontree_checks", 0)), max_degree)
        total += max(ops / peak["flops_per_s"],
                     nbytes / peak["hbm_bytes_per_s"])
        prev_kept = float(rec["kept"])
    return total
