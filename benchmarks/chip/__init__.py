"""Chip benchmark: SPARQL over HTTP at fixed open-loop rates on one TPU.

``run.py`` is the command; ``BENCHMARK.json`` at the repository root lists
the cells.  Each configuration, traffic mix, data generator and metric is
a file of its own under this directory, found by the name the cell gives.
"""
