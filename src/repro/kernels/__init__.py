"""Kernels for the engine's compute hot-spots.

The paper's profiling singles out ExploreCandidateRegion and SubgraphSearch
(IsJoinable in particular) as the dominating costs.  Every vectorized
primitive has a pure-jnp implementation in ``ref.py`` (ragged expansion,
binary-search edge probes, delta merges, signature and bitmap filters);
two also have Pallas TPU kernels:

- ``sorted_intersect``  — tiled compare-all membership (+INT, VPU-shaped)
- ``bitmap_filter``     — packed-bitmap superset probes (label / NLF filters)

``ops.py`` dispatches through one table (``ops.TPU_IMPL``) that names, per
kernel, whether a TPU runs its Pallas kernel or its XLA (jnp)
implementation.
"""

from repro.kernels import ops, ref

__all__ = ["ops", "ref"]
