"""Compile for a described TPU v5e, with no chip attached.

Every kernel that ``kernels.ops.TPU_IMPL`` sends to Pallas is compiled by
the TPU compiler at the widths the executor gives it on a scale-1000 LUBM
graph, and so is one whole LUBM chunk program from ``build_chunk_fn``.  A
kernel Mosaic refuses fails here, at no chip time.  The topology is
described inside a fixture, never while a module is imported: only one
process may load the TPU library, and the test runner's other workers
import this file too.
"""

import dataclasses

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import ExecOpts, build_query_graph
from repro.core.exec import DeviceGraph, _plan_arrays, build_chunk_fn
from repro.core.planner import build_plan
from repro.kernels import ops as kops
from repro.rdf.sparql import parse_sparql
from repro.rdf.workloads import LUBM_QUERIES

# LUBM at scale 1000, density 1.0, seed 0: vertices and edges after the
# type-aware transform, as chip_smoke.py builds it
V_1000 = 2_641_315
E_1000 = 5_926_720
# the executor's default chunk, and the capacities a step table reaches
CHUNK = ExecOpts().chunk
CAPS = (4096, 1 << 16)

PALLAS_ON_TPU = sorted(k for k, v in kops.TPU_IMPL.items() if v == kops.PALLAS)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of these compiles
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def lubm_widths(lubm_graph):
    """Label-bitmap and signature widths (fixed by the ontology, not the
    scale) and one tiny graph to plan against."""
    g, maps = lubm_graph
    dg = DeviceGraph.from_graph(g, with_prune=True)
    return g, maps, dg


def _spec(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _kernel_cases(name, one_chip, dg):
    """(callable, args) pairs at the executor's shapes for ``name``."""
    from repro.kernels.bitmap_filter import bitmap_superset_pallas
    from repro.kernels.sorted_intersect import tile_membership_pallas

    s = lambda shape, dt=jnp.int32: _spec(one_chip, shape, dt)  # noqa: E731
    if name == "bitmap_superset":
        # label filter on the unfused path: [capacity, label words]
        w = int(dg.arrays["label_bitmap"].shape[1])
        return [(bitmap_superset_pallas, (s((cap, w), jnp.uint32),
                                          s((w,), jnp.uint32)))
                for cap in CAPS]
    if name == "tile_membership":
        # +INT join: one candidate per row against a pow2 adjacency tile
        # of at most ExecOpts.int_tile entries
        return [(tile_membership_pallas, (s((cap, 1)), s((cap, tb))))
                for cap in CAPS for tb in (8, ExecOpts().int_tile)]
    raise AssertionError(f"no compile case for Pallas kernel {name!r}")


@pytest.mark.parametrize("name", PALLAS_ON_TPU)
def test_pallas_kernel_compiles_for_v5e(name, one_chip, lubm_widths):
    _, _, dg = lubm_widths
    for fn, args in _kernel_cases(name, one_chip, dg):
        compiled = jax.jit(fn).lower(*args).compile()
        assert "tpu_custom_call" in compiled.as_text(), name


def _at_scale(x, n_v, n_e, one_chip):
    """The shape ``x`` takes on the scale-1000 graph: per-vertex and
    per-edge leading dimensions grow, everything else stays."""
    shape = tuple(x.shape)
    if shape and shape[0] == n_v + 1:
        shape = (V_1000 + 1,) + shape[1:]
    elif shape and shape[0] == n_v:
        shape = (V_1000,) + shape[1:]
    elif shape and shape[0] == n_e:
        shape = (E_1000,) + shape[1:]
    return _spec(one_chip, shape, x.dtype)


def test_lubm_chunk_program_compiles_for_v5e(one_chip, lubm_widths,
                                              monkeypatch):
    g, maps, dg = lubm_widths
    opts = ExecOpts()
    q = build_query_graph(parse_sparql(LUBM_QUERIES["Q2"]).where.triples, maps)
    plan = build_plan(g, q, use_sig=opts.use_prune)
    caps = plan.capacity_schedule(CHUNK, opts.init_cap, opts.max_cap,
                                  opts.cap_slack)
    # the program as a TPU process builds it: kernels.ops asks the
    # backend, which here is the CPU, so the test answers "tpu" for it
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    big = dataclasses.replace(dg, n_vertices=V_1000, pad_vertices=V_1000,
                              base_vertices=V_1000)
    fn = build_chunk_fn(big, plan, caps, CHUNK, opts, table_input=False)
    scale = lambda tree: jax.tree.map(  # noqa: E731
        lambda x: _at_scale(x, g.n_vertices, g.n_edges, one_chip), tree)
    s = lambda shape, dt=jnp.int32: _spec(one_chip, shape, dt)  # noqa: E731
    npv = max(1, plan.n_pvars)
    lowered = jax.jit(fn).lower(
        s((CHUNK,)), s(()), s((CHUNK, npv)), s((CHUNK,)), s((0,)),
        scale(_plan_arrays(g, plan, opts.use_prune)), scale(dg.arrays))
    compiled = lowered.compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    # the graph is an input of the program, not a constant baked into it
    assert mem.argument_size_in_bytes > E_1000 * 4
    assert mem.generated_code_size_in_bytes < 64 << 20
