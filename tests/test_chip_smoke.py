"""CPU rehearsal of ``chip_smoke.py``: its phases at a tiny scale with the
host reference checks on, its refusal to run without a TPU, and the
compile-cache directory rule its entry points share."""

import sys
from pathlib import Path

import jax
import pytest

from repro.serve.server import DatasetRegistry
from repro.utils import compile_cache

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

import chip_smoke  # noqa: E402


@pytest.fixture(scope="module")
def served():
    g, maps = chip_smoke.build_dataset(scale=2, density=1.0, seed=0)
    registry = DatasetRegistry()
    registry.register("lubm", g, maps, updatable=True)
    registry.register("lubm_resume", g, maps, opts=chip_smoke.RESUME_OPTS)
    compiles = chip_smoke.CompileCounter()
    server, base = chip_smoke.start_server(registry)
    yield g, maps, registry, base, compiles
    chip_smoke.stop_server(server)
    compiles.close()


def test_chip_smoke_phases_on_cpu(served):
    g, maps, registry, base, compiles = served
    results = chip_smoke.run_mix(base, compiles)
    assert set(results) == set(chip_smoke.MIX)
    assert all(r["count"] > 0 for r in results.values())
    assert compiles.n > 0
    # off a TPU every kernel runs its XLA implementation, and says so
    kernels = [k for r in results.values() for ks in r["kernels"] for k in ks]
    assert kernels and all(k.endswith(":xla") for k in kernels)
    assert chip_smoke.check_consistency(results) == []
    assert chip_smoke.check_count_path(registry, results) == []
    assert chip_smoke.check_limit(base, results) == []
    bad, info = chip_smoke.check_resume(registry, results)
    assert bad == [] and info["resumes"] > 0 and info["donated"] > 0
    assert chip_smoke.check_reference(g, maps, results) == []
    assert chip_smoke.check_updates(base, results) == []
    assert chip_smoke.check_resilience(registry) == []
    sizes = chip_smoke.device_bytes(registry)
    assert sizes["nbr_entries"] == g.n_edges


def test_chip_smoke_kernel_check(monkeypatch):
    # off a TPU nothing runs as Pallas, so nothing needs checking ...
    assert chip_smoke.check_kernels(0, rows=256) == ([], [])
    # ... and in interpret mode both table-routed kernels are compared with
    # their XLA implementations ...
    monkeypatch.setenv("REPRO_KERNELS", "pallas_interpret")
    bad, checked = chip_smoke.check_kernels(0, rows=256)
    assert checked == ["bitmap_superset:pallas_interpret",
                       "tile_membership:pallas_interpret"]
    assert bad == []
    # ... while a kernel routed to Pallas with no on-chip case is flagged
    monkeypatch.setitem(chip_smoke.kops.TPU_IMPL, "edge_exists",
                        chip_smoke.kops.PALLAS)
    bad, _ = chip_smoke.check_kernels(0, rows=256)
    assert bad == ["edge_exists: runs as pallas_interpret but has no "
                   "on-chip check"]


def test_chip_smoke_main_fails_without_tpu(capsys):
    assert jax.default_backend() != "tpu"
    assert chip_smoke.main(["--scale", "1"]) != 0
    out, err = capsys.readouterr()
    assert out == "" and "no TPU" in err


def test_compile_cache_dir_rule(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    min_s = jax.config.jax_persistent_cache_min_compile_time_secs
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert compile_cache.enable_compile_cache() == tmp_path
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        fixed = compile_cache.enable_compile_cache()
        assert fixed == REPO / ".jax_cache" == compile_cache.DEFAULT_DIR
        assert jax.config.jax_compilation_cache_dir == str(fixed)
        # every program is cached, however fast it compiled
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", min_s)
