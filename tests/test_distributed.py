"""Multi-device behavior, via subprocesses with forced host device counts
(jax pins the device count at first init, so each scenario gets its own
interpreter).  Covers: sharded engine == host engine, DP+TP train step ==
single-device step, pipeline-parallel loss/grads == dense loss/grads,
elastic checkpoint restore across mesh shapes, and the GreedyChunker."""

import json
import os
import subprocess
import sys
import textwrap

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_script(body: str, devices: int = 8, timeout: int = 420) -> str:
    script = (
        "import os\n"
        f"os.environ['XLA_FLAGS'] = "
        f"'--xla_force_host_platform_device_count={devices}'\n"
        + textwrap.dedent(body)
    )
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=timeout)
    assert proc.returncode == 0, f"STDOUT:\n{proc.stdout}\nERR:\n{proc.stderr}"
    return proc.stdout


def test_sharded_engine_matches_host():
    out = run_script("""
        import jax, numpy as np
        from repro.rdf.generator import generate_lubm
        from repro.rdf.transform import type_aware_transform
        from repro.rdf.sparql import parse_sparql
        from repro.rdf.workloads import LUBM_QUERIES
        from repro.core import ExecOpts, Executor, build_plan, build_query_graph
        from repro.core.distributed import run_sharded

        st = generate_lubm(scale=1, seed=0, density=0.3); st.finalize()
        g, maps = type_aware_transform(st)
        mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
        ex = Executor(g, ExecOpts())
        for name in ("Q2", "Q9", "Q6"):
            ast = parse_sparql(LUBM_QUERIES[name])
            q = build_query_graph(ast.where.triples, maps)
            plan = build_plan(g, q)
            host = ex.run(plan, collect="count").count
            if not plan.steps:
                print(f"{name} point {host}"); continue
            dist = run_sharded(ex, plan, mesh)
            print(f"{name} host={host} dist={dist}")
            assert host == dist, (name, host, dist)
        print("ENGINE_OK")
    """)
    assert "ENGINE_OK" in out


def test_dp_tp_train_step_matches_single_device():
    out = run_script("""
        import dataclasses, jax, numpy as np, jax.numpy as jnp
        from repro.configs import get_arch
        from repro.launch.cells import _named
        from repro.models import transformer
        from repro.sharding.specs import batch_specs, opt_state_specs, param_specs
        from repro.train.optimizer import OptConfig, adamw_init
        from repro.train.trainstep import make_train_step

        arch = get_arch("qwen3-8b")
        cfg, batch = arch.smoke()
        cfg = dataclasses.replace(cfg, compute_dtype="float32")
        params = transformer.init_params(jax.random.PRNGKey(0), cfg)
        opt_cfg = OptConfig(lr=1e-3, warmup_steps=1)
        opt = adamw_init(params, opt_cfg)
        step = make_train_step(transformer.loss_fn, cfg, opt_cfg)
        p1, o1, m1 = jax.jit(step)(params, opt, batch)

        mesh = jax.make_mesh((2, 4), ("data", "model"))
        pspecs = param_specs(jax.eval_shape(lambda: params), "lm", mesh)
        psh = _named(mesh, pspecs)
        osh = _named(mesh, opt_state_specs(pspecs, opt))
        bsh = _named(mesh, batch_specs("lm", "train",
                                       jax.eval_shape(lambda: batch), mesh))
        with jax.set_mesh(mesh):
            sharded = jax.jit(step, in_shardings=(psh, osh, bsh),
                              out_shardings=(psh, osh, None))
            p2, o2, m2 = sharded(jax.device_put(params, psh),
                                 jax.device_put(opt, osh),
                                 jax.device_put(batch, bsh))
        print("loss", float(m1["loss"]), float(m2["loss"]))
        assert abs(float(m1["loss"]) - float(m2["loss"])) < 1e-3
        for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32),
                                       rtol=2e-3, atol=2e-3)
        print("DPTP_OK")
    """)
    assert "DPTP_OK" in out


def test_pipeline_parallel_matches_dense():
    out = run_script("""
        import dataclasses, jax, numpy as np, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.configs import get_arch
        from repro.models import transformer
        from repro.sharding.pipeline import pipelined_loss

        arch = get_arch("qwen3-8b")
        cfg, batch = arch.smoke()
        cfg = dataclasses.replace(cfg, compute_dtype="float32", n_layers=4,
                                  remat=False)
        params = transformer.init_params(jax.random.PRNGKey(0), cfg)

        dense_loss = transformer.loss_fn(params, batch, cfg)

        mesh = jax.make_mesh((4,), ("pod",))
        n_stages, n_mb = 4, 2
        pspec = jax.tree.map(lambda _: P(), params)
        pspec["dense_layers"] = jax.tree.map(lambda _: P("pod"),
                                             params["dense_layers"])
        bspec = {"tokens": P(), "labels": P()}

        def loss_fn(p, b):
            return pipelined_loss(p, b, cfg, n_stages=n_stages,
                                  n_microbatches=n_mb)

        with jax.set_mesh(mesh):
            sm = jax.shard_map(loss_fn, mesh=mesh, in_specs=(pspec, bspec),
                               out_specs=P(), check_vma=False)
            pl_loss = jax.jit(sm)(params, batch)
            g_dense = jax.grad(lambda p: transformer.loss_fn(p, batch, cfg))(params)
            g_pipe = jax.jit(jax.grad(lambda p: sm(p, batch)))(params)
        print("dense", float(dense_loss), "pipe", float(pl_loss))
        assert abs(float(dense_loss) - float(pl_loss)) < 2e-3
        for a, b in zip(jax.tree.leaves(g_dense), jax.tree.leaves(g_pipe)):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32),
                                       rtol=5e-2, atol=5e-3)
        print("PIPE_OK")
    """)
    assert "PIPE_OK" in out


def test_elastic_checkpoint_restore_new_mesh(tmp_path):
    out = run_script(f"""
        import jax, numpy as np, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.train.checkpoint import Checkpointer

        params = {{"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8)}}
        mesh1 = jax.make_mesh((4, 2), ("data", "model"))
        sh1 = {{"w": NamedSharding(mesh1, P("data", "model"))}}
        p1 = jax.device_put(params, sh1)
        ck = Checkpointer(r"{tmp_path}", keep=2)
        ck.save(7, {{"params": p1}})

        mesh2 = jax.make_mesh((2, 2), ("data", "model"))  # topology changed
        sh2 = {{"w": NamedSharding(mesh2, P("model", "data"))}}
        step, trees, _ = ck.restore({{"params": params}},
                                    shardings={{"params": sh2}})
        assert step == 7
        np.testing.assert_array_equal(np.asarray(trees["params"]["w"]),
                                      np.asarray(params["w"]))
        assert trees["params"]["w"].sharding.mesh.shape["data"] == 2
        print("ELASTIC_OK")
    """)
    assert "ELASTIC_OK" in out


def test_greedy_chunker_balance():
    from repro.core.distributed import GreedyChunker

    rng = np.random.default_rng(0)
    degree = rng.zipf(1.5, 1000).astype(np.int64)
    cands = np.arange(1000, dtype=np.int32)
    chunks, counts, loads = GreedyChunker(8).partition(cands, degree)
    assert chunks.shape[0] == 8
    assert counts.sum() == 1000
    # LPT guarantee: makespan ≤ max(heaviest single item, 4/3 × ideal)
    est = degree[cands].astype(np.float64) + 1.0
    ideal = est.sum() / 8
    assert loads.max() <= max(est.max(), ideal * 4 / 3) + 1e-9
    # every candidate appears exactly once
    got = np.sort(chunks[chunks >= 0])
    np.testing.assert_array_equal(got, cands)


def test_gnn_spmd_matches_single_device():
    """Explicit-SPMD GNN gradients (shard_map profile) == single-device
    gradients, for all four archs (sum/mean/max/min/std aggregators)."""
    out = run_script("""
        import dataclasses, jax, numpy as np, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.configs import get_arch
        from repro.sharding.gnn_spmd import (SHARDED_FIELDS, pad_gnn_batch,
                                             n_shards_of, mesh_axes)
        from repro.models.gnn import dimenet, gcn, meshgraphnet, pna

        mesh = jax.make_mesh((2, 4), ("data", "model"))
        mods = {"gcn-cora": gcn, "pna": pna, "meshgraphnet": meshgraphnet,
                "dimenet": dimenet}
        tols = {"gcn-cora": 1e-4, "meshgraphnet": 1e-4, "dimenet": 1e-4,
                "pna": 1e-3}
        for name, mod in mods.items():
            arch = get_arch(name)
            cfg, batch = arch.smoke()
            params = mod.init_params(jax.random.PRNGKey(0), cfg)
            g_true = jax.grad(lambda p: mod.loss_fn(p, batch, cfg))(params)
            n_seg = batch["edge_src"].shape[0] if name == "dimenet" \\
                else batch["x"].shape[0]
            ns = n_shards_of(mesh)
            pb = pad_gnn_batch(name, {k: np.asarray(v)
                                      for k, v in batch.items()}, ns, n_seg)
            pb = {k: jnp.asarray(v) for k, v in pb.items()}
            cfg2 = dataclasses.replace(cfg, spmd_axes=mesh_axes(mesh),
                                       spmd_shards=ns)
            def local(p, b, cfg2=cfg2):
                g = jax.grad(lambda pp: mod.loss_fn(pp, b, cfg2))(p)
                return jax.lax.pmean(g, mesh_axes(mesh))
            sharded = set(SHARDED_FIELDS[name])
            bspec = {k: P(mesh_axes(mesh)) if k in sharded else P()
                     for k in pb}
            sm = jax.shard_map(local, mesh=mesh,
                               in_specs=(jax.tree.map(lambda _: P(), params),
                                         bspec),
                               out_specs=jax.tree.map(lambda _: P(), params),
                               check_vma=False)
            with jax.set_mesh(mesh):
                g2 = jax.jit(sm)(params, pb)
            rel = max(float(jnp.linalg.norm(a - b))
                      / (float(jnp.linalg.norm(a)) + 1e-12)
                      for a, b in zip(jax.tree.leaves(g_true),
                                      jax.tree.leaves(g2)))
            print(name, "rel", rel)
            assert rel < tols[name], (name, rel)
        print("GNN_SPMD_OK")
    """, timeout=420)
    assert "GNN_SPMD_OK" in out


def test_dimenet_edge_sharded_matches_single_device():
    """DimeNet v2 (edge-sharded, §Perf 4.2 iter 2): loss and gradients match
    the single-device forward exactly (all_gather transposes to
    reduce-scatter, so AD through the exchange is exact)."""
    out = run_script("""
        import dataclasses, jax, numpy as np, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.configs import get_arch
        from repro.models.gnn import dimenet

        NS = 4
        mesh = jax.make_mesh((NS,), ("data",))
        arch = get_arch("dimenet")
        cfg, batch = arch.smoke()
        params = dimenet.init_params(jax.random.PRNGKey(0), cfg)
        g_true = jax.grad(lambda p: dimenet.loss_fn(p, batch, cfg))(params)
        l_true = dimenet.loss_fn(params, batch, cfg)

        b = {k: np.asarray(v) for k, v in batch.items()}
        E = b["edge_src"].shape[0]
        E_pad = ((E + NS - 1) // NS) * NS
        n = b["pos"].shape[0]
        esrc = np.pad(b["edge_src"], (0, E_pad - E))
        edst = np.pad(b["edge_dst"], (0, E_pad - E), constant_values=n)
        e_l = E_pad // NS
        t_kj, t_ji = b["t_kj"], b["t_ji"]
        shard_of = t_ji // e_l
        T_pad = max(np.bincount(shard_of, minlength=NS).max(), 1)
        tkj_sh = np.zeros((NS, T_pad), np.int32)
        tji_sh = np.full((NS, T_pad), e_l, np.int32)
        for s in range(NS):
            sel = shard_of == s
            k = sel.sum()
            tkj_sh[s, :k] = t_kj[sel]
            tji_sh[s, :k] = t_ji[sel] - s * e_l
        sb = dict(b)
        sb["edge_src"], sb["edge_dst"] = esrc, edst
        sb["t_kj"], sb["t_ji"] = tkj_sh.reshape(-1), tji_sh.reshape(-1)
        sb = {k: jnp.asarray(v) for k, v in sb.items()}

        cfg2 = dataclasses.replace(cfg, spmd_axes=("data",), spmd_shards=NS,
                                   edge_sharded=True)
        def local(p, bb):
            l = dimenet.loss_fn(p, bb, cfg2)
            g = jax.grad(lambda pp: dimenet.loss_fn(pp, bb, cfg2))(p)
            return jax.lax.pmean(l, ("data",)), jax.lax.pmean(g, ("data",))
        bspec = {k: P("data") if k in ("edge_src", "edge_dst", "t_kj",
                                       "t_ji") else P() for k in sb}
        sm = jax.shard_map(local, mesh=mesh,
                           in_specs=(jax.tree.map(lambda _: P(), params),
                                     bspec),
                           out_specs=(P(), jax.tree.map(lambda _: P(),
                                                        params)),
                           check_vma=False)
        with jax.set_mesh(mesh):
            l2, g2 = jax.jit(sm)(params, sb)
        assert abs(float(l_true) - float(l2)) < 1e-5
        rel = max(float(jnp.linalg.norm(a - bb))
                  / (float(jnp.linalg.norm(a)) + 1e-12)
                  for a, bb in zip(jax.tree.leaves(g_true),
                                   jax.tree.leaves(g2)))
        assert rel < 1e-4, rel
        print("DIMENET_V2_OK")
    """)
    assert "DIMENET_V2_OK" in out
