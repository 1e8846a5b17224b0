"""Distributed correctness on fake multi-device meshes.

Device count is locked at first jax init, so these tests run in
subprocesses with XLA_FLAGS set (the main pytest process stays at 1
device, as the harness requires)."""

import pytest

from _forced_devices import run_forced_devices as _run


def test_moe_shard_map_matches_single_device():
    """EP+TP shard_map MoE == single-device oracle (fwd and grads)."""
    _run("""
        import jax, jax.numpy as jnp
        from repro.launch.mesh import make_debug_mesh
        from repro.models.moe import MoEConfig, init_moe, moe_ffn, _moe_core

        mesh = make_debug_mesh(2, 2, pods=2)  # (2,2,2) = 8 devices
        cfg = MoEConfig(n_experts=8, top_k=2, d_ff=32, n_shared=1,
                        capacity_factor=16.0, dispatch="sorted")
        p = init_moe(jax.random.PRNGKey(0), 64, cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (32, 64))

        def single(p, x):
            y, aux = _moe_core(x, p, cfg, "sorted")
            return jnp.sum(y * y) + 0.0 * aux

        def dist(p, x):
            with jax.sharding.use_mesh(mesh) if hasattr(jax.sharding, "use_mesh") else mesh:
                y, aux = moe_ffn(p, x, cfg, mesh=mesh, batch_axes=("pod", "data"))
            return jnp.sum(y * y) + 0.0 * aux

        l1, g1 = jax.value_and_grad(single)(p, x)
        with mesh:
            l2, g2 = jax.jit(jax.value_and_grad(dist))(p, x)
        assert jnp.allclose(l1, l2, rtol=1e-4), (l1, l2)
        diffs = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(a - b))), g1, g2)
        md = max(jax.tree.leaves(diffs))
        assert md < 1e-3, diffs
        print("OK moe dist", float(l1), float(l2), md)
    """)


def test_lm_train_step_on_debug_mesh():
    """A sharded tiny-LM train step runs and matches single-device loss."""
    _run("""
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.launch.mesh import make_debug_mesh
        from repro.models import transformer as tf
        from repro.dist.sharding import lm_rule, tree_shardings, batch_axes
        from repro.train.optimizer import OptimizerConfig
        from repro.train.train_step import init_train_state, make_train_step

        mesh = make_debug_mesh(2, 4)
        cfg = tf.TransformerConfig(
            name="t", n_layers=2, d_model=64, n_heads=4, n_kv_heads=4, d_head=16,
            d_ff=128, vocab=128, dtype="float32", param_dtype="float32")
        oc = OptimizerConfig(learning_rate=1e-3, warmup_steps=0, schedule="constant")
        params = tf.init_transformer(jax.random.PRNGKey(0), cfg)
        state = init_train_state(params, oc)
        toks = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, 128)
        ba = batch_axes(mesh)
        loss_fn = lambda p, b: tf.lm_loss(p, b["tokens"], cfg, mesh=mesh, batch_axes=ba)
        step = make_train_step(loss_fn, oc)
        st_sh = tree_shardings(state, mesh, lm_rule(mesh))
        b_sh = {"tokens": NamedSharding(mesh, P(ba, None))}
        with mesh:
            jstep = jax.jit(step, in_shardings=(st_sh, b_sh))
            new_state, metrics = jstep(state, {"tokens": toks})
        l_dist = float(metrics["loss"])
        # single-device reference
        st2 = init_train_state(params, oc)
        _, m2 = jax.jit(make_train_step(lambda p, b: tf.lm_loss(p, b["tokens"], cfg), oc))(st2, {"tokens": toks})
        assert abs(l_dist - float(m2["loss"])) < 1e-4, (l_dist, float(m2["loss"]))
        print("OK lm dist", l_dist)
    """)


def test_sharded_ce_matches_unsharded():
    _run("""
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.launch.mesh import make_debug_mesh
        from repro.models.common import cross_entropy_loss

        mesh = make_debug_mesh(2, 4)
        logits = jax.random.normal(jax.random.PRNGKey(0), (16, 64))
        labels = jax.random.randint(jax.random.PRNGKey(1), (16,), 0, 64)
        base = float(cross_entropy_loss(logits, labels))
        with mesh:
            sh = jax.device_put(logits, NamedSharding(mesh, P("data", "model")))
            dist = float(jax.jit(cross_entropy_loss)(sh, labels))
        assert abs(base - dist) < 1e-5, (base, dist)
        print("OK ce", base, dist)
    """)


_SHARDED_HYTM_SCRIPT = """
    import dataclasses
    import numpy as np
    import jax
    assert len(jax.devices()) == {devices}
    from repro.core.hytm import HyTMConfig, run_hytm
    from repro.graph.algorithms import (
        BFS, PAGERANK, SSSP, reference_bfs, reference_pagerank, reference_sssp,
    )
    from repro.graph.generators import rmat_graph

    g = rmat_graph(600, 5000, seed=7)
    # tolerance tightened so the converged Δ residual is small enough for
    # the numpy-reference comparison (the equivalence checks don't care)
    pr = dataclasses.replace(PAGERANK, tolerance=1e-6)
    for prog, src, name in ((BFS, 0, "bfs"), (SSSP, 0, "sssp"),
                            (pr, None, "pagerank")):
        cfg = HyTMConfig(
            n_partitions=16, async_sweep=False, mesh_axis="graph",
            cds_mode="delta" if prog.combine else "hub",
        )
        sharded = run_hytm(g, prog, source=src, config=cfg)
        oracle = run_hytm(
            g, prog, source=src, config=dataclasses.replace(cfg, mesh_axis=None)
        )
        # the acceptance triple: values, iteration count, transfer bytes
        assert sharded.iterations == oracle.iterations, name
        if prog.combine == 0:  # min-combine: bit-exact
            np.testing.assert_array_equal(sharded.values, oracle.values)
            assert sharded.total_transfer_bytes == oracle.total_transfer_bytes
        else:  # sum-combine: exact up to FP summation order of the psum
            np.testing.assert_allclose(
                sharded.values, oracle.values, rtol=0, atol=1e-5)
            np.testing.assert_allclose(
                sharded.total_transfer_bytes, oracle.total_transfer_bytes,
                rtol=1e-6)
        np.testing.assert_array_equal(
            sharded.history["engines"], oracle.history["engines"])
        # ...and against the numpy references
        finite = lambda x: np.where(np.isfinite(x), x, -1.0)
        if name == "bfs":
            np.testing.assert_array_equal(
                finite(sharded.values), finite(reference_bfs(g, 0)))
        elif name == "sssp":
            assert np.allclose(
                finite(sharded.values), finite(reference_sssp(g, 0)))
        else:
            ref = reference_pagerank(g)
            assert np.max(np.abs(sharded.values + sharded.delta - ref)) < 1e-2
        print("OK", name, sharded.iterations)
"""


@pytest.mark.parametrize("devices", [4, 8])
def test_sharded_hytm_matches_single_device_oracle(devices):
    """BFS/SSSP/PageRank through the shard_mapped sweep on forced-host
    meshes must reproduce the single-device run: same values, same
    iteration count, same modeled transfer bytes, same engine picks."""
    _run(_SHARDED_HYTM_SCRIPT.format(devices=devices), devices=devices)


_OWNER_SHARDED_SCRIPT = """
    import dataclasses
    import numpy as np
    import jax
    assert len(jax.devices()) == {devices}, jax.devices()
    from repro.core.hytm import HyTMConfig, run_hytm
    from repro.graph.algorithms import (ALGORITHMS, BFS, PAGERANK, SSSP,
                                        reference_kcore)
    from repro.graph.generators import rmat_graph

    g = rmat_graph(600, 5000, seed=7)
    pr = dataclasses.replace(PAGERANK, tolerance=1e-6)
    KCORE = ALGORITHMS["kcore"]
    for prog, src, name in ((BFS, 0, "bfs"), (SSSP, 0, "sssp"),
                            (pr, None, "pagerank"), (KCORE, None, "kcore")):
        cfg = HyTMConfig(
            n_partitions=16, async_sweep=False, mesh_axis="graph",
            cds_mode="delta" if (prog.combine and prog.peel_k is None)
            else "hub",
            vertex_sharding="owner",
        )
        sharded = run_hytm(g, prog, source=src, config=cfg)
        oracle = run_hytm(g, prog, source=src,
                          config=dataclasses.replace(
                              cfg, mesh_axis=None,
                              vertex_sharding="replicated"))
        assert sharded.iterations == oracle.iterations, name
        assert sharded.values.shape == (600,), sharded.values.shape
        if prog.combine == 0 or prog.peel_k is not None:
            # MIN family + peeling: bit-identical to the oracle
            np.testing.assert_array_equal(sharded.values, oracle.values)
            assert (sharded.total_transfer_bytes
                    == oracle.total_transfer_bytes), name
        else:
            np.testing.assert_allclose(sharded.values, oracle.values,
                                       rtol=0, atol=1e-5)
            np.testing.assert_allclose(sharded.total_transfer_bytes,
                                       oracle.total_transfer_bytes,
                                       rtol=1e-6)
        np.testing.assert_array_equal(sharded.history["engines"],
                                      oracle.history["engines"])
        if name == "kcore":
            ref_removed, ref_deg = reference_kcore(g, 2.0)
            np.testing.assert_array_equal(sharded.delta > 0.5, ref_removed)
            np.testing.assert_allclose(sharded.values, ref_deg)
        print("OK", name, sharded.iterations)

    # chunked driver under the owner layout (K > 1 lane through
    # make_sharded_batched_chunk)
    cfg = HyTMConfig(n_partitions=16, async_sweep=False, mesh_axis="graph",
                     sync_every=4, vertex_sharding="owner")
    sharded = run_hytm(g, SSSP, source=0, config=cfg)
    oracle = run_hytm(g, SSSP, source=0,
                      config=dataclasses.replace(cfg, mesh_axis=None,
                                                 vertex_sharding="replicated"))
    np.testing.assert_array_equal(sharded.values, oracle.values)
    assert sharded.iterations == oracle.iterations
    print("OK chunked", sharded.iterations)
"""


@pytest.mark.parametrize("devices", [4, 16])
def test_owner_sharded_matches_single_device_oracle(devices):
    """``vertex_sharding="owner"`` (owner-sharded ``(n/D,)`` state with a
    compacted halo exchange) reproduces the single-device oracle for
    BFS/SSSP/k-core bit-exactly (MIN family + peeling) and PageRank
    within tolerance — values, iterations, transfer bytes, engine picks
    — on 4 and 16 forced-host devices, iteration and chunked drivers."""
    out = _run(_OWNER_SHARDED_SCRIPT.format(devices=devices),
               devices=devices)
    assert out.count("OK") == 5, out


def test_sharded_hytm_padding_and_forced_engines():
    """Partition counts that do not divide the device count pad with
    empty partitions; forced single-engine baselines stay correct."""
    _run("""
        import dataclasses
        import numpy as np
        from repro.core.cost_model import COMPACT, FILTER, ZEROCOPY
        from repro.core.hytm import HyTMConfig, run_hytm
        from repro.graph.algorithms import SSSP, reference_sssp
        from repro.graph.generators import rmat_graph

        g = rmat_graph(500, 4000, seed=11)
        ref = reference_sssp(g, 0)
        for eng in (FILTER, COMPACT, ZEROCOPY, None):
            cfg = HyTMConfig(n_partitions=10, async_sweep=False,
                             mesh_axis="graph", forced_engine=eng)
            sharded = run_hytm(g, SSSP, source=0, config=cfg)
            oracle = run_hytm(g, SSSP, source=0,
                              config=dataclasses.replace(cfg, mesh_axis=None))
            np.testing.assert_array_equal(sharded.values, oracle.values)
            assert sharded.iterations == oracle.iterations
            assert np.allclose(sharded.values, ref), f"engine {eng}"
        print("OK padded+forced")
    """, devices=8)


def test_sharded_hytm_recompute_once_and_hubs():
    """The recompute-once second pass (global priority mask) agrees with
    the single-device schedule when hub partitions are designated."""
    _run("""
        import dataclasses
        import numpy as np
        from repro.core.hytm import HyTMConfig, run_hytm
        from repro.graph.algorithms import SSSP
        from repro.graph.generators import rmat_graph
        from repro.graph.hub_sort import hub_sort

        g = rmat_graph(800, 7000, seed=5)
        hs = hub_sort(g, hub_fraction=0.1)
        g2, n_hubs = hs.graph, hs.n_hubs
        cfg = HyTMConfig(n_partitions=16, async_sweep=False,
                         mesh_axis="graph", cds_mode="hub", recompute_once=True)
        sharded = run_hytm(g2, SSSP, source=0, config=cfg, n_hubs=n_hubs)
        oracle = run_hytm(g2, SSSP, source=0, n_hubs=n_hubs,
                          config=dataclasses.replace(cfg, mesh_axis=None))
        np.testing.assert_array_equal(sharded.values, oracle.values)
        assert sharded.iterations == oracle.iterations
        assert sharded.total_transfer_bytes == oracle.total_transfer_bytes
        print("OK hubs", sharded.iterations)
    """, devices=4)


def test_checkpoint_elastic_reshard():
    """Save on a (2,4) mesh, restore onto (4,2) — topology-elastic."""
    _run("""
        import tempfile
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.launch.mesh import make_debug_mesh
        from repro.train.checkpoint import save_checkpoint, restore_checkpoint

        m1 = make_debug_mesh(2, 4)
        m2 = make_debug_mesh(4, 2)
        x = jnp.arange(64.0).reshape(8, 8)
        tree = {"w": jax.device_put(x, NamedSharding(m1, P("data", "model")))}
        with tempfile.TemporaryDirectory() as td:
            save_checkpoint(td, 1, tree)
            new_sh = {"w": NamedSharding(m2, P("data", "model"))}
            step, restored = restore_checkpoint(td, tree, shardings=new_sh)
        assert step == 1
        assert restored["w"].sharding.mesh.shape == m2.shape
        assert jnp.array_equal(restored["w"], x)
        print("OK elastic")
    """)


def test_forced_host_devices_refuse_an_accelerator_parent(monkeypatch):
    """On a chip the parent already holds the device, and forced-host CPU
    children would time the CPU under a benchmark's name: the recipe
    behind fig9-devices and stream-devices refuses with the reason."""
    import jax

    from repro.launch.mesh import forced_host_device_env

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="CPU rehearsal"):
        forced_host_device_env(4)


def test_fig9_device_sweep_fails_on_a_failed_child(monkeypatch):
    """A failed device-count child fails the sweep (no FAILED row and a
    zero exit)."""
    import subprocess

    from benchmarks import fig9_scaling

    monkeypatch.setattr(
        subprocess, "run",
        lambda cmd, **_: subprocess.CompletedProcess(cmd, 1, "", "child died"))
    with pytest.raises(SystemExit, match="child died"):
        fig9_scaling.run_devices(device_counts=(2,), fast=True)
