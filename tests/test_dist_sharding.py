"""repro.dist unit tests that run in the main (1-device) process.

The sharding rule DSL and the sharded HyTM machinery are both exercised
on 1-device meshes here (mesh semantics are size-independent); the real
multi-device equivalence runs live in test_distributed.py subprocesses.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P
from jax.tree_util import tree_flatten_with_path, tree_leaves

from repro.configs import get_arch, list_archs
from repro.dist.sharding import (
    batch_axes,
    fit_spec,
    lm_batch_spec,
    lm_cache_rule,
    lm_rule,
    path_str,
    spec_for,
    tree_shardings,
)
from repro.launch.mesh import make_debug_mesh, make_graph_mesh


@pytest.fixture(scope="module")
def mesh11():
    return make_debug_mesh(1, 1)


# ------------------------------------------------------------- rule DSL

def test_batch_axes_subsets(mesh11):
    assert batch_axes(mesh11) == ("data",)
    pod = make_debug_mesh(1, 1, pods=1)
    assert batch_axes(pod) == ("pod", "data")
    graph = make_graph_mesh(1)
    assert batch_axes(graph) == ()
    assert lm_batch_spec(mesh11) == P(("data",), None)


def test_fit_spec_right_aligns_and_pads(mesh11):
    # stacked scan-layer weight: rank-2 rule onto a rank-3 leaf
    assert fit_spec(P(None, "model"), (4, 64, 128), mesh11) == P(None, None, "model")
    # rule longer than the leaf keeps the trailing entries
    assert fit_spec(P("data", None, "model"), (64, 128), mesh11) == P(None, "model")
    # scalars always replicate
    assert fit_spec(P("model"), (), mesh11) == P()


def test_fit_spec_divisibility_fallback():
    # divisibility is checked against mesh axis *sizes*, so a shaped stub
    # exercises the multi-device fallback without allocating devices
    class FakeMesh:
        axis_names = ("data", "model")
        shape = {"data": 4, "model": 8}

    # 30 % 8 != 0 -> that dim replicates; 32 % 8 == 0 -> sharded
    assert fit_spec(P(None, "model"), (16, 30), FakeMesh()) == P()
    assert fit_spec(P(None, "model"), (16, 32), FakeMesh()) == P(None, "model")


def test_first_matching_rule_wins(mesh11):
    rule = lm_rule(mesh11)
    # moe w_gate (expert-banked) and ffn w_gate (dense) hit different rules
    moe = spec_for("layers/moe/w_gate", (2, 8, 64, 32), mesh11, rule)
    ffn = spec_for("layers/ffn/w_gate", (2, 64, 128), mesh11, rule)
    assert moe[-1] == "model" and moe != ffn
    # optimizer moment trees mirror the param paths
    m = spec_for("0/m/layers/ffn/w_gate", (2, 64, 128), mesh11, rule)
    assert m == ffn
    # unmatched -> replicated
    assert spec_for("final_norm", (64,), mesh11, rule) == P()


def test_tree_shardings_covers_every_leaf(mesh11):
    from repro.models import transformer as tf
    from repro.train.optimizer import OptimizerConfig
    from repro.train.train_step import init_train_state

    cfg = tf.TransformerConfig(
        name="t", n_layers=2, d_model=64, n_heads=4, n_kv_heads=4, d_head=16,
        d_ff=128, vocab=128, dtype="float32", param_dtype="float32",
    )
    oc = OptimizerConfig(learning_rate=1e-3, warmup_steps=0, schedule="constant")
    state = jax.eval_shape(
        lambda: init_train_state(tf.abstract_params(cfg), oc)
    )
    sh = tree_shardings(state, mesh11, lm_rule(mesh11))
    flat_state = tree_flatten_with_path(state)[0]
    flat_sh = tree_leaves(sh, is_leaf=lambda x: hasattr(x, "spec"))
    assert len(flat_state) == len(flat_sh)
    for (path, leaf), s in zip(flat_state, flat_sh):
        assert len(s.spec) <= leaf.ndim, (path_str(path), leaf.shape, s.spec)


def test_cache_rule_kv_heads_vs_sequence():
    class FakeMesh:
        axis_names = ("data", "model")
        shape = {"data": 2, "model": 4}

    # kv=8 divides model=4 -> heads shard; kv=1 (MQA) -> sequence shards
    heads = dict(lm_cache_rule(FakeMesh(), 8))
    seq = dict(lm_cache_rule(FakeMesh(), 1))
    # PartitionSpec equality normalizes a 1-tuple axis to its bare name
    assert heads[r"(^|/)[kv]$"] == P(("data",), None, "model", None)
    assert seq[r"(^|/)[kv]$"] == P(("data",), "model", None, None)


def test_all_arch_cells_build_on_debug_mesh(mesh11):
    """Every registered (arch x shape) cell resolves its shardings: the
    rule DSL must never crash on any real parameter/optimizer/cache tree
    (cells build abstractly — no allocation)."""
    built = 0
    for name in list_archs():
        arch = get_arch(name)
        for shape, builder in arch.cells.items():
            cell = builder(mesh11)
            assert cell.fn is not None, (name, shape)
            # in_shardings mirror the args pytree structure
            for args_leaf, sh_leaf in zip(
                tree_leaves(cell.args),
                tree_leaves(cell.in_shardings, is_leaf=lambda x: hasattr(x, "spec")),
            ):
                assert len(sh_leaf.spec) <= args_leaf.ndim
            built += 1
    assert built >= 30  # 10 archs x ~3-4 cells


# ------------------------------------------------- sharded HyTM, 1 device

def _oracle(cfg):
    return dataclasses.replace(cfg, mesh_axis=None)


def test_sharded_hytm_single_device_mesh_exact():
    """mesh_axis over a 1-device mesh must equal the single-device
    synchronous run bit-for-bit (the full shard_map machinery runs)."""
    from repro.core.hytm import HyTMConfig, run_hytm
    from repro.graph.algorithms import BFS, SSSP, reference_sssp
    from repro.graph.generators import rmat_graph

    g = rmat_graph(400, 3000, seed=21)
    for prog in (BFS, SSSP):
        cfg = HyTMConfig(n_partitions=8, async_sweep=False, mesh_axis="graph")
        a = run_hytm(g, prog, source=0, config=cfg)
        b = run_hytm(g, prog, source=0, config=_oracle(cfg))
        assert a.iterations == b.iterations
        np.testing.assert_array_equal(a.values, b.values)
        assert a.total_transfer_bytes == b.total_transfer_bytes
    ref = reference_sssp(g, 0)
    res = run_hytm(
        g, SSSP, source=0,
        config=HyTMConfig(n_partitions=8, async_sweep=False, mesh_axis="graph"),
    )
    assert np.allclose(res.values, ref)


def test_sharded_hytm_pagerank_single_device_mesh():
    from repro.core.hytm import HyTMConfig, run_hytm
    from repro.graph.algorithms import PAGERANK
    from repro.graph.generators import rmat_graph

    g = rmat_graph(300, 2400, seed=22)
    cfg = HyTMConfig(
        n_partitions=8, async_sweep=False, mesh_axis="graph", cds_mode="delta",
    )
    a = run_hytm(g, PAGERANK, source=None, config=cfg)
    b = run_hytm(g, PAGERANK, source=None, config=_oracle(cfg))
    assert a.iterations == b.iterations
    np.testing.assert_allclose(a.values, b.values, rtol=0, atol=1e-5)
    np.testing.assert_allclose(
        a.total_transfer_bytes, b.total_transfer_bytes, rtol=1e-6
    )


def test_blocked_runtime_matches_csr_slices():
    """The (P, B) blocked edge grid holds exactly each partition's edge
    segment (padding masked), including the empty padding partitions that
    round P up to the device count."""
    from repro.core.hytm import HyTMConfig
    from repro.dist.graph_shard import build_sharded_runtime
    from repro.graph.generators import rmat_graph

    g = rmat_graph(200, 1500, seed=23)
    cfg = HyTMConfig(n_partitions=5, mesh_axis="graph")  # 5 -> pads to n_dev
    mesh = make_graph_mesh(1)
    rt = build_sharded_runtime(g, cfg, mesh)
    assert rt.n_partitions % int(mesh.shape["graph"]) == 0
    src_all = g.edge_sources()
    es = np.asarray(rt.parts.edge_start)
    blocks_src = np.asarray(rt.blocks.src)
    in_range = np.asarray(rt.blocks.in_range)
    for p in range(rt.n_partitions):
        k = int(es[p + 1] - es[p])
        assert in_range[p, :k].all() and not in_range[p, k:].any()
        np.testing.assert_array_equal(
            blocks_src[p, :k], src_all[es[p]:es[p + 1]]
        )
    # padded partitions are empty and own no vertices
    part_edges = np.asarray(rt.parts.part_edges)
    assert (part_edges >= 0).all()
    assert int(part_edges.sum()) == g.n_edges


def test_forced_engines_match_on_sharded_path():
    """Engine forcing (baseline systems) flows through the sharded
    selection identically."""
    from repro.core.cost_model import COMPACT, FILTER, ZEROCOPY
    from repro.core.hytm import HyTMConfig, run_hytm
    from repro.graph.algorithms import SSSP
    from repro.graph.generators import rmat_graph

    g = rmat_graph(300, 2000, seed=24)
    for eng in (FILTER, COMPACT, ZEROCOPY):
        cfg = HyTMConfig(
            n_partitions=8, async_sweep=False, mesh_axis="graph",
            forced_engine=eng,
        )
        a = run_hytm(g, SSSP, source=0, config=cfg)
        b = run_hytm(g, SSSP, source=0, config=_oracle(cfg))
        np.testing.assert_array_equal(a.values, b.values)
        np.testing.assert_array_equal(
            a.history["engines"], b.history["engines"]
        )
