"""The three transfer engines must produce identical relax results
(property-tested), and the full HyTM runs must be engine-invariant."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.core.cost_model import COMPACT, FILTER, ZEROCOPY
from repro.core.engines import (
    ENGINE_FNS,
    EdgeBlock,
    relax_compact,
    relax_filter,
    relax_with_engine,
    relax_zerocopy,
)
from repro.core.hytm import HyTMConfig, run_hytm
from repro.graph.algorithms import PAGERANK, SSSP, reference_pagerank, reference_sssp
from repro.graph.generators import rmat_graph


@settings(deadline=None, max_examples=30)
@given(
    n=st.integers(2, 64),
    b=st.integers(1, 256),
    seed=st.integers(0, 1000),
    combine_min=st.booleans(),
)
def test_engines_identical_property(n, b, seed, combine_min):
    rng = np.random.default_rng(seed)
    block = EdgeBlock(
        src=jnp.asarray(rng.integers(0, n, b), jnp.int32),
        dst=jnp.asarray(rng.integers(0, n, b), jnp.int32),
        weight=jnp.asarray(rng.random(b), jnp.float32),
        active=jnp.asarray(rng.random(b) < 0.5),
    )
    operand = jnp.asarray(rng.random(n), jnp.float32)
    prog = SSSP if combine_min else PAGERANK
    outs = [
        fn(block, operand, n, prog)
        for fn in (relax_filter, relax_compact, relax_zerocopy)
    ]
    for o in outs[1:]:
        assert jnp.allclose(outs[0].agg, o.agg, atol=1e-5, equal_nan=True)
        assert jnp.array_equal(outs[0].touched, o.touched)


@settings(deadline=None, max_examples=20)
@given(
    n=st.integers(2, 48),
    b=st.integers(1, 128),
    seed=st.integers(0, 1000),
    combine_min=st.booleans(),
)
def test_lax_switch_dispatch_matches_direct(n, b, seed, combine_min):
    """``relax_with_engine`` (the traced lax.switch used inside the jitted
    sweep) must route each engine id to exactly the direct function."""
    rng = np.random.default_rng(seed)
    block = EdgeBlock(
        src=jnp.asarray(rng.integers(0, n, b), jnp.int32),
        dst=jnp.asarray(rng.integers(0, n, b), jnp.int32),
        weight=jnp.asarray(rng.random(b), jnp.float32),
        active=jnp.asarray(rng.random(b) < 0.5),
    )
    operand = jnp.asarray(rng.random(n), jnp.float32)
    prog = SSSP if combine_min else PAGERANK
    for eng in (FILTER, COMPACT, ZEROCOPY):
        switched = jax.jit(
            lambda e: relax_with_engine(e, block, operand, n, prog)
        )(jnp.int32(eng))
        direct = ENGINE_FNS[eng](block, operand, n, prog)
        assert jnp.allclose(switched.agg, direct.agg, atol=1e-6, equal_nan=True)
        assert jnp.array_equal(switched.touched, direct.touched)


def _converges_to_reference(g, engine):
    cfg = HyTMConfig(n_partitions=8, forced_engine=engine)
    res = run_hytm(g, SSSP, source=0, config=cfg)
    ref = reference_sssp(g, 0)
    return np.allclose(res.values, ref, equal_nan=False)


def test_full_run_engine_invariant():
    g = rmat_graph(500, 4000, seed=11)
    for eng in (FILTER, COMPACT, ZEROCOPY, None):
        cfg = HyTMConfig(n_partitions=8, forced_engine=eng)
        res = run_hytm(g, SSSP, source=0, config=cfg)
        ref = reference_sssp(g, 0)
        assert np.allclose(res.values, ref), f"engine {eng} diverged"


def test_pagerank_engine_invariant():
    g = rmat_graph(400, 3000, seed=12)
    prog = dataclasses.replace(PAGERANK, tolerance=1e-7)
    ref = reference_pagerank(g)
    for eng in (FILTER, COMPACT, ZEROCOPY, None):
        cfg = HyTMConfig(n_partitions=8, forced_engine=eng, cds_mode="delta")
        res = run_hytm(g, prog, source=None, config=cfg)
        assert np.max(np.abs(res.values + res.delta - ref)) < 1e-3


def test_transfer_bytes_ordering():
    """Modeled transfer (Table VI): filter moves the most (whole
    partitions); compaction the least; zero-copy sits above compaction —
    its request-granularity rounding on low-degree vertices is the
    paper's Fig-3(d) 'redundant ZC transfer'."""
    g = rmat_graph(2000, 16000, seed=13)
    bytes_by_engine = {}
    for eng in (FILTER, COMPACT, ZEROCOPY):
        cfg = HyTMConfig(n_partitions=16, forced_engine=eng, recompute_once=False)
        res = run_hytm(g, SSSP, source=0, config=cfg)
        bytes_by_engine[eng] = res.total_transfer_bytes
    assert bytes_by_engine[FILTER] >= bytes_by_engine[COMPACT]
    assert bytes_by_engine[ZEROCOPY] >= bytes_by_engine[COMPACT]


def test_kernel_engines_match_oracles():
    """Each kernel-backed engine (use_kernels=True) vs its pure-JAX oracle:
    MIN bit-exact, SUM tolerance-bounded with a bit-exact touched mask —
    the `HyTMConfig.use_kernels` contract."""
    for seed in (0, 1, 2):
        rng = np.random.default_rng(seed)
        n, b = 64, 300
        block = EdgeBlock(
            src=jnp.asarray(rng.integers(0, n, b), jnp.int32),
            dst=jnp.asarray(rng.integers(0, n, b), jnp.int32),
            weight=jnp.asarray(rng.random(b), jnp.float32),
            active=jnp.asarray(rng.random(b) < 0.5),
        )
        operand = jnp.asarray(rng.random(n), jnp.float32)
        for fn in ENGINE_FNS:
            for prog in (SSSP, PAGERANK):
                ref = fn(block, operand, n, prog, use_kernels=False)
                ker = fn(block, operand, n, prog, use_kernels=True)
                if prog is SSSP:
                    assert jnp.array_equal(ref.agg, ker.agg), (fn.__name__, seed)
                else:
                    assert jnp.allclose(ref.agg, ker.agg, atol=1e-5), (fn.__name__, seed)
                assert jnp.array_equal(ref.touched, ker.touched), (fn.__name__, seed)


def test_use_kernels_end_to_end_bit_exact():
    """Full MIN runs with use_kernels on vs off: values, iteration count,
    transfer accounting, and per-iteration engine picks all bit-identical —
    across the single-dispatch (K=1) and chunked (K=4) drivers."""
    g = rmat_graph(400, 3000, seed=21)
    for K in (1, 4):
        cfg = HyTMConfig(n_partitions=8, sync_every=K)
        off = run_hytm(g, SSSP, source=0,
                       config=dataclasses.replace(cfg, use_kernels=False))
        on = run_hytm(g, SSSP, source=0,
                      config=dataclasses.replace(cfg, use_kernels=True))
        np.testing.assert_array_equal(off.values, on.values)
        assert off.iterations == on.iterations
        assert off.total_transfer_bytes == on.total_transfer_bytes
        np.testing.assert_array_equal(
            off.history["engines"], on.history["engines"])


def test_use_kernels_pagerank_tolerance():
    """SUM combiner: the tiled kernel accumulation reassociates float adds,
    so values are tolerance-bounded; the engine trajectory stays identical
    (selection consumes exact activity stats, not the summed values)."""
    g = rmat_graph(300, 2400, seed=22)
    prog = dataclasses.replace(PAGERANK, tolerance=1e-7)
    cfg = HyTMConfig(n_partitions=8, cds_mode="delta")
    off = run_hytm(g, prog, source=None,
                   config=dataclasses.replace(cfg, use_kernels=False))
    on = run_hytm(g, prog, source=None,
                  config=dataclasses.replace(cfg, use_kernels=True))
    assert np.max(np.abs(off.values - on.values)) < 1e-4
    np.testing.assert_array_equal(off.history["engines"], on.history["engines"])


def test_hybrid_never_worse_than_worst_engine():
    g = rmat_graph(1500, 12000, seed=14)
    times = {}
    for eng in (FILTER, COMPACT, ZEROCOPY, None):
        cfg = HyTMConfig(n_partitions=16, forced_engine=eng, recompute_once=False)
        res = run_hytm(g, SSSP, source=0, config=cfg)
        times[eng] = res.modeled_seconds
    assert times[None] <= max(times[FILTER], times[COMPACT], times[ZEROCOPY]) + 1e-9


# ------------------------------------------------------------ prebuilt route

def _kron_like(scale=10, seed=5):
    """A small undirected GAP-kron-like graph: Graph500 quadrant
    probabilities, both directions of every edge, duplicates kept."""
    from repro.graph.csr import csr_from_edges

    g = rmat_graph(1 << scale, 8 << scale, seed=seed)
    src = g.edge_sources()
    return csr_from_edges(g.n_nodes, np.concatenate([src, g.indices]),
                          np.concatenate([g.indices, src]),
                          np.concatenate([g.weights, g.weights]))


def test_route_layout_per_partition():
    """Each routed row is a permutation of exactly its partition's edges,
    destination blocks non-decreasing and sources ascending within a
    block, pads at the sentinel, and first/last equal a searchsorted over
    the sorted block keys."""
    from repro.core.partition import partition_graph, route_partitions, to_device_partitions
    from repro.kernels.segment_spmm.segment_spmm import LANES, TILE_LANES, TILE_N

    g = _kron_like()
    table = partition_graph(g, n_partitions=16)
    B = to_device_partitions(table, g.n_nodes, g.n_edges).block_size
    route = route_partitions(g, table, B)
    n_blocks = -(-g.n_nodes // TILE_N)
    assert route.width % TILE_LANES == 0 and B <= route.width < B + TILE_LANES
    src_all = g.edge_sources()
    for p in range(table.n_partitions):
        e0, e1 = table.edge_start[p], table.edge_start[p + 1]
        k = e1 - e0
        src, dst = np.asarray(route.src[p]), np.asarray(route.dst[p])
        w = np.asarray(route.weight[p])
        assert sorted(zip(src[:k], dst[:k], w[:k])) == sorted(
            zip(src_all[e0:e1], g.indices[e0:e1], g.weights[e0:e1]))
        block = dst[:k] // TILE_N
        assert np.all(np.diff(block) >= 0)
        same = np.diff(block) == 0
        assert np.all(np.diff(src[:k])[same] >= 0)
        assert np.all(dst[k:] == n_blocks * TILE_N)
        assert np.all(src[k:] == 0) and np.all(w[k:] == 0)
        bounds = np.searchsorted(block, np.arange(n_blocks + 1))
        first = bounds[:-1] // LANES
        last = np.where(bounds[1:] > bounds[:-1], -(-bounds[1:] // LANES), first)
        np.testing.assert_array_equal(np.asarray(route.first[p]), first)
        np.testing.assert_array_equal(np.asarray(route.last[p]), last)


def test_prebuilt_route_whole_run_matches_oracles():
    """run_hytm over a built runtime (routed blocks, kernels in interpret
    mode) against the oracle engines: SSSP and BFS values, iteration
    counts and engine picks bit-identical, Δ-PageRank within tolerance;
    a DeltaCSR view (no route, the per-call sort) gives the same
    answers."""
    from repro.core.hytm import build_runtime
    from repro.graph.algorithms import BFS
    from repro.stream.delta_csr import DeltaCSR

    g = _kron_like()
    cfg = HyTMConfig(n_partitions=16, use_kernels=True)
    rt = build_runtime(g, cfg)
    assert rt.route is not None and rt.route_args()["route"] == "prebuilt"
    view = DeltaCSR(g, cfg).runtime_for(SSSP)
    assert view.route is None and view.route_args()["route"] == "per_call"
    for prog in (SSSP, BFS):
        on = run_hytm(g, prog, source=3, config=cfg, runtime=rt)
        off = run_hytm(g, prog, source=3,
                       config=dataclasses.replace(cfg, use_kernels=False))
        np.testing.assert_array_equal(on.values, off.values)
        assert on.iterations == off.iterations
        np.testing.assert_array_equal(on.history["engines"], off.history["engines"])
        per_call = run_hytm(None, prog, source=3, config=cfg,
                            runtime=DeltaCSR(g, cfg).runtime_for(prog))
        np.testing.assert_array_equal(on.values, per_call.values)
    prog = dataclasses.replace(PAGERANK, tolerance=1e-6)
    pr_cfg = dataclasses.replace(cfg, cds_mode="delta")
    on = run_hytm(g, prog, source=None, config=pr_cfg)
    off = run_hytm(g, prog, source=None,
                   config=dataclasses.replace(pr_cfg, use_kernels=False))
    per_call = run_hytm(None, prog, source=None, config=pr_cfg,
                        runtime=DeltaCSR(g, pr_cfg).runtime_for(prog))
    ref = reference_pagerank(g)
    for res in (on, off, per_call):
        assert np.max(np.abs(res.values + res.delta - ref)) < 1e-3
    assert np.max(np.abs(on.values - off.values)) < 1e-4
