"""End-to-end system behaviour: the full HyTGraph pipeline (preprocess ->
hub sort -> partition -> hybrid iterate -> converge) and its interaction
with scheduling options — the paper's Fig. 5 loop as one test surface."""

import dataclasses

import numpy as np

from repro.core.hytm import HyTMConfig, build_runtime, run_hytm
from repro.graph.algorithms import PAGERANK, SSSP, reference_pagerank, reference_sssp
from repro.graph.generators import rmat_graph
from repro.graph.hub_sort import hub_sort


def test_end_to_end_pipeline():
    """Generate -> hub-sort -> run all scheduling variants -> validate."""
    g = rmat_graph(3000, 24000, seed=99)
    hs = hub_sort(g)
    src_new = int(hs.perm[0])
    ref = reference_sssp(g, 0)

    variants = {
        "full": HyTMConfig(n_partitions=24, cds_mode="hub", recompute_once=True),
        "no-cds": HyTMConfig(n_partitions=24, cds_mode="none", recompute_once=False),
        "no-tc": HyTMConfig(n_partitions=24, enable_task_combination=False),
        "sync": HyTMConfig(n_partitions=24, async_sweep=False),
    }
    stats = {}
    for name, cfg in variants.items():
        res = run_hytm(hs.graph, SSSP, source=src_new, config=cfg, n_hubs=hs.n_hubs)
        assert np.allclose(hs.values_to_old(res.values), ref), name
        stats[name] = res
    # task combining reduces scheduled tasks
    assert stats["full"].history["n_tasks"].sum() <= stats["no-tc"].history["n_tasks"].sum()
    # async converges in <= sync iterations (paper §VI)
    assert stats["full"].iterations <= stats["sync"].iterations


def test_runtime_reuse_across_algorithms():
    """Preprocessing (partition/upload) happens once; algorithms share it
    (paper: hub sorting is done once in data preparation)."""
    g = rmat_graph(1000, 8000, seed=100)
    cfg = HyTMConfig(n_partitions=8)
    rt = build_runtime(g, cfg)
    r1 = run_hytm(g, SSSP, source=0, config=cfg, runtime=rt)
    prog = dataclasses.replace(PAGERANK, tolerance=1e-7)
    r2 = run_hytm(g, prog, source=None, config=cfg, runtime=rt)
    assert np.allclose(r1.values, reference_sssp(g, 0))
    assert np.max(np.abs(r2.values + r2.delta - reference_pagerank(g))) < 1e-3


def test_execution_path_follows_frontier_density():
    """Fig. 7: when nearly everything is active (PR start) the scheduler
    leans on filter; on sparse frontiers (SSSP start) zerocopy/compaction
    dominate.  mr is shrunk so transaction-group rounding doesn't tie the
    costs at CPU-test scale (the paper's partitions are 32 MB)."""
    from repro.core.constants import PCIE3
    from repro.core.cost_model import FILTER, ZEROCOPY

    link = PCIE3.with_(mr=4.0)
    g = rmat_graph(4000, 64000, seed=101)
    pr = run_hytm(g, PAGERANK, source=None, config=HyTMConfig(n_partitions=32, link=link))
    first_iter = pr.history["engines"][0]
    assert (first_iter == FILTER).sum() >= (first_iter == ZEROCOPY).sum()

    ss = run_hytm(g, SSSP, source=0, config=HyTMConfig(n_partitions=32, link=link))
    early = ss.history["engines"][0]
    assert (early == ZEROCOPY).sum() + (early == -1).sum() >= (early == FILTER).sum()


def test_compile_cache_directory(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR wins and no other directory is set;
    without it the cache sits at the checkout's fixed .jax_cache."""
    from pathlib import Path

    import jax

    from repro.launch.compile_cache import enable_compile_cache

    saved = (jax.config.jax_compilation_cache_dir,
             jax.config.jax_persistent_cache_min_compile_time_secs)
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/where")
        assert enable_compile_cache() == "/some/where"
        assert jax.config.jax_compilation_cache_dir == saved[0]
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = enable_compile_cache()
        checkout = Path(__file__).resolve().parents[1]
        assert path == str(checkout / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        assert ".jax_cache/" in (checkout / ".gitignore").read_text().split()
    finally:
        jax.config.update("jax_compilation_cache_dir", saved[0])
        jax.config.update("jax_persistent_cache_min_compile_time_secs", saved[1])
