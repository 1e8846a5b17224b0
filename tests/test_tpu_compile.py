"""Compile the three graph kernels for a TPU v5e without the chip.

The TPU compiler is installed beside JAX, and it compiles for a chip
that is described but not attached.  These compiles run Mosaic on the
kernels with ``interpret=False`` at the widths of
``configs/hytgraph_paper.py`` (100,000 vertices, 64 partitions, so an
edge block of B = 26,368), which catches what interpret mode accepts and
the chip refuses: unaligned slices, layouts Mosaic cannot infer, scoped
VMEM overruns; the fold over a prebuilt route compiles at the benchmark
cells' widths too.  Nothing runs, so these say nothing about results or
time.

The topology is described inside a fixture: only the worker that runs
this file loads the TPU library (one process at a time may hold it).
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.frontier_compact.frontier_compact import frontier_compact_pallas
from repro.kernels.hyb_gather.hyb_gather import PAD, hyb_gather_pallas
from repro.kernels.segment_spmm.segment_spmm import (
    LANES,
    TILE_LANES,
    TILE_N,
    segment_spmm_fold,
    segment_spmm_pallas,
)

N = 100_000
BLOCK = 26_368


@pytest.fixture(scope="module")
def topo():
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def shape(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda dims, dtype: jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)


def _compile_has_kernel(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("combine,d", [("min", 1), ("sum", 2)])
def test_segment_spmm_compiles_for_v5e(shape, combine, d):
    _compile_has_kernel(
        lambda m, s, v: segment_spmm_pallas(m, s, v, N, combine=combine,
                                            interpret=False),
        shape((BLOCK, d), jnp.float32), shape((BLOCK,), jnp.int32),
        shape((BLOCK,), jnp.bool_))


@pytest.mark.parametrize("combine,d", [("min", 1), ("sum", 2)])
@pytest.mark.parametrize("block", [93_696, 65_664], ids=["kron17", "urand17"])
def test_routed_fold_compiles_for_v5e(shape, block, combine, d):
    """The fold over a prebuilt route at the benchmark cells' widths
    (2^17 vertices, 64 partitions): B rounded up to whole (8, 128) tiles,
    no sort in the program."""
    n = 1 << 17
    rows = -(-block // TILE_LANES) * TILE_LANES // LANES
    text = jax.jit(
        lambda m, s, f, l: segment_spmm_fold(m, s, f, l, n, combine=combine,
                                             interpret=False)
    ).lower(shape((d, rows, LANES), jnp.float32), shape((rows, LANES), jnp.int32),
            shape((n // TILE_N,), jnp.int32), shape((n // TILE_N,), jnp.int32)
            ).compile().as_text()
    assert "tpu_custom_call" in text and " sort(" not in text


def test_frontier_compact_compiles_for_v5e(shape):
    _compile_has_kernel(
        lambda x, k: frontier_compact_pallas(x, k, interpret=False),
        shape((BLOCK, 3), jnp.float32), shape((BLOCK,), jnp.bool_))


def test_hyb_gather_compiles_for_v5e(shape):
    windows = -(-BLOCK // PAD)
    _compile_has_kernel(
        lambda e, s, d: hyb_gather_pallas(e, s, d, interpret=False),
        shape((BLOCK, 4), jnp.float32), shape((windows,), jnp.int32),
        shape((windows,), jnp.int32))


def _chunk_program_for_v5e(shape, monkeypatch, routed: bool, stream: bool = False):
    """The whole chunk program as the chip compiles it, at scale 10, with
    or without the route built with the runtime, or over a ``DeltaCSR``
    view (``stream``): (instruction scopes, block size)."""
    import repro.kernels.runtime as runtime
    from repro.core.cost_model import init_history_buffers
    from repro.core.hytm import HyTMConfig, HyTMState, _iteration_impl, build_runtime, hytm_chunk
    from repro.graph.algorithms import SSSP
    from repro.graph.generators import rmat_graph
    from repro.obs import scopes
    from repro.stream import DeltaCSR

    monkeypatch.setattr(runtime, "on_tpu", lambda: True)  # compiled kernels
    g = rmat_graph(1 << 10, 16 << 10, seed=3)
    cfg = HyTMConfig(n_partitions=8, sync_every=4)
    rt = DeltaCSR(g, cfg).runtime_for(SSSP) if stream else build_runtime(g, cfg)
    state = HyTMState(*SSSP.init_state(g.n_nodes, 0))
    args = (rt.csr, rt.parts, rt.zc_req, rt.inv_deg, SSSP, cfg, rt.n_hub_partitions)
    info = jax.eval_shape(lambda s: _iteration_impl(s, *args)[1], state)
    on_chip = lambda tree: jax.tree.map(  # noqa: E731
        lambda x: shape(x.shape, x.dtype) if hasattr(x, "shape") else x, tree)
    route = on_chip(rt.route) if routed else None
    text = hytm_chunk.lower(on_chip(state), on_chip(init_history_buffers(info, 4)),
                            *on_chip(args[:4]), *args[4:], 4, None, route
                            ).compile().as_text()
    return scopes.instruction_scopes(text), rt.parts.block_size


def _kernels_and_block_sorts(got, block_size):
    import re

    kernels = {re.match(r"%([a-z_]+)", line).group(1): s for line, s in got
               if "custom-call(" in line and "_pallas" in line}
    assert kernels == {"segment_spmm_pallas": "engine.filter",
                       "frontier_compact_pallas": "engine.compact",
                       "hyb_gather_pallas": "engine.zerocopy"}
    return [s for line, s in got if re.search(rf"= s32\[{block_size}\]\S* sort\(", line)]


def test_chunk_program_names_its_kernels_and_scopes_for_v5e(topo, shape, monkeypatch):
    """The whole chunk program as the chip compiles it, routing per call:
    each Pallas call keeps the name the device-trace metrics match,
    inside its engine's scope, and the per-call sort of the block maps
    to ``filter.order``."""
    from repro.obs import scopes

    got, block_size = _chunk_program_for_v5e(shape, monkeypatch, routed=False)
    assert set(scopes.SCOPES) <= {s for _, s in got}
    block_sorts = _kernels_and_block_sorts(got, block_size)
    assert block_sorts and set(block_sorts) == {"filter.order"}


def test_prebuilt_route_chunk_program_sorts_no_block_for_v5e(topo, shape, monkeypatch):
    """Over the route built with the runtime the same program keeps its
    kernels' names and scopes, sorts no block, and has no
    ``filter.order``."""
    from repro.obs import scopes

    got, block_size = _chunk_program_for_v5e(shape, monkeypatch, routed=True)
    assert {s for _, s in got} - {None} == set(scopes.SCOPES) - {scopes.FILTER_ORDER}
    assert _kernels_and_block_sorts(got, block_size) == []


def test_delta_csr_view_chunk_program_routes_per_call_for_v5e(topo, shape, monkeypatch):
    """The chunk program over a ``DeltaCSR`` view, as the stream cell
    runs it: its slack blocks carry no route, so the kernels keep their
    names and scopes and the per-call sort of the block maps to
    ``filter.order``."""
    from repro.obs import scopes

    got, block_size = _chunk_program_for_v5e(shape, monkeypatch, routed=False, stream=True)
    assert set(scopes.SCOPES) <= {s for _, s in got}
    block_sorts = _kernels_and_block_sorts(got, block_size)
    assert block_sorts and set(block_sorts) == {"filter.order"}


@pytest.mark.parametrize("lanes", [512, 1024, 2048, 4096])
def test_stream_patch_compiles_for_v5e(shape, lanes):
    """``DeltaCSR``'s patch scatter at the stream cell's widths (2^17
    vertices, P = 64 blocks of B = 140,544 lanes) for each bucket of
    touched lanes its batches reach: the fusions that scatter the lanes
    map to ``stream.patch``."""
    from repro.obs import scopes
    from repro.stream.delta_csr import _scatter_lanes

    cap = 64 * 140_544
    text = _scatter_lanes.lower(
        shape((cap,), jnp.int32), shape((cap,), jnp.int32),
        shape((cap,), jnp.float32), shape((cap,), jnp.bool_),
        shape((lanes,), jnp.int32), shape((lanes,), jnp.int32),
        shape((lanes,), jnp.int32), shape((lanes,), jnp.float32),
        shape((lanes,), jnp.bool_)).compile().as_text()
    fusions = {s for line, s in scopes.instruction_scopes(text) if " fusion(" in line}
    assert " scatter(" in text and fusions == {scopes.STREAM_PATCH}
