"""Compile the three graph kernels for a TPU v5e without the chip.

The TPU compiler is installed beside JAX, and it compiles for a chip
that is described but not attached.  These compiles run Mosaic on the
kernels with ``interpret=False`` at the widths of
``configs/hytgraph_paper.py`` (100,000 vertices, 64 partitions, so an
edge block of B = 26,368), which catches what interpret mode accepts and
the chip refuses: unaligned slices, layouts Mosaic cannot infer, scoped
VMEM overruns.  Nothing runs, so these say nothing about results or time.

The topology is described inside a fixture: only the worker that runs
this file loads the TPU library (one process at a time may hold it).
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.frontier_compact.frontier_compact import frontier_compact_pallas
from repro.kernels.hyb_gather.hyb_gather import PAD, hyb_gather_pallas
from repro.kernels.segment_spmm.segment_spmm import segment_spmm_pallas

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
