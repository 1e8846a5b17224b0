"""Chunk-based edge-balanced graph partitioning (paper §IV, following
Scaph [44] / Gemini [46]).

Each partition P_i is a set of consecutively-numbered vertices whose edge
segments are contiguous in the CSR edge arrays and hold ~equal edge counts
(the paper's 32 MB default).  HyTGraph *decouples* graph partitioning from
task scheduling (paper §V-B): partitions stay small for fine-grained cost
analysis; the task combiner merges them at schedule time.

``DevicePartitions`` pads every partition's edge range to a common static
``block_size`` so jitted code can ``dynamic_slice`` fixed-size edge blocks
— the JAX analogue of streaming one partition through the transfer engine.
``EdgeRoute`` stores the same blocks once more, each partition's own edges
grouped by destination block, so the FILTER engine's fold reads them in
place instead of sorting its block on every visit.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from repro.graph.csr import CSRGraph
from repro.kernels.segment_spmm.segment_spmm import LANES, TILE_LANES, TILE_N


@dataclass(frozen=True)
class PartitionTable:
    """Host-side partition boundaries."""

    vertex_start: np.ndarray  # (P+1,) int64
    edge_start: np.ndarray    # (P+1,) int64

    @property
    def n_partitions(self) -> int:
        return len(self.vertex_start) - 1

    @property
    def edges_per_partition(self) -> np.ndarray:
        return np.diff(self.edge_start)

    @property
    def vertices_per_partition(self) -> np.ndarray:
        return np.diff(self.vertex_start)


def partition_graph(
    g: CSRGraph,
    n_partitions: int | None = None,
    partition_bytes: int = 32 * 2**20,
    d1: float = 4.0,
) -> PartitionTable:
    """Edge-balanced chunk partitioning.

    If ``n_partitions`` is None it is derived from the paper's 32 MB
    partition size (``partition_bytes / d1`` edges per partition).
    Boundaries are vertex-aligned: a vertex's whole edge segment stays in
    one partition (required by all three engines).
    """
    m = max(g.n_edges, 1)
    if n_partitions is None:
        epp = max(int(partition_bytes / d1), 1)
        n_partitions = max(1, -(-m // epp))
    n_partitions = min(n_partitions, g.n_nodes)
    targets = np.linspace(0, m, n_partitions + 1)
    # vertex_start[i] = first vertex whose edge segment starts at/after target
    vertex_start = np.searchsorted(g.indptr, targets, side="left").astype(np.int64)
    vertex_start[0], vertex_start[-1] = 0, g.n_nodes
    vertex_start = np.maximum.accumulate(vertex_start)
    edge_start = g.indptr[vertex_start]
    return PartitionTable(vertex_start=vertex_start, edge_start=edge_start)


@jax.tree_util.register_dataclass
@dataclass(frozen=True)
class DevicePartitions:
    vertex_start: jax.Array   # (P+1,) int32
    edge_start: jax.Array     # (P+1,) int32
    part_edges: jax.Array     # (P,) int32 — E_i
    vertex_part_id: jax.Array  # (n,) int32
    n_partitions: int = dataclasses.field(metadata=dict(static=True))
    block_size: int = dataclasses.field(metadata=dict(static=True))

    @property
    def max_edge_start(self) -> int:
        return int(self.n_partitions)


def to_device_partitions(
    table: PartitionTable, n_nodes: int, edge_capacity: int, block_multiple: int = 128
) -> DevicePartitions:
    epp = table.edges_per_partition
    block = int(epp.max(initial=1))
    block = max(block_multiple, -(-block // block_multiple) * block_multiple)
    # dynamic_slice clamps the start index; padding edges (>= n_edges) are
    # masked by the in-range test, so block may exceed capacity remainder.
    block = min(block, edge_capacity)
    part_id = np.repeat(
        np.arange(table.n_partitions, dtype=np.int32),
        table.vertices_per_partition,
    )
    assert len(part_id) == n_nodes
    return DevicePartitions(
        vertex_start=jnp.asarray(table.vertex_start, dtype=jnp.int32),
        edge_start=jnp.asarray(table.edge_start, dtype=jnp.int32),
        part_edges=jnp.asarray(epp, dtype=jnp.int32),
        vertex_part_id=jnp.asarray(part_id),
        n_partitions=table.n_partitions,
        block_size=block,
    )


@jax.tree_util.register_dataclass
@dataclass(frozen=True)
class EdgeRoute:
    """Each partition's edges in destination-block order, one row per
    partition, built once on the host (:func:`route_partitions`).

    Row ``p`` holds exactly partition ``p``'s edges in lanes
    ``[0, part_edges[p])``, ordered stably by ``dst // TILE_N`` (so sources
    stay ascending within a block), then pads: source 0, weight 0 and the
    sentinel destination ``n_blocks · TILE_N``, past every output block.
    ``first[p, b]`` / ``last[p, b]`` bound the 128-lane rows that hold
    block ``b``'s edges (``first == last`` for a block with none), which
    is what ``segment_spmm_routed`` scalar-prefetches."""

    src: jax.Array     # (P, width) int32
    dst: jax.Array     # (P, width) int32
    weight: jax.Array  # (P, width) float32
    first: jax.Array   # (P, n_blocks) int32
    last: jax.Array    # (P, n_blocks) int32

    @property
    def width(self) -> int:
        return self.src.shape[-1]


def route_partitions(g: CSRGraph, table: PartitionTable, block_size: int) -> EdgeRoute:
    """Route every partition's edges by destination block (NumPy, once).

    ``width`` is ``block_size`` rounded up to whole (8, 128) tiles, so a
    row reshapes to the fold's (rows, 128) view as laid out.  Keys and
    bounds are int64 here, so no packing limit applies."""
    n_parts = table.n_partitions
    n_blocks = -(-g.n_nodes // TILE_N)
    width = -(-block_size // TILE_LANES) * TILE_LANES
    part = np.repeat(np.arange(n_parts, dtype=np.int64), table.edges_per_partition)
    key = part * n_blocks + g.indices.astype(np.int64) // TILE_N
    order = np.argsort(key, kind="stable")
    # sorting by partition first keeps each partition's edges where they
    # were, so a routed edge's lane is its rank past the partition start
    lane = np.arange(g.n_edges, dtype=np.int64) - table.edge_start[part]
    src = np.zeros((n_parts, width), np.int32)
    dst = np.full((n_parts, width), n_blocks * TILE_N, np.int32)
    weight = np.zeros((n_parts, width), np.float32)
    src[part, lane] = g.edge_sources()[order]
    dst[part, lane] = g.indices[order]
    weight[part, lane] = 1.0 if g.weights is None else g.weights[order]

    counts = np.bincount(key, minlength=n_parts * n_blocks).reshape(n_parts, n_blocks)
    ends = np.cumsum(counts, axis=1)
    first = (ends - counts) // LANES
    last = np.where(counts > 0, -(-ends // LANES), first)
    return EdgeRoute(
        src=jnp.asarray(src), dst=jnp.asarray(dst), weight=jnp.asarray(weight),
        first=jnp.asarray(first, dtype=jnp.int32),
        last=jnp.asarray(last, dtype=jnp.int32),
    )
