"""Multi-device HyTM: the partition sweep shard_mapped over a 1-D mesh.

Scale-out story (Totem / Garaph lineage): HyTGraph's unit of transfer
management — the edge-balanced partition — is also the natural unit of
*distribution*.  Each device owns a contiguous shard of the partition
space as a ``(P_local, B)`` blocked edge array; vertex state (values,
pending Δ, frontier) is replicated, the per-iteration flow is:

  1. partition activity stats + Δ mass        (replicated, O(P))
  2. per-device cost model + engine selection (Algorithm 1 on the local
     stats shard — selection is per-partition, so the local result equals
     the single-device one)
  3. per-device priority schedule over its local partitions (hub ids are
     globalized with the device's partition offset; the Δ-mode top-K
     second-pass mask is a global rank, precomputed on replicated state)
  4. local sweep over the local blocks, then one collective merge:
     ``pmin`` for traversal combiners, ``psum`` for accumulative ones —
     the frontier/Δ exchange of the two-level HyTM
  5. recompute-once second pass over loaded priority partitions, merged
     the same way.

The cross-device sweep is **bulk-synchronous**: every device relaxes
against the iteration-start state and updates merge once per pass.  That
makes the sharded run reproduce the single-device ``async_sweep=False``
dataflow exactly — bit-for-bit for min-combiners, up to float-summation
order for sum-combiners — which is the equivalence contract
``tests/test_distributed.py`` checks on forced-host meshes.

Engine semantics are unchanged: each local partition still relaxes
through its selected FILTER/COMPACT/ZEROCOPY engine via ``lax.switch``,
so the cost model's per-partition decisions (and the modeled transfer
accounting) are identical to the single-device run.

Second level (DESIGN.md §2): the cross-device merge is itself
transfer-managed *in the model* — ``ici_level_cost`` selects per
iteration between a dense all-reduce (filter analogue) and a compacted
active-entry exchange (compact analogue) over ``HyTMConfig.ici_link``,
optionally reweighed by the online-feedback correction
(``HyTMConfig.autotune``, repro.autotune).  The executed collective
stays the bulk-synchronous pmin/psum merge, preserving the oracle
equivalence contract.

Vertex-state layout (``HyTMConfig.vertex_sharding``): by default the
(values, Δ, frontier) triple is **replicated** — every device holds the
full ``(n,)`` vectors, the per-device memory ceiling.  With ``"owner"``
the triple is **owner-sharded** (Totem's owner/halo split): the node
count pads to ``n_pad = ceil(n/D)*D``, device ``d`` owns the contiguous
slice ``[d*n_loc, (d+1)*n_loc)`` and holds only it, and each sweep pass
(a) all-gathers the frontier/operand shards into the full view its local
edge blocks read (the halo fill), (b) relaxes locally exactly as before,
and (c) merges back to owned slices — ``pmin`` + owned-slice extraction
for min-combiners (bit-exact: the same elementwise pmin, sliced), a
tiled ``psum_scatter`` for sum-combiners.  Per-device state drops
~D-fold (``cost_model.vertex_state_bytes``); the boundary-vertex counts
a compacted exchange would actually ship are precomputed host-side as a
:class:`HaloPlan`, and ``halo_level_cost`` caps the ICI level's
compacted candidate at the halo size so the two-level cost model (and
the autotune corrections steering it) charge the owner layout's real
exchange.  Results stay bit-identical to the single-device
``async_sweep=False`` oracle for min-combiners and tolerance-bounded for
sum-combiners, exactly like the replicated layout.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core.cost_model import (
    COMPACT,
    FILTER,
    HISTORY_KEYS,
    KEY_ACTIVE_EDGES,
    KEY_ACTIVE_VERTICES,
    KEY_ENGINES,
    KEY_ICI_BYTES,
    KEY_ICI_ENGINE,
    KEY_ICI_TIME,
    KEY_MERGED_ENTRIES,
    KEY_MISPREDICTIONS,
    KEY_N_TASKS,
    KEY_PER_ENGINE_TIME,
    KEY_TRANSFER_BYTES,
    KEY_TRANSFER_TIME,
    NONE,
    engine_costs,
    init_history_buffers,
    partition_stats,
    select_engines,
    selection_diagnostics,
    zc_request_counts,
)
from repro.core.engines import EdgeBlock, relax_with_engine
from repro.kernels.runtime import resolve_use_kernels
from repro.core.hytm import (
    HyTMConfig,
    HyTMResult,
    HyTMState,
    _consume_warm,
    chunked_while,
    quiet_donation,
)
from repro.core.partition import (
    DevicePartitions,
    PartitionTable,
    partition_graph,
)
from repro.core.scheduler import make_schedule
from repro.core.task_generation import forced_engine_plan, generate_tasks
from repro.graph.algorithms import MIN, SUM, VertexProgram
from repro.graph.csr import CSRGraph
from repro.obs import span
from repro.obs.export import CAT_RUN


@jax.tree_util.register_dataclass
@dataclass(frozen=True)
class BlockedEdges:
    """Partition-blocked COO edges, padded to a static (P, B) grid.

    Row ``p`` holds partition ``p``'s edge segment; lanes past
    ``part_edges[p]`` are padding (masked via ``in_range``).  This is the
    array that shards over the graph mesh axis.
    """

    src: jax.Array       # (P, B) int32
    dst: jax.Array       # (P, B) int32
    weight: jax.Array    # (P, B) float32
    in_range: jax.Array  # (P, B) bool


@dataclass(frozen=True)
class HaloPlan:
    """Host-side owner/halo layout of one sharded runtime.

    Device ``d`` owns the contiguous vertex slice
    ``[d*n_loc, (d+1)*n_loc)`` of the ``n_pad = n_loc*D``-padded id
    space; its *halo* is the set of vertices outside that slice which
    its local edge blocks reference (as source or destination) — the
    boundary entries a compacted owner-layout exchange would ship.
    Rebuilt whenever the edge-block grid changes (build, refill, patch,
    merge-compaction / ``layout_version`` bumps)."""

    n_pad: int
    n_loc: int
    halo_counts: tuple     # (D,) ints: unique boundary vertices per device
    halo_total: int

    @property
    def max_halo(self) -> int:
        return max(self.halo_counts) if self.halo_counts else 0


def build_halo_plan(
    src: np.ndarray, dst: np.ndarray, valid: np.ndarray,
    n_nodes: int, n_devices: int,
) -> HaloPlan:
    """Build the owner/halo plan from the host-side ``(P_total, B)``
    blocked-edge grids (rows ``[d*P_local, (d+1)*P_local)`` live on
    device ``d``)."""
    n_loc = -(-n_nodes // n_devices)
    n_pad = n_loc * n_devices
    P_total = src.shape[0]
    P_local = P_total // n_devices
    counts = []
    for d in range(n_devices):
        rows = slice(d * P_local, (d + 1) * P_local)
        v = np.asarray(valid[rows], bool)
        refs = np.unique(np.concatenate(
            [np.asarray(src[rows])[v], np.asarray(dst[rows])[v]]
        )) if v.any() else np.empty(0, np.int64)
        lo, hi = d * n_loc, (d + 1) * n_loc
        counts.append(int(np.count_nonzero((refs < lo) | (refs >= hi))))
    return HaloPlan(n_pad=n_pad, n_loc=n_loc, halo_counts=tuple(counts),
                    halo_total=int(sum(counts)))


@dataclass
class ShardedRuntime:
    """Device-placed inputs shared by every sharded iteration."""

    mesh: jax.sharding.Mesh
    axis: str
    blocks: BlockedEdges       # sharded: P(axis, None)
    parts: DevicePartitions    # replicated (vertex_part_id drives stats)
    out_degree: jax.Array      # (n,) int32, replicated
    zc_req: jax.Array          # (n,) float32, replicated
    inv_deg: jax.Array         # (n,) float32, replicated
    n_nodes: int
    n_partitions: int          # padded: multiple of mesh.shape[axis]
    n_hub_partitions: int
    # Vertex-state layout (HyTMConfig.vertex_sharding).  "owner": state
    # vectors are logically (n_pad,) and owner-sharded P(axis) — each
    # device stores its (n_loc,) owned slice — and the per-vertex runtime
    # vectors above are replicated but padded to (n_pad,) with inert
    # values (out_degree 0, zc_req 0, inv_deg 1, vertex_part_id P-1).
    # "replicated" keeps today's (n,) layout byte-identical; n_pad ==
    # n_nodes and halo is None.
    vertex_sharding: str = "replicated"
    n_pad: int = 0
    halo: HaloPlan | None = None
    # (program, config[, chunk]) -> jitted iteration/chunk; reusing a
    # runtime across run_hytm_sharded calls reuses the compiled sweep
    # instead of retracing a fresh shard_map closure every run.  The
    # device buffers above are *arguments* of the compiled functions, not
    # baked-in constants, so a holder (DeltaCSR's sharded view) may swap
    # them between calls — same shapes reuse the compiled sweep, changed
    # shapes (merge-compaction) re-specialize through the jit cache.
    iteration_cache: dict = field(default_factory=dict, repr=False)


def _pad_table(table: PartitionTable, n_dev: int) -> PartitionTable:
    """Append empty partitions so the partition count divides the mesh."""
    P_real = table.n_partitions
    P_pad = -(-P_real // n_dev) * n_dev
    if P_pad == P_real:
        return table
    extra = P_pad - P_real
    vs = np.concatenate([table.vertex_start, np.full(extra, table.vertex_start[-1])])
    es = np.concatenate([table.edge_start, np.full(extra, table.edge_start[-1])])
    return PartitionTable(vertex_start=vs.astype(np.int64), edge_start=es.astype(np.int64))


def build_sharded_runtime(
    g: CSRGraph,
    config: HyTMConfig,
    mesh: jax.sharding.Mesh,
    n_hubs: int = 0,
    weighted_norm: bool = False,
) -> ShardedRuntime:
    axis = config.mesh_axis
    if axis not in mesh.axis_names:
        # a raised guard, not an assert: under ``python -O`` an assert
        # vanishes and the sweep would shard over a nonexistent axis
        raise ValueError(
            f"config.mesh_axis={axis!r} is not an axis of the mesh "
            f"(axes: {mesh.axis_names})")
    n_dev = int(mesh.shape[axis])

    table = _pad_table(
        partition_graph(
            g, n_partitions=config.n_partitions,
            partition_bytes=config.partition_bytes, d1=config.link.d1,
        ),
        n_dev,
    )
    P_total = table.n_partitions
    epp = table.edges_per_partition
    B = int(epp.max(initial=1))
    B = max(128, -(-B // 128) * 128)

    # host-side blocking: copy each partition's edge slice into its row
    src_all = g.edge_sources()
    dst_all = g.indices
    w_all = g.weights if g.weights is not None else np.ones(g.n_edges, np.float32)
    src = np.zeros((P_total, B), np.int32)
    dst = np.zeros((P_total, B), np.int32)
    w = np.full((P_total, B), np.float32(np.inf), np.float32)
    in_range = np.zeros((P_total, B), bool)
    for p in range(P_total):
        e0, e1 = int(table.edge_start[p]), int(table.edge_start[p + 1])
        k = e1 - e0
        src[p, :k] = src_all[e0:e1]
        dst[p, :k] = dst_all[e0:e1]
        w[p, :k] = w_all[e0:e1]
        in_range[p, :k] = True

    part_id = np.repeat(
        np.arange(P_total, dtype=np.int32), table.vertices_per_partition
    )

    row = NamedSharding(mesh, P(axis, None))
    rep = NamedSharding(mesh, P())
    blocks = BlockedEdges(
        src=jax.device_put(src, row),
        dst=jax.device_put(dst, row),
        weight=jax.device_put(w, row),
        in_range=jax.device_put(in_range, row),
    )

    out_degree = jnp.asarray(g.out_degrees, jnp.int32)
    seg_start = jnp.asarray(g.indptr[:-1], jnp.int32)
    zc_req = zc_request_counts(out_degree, seg_start, config.link)
    if weighted_norm:
        wsum = np.zeros(g.n_nodes, np.float64)
        np.add.at(wsum, src_all, w_all)
        inv_deg = jnp.asarray(1.0 / np.maximum(wsum, 1e-30), jnp.float32)
    else:
        inv_deg = 1.0 / jnp.maximum(out_degree.astype(jnp.float32), 1.0)

    n_hub_parts = int(np.searchsorted(np.asarray(table.vertex_start), n_hubs, side="left"))
    n_hub_parts = max(n_hub_parts, 1) if n_hubs > 0 else 0

    sharding = _check_vertex_sharding(config.vertex_sharding)
    halo = None
    n_pad = g.n_nodes
    if sharding == "owner":
        halo = build_halo_plan(src, dst, in_range, g.n_nodes, n_dev)
        n_pad = halo.n_pad
        out_degree = _pad_vertex_vec(out_degree, n_pad, 0)
        zc_req = _pad_vertex_vec(zc_req, n_pad, 0.0)
        inv_deg = _pad_vertex_vec(inv_deg, n_pad, 1.0)
        part_id = np.concatenate(
            [part_id, np.full(n_pad - g.n_nodes, P_total - 1, np.int32)])

    parts = DevicePartitions(
        vertex_start=jnp.asarray(table.vertex_start, jnp.int32),
        edge_start=jnp.asarray(table.edge_start, jnp.int32),
        part_edges=jnp.asarray(epp, jnp.int32),
        vertex_part_id=jnp.asarray(part_id),
        n_partitions=P_total,
        block_size=B,
    )

    return ShardedRuntime(
        mesh=mesh,
        axis=axis,
        blocks=blocks,
        parts=parts,
        out_degree=jax.device_put(out_degree, rep),
        zc_req=jax.device_put(zc_req, rep),
        inv_deg=jax.device_put(inv_deg, rep),
        n_nodes=g.n_nodes,
        n_partitions=P_total,
        n_hub_partitions=n_hub_parts,
        vertex_sharding=sharding,
        n_pad=n_pad,
        halo=halo,
    )


def _check_vertex_sharding(sharding: str) -> str:
    if sharding not in ("replicated", "owner"):
        raise ValueError(
            f"vertex_sharding must be 'replicated' or 'owner', "
            f"got {sharding!r}")
    return sharding


def _pad_vertex_vec(vec: jax.Array, n_pad: int, fill) -> jax.Array:
    """Pad a per-vertex runtime vector from (n,) to (n_pad,) with an
    inert fill value (padded ids carry no edges and never activate)."""
    extra = n_pad - vec.shape[0]
    if extra <= 0:
        return vec
    return jnp.concatenate([vec, jnp.full(extra, fill, vec.dtype)])


# --------------------------------------------------------------------------
# One sharded iteration
# --------------------------------------------------------------------------

def _local_sweep(
    blocks: BlockedEdges,      # (P_local, B) — this device's shard
    engines: jax.Array,        # (P_local,) — NONE entries are skipped
    order: jax.Array,          # (P_local,) local processing order
    frontier: jax.Array,       # (n,) full per-device view (halo-filled)
    operand: jax.Array,        # (n,) full message operand view
    n: int,
    program: VertexProgram,
    axis: str,
    use_kernels: bool = False,
    owner: bool = False,
    n_loc: int = 0,
):
    """Relax this device's partitions, then merge across the mesh.

    Replicated layout: returns the globally merged (n,) (agg, touched) —
    ``pmin`` for traversal (min) combiners, ``psum`` for accumulative
    (sum) combiners — one collective exchange of the contribution vector
    per pass.  Owner layout: returns this device's **owned (n_loc,)
    slice** of the same merge — the pmin result sliced at the owner
    offset (bit-exact: the identical elementwise pmin, restricted), a
    tiled ``psum_scatter`` for sum combiners.
    """
    identity = jnp.inf if program.combine == MIN else 0.0

    def body(carry, p):
        agg, touched = carry
        eng = engines[p]
        src, dst = blocks.src[p], blocks.dst[p]
        weight, in_range = blocks.weight[p], blocks.in_range[p]
        active = frontier[src] & in_range & (eng != NONE)
        block = EdgeBlock(src=src, dst=dst, weight=weight, active=active)
        out = relax_with_engine(eng, block, operand, n, program, use_kernels)
        if program.combine == MIN:
            agg = jnp.minimum(agg, out.agg)
        else:
            agg = agg + out.agg
        return (agg, touched | out.touched), None

    init = (jnp.full(n, identity, jnp.float32), jnp.zeros(n, bool))
    (agg, touched), _ = jax.lax.scan(body, init, order)
    if program.combine == MIN:
        agg = jax.lax.pmin(agg, axis)
        touched = jax.lax.psum(touched.astype(jnp.int32), axis) > 0
        if owner:
            dev = jax.lax.axis_index(axis)
            agg = jax.lax.dynamic_slice_in_dim(agg, dev * n_loc, n_loc)
            touched = jax.lax.dynamic_slice_in_dim(touched, dev * n_loc, n_loc)
    else:
        if owner:
            agg = jax.lax.psum_scatter(agg, axis, scatter_dimension=0,
                                       tiled=True)
            touched = jax.lax.psum_scatter(
                touched.astype(jnp.int32), axis, scatter_dimension=0,
                tiled=True) > 0
        else:
            agg = jax.lax.psum(agg, axis)
            touched = jax.lax.psum(touched.astype(jnp.int32), axis) > 0
    return agg, touched


def _apply_merged(
    values: jax.Array,
    delta: jax.Array,
    consumed: jax.Array,   # (n,) bool — frontier vertices absorbing delta
    agg: jax.Array,
    touched: jax.Array,
    program: VertexProgram,
):
    """Synchronous state update from a globally merged contribution vector
    (the shard_map analogue of core.hytm._sweep's sync branch)."""
    if program.combine == MIN:
        improved = touched & (agg < values)
        values = jnp.where(improved, agg, values)
        return values, delta, improved
    values = values + jnp.where(consumed, delta, 0.0)
    delta = jnp.where(consumed, 0.0, delta) + agg
    return values, delta, touched


def _make_iteration_impl(
    rt: ShardedRuntime, program: VertexProgram, config: HyTMConfig
):
    """Build the untraced per-iteration body for one runtime/program.
    ``make_sharded_iteration`` jits it directly (the sync_every=1 driver);
    ``make_sharded_chunk`` inlines it in a ``lax.while_loop`` so K
    shard_mapped iterations share one dispatch; ``vmap`` lifts it over a
    lane dimension (``make_sharded_batched_chunk``).

    ``rt`` contributes only the *static* structure (mesh, axis, node and
    partition counts) — the device buffers are traced **arguments** of
    the returned ``iteration(state, blocks, parts, out_degree, zc_req,
    inv_deg, correction)``, never baked-in constants.  That is what lets
    ``DeltaCSR``'s sharded view patch the (P, B) edge-block grid between
    calls while the compiled sweep survives: same shapes hit the jit
    cache, a merge-compaction's new shapes re-specialize through it."""
    mesh, axis = rt.mesh, rt.axis
    n_dev = int(mesh.shape[axis])
    owner = rt.vertex_sharding == "owner"
    # owner layout: state vectors are (n_pad,) owner-sharded; each sweep
    # pass all-gathers the (n_loc,) shards into the full view the local
    # edge blocks read, then merges back to owned slices (_local_sweep)
    n = rt.n_pad if owner else rt.n_nodes
    n_loc = n // n_dev if owner else 0
    P_total = rt.n_partitions
    P_local = P_total // n_dev
    mode = config.cds_mode
    # resolved once at trace time, like the single-device sweep; the
    # shard_mapped local sweep then routes through the same kernel or
    # oracle engines as every other consumer
    use_kernels = resolve_use_kernels(config.use_kernels)

    def select_local(stats_slice, correction):
        """Algorithm 1 on a (P_local,) stats shard — identical result to
        slicing the global selection (selection is per-partition)."""
        if config.forced_engine is None:
            costs = engine_costs(stats_slice, config.link)
            return select_engines(stats_slice, costs, config.link, correction)
        return jnp.where(
            stats_slice.active_edges > 0, config.forced_engine, NONE
        ).astype(jnp.int32)

    def sweep_pass(blocks, stats, second_mask, frontier, operand, delta_mass,
                   correction, pass_two: bool):
        """One shard_mapped sweep pass; returns merged (agg, touched) plus
        the engines each device selected (for the second pass mask)."""

        def local(blocks_l, stats_l, mask_l, dmass_l, frontier_, operand_,
                  corr_):
            dev = jax.lax.axis_index(axis)
            engines_l = select_local(stats_l, corr_)
            if pass_two:
                engines_l = jnp.where(mask_l, engines_l, NONE)
            sched = make_schedule(
                engines_l, dmass_l, rt.n_hub_partitions, mode,
                config.recompute_once, pid_offset=dev * P_local,
                priority_mask=mask_l,
            )
            if owner:
                # halo fill: gather the owned shards into the full view
                # the local edge blocks read (dense exchange; the cost
                # model charges the compacted halo candidate against it)
                frontier_ = jax.lax.all_gather(frontier_, axis, tiled=True)
                operand_ = jax.lax.all_gather(operand_, axis, tiled=True)
            agg, touched = _local_sweep(
                blocks_l, engines_l, sched.order, frontier_, operand_,
                n, program, axis, use_kernels,
                owner=owner, n_loc=n_loc,
            )
            return agg, touched

        shard = P(axis)
        rep = P()
        state_spec = shard if owner else rep
        fn = jax.shard_map(
            local,
            mesh=mesh,
            in_specs=(
                BlockedEdges(src=P(axis, None), dst=P(axis, None),
                             weight=P(axis, None), in_range=P(axis, None)),
                jax.tree.map(lambda _: shard, stats),
                shard, shard, state_spec, state_spec, rep,
            ),
            out_specs=(state_spec, state_spec),
            check_vma=False,
        )
        return fn(blocks, stats, second_mask, delta_mass, frontier,
                  operand, correction)

    def iteration(
        state: HyTMState,
        blocks: BlockedEdges,
        parts: DevicePartitions,
        out_degree: jax.Array,
        zc_req: jax.Array,
        inv_deg: jax.Array,
        correction: jax.Array | None = None,
    ):
        if correction is None:
            # identity correction: float multiply by 1.0 is exact, so the
            # uncorrected path stays bit-identical to the oracle contract
            correction = jnp.ones(3, jnp.float32)
        frontier = state.frontier
        values, delta = state.values, state.delta

        # (1) global stats + Δ mass on the replicated vertex state.  As in
        # core.hytm: only the 'delta' CDS mode reads the Δ mass, and
        # min-combine programs carry an identically-zero Δ — skip the
        # segment-sum in both cases.
        stats = partition_stats(frontier, out_degree, zc_req, parts)
        if program.combine == MIN or mode != "delta":
            delta_mass = jnp.zeros(P_total, jnp.float32)
        else:
            delta_mass = jax.ops.segment_sum(
                jnp.abs(delta) * frontier, parts.vertex_part_id,
                num_segments=P_total,
            )

        # (2) global plan for the transfer accounting (identical to the
        # per-device selections — selection is per-partition)
        if config.forced_engine is None:
            plan = generate_tasks(
                stats, config.link, combine_k=config.combine_k,
                enable_combination=config.enable_task_combination,
                correction=correction,
            )
        else:
            plan = forced_engine_plan(
                stats, config.link, config.forced_engine,
                enable_combination=config.enable_task_combination,
                combine_k=config.combine_k,
            )

        # (3) global second-pass mask (Δ-mode top-K is a global rank)
        sched_global = make_schedule(
            plan.engines, delta_mass, rt.n_hub_partitions, mode,
            config.recompute_once,
        )
        second_mask = sched_global.second_pass

        # (4) pass 1: every active partition, synchronous merge
        if program.combine == SUM:
            operand = program.damping * delta * inv_deg
        else:
            operand = values
        agg, touched = sweep_pass(
            blocks, stats, second_mask, frontier, operand, delta_mass,
            correction, pass_two=False,
        )
        if program.peel_k is not None:
            # peeling: merged agg is the per-vertex count of newly-removed
            # in-neighbors; subtract from the remaining degree (additive,
            # so async == sync == sharded — core.hytm._sweep's peel branch)
            values1, delta1, activated = values - agg, delta, touched
        else:
            values1, delta1, activated = _apply_merged(
                values, delta, frontier, agg, touched, program,
            )

        # (5) pass 2: recompute-once over loaded priority partitions
        if program.peel_k is not None:
            # a second peel pass would double-subtract the removal counts
            frontier2 = jnp.zeros_like(frontier)
        elif program.combine == MIN:
            frontier2 = frontier | activated
        else:
            # |Δ| matches core.hytm: signed correction deltas (the
            # incremental repro.stream path) must keep propagating.
            frontier2 = jnp.abs(delta1) > program.tolerance
        if program.combine == SUM:
            operand2 = program.damping * delta1 * inv_deg
        else:
            operand2 = values1
        agg2, touched2 = sweep_pass(
            blocks, stats, second_mask, frontier2, operand2, delta_mass,
            correction, pass_two=True,
        )
        # pass-2 consumption only touches re-processed partitions
        processed2 = second_mask[parts.vertex_part_id] & (
            plan.engines[parts.vertex_part_id] != NONE
        )
        if program.peel_k is not None:
            values2, delta2, activated2 = values1 - agg2, delta1, touched2
        else:
            values2, delta2, activated2 = _apply_merged(
                values1, delta1, frontier2 & processed2, agg2, touched2,
                program,
            )
        activated = activated | activated2
        # entries a compacted ICI exchange would ship: destinations any
        # device touched this iteration (both passes) — NOT the source
        # frontier, which undercounts by the fan-out in hub regimes
        merged_entries = jnp.sum((touched | touched2).astype(jnp.int32))

        if program.peel_k is not None:
            # newly-removed: alive vertices whose remaining degree fell
            # below k this round (matches core.hytm's peel post-pass)
            alive = delta2 < 0.5
            newly = alive & (values2 < program.peel_k)
            next_frontier = newly
            delta2 = delta2 + newly.astype(jnp.float32)
        elif program.combine == MIN:
            next_frontier = activated
        else:
            next_frontier = jnp.abs(delta2) > program.tolerance

        new_state = HyTMState(values=values2, delta=delta2, frontier=next_frontier)
        per_engine_time, mispredictions = selection_diagnostics(
            plan.engines, plan.transfer_time, stats, plan.costs, correction,
        )
        info = {
            KEY_ENGINES: plan.engines,
            KEY_TRANSFER_BYTES: plan.transfer_bytes,
            KEY_TRANSFER_TIME: jnp.sum(plan.transfer_time)
            + plan.n_tasks.astype(jnp.float32) * config.link.launch_overhead_s,
            KEY_N_TASKS: plan.n_tasks,
            KEY_ACTIVE_VERTICES: jnp.sum(frontier.astype(jnp.int32)),
            KEY_ACTIVE_EDGES: jnp.sum(stats.active_edges),
            "next_active": jnp.sum(next_frontier.astype(jnp.int32)),
            KEY_PER_ENGINE_TIME: per_engine_time,
            KEY_MISPREDICTIONS: mispredictions,
            KEY_MERGED_ENTRIES: merged_entries,
        }
        return new_state, info

    return iteration


def _runtime_args(rt: ShardedRuntime) -> tuple:
    """The traced device-buffer arguments every compiled sharded driver
    takes, read fresh from the runtime at each dispatch (so a patched
    view — DeltaCSR's sharded grid — is always what executes)."""
    return rt.blocks, rt.parts, rt.out_degree, rt.zc_req, rt.inv_deg


def make_sharded_iteration(
    rt: ShardedRuntime, program: VertexProgram, config: HyTMConfig
):
    """Build the jitted per-iteration function for one runtime/program:
    ``iteration(state, blocks, parts, out_degree, zc_req, inv_deg,
    correction)``."""
    return jax.jit(_make_iteration_impl(rt, program, config))


def make_sharded_chunk(
    rt: ShardedRuntime, program: VertexProgram, config: HyTMConfig,
    chunk: int,
):
    """Chunked sharded driver: up to ``chunk`` shard_mapped iterations
    inside one ``lax.while_loop`` dispatch, same chunk/early-exit and
    history-draining contract as ``core.hytm.hytm_chunk`` (state and
    history buffers donated; the while-condition tests the previous
    iteration's ``next_active``, so convergence never overshoots).  The
    history buffers additionally carry ``merged_entries`` — the
    per-iteration input of the host-side ICI-level accounting
    (``ici_level_cost``), which runs over the drained rows once per
    chunk.  The edge blocks and vertex vectors are traced arguments (see
    ``_make_iteration_impl``), so warm-started reruns over a patched
    ``DeltaCSR`` view reuse this compiled chunk."""
    impl = _make_iteration_impl(rt, program, config)
    keys = HISTORY_KEYS + (KEY_MERGED_ENTRIES,)

    @partial(jax.jit, donate_argnames=("state", "history"))
    def chunk_fn(state: HyTMState, history: dict, blocks, parts, out_degree,
                 zc_req, inv_deg, correction: jax.Array):
        return chunked_while(
            lambda st: impl(st, blocks, parts, out_degree, zc_req, inv_deg,
                            correction),
            state, history, chunk)

    shapes_cell: dict = {}  # eval_shape once per shape signature

    def init_history(state: HyTMState, correction: jax.Array) -> dict:
        shape_key = (rt.blocks.src.shape, rt.parts.n_partitions,
                     rt.parts.block_size)
        if shape_key not in shapes_cell:
            shapes_cell[shape_key] = jax.eval_shape(
                impl, state, *_runtime_args(rt), correction)[1]
        return init_history_buffers(shapes_cell[shape_key], chunk, keys=keys)

    return chunk_fn, init_history


def make_sharded_batched_chunk(
    rt: ShardedRuntime, program: VertexProgram, config: HyTMConfig,
    chunk: int,
):
    """Service lane sweep over the mesh (``GraphService`` with
    ``config.mesh_axis`` set): up to ``chunk`` iterations of the sharded
    iteration, ``vmap``ped over the leading lane dimension of ``state``
    — each lane runs its own cost model / engine selection / schedule
    over its own frontier, while the edge blocks stay sharded over the
    mesh axis and every relaxation merges with the same bulk-synchronous
    pmin/psum collectives as the single-lane sweep (one batched
    collective carries all lanes).  The carry holds the **per-lane**
    ``next_active`` vector (the early-exit condition sums it, matching
    ``core.hytm.hytm_batched_chunk``): converged lanes idle as no-ops
    only while a straggler is still inside the chunk, and the returned
    ``lane_active`` is the signal the continuous scheduler
    (``repro.serve``) uses to free converged lanes at the chunk boundary
    and backfill their slots on the mesh path.

    The service reads no per-iteration history; the loop carries running
    reductions (summed per-engine modeled seconds + mispredictions — the
    calibrator's chunk-granular observation inputs) plus a ``(chunk,)``
    row of lane-summed ``merged_entries`` for the host-side ICI-level
    accounting.  Returns ``(state, n_done, lane_active,
    per_engine_sum, mispred_sum, merged_rows)`` with ``lane_active`` of
    shape ``(Q,)``."""
    impl = _make_iteration_impl(rt, program, config)

    @partial(jax.jit, donate_argnames=("state",))
    def chunk_fn(state: HyTMState, blocks, parts, out_degree, zc_req,
                 inv_deg, correction):
        def one(s):
            return impl(s, blocks, parts, out_degree, zc_req, inv_deg,
                        correction)

        def cond(carry):
            _s, i, lane_active, _pe, _mp, _me = carry
            return (i < chunk) & (jnp.sum(lane_active) != 0)

        def body(carry):
            s, i, _prev, pe, mp, me = carry
            s2, info = jax.vmap(one)(s)
            return (
                s2,
                i + 1,
                info["next_active"],
                pe + jnp.sum(info[KEY_PER_ENGINE_TIME], axis=0),
                mp + jnp.sum(info[KEY_MISPREDICTIONS]),
                me.at[i].set(jnp.sum(info[KEY_MERGED_ENTRIES])),
            )

        n_lanes = state.values.shape[0]
        # sentinel ones: the first iteration always runs, matching the
        # K=1 loop (which runs one iteration even on an empty frontier)
        init = (state, jnp.int32(0), jnp.ones(n_lanes, jnp.int32),
                jnp.zeros(3, jnp.float32), jnp.int32(0),
                jnp.zeros(chunk, jnp.int32))
        return jax.lax.while_loop(cond, body, init)

    return chunk_fn


# --------------------------------------------------------------------------
# Second transfer-management level: the cross-device merge
# --------------------------------------------------------------------------

def _ring_per_dev_bytes(payload_bytes: float, n_devices: int) -> float:
    """Bytes one device moves for a ring all-reduce of ``payload_bytes``."""
    return 2.0 * (n_devices - 1) / n_devices * payload_bytes


def _collective_charge(per_dev_bytes: float, link) -> float:
    """Seconds for one collective, through the Eq-1 transaction-group
    model (shared by the dense and compacted ICI candidates — they must
    never diverge, or the second-level engine comparison is corrupted)."""
    group = link.m * link.mr
    return float(np.ceil(per_dev_bytes / group)) * link.rtt + link.launch_overhead_s


def ici_merge_cost(
    n_nodes: int, n_devices: int, link, n_collectives: int = 4
) -> tuple[float, float]:
    """Modeled (bytes, seconds) of one iteration's cross-device merges.

    Each sweep pass all-reduces two dense (n,) vectors — the contribution
    aggregate (f32) and the touched mask (i32) — and an iteration runs two
    passes, so ``n_collectives`` = 4.  A ring all-reduce moves
    ``2*(D-1)/D * n * 4`` bytes per device per collective; bytes are the
    all-device total (what the fabric carries), time is the per-device
    critical path through the same transaction-group model as Eqs. 1-3
    (DESIGN.md §2: all-gather of whole value arrays == the filter engine
    of the ICI level).
    """
    if n_devices <= 1:
        return 0.0, 0.0
    per_dev = _ring_per_dev_bytes(n_nodes * 4.0, n_devices)
    total_bytes = per_dev * n_devices * n_collectives
    return total_bytes, n_collectives * _collective_charge(per_dev, link)


def ici_level_cost(
    n_nodes: int,
    merged_entries: float,
    n_devices: int,
    link,
    correction: np.ndarray | None = None,
    n_collectives: int = 4,
) -> tuple[float, float, int]:
    """Per-iteration ICI-level *engine selection* (Algorithm 1 at the
    second transfer-management level): dense all-reduce of the whole
    (n,) contribution vectors (the FILTER analogue) vs a compacted
    exchange of only the ``merged_entries`` destinations the sweep
    touched — (index, payload) pairs, 8 B — (the COMPACT analogue).
    Returns (bytes, seconds, engine).

    ``correction`` is the same (3,) online-feedback vector the HBM level
    uses (repro.autotune.feedback); it rescales the two candidate costs
    before *comparison* only — the returned charge is the chosen
    engine's uncorrected model time, matching the HBM level's
    select-corrected / account-uncorrected contract.  Accounting-level
    selection: the executed collective stays the bulk-synchronous
    pmin/psum merge (oracle equivalence); what moves is the modeled
    charge, exactly as the HBM level's accounting does.
    """
    if n_devices <= 1:
        return 0.0, 0.0, NONE
    c = np.ones(3) if correction is None else np.asarray(correction, float)
    per_dev_comp = _ring_per_dev_bytes(float(merged_entries) * 8.0, n_devices)
    t_comp = n_collectives * _collective_charge(per_dev_comp, link)
    dense_bytes, t_dense = ici_merge_cost(
        n_nodes, n_devices, link, n_collectives=n_collectives)
    if t_comp * c[COMPACT] < t_dense * c[FILTER]:
        return per_dev_comp * n_devices * n_collectives, t_comp, COMPACT
    return dense_bytes, t_dense, FILTER


def halo_level_cost(
    n_nodes: int,
    merged_entries: float,
    halo_total: int,
    n_devices: int,
    link,
    correction: np.ndarray | None = None,
    n_collectives: int = 4,
) -> tuple[float, float, int]:
    """``ici_level_cost`` generalized to the owner/halo layout: a
    compacted exchange never ships more than the boundary vertices the
    edge blocks actually reference, so the compacted candidate's entry
    count is capped at ``HaloPlan.halo_total`` — the halo is the
    owner-layout analogue of the touched-destination set.  The dense
    candidate (all-gather + merge of whole vectors) is unchanged, and the
    select-corrected / account-uncorrected contract carries over."""
    return ici_level_cost(
        n_nodes, min(float(merged_entries), float(halo_total)), n_devices,
        link, correction, n_collectives,
    )


# --------------------------------------------------------------------------
# Convergence loop
# --------------------------------------------------------------------------

def owner_state_pad_values(program: VertexProgram) -> tuple[float, float]:
    """(values, delta) fill for the ``[n, n_pad)`` ghost vertices of the
    owner layout.  Pads carry no edges, so the fills only need to keep
    them *inert* in the next-frontier rules: Δ-pads 0 would re-activate
    under a peel (alive with degree < k), so peels pad Δ=1 (removed);
    min-combiners pad values=inf (unreachable); frontier pads are always
    False."""
    if program.peel_k is not None:
        return 0.0, 1.0
    if program.use_delta:
        return 0.0, 0.0
    return float(np.inf), 0.0


def _owner_place_state(
    rt: ShardedRuntime, program: VertexProgram,
    values: jax.Array, delta: jax.Array, frontier: jax.Array,
) -> HyTMState:
    """Pad an (n,) state triple to (n_pad,) and owner-shard it P(axis) —
    the placement every owner-mode dispatch (cold, warm, incremental,
    resumed) takes."""
    pad_v, pad_d = owner_state_pad_values(program)
    values = _pad_vertex_vec(jnp.asarray(values, jnp.float32), rt.n_pad,
                             pad_v)
    delta = _pad_vertex_vec(jnp.asarray(delta, jnp.float32), rt.n_pad, pad_d)
    frontier = _pad_vertex_vec(jnp.asarray(frontier, bool), rt.n_pad, False)
    shard = NamedSharding(rt.mesh, P(rt.axis))
    return HyTMState(
        values=jax.device_put(values, shard),
        delta=jax.device_put(delta, shard),
        frontier=jax.device_put(frontier, shard),
    )


def run_hytm_sharded(
    g: CSRGraph,
    program: VertexProgram,
    source: int | None = 0,
    config: HyTMConfig = HyTMConfig(mesh_axis="graph"),
    n_hubs: int = 0,
    mesh: jax.sharding.Mesh | None = None,
    runtime: ShardedRuntime | None = None,
    calibrator=None,
    initial_state: HyTMState | None = None,
    obs=None,
    faults=None,
    retry=None,
    on_chunk=None,
) -> HyTMResult:
    """Drop-in ``run_hytm`` over a 1-D device mesh.

    Equivalence contract: identical per-partition engine selections and
    modeled transfer accounting as single-device, and state trajectories
    matching the single-device ``async_sweep=False`` run (exact for
    min-combine programs; up to FP summation order for sum-combine).

    ``config.vertex_sharding`` picks the vertex-state layout.
    ``"replicated"`` (default) keeps the full (n,) triple on every
    device — byte-identical to the historical path.  ``"owner"``
    owner-shards the triple: each device stores only its contiguous
    ``(n_loc,) = (ceil(n/D),)`` owned slice plus the halo view its edge
    blocks gather per pass, cutting per-device vertex-state bytes
    ~D-fold (``cost_model.vertex_state_bytes``); the ICI level then
    charges ``halo_level_cost`` — the compacted candidate capped at the
    runtime's :class:`HaloPlan` boundary count.  Both layouts satisfy
    the same oracle contract above; ``HyTMResult.values``/``delta`` are
    always returned as host (n,) arrays regardless of layout.

    ``initial_state`` warm-starts the sharded convergence loop from an
    arbitrary (values, Δ, frontier) triple — the entry point of the
    sharded incremental path (repro.stream.incremental with
    ``config.mesh_axis`` set).  The warm state is re-placed replicated
    over the mesh (the same sharding the cold start's init state takes),
    so it re-enters the compiled chunk under identical layout; the warm
    equivalence contract mirrors the cold one (warm sharded ==
    single-device ``async_sweep=False`` warm, bit-for-bit for
    min-combine).  With ``runtime`` and ``initial_state`` both given,
    ``g`` may be ``None``.

    ``faults``/``retry``/``on_chunk`` mirror ``run_hytm``: injected
    ``"chunk_dispatch"`` faults fire before the shard_mapped dispatch
    (donated buffers intact, retries bit-identical), and ``on_chunk``
    observes every chunk boundary for checkpointing — all zero-overhead
    when absent.
    """
    if runtime is not None:
        rt = runtime
        mesh = rt.mesh if mesh is None else mesh
    else:
        if g is None:
            raise ValueError(
                "run_hytm_sharded needs a graph or a prebuilt runtime")
        if mesh is None:
            from repro.launch.mesh import make_graph_mesh

            mesh = make_graph_mesh(axis=config.mesh_axis)
        if program.symmetrize:
            # WCC-family programs sweep the underlying undirected graph
            g = g.symmetrize()
        rt = build_sharded_runtime(
            g, config, mesh, n_hubs=n_hubs,
            weighted_norm=program.use_delta and program.weighted,
        )
    owner = _check_vertex_sharding(config.vertex_sharding) == "owner"
    if rt.vertex_sharding != config.vertex_sharding:
        raise ValueError(
            f"runtime was built with vertex_sharding="
            f"{rt.vertex_sharding!r} but config requests "
            f"{config.vertex_sharding!r}; rebuild the runtime")
    if initial_state is None:
        if program.peel_k is not None:
            # peeling programs seed from vertex degrees (init_state has no
            # degree access); rt.out_degree is padded in owner mode —
            # slice to the real vertices so pads never enter the frontier
            deg = np.asarray(rt.out_degree)[:rt.n_nodes].astype(np.float32)
            removed = deg < program.peel_k
            values, delta, frontier = (
                jnp.asarray(deg), jnp.asarray(removed, jnp.float32),
                jnp.asarray(removed))
        else:
            values, delta, frontier = program.init_state(rt.n_nodes, source)
        if owner:
            state = _owner_place_state(rt, program, values, delta, frontier)
        else:
            state = HyTMState(values=values, delta=delta, frontier=frontier)
    elif owner:
        state = _owner_place_state(
            rt, program, jnp.asarray(initial_state.values),
            jnp.asarray(initial_state.delta),
            jnp.asarray(initial_state.frontier))
    else:
        # replicate the warm triple over the mesh — identical placement to
        # the cold start, so the compiled sweep sees one layout either way
        rep = NamedSharding(mesh, P())
        state = HyTMState(
            values=jax.device_put(jnp.asarray(initial_state.values), rep),
            delta=jax.device_put(jnp.asarray(initial_state.delta), rep),
            frontier=jax.device_put(jnp.asarray(initial_state.frontier), rep),
        )

    n_dev = int(mesh.shape[config.mesh_axis])

    calib = None
    correction = None
    corr_np = None
    if config.autotune:
        from repro.autotune.feedback import OnlineCalibrator

        calib = (calibrator if calibrator is not None
                 else OnlineCalibrator(decay=config.autotune_decay))
        correction = jnp.asarray(calib.correction(), jnp.float32)
        corr_np = np.asarray(correction, dtype=float)

    if config.sync_every < 1:
        raise ValueError(f"sync_every must be >= 1, got {config.sync_every}")
    if on_chunk is not None and config.sync_every == 1:
        raise ValueError(
            "on_chunk (checkpointing) requires the chunked driver — "
            "set sync_every >= 2")
    rows: dict[str, list] = {k: [] for k in HISTORY_KEYS}
    # second-level accounting (per iteration: the exchange mode depends on
    # the live active-vertex count, and feedback can reweigh the choice)
    ici_hist: dict[str, list] = {
        KEY_ICI_BYTES: [], KEY_ICI_TIME: [], KEY_ICI_ENGINE: []}

    def charge_ici(merged_entries: float) -> None:
        if owner and rt.halo is not None:
            # owner layout: a compacted exchange ships at most the halo
            halo_entries = min(float(merged_entries),
                               float(rt.halo.halo_total))
            ib, it_, ie = halo_level_cost(
                rt.n_nodes, float(merged_entries), rt.halo.halo_total,
                n_dev, config.ici_link, corr_np,
            )
        else:
            halo_entries = None
            ib, it_, ie = ici_level_cost(
                rt.n_nodes, float(merged_entries), n_dev, config.ici_link,
                corr_np,
            )
        it = len(ici_hist[KEY_ICI_BYTES])  # global iteration index
        ici_hist[KEY_ICI_BYTES].append(ib)
        ici_hist[KEY_ICI_TIME].append(it_)
        ici_hist[KEY_ICI_ENGINE].append(ie)
        if obs is not None:
            from repro.obs.record import record_ici

            record_ici(
                obs, track="ici", it=it, bytes_=ib, seconds=it_, engine=ie,
                merged_entries=float(merged_entries),
                halo_entries=halo_entries,
            )

    t0 = time.monotonic()
    iters = 0
    if config.sync_every > 1:
        # Chunked driver: one shard_mapped lax.while_loop dispatch per K
        # iterations (same contract as core.hytm.hytm_chunk); the ICI
        # level is charged per executed iteration from the drained
        # merged_entries rows, under the SAME correction the chunk's
        # HBM-level selections ran with.
        corr_arr = (correction if correction is not None
                    else jnp.ones(3, jnp.float32))
        history, cur_chunk = None, -1
        while iters < config.max_iters:
            chunk = min(config.sync_every, config.max_iters - iters)
            key = ("chunk", program, config, chunk)
            cached = rt.iteration_cache.get(key)
            if cached is None:
                chunk_fn, init_history = make_sharded_chunk(
                    rt, program, config, chunk)
                cached = {"fn": chunk_fn, "init": init_history,
                          "seen": set()}
                rt.iteration_cache[key] = cached
            if chunk != cur_chunk:
                # allocated once per chunk size; afterwards the drained
                # buffers cycle back in (donated reuse on accelerators)
                history = cached["init"](state, corr_arr)
                cur_chunk = chunk
            # warm iff THIS chunk_fn already dispatched THESE shapes: the
            # seen-set lives on the cached entry, so when a DeltaCSR
            # merge-compaction drops the entry (fresh jit cache) or moves
            # the block grid, the recompiling dispatch is cold and its
            # wall time never feeds the calibrator
            warm = _consume_warm(
                (rt.blocks.src.shape, rt.parts.n_partitions,
                 rt.parts.block_size),
                registry=cached["seen"],
            )
            # faults fire BEFORE the shard_mapped dispatch — donated
            # buffers from the previous chunk stay intact, so a retried
            # dispatch is bit-identical
            def attempt(st=state, h=history, ca=corr_arr, fn=cached["fn"]):
                with quiet_donation():
                    return fn(st, h, *_runtime_args(rt), ca)

            with span("chunk", obs, cat=CAT_RUN, track="mesh",
                      vt=float(iters)) as chunk_span:
                t_chunk = time.monotonic()
                if faults is None:
                    out = attempt()
                else:
                    from repro.resilience.supervisor import guarded_dispatch

                    out = guarded_dispatch(
                        attempt, site="chunk_dispatch", faults=faults,
                        policy=retry, obs=obs, mesh=True,
                        kernels=resolve_use_kernels(config.use_kernels),
                    )
                state, history, n_done, last_active, pe_sum = out
                n_done = int(n_done)
                iters += n_done
                if calib is not None:
                    # observe BEFORE the drain + ICI loop: the measured
                    # wall window covers dispatch + execution only
                    corr_arr = calib.observe_chunk(
                        state.values, np.asarray(pe_sum, dtype=float),
                        t_chunk, skip=not warm,
                    )
                # drain BEFORE the next dispatch donates these buffers
                drained = jax.device_get(history)
                for me in drained[KEY_MERGED_ENTRIES][:n_done]:
                    charge_ici(me)  # charged under the chunk's correction
                if calib is not None:
                    corr_np = np.asarray(corr_arr, dtype=float)
                for k in rows:
                    rows[k].append(drained[k][:n_done])
                if obs is not None:
                    from repro.obs.record import record_history_rows

                    record_history_rows(
                        obs, drained, n_done, iters - n_done, track="mesh")
                    chunk_span.vt_dur = float(n_done)
                    chunk_span.args.update(n_done=n_done, warm=warm)
            if on_chunk is not None:
                on_chunk(state=state, iterations=iters, rows=rows,
                         calibrator=calib, last_active=int(last_active))
            if int(last_active) == 0:
                break
        history = {k: np.concatenate(v) for k, v in rows.items()}
    else:
        cache_key = (program, config)
        iteration = rt.iteration_cache.get(cache_key)
        if iteration is None:
            iteration = make_sharded_iteration(rt, program, config)
            rt.iteration_cache[cache_key] = iteration
        for _ in range(config.max_iters):
            t_iter = time.monotonic()
            if faults is None:
                state, info = iteration(
                    state, *_runtime_args(rt), correction)
            else:
                from repro.resilience.supervisor import guarded_dispatch

                def _attempt(st=state, corr=correction):
                    return iteration(st, *_runtime_args(rt), corr)

                state, info = guarded_dispatch(
                    _attempt, site="chunk_dispatch", faults=faults,
                    policy=retry, obs=obs, mesh=True,
                    kernels=resolve_use_kernels(config.use_kernels),
                )
            iters += 1
            # charge the ICI level under the SAME correction this
            # iteration's HBM-level selection ran with (the update below
            # only steers the next iteration, exactly as on the
            # single-device path)
            charge_ici(info[KEY_MERGED_ENTRIES])
            if calib is not None:
                correction = calib.observe_iteration(
                    state.values, info[KEY_PER_ENGINE_TIME], t_iter,
                    skip=iters == 1,  # iteration 1 measures compile
                )
                corr_np = np.asarray(correction, dtype=float)
            for k in rows:
                rows[k].append(info[k])
            if int(info["next_active"]) == 0:
                break
        # history stayed on device during the loop; one pull post-hoc
        staged = jax.device_get(rows)
        history = {k: np.stack(v) for k, v in staged.items()}
        if obs is not None:
            from repro.obs.record import record_history_rows

            record_history_rows(obs, history, iters, 0, track="mesh")
    jax.block_until_ready(state.values)
    wall = time.monotonic() - t0

    for k, v in ici_hist.items():
        history[k] = np.asarray(v)
    result = HyTMResult(
        # owner mode: gather the sharded (n_pad,) vectors and drop the
        # ghost pads so callers always see host (n,) arrays
        values=np.asarray(state.values)[:rt.n_nodes],
        delta=np.asarray(state.delta)[:rt.n_nodes],
        iterations=iters,
        wall_seconds=wall,
        modeled_seconds=float(np.sum(history[KEY_TRANSFER_TIME])),
        total_transfer_bytes=float(np.sum(history[KEY_TRANSFER_BYTES])),
        history=history,
        total_ici_bytes=float(np.sum(history[KEY_ICI_BYTES])),
        modeled_ici_seconds=float(np.sum(history[KEY_ICI_TIME])),
        total_mispredictions=int(np.sum(history[KEY_MISPREDICTIONS])),
        engine_corrections=(
            calib.correction() if calib is not None else None
        ),
    )
    if obs is not None:
        from repro.obs.record import record_run

        record_run(
            obs, result, track="mesh", wall_start=obs.wall_at(t0),
            wall_dur=wall, program=program.name, label=f"run[{n_dev}dev]",
        )
    return result
