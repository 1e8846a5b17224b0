"""HyTM engine orchestration — ties cost model, task generation, and
asynchronous scheduling into the iterate-until-convergence loop (paper
Fig. 5: cost-aware task generation <-> asynchronous task scheduling).

One *iteration* is a single jitted function:

  1. per-partition activity stats      (segment reductions, on device)
  2. cost model + engine selection     (Eqs. 1-3, Algorithm 1)
  3. task combination                  (merged task count -> launch overhead)
  4. priority schedule                 (hub / delta contribution-driven order)
  5. asynchronous sweep                (scan over partitions in priority
     order; each partition relaxes through its selected engine against the
     *current* values — later partitions see earlier updates)
  6. recompute-once second pass        (loaded priority partitions, no
     additional transfer)

The convergence loop is **device-resident and chunked**
(``HyTMConfig.sync_every = K``): ``hytm_chunk`` runs up to K iterations
inside one compiled ``jax.lax.while_loop`` dispatch, with the state and
the preallocated on-device history buffers donated so values/Δ/frontier
update in place instead of round-tripping through host.  The chunk's
while-condition checks the *previous* iteration's frontier population
(``next_active == 0``), so a converged run early-exits inside the chunk
and never executes a single iteration past convergence; the host only
syncs once per chunk — to drain the ``(K, ...)`` history rows actually
written and to read the loop-exit flag — instead of twice per iteration.
``K = 1`` keeps the legacy one-dispatch-per-iteration loop (whose
per-iteration device->host sync on the frontier population is the same
sync real GPU frameworks pay), reproducing the pre-chunk dataflow
bit-for-bit; ``K > 1`` is bit-identical for min-combine programs and
tolerance-bounded for sum-combine (XLA may fuse the loop body
differently than the standalone iteration).  The drained history feeds
the Fig-7 execution path, Table-VI transfer volume, and Table-V runtime
analyses exactly as before — chunking changes *when* history reaches the
host, never what it records.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
import warnings
from dataclasses import dataclass, field
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.constants import PCIE3, TPU_V5E_ICI, LinkModel
from repro.core.cost_model import (
    COMPACT,
    FILTER,
    HISTORY_KEYS,
    KEY_ACTIVE_EDGES,
    KEY_ACTIVE_VERTICES,
    KEY_ENGINES,
    KEY_MISPREDICTIONS,
    KEY_N_TASKS,
    KEY_PER_ENGINE_TIME,
    KEY_TRANSFER_BYTES,
    KEY_TRANSFER_TIME,
    NONE,
    ZEROCOPY,
    init_history_buffers,
    partition_stats,
    selection_diagnostics,
    zc_request_counts,
)
from repro.core.engines import EdgeBlock, relax_with_engine
from repro.kernels.runtime import resolve_use_kernels
from repro.core.partition import (
    DevicePartitions,
    EdgeRoute,
    PartitionTable,
    partition_graph,
    route_partitions,
    to_device_partitions,
)
from repro.core.scheduler import make_schedule
from repro.core.task_generation import TaskPlan, forced_engine_plan, generate_tasks
from repro.graph.algorithms import MIN, SUM, VertexProgram
from repro.graph.csr import CSRGraph, DeviceCSR, to_device_csr
from repro.obs import scopes, span
from repro.obs.export import CAT_RUN
from repro.obs.scopes import SELECT, SWEEP, SWEEP_BLOCK, SWEEP_COMBINE, UPDATE, scope


@dataclass(frozen=True)
class HyTMConfig:
    link: LinkModel = PCIE3
    n_partitions: int | None = None
    partition_bytes: int = 32 * 2**20  # paper default: 32 MB partitions
    async_sweep: bool = True
    cds_mode: str = "hub"  # 'hub' | 'delta' | 'none'
    enable_task_combination: bool = True
    recompute_once: bool = True
    combine_k: int = 4
    max_iters: int = 10_000
    # Convergence-loop chunk size K: each device dispatch runs up to K
    # iterations inside one compiled lax.while_loop (early-exiting the
    # moment the frontier drains), and the host syncs once per chunk
    # instead of twice per iteration.  K=1 keeps the legacy
    # one-dispatch-per-iteration loop (bit-for-bit the pre-chunk
    # dataflow); the default is tuned for dispatch-bound many-iteration
    # workloads (benchmarks/iterloop.py) — large enough to amortize
    # dispatch+sync, small enough that history draining and the online
    # calibrator keep a useful cadence.
    sync_every: int = 8
    # Engine implementation dispatch: route the FILTER/COMPACT/ZEROCOPY
    # relaxations through the Pallas kernels (kernels/segment_spmm,
    # kernels/frontier_compact, kernels/hyb_gather) instead of the
    # pure-JAX oracle engines.  Tri-state: "auto" (default) resolves via
    # kernels.runtime.on_tpu() — compiled kernels on TPU backends, the
    # oracles elsewhere (interpret mode would only add overhead); True
    # forces the kernel path (interpret mode off-TPU: how the equivalence
    # tests and the CI roofline gate execute the kernel bodies on CPU);
    # False forces the oracles.  Contract: the kernel path is
    # bit-identical for MIN programs (values, iterations, transfer bytes,
    # engine picks) and tolerance-bounded for SUM, on the single-device,
    # sharded, chunked, and GraphService paths alike — engine *selection*
    # and transfer accounting never depend on the flag.
    use_kernels: bool | str = "auto"
    forced_engine: int | None = None  # force a single engine (baselines)
    hub_fraction: float = 0.08
    # Second transfer-management level (DESIGN.md §2): the link model used
    # to charge the cross-device merge of the sharded sweep.  Only read on
    # the mesh_axis path; the single-device run reports zero ICI traffic.
    ici_link: LinkModel = TPU_V5E_ICI
    # Online autotuning (repro.autotune.feedback): per-iteration measured
    # sweep times feed an EWMA per-engine correction factor that rescales
    # the Algorithm-1 selection costs (and the sharded path's ICI-level
    # exchange choice).  Transfer *accounting* stays in model units; the
    # engines are semantically interchangeable, so results are unchanged
    # — only which engine pays for each partition moves.
    autotune: bool = False
    autotune_decay: float = 0.25  # EWMA forgetting factor of the calibrator
    # Name of a 1-D mesh axis to shard the partition edge blocks over
    # (repro.dist.graph_shard).  None = the single-device path below
    # (note: the sync-sweep SUM consumption fix in ``_sweep`` changed
    # async_sweep=False results relative to older revisions; the default
    # async path is untouched).  The sharded sweep is bulk-synchronous
    # across devices, so it reproduces the single-device
    # ``async_sweep=False`` dataflow exactly.
    mesh_axis: str | None = None
    # Vertex-state layout of the sharded path (read only when mesh_axis
    # is set).  "replicated" (default): every device holds the full (n,)
    # values/Δ/frontier triple — byte-identical to the pre-owner-sharding
    # behavior.  "owner": each device owns the ceil(n/D) vertices of its
    # partition rows and holds only that slice (plus the boundary halo
    # its local edge blocks reference), exchanging boundary contributions
    # per iteration — per-device vertex-state bytes drop ~D-fold
    # (cost_model.vertex_state_bytes) while results stay bit-identical to
    # the single-device ``async_sweep=False`` oracle for min-combine
    # programs and tolerance-bounded for sum-combine
    # (dist.graph_shard).
    vertex_sharding: str = "replicated"


@jax.tree_util.register_dataclass
@dataclass
class HyTMState:
    values: jax.Array   # (n,) f32
    delta: jax.Array    # (n,) f32 (accumulative programs)
    frontier: jax.Array  # (n,) bool


@dataclass
class Runtime:
    """Device-resident inputs shared by every iteration."""

    csr: DeviceCSR
    parts: DevicePartitions
    zc_req: jax.Array          # (n,) float32
    inv_deg: jax.Array         # (n,) float32 — 1/max(deg,1) (or 1/sum(w)
                               # for weighted accumulative programs: PHP)
    n_hub_partitions: int
    # each partition's edges routed by destination block (build_runtime):
    # the sweep reads its blocks from here and FILTER's fold skips the
    # per-visit sort.  None — a view over a CSR that is patched in place
    # (DeltaCSR.runtime_for) — slices the CSR and routes on every visit.
    route: EdgeRoute | None = None
    # (program, config, shapes) -> iteration info ShapeDtypeStructs;
    # reusing a runtime across run_hytm calls — or sharing this dict
    # across runtime views, as DeltaCSR.runtime_for does — skips the
    # per-call jax.eval_shape re-trace of the iteration body.  Keys
    # include the specializing shapes, so a shared dict stays correct
    # when the underlying buffers are re-blocked (merge-compaction).
    info_shape_cache: dict = field(default_factory=dict, repr=False)

    def route_args(self) -> dict:
        """The ``hytm.run`` span's args: how FILTER's fold gets its blocks
        routed (``prebuilt`` once, or ``per_call`` on every visit), and
        the lanes a sweep visits per stored edge (P · block width / E)."""
        width = self.parts.block_size if self.route is None else self.route.width
        return {
            "route": "per_call" if self.route is None else "prebuilt",
            "padding": self.parts.n_partitions * width / max(self.csr.n_edges, 1),
        }


def build_runtime(
    g: CSRGraph, config: HyTMConfig, n_hubs: int = 0, weighted_norm: bool = False
) -> Runtime:
    table: PartitionTable = partition_graph(
        g, n_partitions=config.n_partitions,
        partition_bytes=config.partition_bytes, d1=config.link.d1,
    )
    block = int(table.edges_per_partition.max(initial=1))
    block = max(128, -(-block // 128) * 128)
    capacity = -(-(g.n_edges + block) // 128) * 128
    csr = to_device_csr(g, capacity=capacity)
    parts = to_device_partitions(table, g.n_nodes, capacity)
    assert parts.block_size <= block
    zc_req = zc_request_counts(csr.out_degree, csr.seg_start, config.link)
    if weighted_norm:
        # accumulative programs over weighted edges (PHP) push
        # delta * w_ij / sum_j w_ij — normalize by weighted out-degree so
        # total mass is non-expanding.
        wsum = jax.ops.segment_sum(
            jnp.where(csr.edge_valid, csr.edge_weight, 0.0),
            csr.edge_src, num_segments=g.n_nodes,
        )
        inv_deg = 1.0 / jnp.maximum(wsum, 1e-30)
    else:
        inv_deg = 1.0 / jnp.maximum(csr.out_degree.astype(jnp.float32), 1.0)
    n_hub_parts = int(np.searchsorted(np.asarray(table.vertex_start), n_hubs, side="left"))
    n_hub_parts = max(n_hub_parts, 1) if n_hubs > 0 else 0
    return Runtime(
        csr=csr, parts=parts, zc_req=zc_req, inv_deg=inv_deg,
        n_hub_partitions=n_hub_parts,
        route=route_partitions(g, table, parts.block_size),
    )


# --------------------------------------------------------------------------
# One iteration (jitted)
# --------------------------------------------------------------------------

def _slice_block(arr: jax.Array, start: jax.Array, size: int) -> jax.Array:
    return jax.lax.dynamic_slice_in_dim(arr, start, size)


def _sweep(
    state: HyTMState,
    rt: Runtime,
    program: VertexProgram,
    engines: jax.Array,       # (P,) — NONE entries are skipped
    order: jax.Array,         # (P,) processing order
    frontier: jax.Array,      # (n,) sources active for this sweep
    async_sweep: bool,
    consume: str,             # 'all' (pass 1: every partition is visited)
                              # | 'processed' (pass 2: only loaded ones)
    use_kernels: bool = False,
) -> tuple[HyTMState, jax.Array]:
    """Scan partitions in priority order; returns new state + activated."""
    n = rt.csr.n_nodes
    route = rt.route
    B = rt.parts.block_size if route is None else route.width
    values0, delta0 = state.values, state.delta

    def combine(out, values, delta, activated, p, processed):
        """The visit's n-wide update of values, Δ and ``activated``."""
        if program.peel_k is not None:
            # peeling (k-core): the aggregate is each destination's count
            # of newly-removed in-neighbors — its remaining degree drops
            # by that much.  Δ (the removed flag) is not consumed here;
            # removal updates happen once per iteration in
            # ``_iteration_impl``.  Counts are additive, so the async and
            # sync sweeps are identical.
            values = values - out.agg
            activated = activated | out.touched
        elif program.combine == MIN:
            improved = out.touched & (out.agg < values)
            values = jnp.where(improved, out.agg, values)
            activated = activated | improved
        else:
            # consumption (rank += delta) is vertex-local compute on
            # accelerator-resident vertex data — it happens for every
            # active vertex of the partition even when the partition has
            # no active *edges* to transfer (deg-0 vertices would
            # otherwise hold their delta forever and never converge).
            in_part = rt.parts.vertex_part_id == p
            if consume == "all":
                consumed = frontier & in_part
            else:  # pass 2 touches only the re-processed partitions
                consumed = frontier & in_part & processed
            # value absorbs the consumed delta; pending delta resets, then
            # accumulates fresh contributions from this partition's edges.
            if async_sweep:
                values = values + jnp.where(consumed, delta, 0.0)
                delta = jnp.where(consumed, 0.0, delta) + out.agg
            else:
                # synchronous dataflow: only the iteration-start delta0 is
                # consumed, so subtract exactly that — zeroing the running
                # delta would drop contributions already delivered by
                # earlier partitions (order-dependent mass loss).  This
                # makes the sync sweep partition-order invariant, which is
                # the single-device oracle the sharded sweep
                # (repro.dist.graph_shard) must match bit-for-bit.
                values = values + jnp.where(consumed, delta0, 0.0)
                delta = jnp.where(consumed, delta - delta0, delta) + out.agg
            activated = activated | out.touched
        return (values, delta, activated), None

    def body(carry, p):
        values, delta, activated = carry
        with scope(SWEEP_BLOCK):
            eng = engines[p]
            local = jnp.arange(B, dtype=jnp.int32)
            # either way the partition's own edges fill lanes [0, E_p)
            in_range = local < rt.parts.part_edges[p]
            if route is None:
                start = rt.parts.edge_start[p]
                src = _slice_block(rt.csr.edge_src, start, B)
                dst = _slice_block(rt.csr.edge_dst, start, B)
                w = _slice_block(rt.csr.edge_weight, start, B)
                first = last = None
            else:
                src, dst, w = route.src[p], route.dst[p], route.weight[p]
                first, last = route.first[p], route.last[p]
            processed = eng != NONE
            active_lane = frontier[src] & in_range & processed
            block = EdgeBlock(src=src, dst=dst, weight=w, active=active_lane,
                              first=first, last=last)

            if program.combine == SUM:
                dsrc = delta if async_sweep else delta0
                operand = program.damping * dsrc * rt.inv_deg
            else:
                operand = values if async_sweep else values0

        out = relax_with_engine(eng, block, operand, n, program, use_kernels)
        with scope(SWEEP_COMBINE):
            return combine(out, values, delta, activated, p, processed)

    init = (values0, delta0, jnp.zeros(n, dtype=bool))
    (values, delta, activated), _ = jax.lax.scan(body, init, order)
    return HyTMState(values=values, delta=delta, frontier=state.frontier), activated


def _iteration_impl(
    state: HyTMState,
    csr: DeviceCSR,
    parts: DevicePartitions,
    zc_req: jax.Array,
    inv_deg: jax.Array,
    program: VertexProgram,
    config: HyTMConfig,
    n_hub_partitions: int,
    correction: jax.Array | None = None,
    route: EdgeRoute | None = None,
) -> tuple[HyTMState, dict[str, Any]]:
    """Untraced single-iteration body.  ``hytm_iteration`` jits it as the
    public per-dispatch entry; ``hytm_chunk`` inlines it inside the
    chunked ``lax.while_loop`` so K iterations share one dispatch.
    ``route`` is ``Runtime.route``, an argument like the CSR (never a
    baked-in constant); None routes FILTER's block on every visit."""
    rt = Runtime(csr=csr, parts=parts, zc_req=zc_req, inv_deg=inv_deg,
                 n_hub_partitions=n_hub_partitions, route=route)
    n = csr.n_nodes
    frontier = state.frontier
    # trace-time resolution: config is static under jit, so the kernel
    # dispatch is a Python-level branch — no runtime cost either way
    use_kernels = resolve_use_kernels(config.use_kernels)

    with scope(SELECT):
        # (1-3) stats -> costs -> engines -> combined tasks
        stats = partition_stats(frontier, csr.out_degree, zc_req, parts)
        if config.forced_engine is None:
            plan: TaskPlan = generate_tasks(
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

        # (4) contribution-driven priority schedule.  Only the 'delta' CDS
        # mode reads the per-partition |Δ| mass, and min-combine programs
        # carry an identically-zero Δ — in both cases the (n,)->(P,)
        # segment-sum would reduce zeros (or feed a schedule that ignores
        # it), so skip it.
        if program.combine == MIN or config.cds_mode != "delta":
            delta_mass = jnp.zeros(parts.n_partitions, jnp.float32)
        else:
            delta_mass = jax.ops.segment_sum(
                jnp.abs(state.delta) * frontier, parts.vertex_part_id,
                num_segments=parts.n_partitions,
            )
        mode = config.cds_mode
        sched = make_schedule(
            plan.engines, delta_mass, n_hub_partitions, mode, config.recompute_once,
        )

    # (5) asynchronous sweep in priority order
    with scope(SWEEP):
        state1, activated = _sweep(
            state, rt, program, plan.engines, sched.order, frontier,
            config.async_sweep, consume="all", use_kernels=use_kernels,
        )

    # (6) recompute-once: loaded priority partitions, zero extra transfer.
    with scope(SWEEP):
        engines2 = jnp.where(sched.second_pass, plan.engines, NONE)
        if program.peel_k is not None:
            # peeling re-relaxation would re-subtract the same removal counts
            # (double-count); an empty frontier makes pass 2 a harmless no-op
            frontier2 = jnp.zeros_like(frontier)
        elif program.combine == MIN:
            frontier2 = frontier | activated
        else:
            # |Δ|: pending deltas are non-negative on a cold start, but the
            # incremental path (repro.stream) injects *signed* correction
            # deltas after edge deletions — negative mass must propagate too.
            frontier2 = jnp.abs(state1.delta) > program.tolerance
        state2, activated2 = _sweep(
            state1, rt, program, engines2, sched.order, frontier2,
            config.async_sweep, consume="processed", use_kernels=use_kernels,
        )
    with scope(UPDATE):
        # next frontier
        activated = activated | activated2
        if program.peel_k is not None:
            # removal update: alive vertices whose remaining degree fell
            # below k are removed now and become the next round's frontier
            alive = state2.delta < 0.5
            newly = alive & (state2.values < program.peel_k)
            next_frontier = newly
            new_state = HyTMState(
                values=state2.values,
                delta=state2.delta + newly.astype(jnp.float32),
                frontier=next_frontier,
            )
        else:
            if program.combine == MIN:
                next_frontier = activated
            else:
                next_frontier = jnp.abs(state2.delta) > program.tolerance
            new_state = HyTMState(values=state2.values, delta=state2.delta,
                                  frontier=next_frontier)

    with scope(SELECT):
        per_engine_time, mispredictions = selection_diagnostics(
            plan.engines, plan.transfer_time, stats, plan.costs, correction,
        )

    with scope(UPDATE):
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
        }
        return new_state, info


# Public per-dispatch entry: one jitted iteration (the K=1 driver and the
# vmapped service lanes dispatch through this).
hytm_iteration = partial(
    jax.jit, static_argnames=("program", "config", "n_hub_partitions"),
)(_iteration_impl)


# --------------------------------------------------------------------------
# Chunked device-resident driver
# --------------------------------------------------------------------------

@contextlib.contextmanager
def quiet_donation():
    """Scoped filter for jax's 'Some donated buffers were not usable'
    warning around a chunk dispatch: CPU backends cannot alias donated
    buffers, so on this container the donation (a device-side
    optimization — state/history update in place on GPU/TPU) would warn
    on every first dispatch.  Scoped, not global: other code's donation
    diagnostics stay visible."""
    with warnings.catch_warnings():
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable")
        yield


def chunked_while(iter_fn, state: HyTMState, history: dict, chunk: int):
    """The shared ``lax.while_loop`` skeleton of every chunked driver
    (``hytm_chunk``, ``graph_shard.make_sharded_chunk``): run up to
    ``chunk`` iterations of ``iter_fn(state) -> (state, info)``, writing
    iteration ``i``'s info rows into ``history[k][i]`` and accumulating
    the (3,) per-engine modeled seconds, with the early-exit condition on
    the *previous* iteration's ``next_active`` (sentinel 1: the first
    iteration of a chunk always runs, matching the K=1 loop, which runs
    one iteration even on an empty frontier).

    Returns ``(state, history, n_done, last_next_active,
    per_engine_sum)``.  ``per_engine_sum`` rides in the carry so the
    online calibrator can observe the chunk *before* the history drain —
    the measured wall window then covers dispatch + execution only.
    """
    def cond(carry):
        _state, _hist, i, prev_active, _pe = carry
        return (i < chunk) & (prev_active != 0)

    def body(carry):
        st, hist, i, _prev, pe = carry
        new_st, info = iter_fn(st)
        with scope(UPDATE):
            hist = {k: hist[k].at[i].set(info[k]) for k in hist}
            return (new_st, hist, i + 1, info["next_active"],
                    pe + info["per_engine_time"])

    init = (state, history, jnp.int32(0), jnp.int32(1),
            jnp.zeros(3, jnp.float32))
    state, history, n_done, last_active, pe_sum = jax.lax.while_loop(
        cond, body, init)
    return state, history, n_done, last_active, pe_sum


@partial(
    jax.jit,
    static_argnames=("program", "config", "n_hub_partitions", "chunk"),
    donate_argnames=("state", "history"),
)
def hytm_chunk(
    state: HyTMState,
    history: dict[str, jax.Array],   # key -> (chunk, ...) preallocated
    csr: DeviceCSR,
    parts: DevicePartitions,
    zc_req: jax.Array,
    inv_deg: jax.Array,
    program: VertexProgram,
    config: HyTMConfig,
    n_hub_partitions: int,
    chunk: int,
    correction: jax.Array | None = None,
    route: EdgeRoute | None = None,
) -> tuple[HyTMState, dict[str, jax.Array], jax.Array, jax.Array, jax.Array]:
    """Run up to ``chunk`` iterations inside one ``lax.while_loop``.

    Contract (the chunk/early-exit contract the chunked drivers share):

    * the loop body is exactly ``_iteration_impl`` — chunking changes how
      many iterations share a dispatch, never what an iteration computes;
    * the while-condition tests the *previous* iteration's
      ``next_active``, so the loop stops immediately after the converging
      iteration — a converged run never executes an iteration past
      convergence, and the iteration count is identical to the K=1 loop;
    * iteration ``i``'s info rows land in ``history[k][i]``; rows at
      index >= the returned ``n_done`` are stale garbage (possibly from a
      previous chunk through the same donated buffer) and must be sliced
      off when draining;
    * ``state`` and ``history`` are donated: on accelerators the
      values/Δ/frontier and history buffers update in place across
      chunks.  Callers must drain (``jax.device_get``) a returned history
      before feeding it back to the next chunk, which invalidates it.

    Returns ``(state, history, n_done, last_next_active,
    per_engine_sum)``; the host reads the scalars (one sync per chunk) to
    decide whether to dispatch another chunk and to feed the calibrator.
    """
    return chunked_while(
        lambda st: _iteration_impl(
            st, csr, parts, zc_req, inv_deg, program, config,
            n_hub_partitions, correction, route,
        ),
        state, history, chunk,
    )


@partial(
    jax.jit,
    static_argnames=("program", "config", "n_hub_partitions", "chunk"),
    donate_argnames=("state",),
)
def hytm_batched_chunk(
    state: HyTMState,        # (Q, n) lane-stacked
    csr: DeviceCSR,
    parts: DevicePartitions,
    zc_req: jax.Array,
    inv_deg: jax.Array,
    program: VertexProgram,
    config: HyTMConfig,
    n_hub_partitions: int,
    chunk: int,
    correction: jax.Array | None = None,
    route: EdgeRoute | None = None,
) -> tuple[HyTMState, jax.Array, jax.Array, jax.Array, jax.Array]:
    """Chunked *lane-batched* sweep: up to ``chunk`` vmapped iterations of
    ``_iteration_impl`` inside one ``lax.while_loop`` dispatch, over a
    state whose leading dimension stacks Q independent source lanes.

    This is the dispatch unit of the serving stack (``repro.serve``):
    the carry holds the **per-lane** ``next_active`` vector, so the chunk
    returns ``lane_active`` — a ``(Q,)`` count of each lane's frontier
    population after its last executed iteration — instead of collapsing
    it to a batch total.  A lane whose entry is 0 has converged (its
    values are already its fixpoint; further iterations are no-ops for
    it), which is exactly the signal the continuous scheduler uses to
    free the lane's slot at the chunk boundary and backfill it from the
    request queue.  The while-condition sums the vector, preserving the
    chunk/early-exit contract of ``hytm_chunk``: the batch runs while any
    lane is still active, and stops the moment every frontier drains.

    Lanes never interact — ``jax.vmap`` evaluates the cost model, engine
    selection, schedule, and sweep per lane — so each lane's trajectory
    is bit-identical to its standalone ``run_hytm`` run for min-combine
    programs (tolerance-bounded for sum-combine), whatever the other
    lanes (including dead, all-``False``-frontier padding lanes) are
    doing.  The loop carries running reductions instead of history:
    summed per-engine modeled seconds and mispredictions, the
    calibrator's chunk-granular observation inputs.

    Returns ``(state, n_done, lane_active, per_engine_sum,
    mispred_sum)``.
    """
    def one(s):
        # the route is closed over, not vmapped: one copy for all lanes
        return _iteration_impl(
            s, csr, parts, zc_req, inv_deg, program, config,
            n_hub_partitions, correction, route,
        )

    def cond(carry):
        _s, i, lane_active, _pe, _mp = carry
        return (i < chunk) & (jnp.sum(lane_active) != 0)

    def body(carry):
        s, i, _prev, pe, mp = carry
        s2, info = jax.vmap(one)(s)
        return (
            s2,
            i + 1,
            info["next_active"],
            pe + jnp.sum(info["per_engine_time"], axis=0),
            mp + jnp.sum(info["mispredictions"]),
        )

    n_lanes = state.values.shape[0]
    # sentinel ones: the first iteration always runs, matching the K=1
    # loop (which runs one iteration even on an empty frontier)
    init = (state, jnp.int32(0), jnp.ones(n_lanes, jnp.int32),
            jnp.zeros(3, jnp.float32), jnp.int32(0))
    state, n_done, lane_active, pe_sum, mp_sum = jax.lax.while_loop(
        cond, body, init)
    return state, n_done, lane_active, pe_sum, mp_sum


def dead_lane_state(program: VertexProgram, n: int) -> tuple:
    """The (values, delta, frontier) triple of a *dead* padding lane: an
    all-``False`` frontier and zero pending Δ, so every iteration is a
    no-op for it — zero active edges, all engines NONE, no consumption,
    and a ``next_active`` of 0 from the first chunk on.  Used to pad a
    partial request batch up to the next static lane bucket
    (``repro.serve.scheduler``) so admission never changes the traced
    lane count."""
    return (
        jnp.zeros(n, jnp.float32) if program.use_delta
        else jnp.full(n, jnp.inf, jnp.float32),
        jnp.zeros(n, jnp.float32),
        jnp.zeros(n, dtype=bool),
    )


@contextlib.contextmanager
def count_driver_dispatches():
    """Count convergence-driver dispatches by swapping the module-global
    entry points (``run_hytm`` resolves both at call time, so the swap
    sees every dispatch).  Yields a live ``{"iteration": n, "chunk": n}``
    dict — the regression seam ``tests/test_chunked.py`` and
    ``benchmarks/iterloop.py --selfcheck`` share to prove the chunked
    loop really batches (chunk dispatches ≤ iterations/K + 1)."""
    mod = __import__("repro.core.hytm", fromlist=["hytm"])
    counts = {"iteration": 0, "chunk": 0}
    orig_iter, orig_chunk = mod.hytm_iteration, mod.hytm_chunk

    def count_iter(*a, **kw):
        counts["iteration"] += 1
        return orig_iter(*a, **kw)

    def count_chunk(*a, **kw):
        counts["chunk"] += 1
        return orig_chunk(*a, **kw)

    # a first dispatch registers the program it ran (obs.scopes), which
    # has to lower like the real one
    count_iter.lower, count_chunk.lower = orig_iter.lower, orig_chunk.lower
    mod.hytm_iteration, mod.hytm_chunk = count_iter, count_chunk
    try:
        yield counts
    finally:
        mod.hytm_iteration, mod.hytm_chunk = orig_iter, orig_chunk


# the recorder track of the single-device driver's events
_TRACK = "device0"

# Host-side registry of dispatch signatures that have already compiled:
# the first dispatch of a given (shapes, program, config) signature pays
# trace+compile, so its wall time must not feed the online calibrator.
# Mirrors the jit cache closely enough (module-level jits persist for the
# process lifetime) without reaching into private jax state.
_WARM_SIGNATURES: set = set()


def _consume_warm(signature, registry: set | None = None) -> bool:
    """True if ``signature`` was already dispatched (compiled) in this
    process; marks it warm either way.  ``registry`` overrides the
    module-level set — callers whose compiled function does NOT live for
    the process lifetime (the sharded drivers: a DeltaCSR
    merge-compaction rebuilds the jitted chunk with a fresh compile
    cache) scope the warm signatures to the function's own lifetime, so
    a rebuilt function's first dispatch is correctly cold even when its
    shapes were seen before."""
    reg = _WARM_SIGNATURES if registry is None else registry
    warm = signature in reg
    reg.add(signature)
    return warm


# --------------------------------------------------------------------------
# Convergence loop
# --------------------------------------------------------------------------

@dataclass
class HyTMResult:
    values: np.ndarray
    delta: np.ndarray
    iterations: int
    wall_seconds: float
    modeled_seconds: float
    total_transfer_bytes: float
    history: dict[str, np.ndarray]  # per-iteration arrays
    # second transfer-management level (sharded sweep only): modeled
    # cross-device merge traffic over config.ici_link.  Zero on the
    # single-device path.
    total_ici_bytes: float = 0.0
    modeled_ici_seconds: float = 0.0
    # autotune diagnostics: partitions where Algorithm 1 diverged from the
    # (corrected) modeled-best engine, summed over iterations, and the
    # final per-engine correction vector (None without config.autotune).
    total_mispredictions: int = 0
    engine_corrections: np.ndarray | None = None


def run_hytm(
    g: CSRGraph,
    program: VertexProgram,
    source: int | None = 0,
    config: HyTMConfig = HyTMConfig(),
    n_hubs: int = 0,
    runtime: Runtime | None = None,
    mesh=None,
    initial_state: HyTMState | None = None,
    calibrator=None,
    obs=None,
    faults=None,
    retry=None,
    on_chunk=None,
) -> HyTMResult:
    """``runtime`` lets callers amortize preprocessing across runs; with
    ``config.mesh_axis`` set it must be a ``graph_shard.ShardedRuntime``
    (reuse also keeps the compiled sharded sweep warm).

    ``config.vertex_sharding`` selects the sharded path's vertex-state
    layout: ``"replicated"`` (default, full ``(n,)`` triple per device,
    byte-identical to previous behavior) or ``"owner"`` (each device
    holds only its ``ceil(n/D)`` owned slice; boundary contributions are
    exchanged per iteration, charged on the ICI track via the halo-aware
    cost model).  Results, iteration counts, transfer bytes, and engine
    picks are identical between the two layouts — bit-identical for
    min-combine programs, tolerance-bounded for sum-combine.  Ignored on
    the single-device path.

    ``initial_state`` warm-starts the convergence loop from an arbitrary
    (values, Δ, frontier) triple instead of ``program.init_state`` — the
    entry point of the incremental path (repro.stream.incremental).  With
    both ``runtime`` and ``initial_state`` given, ``g`` may be ``None``.
    With ``config.sync_every > 1`` the state is *donated* to the chunked
    driver (``hytm_chunk``): on accelerator backends the caller's
    ``initial_state`` buffers are invalidated by the first chunk — pass a
    copy if they must survive the run.  Warm-start composes with
    ``config.mesh_axis``: the sharded driver replicates the triple over
    the mesh and resumes the shard_mapped chunk from it, bit-identical to
    the single-device ``async_sweep=False`` warm run for min-combine
    programs (``run_hytm_sharded``).

    ``calibrator``: an external ``repro.autotune.OnlineCalibrator`` to
    learn into (and start from) instead of a fresh per-run one — how
    ``GraphService`` keeps one feedback loop across queries.  Only read
    when ``config.autotune`` is set.

    ``obs``: an optional ``repro.obs.TraceRecorder``.  A single-device
    run always opens the live spans ``hytm.run`` > ``hytm.init``,
    ``chunk`` > ``hytm.dispatch`` (``hytm.compile`` when the program is
    new) / ``hytm.wait`` / ``hytm.drain``, and ``hytm.result`` as
    profiler annotations (inert without a profile); ``hytm.run`` carries
    ``Runtime.route_args()`` (a runtime it has to build is built before
    it opens).  A recorder records
    them too, plus per-iteration events from the drained history rows
    and one run-summary span whose totals equal the returned
    ``HyTMResult`` fields exactly.  ``obs=None`` (the default) records
    nothing and runs the identical jit programs — the traced and
    untraced paths are bit-identical.  A program's first dispatch
    registers its abstract signature with ``repro.obs.scopes``.

    ``faults``/``retry``: an optional ``repro.resilience.FaultPlan`` and
    ``RetryPolicy``.  Injected chunk-dispatch faults (site
    ``"chunk_dispatch"``) fire *before* the jit dispatch — donated
    buffers are still intact, so a retried dispatch is bit-identical.
    ``faults=None`` (the default) takes the unhooked code path exactly,
    mirroring the ``obs=None`` zero-overhead contract.

    ``on_chunk``: called at every chunk boundary (after the history
    drain, before the convergence check) with ``state`` (live device
    state), ``iterations``, ``rows`` (drained host history so far),
    ``calibrator``, and ``last_active`` — the attachment point for
    ``repro.resilience.CheckpointHook``.  Chunked driver only
    (``sync_every > 1``).
    """
    if config.mesh_axis is not None:
        # late import: graph_shard depends on this module's dataclasses
        from repro.dist.graph_shard import run_hytm_sharded

        return run_hytm_sharded(
            g, program, source=source, config=config, n_hubs=n_hubs,
            mesh=mesh, runtime=runtime, calibrator=calibrator,
            initial_state=initial_state, obs=obs, faults=faults,
            retry=retry, on_chunk=on_chunk,
        )
    if g is None and runtime is None:
        raise ValueError("run_hytm needs a graph or a prebuilt runtime")
    # raised (not asserted): under ``python -O`` an assert vanishes and a
    # zero/negative chunk size would silently run the wrong driver
    if config.sync_every < 1:
        raise ValueError(f"sync_every must be >= 1, got {config.sync_every}")
    if on_chunk is not None and config.sync_every == 1:
        raise ValueError(
            "on_chunk (checkpointing) requires the chunked driver — "
            "set sync_every >= 2")
    if runtime is None:
        if program.symmetrize:
            # WCC-family programs are defined on the underlying undirected
            # graph; a prebuilt runtime is assumed already symmetrized
            g = g.symmetrize()
        runtime = build_runtime(
            g, config, n_hubs=n_hubs,
            weighted_norm=program.use_delta and program.weighted,
        )
    with span("hytm.run", obs, track=_TRACK, **runtime.route_args()):
        return _run_single_device(
            program, source, config, runtime, initial_state,
            calibrator, obs, faults, retry, on_chunk)


def _run_single_device(program, source, config, rt, initial_state,
                       calibrator, obs, faults, retry, on_chunk) -> HyTMResult:
    """``run_hytm`` on one device over a built runtime, in the host spans
    ``hytm.init``, ``hytm.dispatch`` (``hytm.compile`` for a program's
    first dispatch), ``hytm.wait``, ``hytm.drain`` and ``hytm.result``."""
    # late imports: both modules import this one
    from repro.obs.record import record_history_rows, record_run
    if faults is not None:
        from repro.resilience.supervisor import guarded_dispatch

    with span("hytm.init", obs, track=_TRACK):
        if initial_state is None:
            if program.peel_k is not None:
                # peeling seeds from the runtime's (symmetrized)
                # out-degrees, which init_state cannot see: values =
                # remaining degree, Δ = removed flag, frontier = the
                # initially-removed set
                deg = rt.csr.out_degree.astype(jnp.float32)
                removed = deg < program.peel_k
                state = HyTMState(values=deg,
                                  delta=removed.astype(jnp.float32),
                                  frontier=removed)
            else:
                values, delta, frontier = program.init_state(
                    rt.csr.n_nodes, source)
                state = HyTMState(values=values, delta=delta,
                                  frontier=frontier)
        else:
            state = initial_state

        calib = None
        correction = None
        if config.autotune:
            from repro.autotune.feedback import OnlineCalibrator

            calib = (calibrator if calibrator is not None
                     else OnlineCalibrator(decay=config.autotune_decay))
            # start from the calibrator's current knowledge (identity when
            # fresh); always an array so the iteration traces once, not
            # twice (None -> array would retrace on iteration 2)
            correction = jnp.asarray(calib.correction(), jnp.float32)

        rows: dict[str, list] = {k: [] for k in HISTORY_KEYS}
        # the warm signature mirrors the jit cache key: statics + every
        # shape the trace specializes on (node/edge capacity, partition
        # grid) — a dispatch not seen here compiles, and its wall time
        # must not feed the calibrator
        shapes = (program, config, rt.n_hub_partitions, rt.csr.n_nodes,
                  rt.csr.edge_src.shape[0], rt.parts.n_partitions,
                  rt.parts.block_size,
                  None if rt.route is None else rt.route.width)
        if config.sync_every > 1:
            info_shapes = rt.info_shape_cache.get(shapes)
            if info_shapes is None:
                info_shapes = jax.eval_shape(
                    lambda s: _iteration_impl(
                        s, rt.csr, rt.parts, rt.zc_req, rt.inv_deg, program,
                        config, rt.n_hub_partitions, correction, rt.route,
                    ),
                    state,
                )[1]
                rt.info_shape_cache[shapes] = info_shapes
            cur_chunk = min(config.sync_every, config.max_iters)
            history = init_history_buffers(info_shapes, cur_chunk)
    t0 = time.monotonic()
    iters = 0
    if config.sync_every > 1:
        # Chunked device-resident driver: one hytm_chunk dispatch per K
        # iterations, one host sync per chunk (n_done + history drain).
        while iters < config.max_iters:
            chunk = min(config.sync_every, config.max_iters - iters)
            if chunk != cur_chunk:
                # the rare max_iters tail; otherwise the drained buffers
                # cycle back in, so on accelerators the donated memory is
                # reused across chunks
                history = init_history_buffers(info_shapes, chunk)
                cur_chunk = chunk
            signature = ("chunk", *shapes, chunk, correction is not None)
            warm = _consume_warm(signature)

            # injected faults fire BEFORE the dispatch (see
            # resilience.supervisor) so the donated buffers of the
            # previous chunk are intact and a retry is bit-identical
            def attempt(st=state, h=history, corr=correction):
                with quiet_donation():
                    return hytm_chunk(
                        st, h, rt.csr, rt.parts, rt.zc_req, rt.inv_deg,
                        program, config, rt.n_hub_partitions, chunk, corr,
                        rt.route,
                    )

            with span("chunk", obs, cat=CAT_RUN, track=_TRACK,
                      vt=float(iters)) as chunk_span:
                t_chunk = time.monotonic()
                with span("hytm.dispatch" if warm else "hytm.compile", obs,
                          track=_TRACK):
                    if not warm:
                        scopes.register(
                            signature, hytm_chunk, state, history, rt.csr,
                            rt.parts, rt.zc_req, rt.inv_deg, program, config,
                            rt.n_hub_partitions, chunk, correction, rt.route)
                    state, history, n_done, last_active, pe_sum = (
                        attempt() if faults is None else guarded_dispatch(
                            attempt, site="chunk_dispatch", faults=faults,
                            policy=retry, obs=obs, mesh=False,
                            kernels=resolve_use_kernels(config.use_kernels),
                        ))
                with span("hytm.wait", obs, track=_TRACK):
                    n_done, last_active = int(n_done), int(last_active)
                iters += n_done
                if calib is not None:
                    # observe BEFORE the history drain so the measured
                    # wall window covers dispatch + execution only
                    correction = calib.observe_chunk(
                        state.values, np.asarray(pe_sum, dtype=float),
                        t_chunk,
                        skip=not warm,  # a compiling chunk measures compile
                    )
                # drain before the next dispatch donates these buffers;
                # rows past n_done are stale (early exit) and sliced off
                with span("hytm.drain", obs, track=_TRACK):
                    drained = jax.device_get(history)
                    for k in rows:
                        rows[k].append(drained[k][:n_done])
                    if obs is not None:
                        record_history_rows(obs, drained, n_done,
                                            iters - n_done)
                        chunk_span.vt_dur = float(n_done)
                        chunk_span.args.update(n_done=n_done, warm=warm)
            if on_chunk is not None:
                # chunk boundary: the drained rows are on host and the
                # next dispatch has not donated the state yet — the one
                # point a checkpoint can capture a resumable snapshot
                on_chunk(state=state, iterations=iters, rows=rows,
                         calibrator=calib, last_active=last_active)
            if last_active == 0:
                break
        history = {k: np.concatenate(v) for k, v in rows.items()}
    else:
        # Legacy per-iteration driver (sync_every == 1): bit-for-bit the
        # pre-chunk dataflow.  History is staged as device references and
        # pulled once after convergence — the only per-iteration sync
        # left is the loop condition itself.
        for _ in range(config.max_iters):
            signature = ("iteration", *shapes, correction is not None)
            warm = _consume_warm(signature)
            t_iter = time.monotonic()

            def attempt(st=state, corr=correction):
                return hytm_iteration(
                    st, rt.csr, rt.parts, rt.zc_req, rt.inv_deg,
                    program, config, rt.n_hub_partitions, corr, rt.route,
                )

            with span("hytm.dispatch" if warm else "hytm.compile", obs,
                      track=_TRACK):
                if not warm:
                    scopes.register(
                        signature, hytm_iteration, state, rt.csr, rt.parts,
                        rt.zc_req, rt.inv_deg, program, config,
                        rt.n_hub_partitions, correction, rt.route)
                state, info = attempt() if faults is None else guarded_dispatch(
                    attempt, site="chunk_dispatch", faults=faults,
                    policy=retry, obs=obs, mesh=False,
                    kernels=resolve_use_kernels(config.use_kernels),
                )
            iters += 1
            if calib is not None:
                correction = calib.observe_iteration(
                    state.values, info["per_engine_time"], t_iter,
                    skip=iters == 1,  # iteration 1 measures compile
                )
            for k in rows:
                rows[k].append(info[k])
            with span("hytm.wait", obs, track=_TRACK):
                next_active = int(info["next_active"])
            if next_active == 0:
                break
        with span("hytm.drain", obs, track=_TRACK):
            staged = jax.device_get(rows)  # one host conversion, post-hoc
            history = {k: np.stack(v) for k, v in staged.items()}
            if obs is not None:
                record_history_rows(obs, history, iters, 0)
    with span("hytm.result", obs, track=_TRACK):
        jax.block_until_ready(state.values)
        wall = time.monotonic() - t0
        result = HyTMResult(
            values=np.asarray(state.values),
            delta=np.asarray(state.delta),
            iterations=iters,
            wall_seconds=wall,
            modeled_seconds=float(np.sum(history[KEY_TRANSFER_TIME])),
            total_transfer_bytes=float(np.sum(history[KEY_TRANSFER_BYTES])),
            history=history,
            total_mispredictions=int(np.sum(history[KEY_MISPREDICTIONS])),
            engine_corrections=(
                calib.correction() if calib is not None else None
            ),
        )
        if obs is not None:
            record_run(
                obs, result, track=_TRACK, wall_start=obs.wall_at(t0),
                wall_dur=wall, program=program.name,
            )
    return result
