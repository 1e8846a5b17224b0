"""Batched graph-query serving over a live ``DeltaCSR``.

``GraphService`` multiplexes concurrent vertex queries (SSSP / BFS / CC /
Δ-PR / Δ-PPR) over one graph container:

* **source-lane batching** — pending single-source queries run through
  the continuous lane scheduler (``repro.serve.scheduler``): sources
  stack into a (Q, n) state padded to a *static lane bucket* and sweep
  through ``core.hytm.hytm_batched_chunk`` under ``jax.vmap``.  Each
  lane carries its own frontier, so the cost model, engine selection,
  and priority schedule are evaluated *per lane*, making every lane's
  dataflow identical to its standalone run (bit-exact for MIN programs).
  Converged lanes free their slot at chunk boundaries and the scheduler
  backfills them from the pending queue mid-flight — the device never
  waits for the straggler before starting the next source;
* **tiered result cache** — converged (values, Δ) keyed by
  ``(program, source)`` in a two-tier warm cache
  (``repro.serve.warm_cache``): a device tier bounded by
  ``device_budget_bytes`` (LRU) spilling to a host-RAM tier.  A repeat
  query at the same version is a pure hit: zero sweep iterations.  An
  update batch invalidates direct hits (the version key moves on) but
  the stale entry is retained as the *warm state* for incremental
  recomputation (repro.stream.incremental) against the reports applied
  since — promoted back to the device tier first if it was spilled;
* **updates** — ``update(batch)`` applies an ``EdgeBatch`` through the
  container (device buffers patched in place) and logs the report for
  later warm-starts (bounded by ``max_reports``: overflow evicts the
  cache entries too stale to replay the retained suffix);
* **mesh serving** — with ``HyTMConfig.mesh_axis`` set, lane sweeps run
  the vmapped sharded chunk over the container's device-sharded
  (P_pad, B) edge grid and incremental recomputes warm-start the
  shard_mapped driver; every lane / warm run stays bit-identical to its
  single-device ``async_sweep=False`` counterpart for MIN programs.

Accumulative programs (``use_delta``) are global — their cache key uses
``source=None`` whatever the caller passed — *except* personalized ones
(Δ-PPR), which key per source and multiplex into the lane sweep like
traversals.

With ``HyTMConfig.autotune`` the service carries one
``repro.autotune.OnlineCalibrator`` for its whole lifetime: every
multiplexed lane sweep contributes a measured-vs-modeled observation,
and the resulting per-engine correction biases each lane's engine
selection (and hence the priority schedule) on subsequent iterations and
queries.  ``stats.extra`` reports the live correction vector, the
accumulated misprediction count, and the warm-cache tier counters.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Sequence

import jax.numpy as jnp
import numpy as np

from repro.core.cost_model import (
    KEY_ENGINE_CORRECTIONS,
    KEY_MISPREDICTIONS,
    KEY_WARM_CACHE,
)
from repro.core.hytm import HyTMConfig, run_hytm
from repro.graph.algorithms import VertexProgram
from repro.graph.csr import CSRGraph
from repro.serve.scheduler import LaneScheduler
from repro.serve.warm_cache import OwnerPlacement, TierPolicy, WarmCache
from repro.stream.delta_csr import DeltaCSR, EdgeBatch, UpdateReport
from repro.stream.incremental import run_incremental


@dataclass
class QueryResult:
    source: int | None
    values: np.ndarray
    iterations: int        # sweep iterations this query paid for
    cache_hit: bool
    mode: str              # 'cache' | 'incremental' | 'batched'


@dataclass
class ServiceStats:
    n_queries: int = 0
    n_cache_hits: int = 0
    n_incremental: int = 0
    n_full: int = 0
    n_updates: int = 0
    sweep_iterations: int = 0
    update_edges: int = 0
    extra: dict = field(default_factory=dict)


class GraphService:
    def __init__(
        self,
        graph: CSRGraph,
        config: HyTMConfig | None = None,
        max_lanes: int = 8,
        incremental: bool = True,
        max_reports: int = 256,
        mesh=None,
        device_budget_bytes: int | None = None,
        lane_buckets: Sequence[int] | None = None,
        obs=None,
        faults=None,
        supervisor=None,
        **delta_kw,
    ):
        # optional repro.obs.TraceRecorder threaded through every consumer
        # the service owns: lane sweeps (scheduler), warm-cache tier
        # transitions, calibrator correction updates, and the
        # run_hytm/run_incremental dispatches.  obs=None (default) records
        # nothing anywhere — the untraced service is bit-identical.
        self.obs = obs
        # optional repro.resilience hooks, threaded the same way: a
        # FaultPlan reaches the warm cache (spill corruption, promote
        # OOM), the scheduler (lane alloc/dispatch), and the engine
        # dispatches; a Supervisor supplies the retry policy and the
        # load-shed rung.  Both None (default) = zero overhead, the
        # exact PR-8 code path.
        self.faults = faults
        self.supervisor = supervisor
        self.config = config if config is not None else HyTMConfig()
        self.dcsr = DeltaCSR(graph, self.config, **delta_kw)
        # With config.mesh_axis set, the service serves *from the mesh*:
        # lane sweeps run the vmapped sharded chunk
        # (graph_shard.make_sharded_batched_chunk) over the container's
        # device-sharded (P_pad, B) grid, and incremental recomputes
        # warm-start the shard_mapped driver — each lane / warm run
        # bit-identical to its single-device async_sweep=False
        # counterpart for MIN programs.
        self.mesh = None
        if self.config.mesh_axis is not None:
            if mesh is None:
                from repro.launch.mesh import make_graph_mesh

                mesh = make_graph_mesh(axis=self.config.mesh_axis)
            self.mesh = mesh
        self.max_lanes = max_lanes
        self.incremental = incremental
        # upper bound on retained UpdateReports: a stale cache entry that
        # is never re-queried would otherwise pin the prune floor and let
        # report memory grow without limit (one abandoned entry = every
        # later report retained forever).  Overflow drops the oldest
        # reports and evicts the cache entries that would have needed
        # them (their next query falls back to a full recompute).
        self.max_reports = max_reports
        # keyed by the (frozen, hashable) program itself, not its name:
        # variants like dataclasses.replace(PAGERANK, tolerance=1e-8)
        # must not collide with each other's converged results.  The
        # tier policy makes the old flat ``max_reports`` bound explicit
        # and adds the device-tier LRU byte budget (warm_cache docstring).
        # owner-sharded serving holds cache entries (and counts the byte
        # budget) at owned-slice granularity — see warm_cache.OwnerPlacement
        placement = None
        if self.mesh is not None and self.config.vertex_sharding == "owner":
            placement = OwnerPlacement(
                self.mesh, self.config.mesh_axis, graph.n_nodes)
        self.cache = WarmCache(TierPolicy(
            device_budget_bytes=device_budget_bytes,
            max_reports=max_reports,
        ), obs=obs, faults=faults, placement=placement)
        self._cache = self.cache  # dict-like; historical alias
        self._reports: list[UpdateReport] = []
        self.stats = ServiceStats()
        # online feedback (repro.autotune): one calibrator for the whole
        # service lifetime — measured lane-sweep times keep correcting the
        # per-engine selection costs across queries and update batches
        self._calibrator = None
        self._correction = None
        if self.config.autotune:
            from repro.autotune.feedback import OnlineCalibrator

            self._calibrator = OnlineCalibrator(
                decay=self.config.autotune_decay, obs=obs)
        # the continuous lane scheduler owns every multiplexed sweep
        # (degenerate single-tenant mode here; multi-tenant closed-loop
        # serving drives LaneScheduler.pump directly — serve_bench)
        self.scheduler = LaneScheduler(
            self, buckets=tuple(lane_buckets) if lane_buckets else None,
            supervisor=supervisor)

    # ----------------------------------------------------------------- update
    @property
    def version(self) -> int:
        return self.dcsr.version

    def update(self, batch: EdgeBatch, batch_id=None,
               faults=None) -> UpdateReport:
        """Apply an edge-update batch.  All cached results become stale for
        direct hits (version bump) and turn into warm states.

        ``batch_id`` opts into exactly-once delivery: a redelivered id
        returns the original report without re-applying (no version
        bump, no duplicate report in the log) — the dedup contract
        ``resilience.supervisor.deliver_update`` relies on.  ``faults``
        forwards to ``DeltaCSR.apply`` (injected delivery drops)."""
        v0 = self.dcsr.version
        rep = self.dcsr.apply(batch, batch_id=batch_id, faults=faults)
        if self.dcsr.version == v0:
            # deduplicated redelivery: the container returned the cached
            # report without applying — keep the log and stats exact
            return rep
        self._reports.append(rep)
        self._prune_reports()
        self.stats.n_updates += 1
        self.stats.update_edges += len(batch)
        return rep

    def _prune_reports(self) -> None:
        """Drop reports no warm state can need: every cached entry only
        ever replays reports *newer* than its own version, so anything at
        or below the oldest cached version (or everything, with no cache
        or incremental disabled) is dead weight.

        Age bound (``TierPolicy.max_reports``): a stale entry that is
        never re-queried pins the floor forever, so past the bound the
        oldest overflow reports are dropped *and* every cache entry too
        old to replay the retained suffix is evicted — correctness
        first: an entry must never warm-start against a gappy report
        list, so eviction forces its next query onto the full-recompute
        path.  This applies to *both* tiers: a host-spilled entry is as
        replayable as a device one right up until its reports drop."""
        if not self.incremental or not len(self._cache):
            self._reports.clear()
            return
        floor = min(e.version for e in self._cache.values())
        self._reports = [r for r in self._reports if r.version > floor]
        if len(self._reports) > self.max_reports:
            # explicit drop count, not a [-max:] slice — max_reports=0
            # (retain nothing) must really drop everything
            drop = len(self._reports) - self.max_reports
            self._reports = self._reports[drop:]
            # versions are consecutive (one report per apply): an entry
            # at version v needs every report with version > v, so it
            # survives only if v >= retained_first - 1
            min_replayable = (self._reports[0].version - 1
                              if self._reports else self.version)
            for k in [k for k, e in self._cache.items()
                      if e.version < min_replayable]:
                del self._cache[k]

    def _reports_since(self, version: int) -> list[UpdateReport]:
        return [r for r in self._reports if r.version > version]

    # ------------------------------------------------------------------ query
    def key_source(self, program: VertexProgram, s: int | None) -> int | None:
        """Cache-key source: global accumulative programs — and peeling
        programs (k-core), which have no source at all — collapse to
        ``None`` (one answer per graph version); traversals and
        personalized accumulative programs (Δ-PPR) key per source."""
        if program.peel_k is not None:
            return None
        if program.use_delta and not program.personalized:
            return None
        return s

    def query(
        self, program: VertexProgram, sources: Sequence[int | None] | int | None
    ) -> list[QueryResult]:
        """Answer a batch of queries; one ``QueryResult`` per requested
        source, in order.  Duplicate sources share one computation."""
        if sources is None or isinstance(sources, int):
            sources = [sources]
        keyed = [self.key_source(program, s) for s in sources]
        results: dict[int | None, QueryResult] = {}
        fresh: list[int | None] = []
        for s in dict.fromkeys(keyed):  # dedupe, keep order
            entry = self.cache.check((program, s))
            if entry is not None and entry.version == self.version:
                results[s] = QueryResult(
                    source=s, values=entry.host_values(), iterations=0,
                    cache_hit=True, mode="cache",
                )
                self.stats.n_cache_hits += 1
            elif entry is not None and self.incremental:
                results[s] = self._query_incremental(program, s)
            else:
                fresh.append(s)
        if fresh:
            results.update(self._query_fresh(program, fresh))
        self.stats.n_queries += len(sources)
        self.stats.extra[KEY_WARM_CACHE] = self.cache.stats.as_dict()
        return [results[k] for k in keyed]

    def _store(self, program, s, values, delta) -> None:
        self.cache.put(
            (program, s), self.version, values, delta,
            reserved_bytes=self.scheduler.pinned_bytes,
        )
        self._prune_reports()  # refreshed entries may raise the floor

    def _record_feedback(self, mispredictions, correction=None) -> None:
        """Single bookkeeping point for every feedback source (lane
        sweeps, incremental runs, full accumulative runs): refresh the
        cached correction and accumulate the misprediction count into
        ``stats.extra``.  ``correction`` skips re-solving when the caller
        already holds the refreshed vector (``OnlineCalibrator.observe``'s
        return)."""
        if self._calibrator is None:
            return
        if correction is None:
            correction = jnp.asarray(
                self._calibrator.correction(), jnp.float32)
        self._correction = correction
        self.stats.extra[KEY_ENGINE_CORRECTIONS] = (
            np.asarray(self._correction).tolist())
        self.stats.extra[KEY_MISPREDICTIONS] = (
            self.stats.extra.get(KEY_MISPREDICTIONS, 0) + int(mispredictions))

    def _absorb_run(self, res) -> None:
        self._record_feedback(res.total_mispredictions)

    def _query_incremental(self, program, s) -> QueryResult:
        # spilled warm states come back through the device tier first
        # (bit-exact round trip — warm_cache.promote), then replay the
        # reports applied since their version.  promote() returns None
        # when the entry failed its integrity checksum (corrupt spill —
        # detected, counted, evicted) or an injected promote OOM refused
        # the transfer: degrade to the full-recompute path rather than
        # warm-start from garbage.
        entry = self.cache.promote((program, s))
        if entry is None:
            return self._query_fresh(program, [s])[s]
        res = run_incremental(
            self.dcsr, program, self._reports_since(entry.version),
            entry.host_values(), entry.host_delta(),
            source=s, config=self.config,
            calibrator=self._calibrator, mesh=self.mesh, obs=self.obs,
            faults=self.faults, retry=self._retry_policy(),
        )
        self._absorb_run(res)
        self._store(program, s, res.values, res.delta)
        self.stats.n_incremental += 1
        self.stats.sweep_iterations += res.iterations
        return QueryResult(
            source=s, values=res.values, iterations=res.iterations,
            cache_hit=False, mode="incremental",
        )

    def _retry_policy(self):
        return self.supervisor.policy if self.supervisor is not None else None

    def _runtime_for(self, program):
        """The container view matching the configured execution path:
        the device-sharded (P_pad, B) grid on the mesh, or the
        single-device blocked log."""
        if self.mesh is not None:
            return self.dcsr.sharded_runtime_for(
                program, mesh=self.mesh, axis=self.config.mesh_axis)
        return self.dcsr.runtime_for(program)

    def _query_fresh(self, program, sources) -> dict:
        out: dict[int | None, QueryResult] = {}
        if program.peel_k is not None or (
                program.use_delta and not program.personalized):
            # global programs (accumulative, and peeling programs whose
            # init comes from the runtime degree vector — they cannot be
            # seeded per-lane): a single full run
            for s in sources:
                res = run_hytm(
                    None, program, source=s, config=self.config,
                    runtime=self._runtime_for(program), mesh=self.mesh,
                    calibrator=self._calibrator, obs=self.obs,
                    faults=self.faults, retry=self._retry_policy(),
                )
                self._absorb_run(res)
                self._store(program, s, res.values, res.delta)
                self.stats.n_full += 1
                self.stats.sweep_iterations += res.iterations
                out[s] = QueryResult(
                    source=s, values=res.values, iterations=res.iterations,
                    cache_hit=False, mode="batched",
                )
            return out
        # per-source programs (traversals + personalized accumulative):
        # the continuous scheduler stacks them into bucketed lanes —
        # admission pads partial batches with dead lanes up to a static
        # bucket (never a recompile), converged lanes free their slot at
        # chunk boundaries, and freed slots backfill from the remaining
        # sources mid-flight
        served = self.scheduler.run_batch(program, sources)
        for s in sources:
            r = served[s]
            if r.mode == "rejected":
                # only possible when device_budget_bytes cannot hold even
                # one lane — a misconfiguration, not a serving decision
                raise RuntimeError(
                    f"device_budget_bytes={self.cache.policy.device_budget_bytes} "
                    f"cannot fit one lane "
                    f"({self.scheduler.lane_bytes} bytes) — query rejected")
            out[s] = QueryResult(
                source=s, values=r.values, iterations=r.iterations,
                cache_hit=False, mode=r.mode,
            )
        return out
