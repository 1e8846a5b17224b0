"""Continuous lane-batching scheduler (repro.serve).

HyTGraph picks the cheapest transfer strategy *per iteration* as the
active set evolves; at serving scale the same decision moves up a level
— which queries share a dispatch, and when.  The scheduler keeps the
device busy with whatever work is ready (Gunrock's frontier-centric
batching shape, PAPERS.md) instead of blocking on fixed ``max_lanes``
batches:

* **static lane buckets** — lane counts come from a small static set
  (default ``{1, 2, 4, ..., max_lanes}``), and a partial batch is padded
  up to its bucket with *dead lanes* (``core.hytm.dead_lane_state``:
  empty frontier, zero Δ — no-ops by construction).  Admission therefore
  never changes a traced lane count: the whole serving lifetime compiles
  at most one ``hytm_batched_chunk`` per (bucket, program), however the
  request sizes arrive;
* **continuous backfill** — each chunk dispatch returns the **per-lane**
  ``next_active`` vector (``core.hytm.hytm_batched_chunk``'s carry), so
  a lane that converges frees its slot at the chunk boundary and the
  scheduler immediately backfills it from the queue *mid-flight*, while
  straggler lanes keep relaxing.  The bucket (and hence the compiled
  sweep) never changes while a batch is in flight;
* **admission control** — slots are filled through
  ``RequestQueue.admit`` (per-tenant quotas, deadline-first ordering,
  device byte budget); the warm cache spills to host RAM before a batch
  pins its lane state, so device-resident bytes (in-flight lanes + warm
  tier) never exceed ``TierPolicy.device_budget_bytes``;
* **warm lanes** — a request whose key has a warm (stale) cache entry is
  admitted as an *incremental* lane: its init state is the
  ``incremental_state`` replay seed (promoting the entry from host tier
  first if it was spilled), so warm recomputes ride the same vmapped
  chunk as cold sweeps.

Equivalence: lanes are ``jax.vmap`` elements — they never interact — so
every lane's trajectory is bit-identical to its standalone
``run_hytm`` / ``run_incremental`` execution for MIN programs and
tolerance-bounded for SUM, regardless of bucket padding, backfill
timing, or what other tenants are doing (tests/test_serve.py).  The
scheduler moves *latency* only.

Latency is tracked on two clocks: wall time and a deterministic
**virtual clock** (cumulative engine iterations executed), which is what
the serve_bench ``--selfcheck`` latency gate compares — virtual latency
is reproducible run-to-run, wall latency is not.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import TYPE_CHECKING

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.cost_model import KEY_ICI_BYTES, KEY_ICI_TIME
from repro.core.hytm import (
    HyTMState,
    _consume_warm,
    dead_lane_state,
    hytm_batched_chunk,
    quiet_donation,
)
from repro.graph.algorithms import VertexProgram
from repro.serve.queue import Request, RequestQueue

if TYPE_CHECKING:  # pragma: no cover - type hints only
    from repro.stream.service import GraphService


def default_buckets(max_lanes: int) -> tuple[int, ...]:
    """The static lane-count buckets: powers of two up to ``max_lanes``,
    plus ``max_lanes`` itself — small enough that the whole set stays
    compiled, spaced so padding waste is bounded by 2x."""
    if max_lanes < 1:
        raise ValueError(f"max_lanes must be >= 1, got {max_lanes}")
    buckets = []
    b = 1
    while b < max_lanes:
        buckets.append(b)
        b *= 2
    buckets.append(max_lanes)
    return tuple(buckets)


# state bytes one lane pins on device: values f32 + delta f32 + frontier
# bool, each (n,)
LANE_STATE_BYTES_PER_NODE = 4 + 4 + 1


@dataclass
class ServedResult:
    request: Request
    values: np.ndarray | None
    delta: np.ndarray | None
    iterations: int            # engine iterations this request's lane ran
    mode: str   # 'cache' | 'incremental' | 'batched' | 'rejected' | 'shed'
    submit_vt: float = 0.0
    done_vt: float = 0.0
    submit_wall: float = 0.0
    done_wall: float = 0.0

    @property
    def vt_latency(self) -> float:
        """Deterministic latency: engine iterations between submit and
        completion (queue wait + stragglers included)."""
        return self.done_vt - self.submit_vt

    @property
    def wall_latency(self) -> float:
        return self.done_wall - self.submit_wall


@dataclass
class SchedulerStats:
    chunks: int = 0
    engine_iterations: int = 0   # the virtual clock
    lane_iterations: int = 0     # live-lane iterations (occupancy numerator)
    slot_iterations: int = 0     # bucket-width iterations (denominator)
    backfills: int = 0
    batches: int = 0
    max_device_bytes: int = 0    # peak in-flight lanes + device-tier cache

    @property
    def occupancy(self) -> float:
        """Fraction of dispatched lane-slots that carried live work."""
        return self.lane_iterations / max(self.slot_iterations, 1)


@dataclass
class _LaneJob:
    request: Request
    mode: str                  # 'batched' | 'incremental'
    init: tuple                # (values, delta, frontier)
    iters: int = 0


class LaneScheduler:
    """Continuous scheduler over one :class:`GraphService`'s container.

    ``GraphService._query_fresh`` drives it in degenerate single-tenant
    mode (no deadlines, no quotas); ``benchmarks/serve_bench.py`` drives
    it closed-loop with a multi-tenant trace through :meth:`pump`.
    """

    def __init__(self, service: "GraphService",
                 buckets: tuple[int, ...] | None = None,
                 backfill: bool = True, supervisor=None):
        self.svc = service
        # optional repro.resilience.Supervisor: retry policy for lane
        # dispatches, OOM-streak tracking, and tiered load shedding.
        # None (the default) disables all of it with zero overhead.
        self.supervisor = supervisor
        # backfill=False degrades to the fixed-batch baseline: a batch
        # runs to full convergence before the queue is consulted again
        # (serve_bench's comparison point — answers are identical either
        # way, only latency moves)
        self.backfill = backfill
        self.buckets = tuple(sorted(set(
            buckets if buckets is not None
            else default_buckets(service.max_lanes))))
        if self.buckets[0] < 1:
            raise ValueError(f"lane buckets must be >= 1: {self.buckets}")
        self.stats = SchedulerStats()
        self.in_flight: dict[str, int] = {}   # tenant -> live lanes
        # device bytes pinned by the in-flight batch's lane state — the
        # warm cache reserves around this when entries are stored
        # mid-flight (GraphService._store)
        self.pinned_bytes = 0

    # ------------------------------------------------------------- geometry
    @property
    def vt(self) -> int:
        return self.stats.engine_iterations

    @property
    def lane_bytes(self) -> int:
        """Device bytes one lane pins.  Owner-sharded serving stores each
        lane split across the mesh, so the budgeted (per-device) cost is
        the owned ``(n_loc,)`` slice, not the full ``(n,)`` row — the same
        owned-slice granularity the warm cache accounts at."""
        n = self.svc.dcsr.n_nodes
        if self._owner_mode():
            n_dev = int(self.svc.mesh.shape[self.svc.config.mesh_axis])
            n = -(-n // n_dev)
        return LANE_STATE_BYTES_PER_NODE * n

    def _owner_mode(self) -> bool:
        svc = self.svc
        return (svc.mesh is not None
                and svc.config.vertex_sharding == "owner")

    def bucket_for(self, q: int) -> int:
        for b in self.buckets:
            if b >= q:
                return b
        return self.buckets[-1]

    def _budget_bucket_cap(self) -> int | None:
        """Largest admissible lane count under the device byte budget
        (the warm cache can spill to zero; in-flight lane state cannot)."""
        budget = self.svc.cache.policy.device_budget_bytes
        if budget is None:
            return None
        fit = [b for b in self.buckets if b * self.lane_bytes <= budget]
        return max(fit) if fit else 0

    # ------------------------------------------------------------ admission
    def _resolve_or_job(self, req: Request) -> ServedResult | _LaneJob:
        """Turn an admitted request into a finished result (exact-version
        cache hit) or a lane job seeded fresh / from the warm cache."""
        svc = self.svc
        key = (req.program, svc.key_source(req.program, req.source))
        entry = svc.cache.get(key)
        if entry is not None and entry.version == svc.version:
            svc.stats.n_cache_hits += 1
            return self._finish(req, entry.host_values(),
                                entry.host_delta(), 0, "cache")
        if entry is not None and svc.incremental:
            # import here: repro.stream imports repro.serve (service owns
            # a LaneScheduler), so the reverse edge must stay lazy
            from repro.stream.incremental import incremental_state

            # warm lane: promote the state to the device tier if it was
            # spilled (bit-exact round trip — warm_cache.promote), then
            # seed the replay-from-reports state.  The lane then runs the
            # identical residual convergence a solo run_incremental would.
            # promote() returns None when the entry failed integrity
            # verification (corrupt spill — evicted) or an injected
            # device OOM refused the transfer: the degradation rung is
            # cache-promote -> full recompute, i.e. fall through to the
            # fresh-seed path below instead of serving garbage.
            entry = svc.cache.promote(key)
            if entry is not None:
                state = incremental_state(
                    req.program, entry.host_values(), entry.host_delta(),
                    svc._reports_since(entry.version), svc.dcsr, key[1],
                )
                svc.stats.n_incremental += 1
                return _LaneJob(req, "incremental",
                                (state.values, state.delta, state.frontier))
        values, delta, frontier = req.program.init_state(
            svc.dcsr.n_nodes, key[1])
        svc.stats.n_full += 1
        return _LaneJob(req, "batched", (values, delta, frontier))

    def _finish(self, req: Request, values, delta, iters: int,
                mode: str) -> ServedResult:
        done_wall = time.monotonic()
        res = ServedResult(
            request=req, values=values, delta=delta, iterations=iters,
            mode=mode, submit_vt=req.submit_vt, done_vt=self.vt,
            submit_wall=req.submit_wall, done_wall=done_wall,
        )
        obs = self.svc.obs
        if obs is not None:
            # one span per served request on its tenant's track: wall
            # coordinates are submit->done monotonic stamps, vt rides in
            # args (submit_vt -> the scheduler's virtual clock)
            wall0 = (obs.wall_at(req.submit_wall)
                     if req.submit_wall else obs.wall())
            obs.span(
                f"request:{mode}", cat="serve",
                track=f"tenant:{req.tenant}",
                wall=wall0,
                wall_dur=max(obs.wall_at(done_wall) - wall0, 0.0),
                vt=float(req.submit_vt),
                vt_dur=float(self.vt - req.submit_vt),
                iterations=iters, program=req.program.name,
                source=-1 if req.source is None else int(req.source),
            )
            obs.metrics.counter(
                "serve.requests", "served requests by mode/tenant").inc(
                1, mode=mode, tenant=req.tenant)
        return res

    def _admit_jobs(
        self, queue: RequestQueue, program: VertexProgram, n_slots: int,
        results: list[ServedResult],
    ) -> list[_LaneJob]:
        """Admit up to ``n_slots`` lane jobs for ``program``: requests
        resolved instantly by the cache do not consume a slot, so keep
        admitting until the slots are full or the queue has nothing
        admissible left.  Rejections (could never run) and instant cache
        resolutions land directly in ``results``."""
        budget = self.svc.cache.policy.device_budget_bytes
        obs = self.svc.obs
        qs = queue.stats
        before = (qs.admitted, qs.deferred, qs.rejected)
        jobs: list[_LaneJob] = []
        while True:
            admitted = queue.admit(
                n_slots - len(jobs), self.in_flight, program=program,
                free_bytes=budget, bytes_per_lane=self.lane_bytes,
                total_budget=budget,
                on_reject=lambda r: results.append(
                    self._finish(r, None, None, 0, "rejected")),
            )
            if not admitted:
                break
            for req in admitted:
                out = self._resolve_or_job(req)
                if isinstance(out, ServedResult):
                    results.append(out)
                else:
                    jobs.append(out)
                    self.in_flight[req.tenant] = (
                        self.in_flight.get(req.tenant, 0) + 1)
            if len(jobs) >= n_slots:
                break
        if obs is not None:
            m = obs.metrics
            for name, prev, cur in zip(
                    ("admitted", "deferred", "rejected"), before,
                    (qs.admitted, qs.deferred, qs.rejected)):
                if cur > prev:
                    m.counter(f"admission.{name}",
                              "queue admission outcomes").inc(cur - prev)
        return jobs

    # ------------------------------------------------------------- dispatch
    def _lane_pad(self, program: VertexProgram):
        """Owner-mode lane geometry: ``(n_pad, pad_values, pad_delta)``,
        or ``None`` when lanes run replicated (n,)."""
        if not self._owner_mode():
            return None
        from repro.dist.graph_shard import owner_state_pad_values

        rt = self.svc._runtime_for(program)
        pad_v, pad_d = owner_state_pad_values(program)
        return rt.n_pad, pad_v, pad_d

    @staticmethod
    def _pad_triple(triple, pad):
        """Pad one lane's (n,) init triple to (n_pad,) with the program's
        inert fills (graph_shard.owner_state_pad_values)."""
        n_pad, pad_v, pad_d = pad
        v, d, f = (jnp.asarray(t) for t in triple)
        extra = n_pad - v.shape[0]
        if extra > 0:
            v = jnp.concatenate([v, jnp.full((extra,), pad_v, v.dtype)])
            d = jnp.concatenate([d, jnp.full((extra,), pad_d, d.dtype)])
            f = jnp.concatenate([f, jnp.zeros((extra,), f.dtype)])
        return v, d, f

    def _stack_state(self, program: VertexProgram,
                     jobs: list[_LaneJob | None], bucket: int) -> HyTMState:
        n = self.svc.dcsr.n_nodes
        dead = dead_lane_state(program, n)
        triples = [j.init if j is not None else dead for j in jobs]
        triples += [dead] * (bucket - len(jobs))
        pad = self._lane_pad(program)
        if pad is not None:
            triples = [self._pad_triple(t, pad) for t in triples]
        state = HyTMState(
            values=jnp.stack([t[0] for t in triples]),
            delta=jnp.stack([t[1] for t in triples]),
            frontier=jnp.stack([t[2] for t in triples]),
        )
        if pad is not None:
            # (Q, n_pad) with the vertex dim owner-sharded: each device
            # holds every lane's owned slice — per-device lane state is
            # Q * n_loc, the granularity lane_bytes pins
            from jax.sharding import NamedSharding, PartitionSpec

            lane = NamedSharding(
                self.svc.mesh,
                PartitionSpec(None, self.svc.config.mesh_axis))
            state = HyTMState(
                values=jax.device_put(state.values, lane),
                delta=jax.device_put(state.delta, lane),
                frontier=jax.device_put(state.frontier, lane),
            )
        return state

    def _dispatch(self, program: VertexProgram, state: HyTMState,
                  bucket: int, correction):
        """One chunk dispatch over the bucketed lane batch; returns
        ``(state, n_done, lane_active, correction)`` with the calibrator
        fed exactly as the pre-serve lane sweep fed it."""
        svc = self.svc
        cfg = svc.config
        chunk = max(cfg.sync_every, 1)
        if svc.mesh is not None:
            return self._dispatch_sharded(program, state, bucket,
                                          correction, chunk)
        rt = svc.dcsr.runtime_for(program)
        warm = _consume_warm((
            "serve-lanes", program, cfg, rt.n_hub_partitions,
            bucket, svc.dcsr.n_nodes, rt.csr.edge_src.shape[0],
            rt.parts.n_partitions, rt.parts.block_size,
            chunk, correction is not None,
        ))
        t_chunk = time.monotonic()
        faults = svc.faults
        if faults is None:
            with quiet_donation():
                state, n_done, lane_active, pe_sum, mp_sum = (
                    hytm_batched_chunk(
                        state, rt.csr, rt.parts, rt.zc_req, rt.inv_deg,
                        program, cfg, rt.n_hub_partitions, chunk,
                        correction,
                    ))
        else:
            # faults fire BEFORE the dispatch (donated lane state from
            # the previous chunk intact), so retries are bit-identical
            from repro.resilience.supervisor import guarded_dispatch

            def _attempt(st=state, corr=correction):
                with quiet_donation():
                    return hytm_batched_chunk(
                        st, rt.csr, rt.parts, rt.zc_req, rt.inv_deg,
                        program, cfg, rt.n_hub_partitions, chunk, corr,
                    )

            sup = self.supervisor
            state, n_done, lane_active, pe_sum, mp_sum = guarded_dispatch(
                _attempt, site="lane_dispatch", faults=faults,
                policy=sup.policy if sup is not None else None,
                obs=svc.obs,
                stats=sup.counters if sup is not None else None,
                bucket=bucket,
            )
        correction = self._observe(pe_sum, mp_sum, t_chunk, warm, correction)
        return state, int(n_done), np.asarray(lane_active), correction

    def _dispatch_sharded(self, program: VertexProgram, state: HyTMState,
                          bucket: int, correction, chunk: int):
        from repro.dist.graph_shard import (
            halo_level_cost,
            ici_level_cost,
            make_sharded_batched_chunk,
        )

        svc = self.svc
        rt = svc._runtime_for(program)
        n_dev = int(svc.mesh.shape[svc.config.mesh_axis])
        key = ("lanes", program, svc.config, chunk, bucket)
        cached = rt.iteration_cache.get(key)
        if cached is None:
            cached = {"fn": make_sharded_batched_chunk(
                rt, program, svc.config, chunk), "seen": set()}
            rt.iteration_cache[key] = cached
        warm = _consume_warm(
            (rt.blocks.src.shape, rt.parts.n_partitions,
             rt.parts.block_size, correction is not None),
            registry=cached["seen"],
        )
        t_chunk = time.monotonic()
        with quiet_donation():
            state, n_done, lane_active, pe_sum, mp_sum, merged = \
                cached["fn"](state, rt.blocks, rt.parts, rt.out_degree,
                             rt.zc_req, rt.inv_deg, correction)
        n_done = int(n_done)
        correction = self._observe(pe_sum, mp_sum, t_chunk, warm, correction)
        # second-level accounting: all lanes merge in one batched
        # collective per iteration (lane-summed entries, Q·(n,) dense)
        corr_np = (np.asarray(correction, dtype=float)
                   if correction is not None else None)
        obs = svc.obs
        base = self.stats.engine_iterations
        owner = rt.vertex_sharding == "owner" and rt.halo is not None
        for k, me in enumerate(np.asarray(merged)[:n_done]):
            halo_entries = None
            if owner:
                # each lane's compacted exchange is capped by the same
                # halo plan, so the batched collective caps at Q * halo
                halo_cap = float(bucket) * float(rt.halo.halo_total)
                halo_entries = min(float(me), halo_cap)
                ib, it_, ie = halo_level_cost(
                    bucket * svc.dcsr.n_nodes, float(me), halo_cap,
                    n_dev, svc.config.ici_link, corr_np,
                )
            else:
                ib, it_, ie = ici_level_cost(
                    bucket * svc.dcsr.n_nodes, float(me), n_dev,
                    svc.config.ici_link, corr_np,
                )
            svc.stats.extra[KEY_ICI_BYTES] = (
                svc.stats.extra.get(KEY_ICI_BYTES, 0.0) + ib)
            svc.stats.extra[KEY_ICI_TIME] = (
                svc.stats.extra.get(KEY_ICI_TIME, 0.0) + it_)
            if obs is not None:
                from repro.obs.record import record_ici

                record_ici(obs, track="ici", it=base + k, bytes_=ib,
                           seconds=it_, engine=ie,
                           merged_entries=float(me),
                           halo_entries=halo_entries)
        return state, n_done, np.asarray(lane_active), correction

    def _observe(self, pe_sum, mp_sum, t_chunk, warm, correction):
        svc = self.svc
        if svc._calibrator is None:
            return correction
        refreshed = svc._calibrator.observe(
            pe_sum, np.asarray(pe_sum, dtype=float), t_chunk, skip=not warm)
        svc._record_feedback(int(mp_sum), refreshed)
        return svc._correction

    def _alloc_pressure(self, queue: RequestQueue, slots: int,
                        results: list, floor: int) -> int:
        """Fire the ``lane_alloc`` fault site for one batch (or backfill)
        formation.  An injected OOM halves the slot count for this round
        — lanes are independent, so a narrower batch defers work without
        changing any lane's answer.  Sustained OOM streaks trip the
        supervisor's load-shed rung: pending requests of tenants below
        the top waiting tier are withdrawn and finished as mode
        ``"shed"``.  No-op (returns ``slots``) without a fault plan."""
        svc = self.svc
        if svc.faults is None:
            return slots
        from repro.resilience.supervisor import record_fault_event

        oom = svc.faults.fire("lane_alloc") == "oom"
        if oom:
            slots = max(slots // 2, floor)
            record_fault_event(svc.obs, "injected", site="lane_alloc",
                               kind="oom")
        sup = self.supervisor
        if sup is not None and sup.note_alloc_pressure(oom):
            for req in sup.shed_candidates(queue.pending()):
                if queue.withdraw(req):
                    sup.record_shed(req)
                    results.append(self._finish(req, None, None, 0, "shed"))
        return slots

    # ------------------------------------------------------------ main loop
    def pump(self, queue: RequestQueue) -> list[ServedResult]:
        """Drain ``queue``: form program-homogeneous bucketed lane
        batches, dispatch chunks, free converged lanes at chunk
        boundaries, and backfill freed slots from the queue mid-flight.
        Returns every request served this call (including instant cache
        resolutions and rejections), in completion order."""
        svc = self.svc
        obs = svc.obs
        results: list[ServedResult] = []
        sup = self.supervisor
        while queue:
            cap = self._budget_bucket_cap()
            max_slots = self.buckets[-1] if cap is None else cap
            max_slots = self._alloc_pressure(queue, max_slots, results,
                                             floor=1)
            if not queue:
                break  # everything pending was shed
            program = queue.peek_program()
            pending_before = len(queue)
            jobs = self._admit_jobs(queue, program, max(max_slots, 0),
                                    results)
            if not jobs:
                if len(queue) == pending_before:
                    # nothing admitted, resolved, or rejected — no lane
                    # in flight either, so no future chunk boundary can
                    # unblock the deferred remainder: stop, don't spin
                    break
                continue  # all resolved/rejected instantly; queue shrank
            bucket = self.bucket_for(len(jobs))
            # warm states yield the device to live lanes: spill the cache
            # until lanes + device tier fit the budget, then record peak
            self.pinned_bytes = bucket * self.lane_bytes
            svc.cache.shrink_to_budget(reserved_bytes=self.pinned_bytes)
            self.stats.max_device_bytes = max(
                self.stats.max_device_bytes,
                self.pinned_bytes + svc.cache.device_bytes)
            self.stats.batches += 1
            if obs is not None:
                obs.metrics.gauge(
                    "serve.device_bytes",
                    "in-flight lanes + device-tier cache bytes").set(
                    float(self.pinned_bytes + svc.cache.device_bytes))
                obs.counter("device_bytes",
                            self.pinned_bytes + svc.cache.device_bytes,
                            cat="serve", track="scheduler",
                            vt=float(self.vt))
            lane_jobs: list[_LaneJob | None] = list(jobs) + [None] * (
                bucket - len(jobs))
            state = self._stack_state(program, lane_jobs, bucket)
            correction = svc._correction
            if svc._calibrator is not None and correction is None:
                correction = jnp.ones(3, jnp.float32)

            while any(j is not None for j in lane_jobs):
                state, n_done, lane_active, correction = self._dispatch(
                    program, state, bucket, correction)
                live = sum(j is not None for j in lane_jobs)
                self.stats.chunks += 1
                self.stats.engine_iterations += n_done
                self.stats.lane_iterations += live * n_done
                self.stats.slot_iterations += bucket * n_done
                if obs is not None:
                    obs.metrics.gauge(
                        "serve.occupancy",
                        "live-lane fraction of dispatched slots").set(
                        self.stats.occupancy)
                    obs.counter("lane_occupancy", live / bucket,
                                cat="serve", track="scheduler",
                                vt=float(self.vt))
                for j in lane_jobs:
                    if j is not None:
                        j.iters += n_done
                # a lane is done when its frontier drained — or it hit
                # the iteration cap (max_iters enforced at chunk
                # granularity, same bound run_hytm's driver applies)
                done_idx = [
                    i for i, j in enumerate(lane_jobs)
                    if j is not None and (
                        lane_active[i] == 0
                        or j.iters >= svc.config.max_iters)
                ]
                if not done_idx:
                    continue
                # owner-mode lanes carry (n_pad,) rows — slice the ghost
                # pads off so stored/served results are canonical (n,)
                values = np.asarray(state.values)[:, :svc.dcsr.n_nodes]
                deltas = np.asarray(state.delta)[:, :svc.dcsr.n_nodes]
                freed = 0
                for i in done_idx:
                    job = lane_jobs[i]
                    key_src = svc.key_source(program, job.request.source)
                    svc._store(program, key_src, values[i], deltas[i])
                    svc.stats.sweep_iterations += job.iters
                    results.append(self._finish(
                        job.request, values[i], deltas[i], job.iters,
                        job.mode))
                    lane_jobs[i] = None
                    self.in_flight[job.request.tenant] -= 1
                    if self.in_flight[job.request.tenant] <= 0:
                        del self.in_flight[job.request.tenant]
                    freed += 1
                # backfill freed slots mid-flight: the bucket (and the
                # compiled chunk) never changes; new jobs drop into the
                # dead rows at the chunk boundary.  Backfill takes any
                # pending same-program request (admit() filters; the
                # freed slots cannot run anything else) — deadline order
                # applies within the program here, and across programs
                # at the next batch formation
                if self.backfill and queue:
                    # backfill is a batch formation too: the fault plane
                    # can refuse the refill allocation (floor 0 — the
                    # outer loop re-forms batches, so admitting nothing
                    # here cannot deadlock)
                    freed = self._alloc_pressure(queue, freed, results,
                                                 floor=0)
                if self.backfill and queue:
                    refill = self._admit_jobs(queue, program, freed, results)
                    slots = [i for i, j in enumerate(lane_jobs) if j is None]
                    pad = self._lane_pad(program) if refill else None
                    for slot, job in zip(slots, refill):
                        lane_jobs[slot] = job
                        v, d, f = (self._pad_triple(job.init, pad)
                                   if pad is not None else job.init)
                        state = HyTMState(
                            values=state.values.at[slot].set(v),
                            delta=state.delta.at[slot].set(d),
                            frontier=state.frontier.at[slot].set(f),
                        )
                        self.stats.backfills += 1
                        if obs is not None:
                            obs.metrics.counter(
                                "serve.backfills",
                                "mid-flight lane refills").inc(1)
                            obs.instant(
                                "backfill", cat="serve", track="scheduler",
                                vt=float(self.vt), slot=slot,
                                tenant=job.request.tenant, mode=job.mode)
            self.pinned_bytes = 0
        return results

    # ------------------------------------------------- service entry point
    def run_batch(self, program: VertexProgram,
                  sources) -> dict:
        """Degenerate single-tenant mode for ``GraphService._query_fresh``:
        wrap ``sources`` as quota-free requests, drain them, and return
        ``{source: ServedResult}``."""
        q = RequestQueue()
        for s in sources:
            q.submit(Request(tenant="_local", program=program, source=s,
                             submit_vt=self.vt,
                             submit_wall=time.monotonic()))
        served = self.pump(q)
        return {r.request.source: r for r in served}
